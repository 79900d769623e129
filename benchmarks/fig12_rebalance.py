"""Fig 12 (beyond-paper) — elastic sharded world state under fill pressure.

A FastFabric peer's in-memory table is a hard capacity wall: fill it and
the channel fail-stops (PR 4 made that overflow exact and observable; this
figure makes it *elastic*). The workload keeps inserting fresh accounts
round after round — a fill-until-overflow sweep:

  * ``static/round=k``  — TPS per round on a fixed table; the sticky
    overflow flag latches once a bucket fills and the peer reports
    unhealthy forever after;
  * ``elastic/round=k`` — same workload with a between-rounds
    ``ResizePolicy``: bucket pressure (min free slots) halves/doubles the
    table, each resize committed to the journal as a re-anchor record
    (``n_buckets`` column shows the growth; ``resized`` marks the epochs);
  * ``static/final`` / ``elastic/final`` — end-of-run health: the CI
    artifact asserts ``overflow_ok`` is False for static and True for
    elastic ON THE SAME WORKLOAD — the split absorbed a load that
    overflows without it;
  * ``equivalence/elastic`` — the elastic peer's final state content
    equals an oracle that ran the whole workload on the FINAL layout from
    block 0 (the resize-epoch exactness the tests pin byte-for-byte);
  * ``recovery/full`` and ``recovery/shard=m`` — restart cost from the
    per-shard snapshot + journal suffix across the re-anchors: the full
    merged recovery vs one bucket shard alone (``parts`` counts snapshot
    shard files read — a shard recovers from 2^epochs parts, not the
    whole table).

Run:  PYTHONPATH=src python -m benchmarks.fig12_rebalance
"""

from __future__ import annotations

import argparse
import dataclasses
import tempfile
import time

import numpy as np

from benchmarks import common
from repro.core import engine, types
from repro.core import world_state as ws
from repro.obs import health as obs_health
from repro.storage import recovery, snapshot


def _mk_engine(policy, n_buckets, slots, block_size, *, n_shards=1,
               snapshot_every=0, snapshot_dir=None, journal_dir=None):
    cfg = engine.EngineConfig(
        dims=types.TEST_DIMS,
        orderer=dataclasses.replace(
            engine.FASTFABRIC.orderer, block_size=block_size
        ),
        n_buckets=n_buckets,
        slots=slots,
        resize_policy=policy,
        snapshot_shards=n_shards,
        snapshot_every_blocks=snapshot_every,
        snapshot_dir=snapshot_dir,
        journal_dir=journal_dir,
        obs=True,  # per-engine registry: resize events and counters
        # Health verdicts here contract on overflow/occupancy, not wall
        # clock: a compile-noise-proof latency objective keeps the
        # static-critical / elastic-healthy contrast deterministic.
        slo=obs_health.SLOConfig(commit_p95_s=60.0),
    )
    return engine.FabricEngine(cfg)


def run(rounds: int, round_txs: int, n_buckets: int, slots: int,
        n_shards: int, grow_free_slots: int) -> None:
    policy = engine.ResizePolicy(grow_free_slots=grow_free_slots)
    block_size = round_txs
    static = _mk_engine(None, n_buckets, slots, block_size)
    with tempfile.TemporaryDirectory() as snapd, \
            tempfile.TemporaryDirectory() as jrnd:
        elastic = _mk_engine(
            policy, n_buckets, slots, block_size, n_shards=n_shards,
            snapshot_every=max(rounds // 2, 1), snapshot_dir=snapd,
            journal_dir=jrnd,
        )
        for label, eng in (("static", static), ("elastic", elastic)):
            for i in range(rounds):
                nb_before = eng.n_buckets
                stats = eng.run_round(eng.make_proposals(round_txs, seed=i))
                common.row(
                    "fig12", f"{label}/round={i}", tps=stats.tps,
                    n_buckets=eng.n_buckets,
                    resized=int(eng.n_buckets != nb_before),
                    overflow=int(eng.overflowed()),
                )
            out = eng.verify()
            m = eng.metrics()
            # Health/SLO rollup on the same sweep: the static table that
            # latched overflow MUST read critical with a per-shard
            # reason; the elastic peer that absorbed the identical load
            # must stay healthy. (The degraded band covers a static run
            # that filled past headroom without overflowing yet.)
            v = eng.health()
            if eng.overflowed():
                assert v.status == "critical", (label, v)
                assert any("shard" in r and "overflow" in r
                           for r in v.reasons), v
            elif label == "elastic":
                assert v.status == "healthy", v
            common.row(
                "fig12", f"{label}/final", overflow_ok=out["overflow_ok"],
                n_buckets=eng.n_buckets,
                n_resizes=len(eng.reanchor_log),
                verify_ok=all(out.values()) if label == "elastic"
                else all(v2 for k, v2 in out.items() if k != "overflow_ok"),
                health=v.status,
                health_reason=(v.reasons[0] if v.reasons else ""),
                resize_grows=m.get("resize.grow", 0),
                overflow_latches=m.get("overflow.latches", 0),
            )

        # Equivalence: whole workload replayed on the FINAL layout == the
        # elastic peer that split mid-run (content digest compare). Only
        # meaningful while the elastic run never overflowed — a dropped
        # insert is not derivable from the table, so an unhealthy elastic
        # run legitimately differs from the never-overflowing oracle.
        oracle = _mk_engine(None, elastic.n_buckets, slots, block_size)
        for i in range(rounds):
            oracle.run_round(oracle.make_proposals(round_txs, seed=i))
        identical = bool(np.array_equal(
            oracle._peer_digest(), elastic._peer_digest()
        ))
        if not elastic.overflowed():
            assert identical, "elastic run diverged from post-split oracle"
        common.row("fig12", "equivalence/elastic", identical=identical,
                   meaningful=int(not elastic.overflowed()))

        # Recovery from the per-shard snapshot + journal suffix (the
        # suffix crosses any re-anchors after the last snapshot).
        elastic.store.drain()
        t0 = time.perf_counter()
        rec = elastic.recover()
        t_full = time.perf_counter() - t0
        ok = bool(np.array_equal(rec.state_digest, elastic._peer_digest()))
        common.row(
            "fig12", "recovery/full", recovery_s=t_full,
            replayed=rec.replayed_records,
            reanchors=rec.crossed_reanchors, match=ok,
        )
        man = snapshot.latest_manifest(snapd)
        if man is not None and man.n_shards == n_shards:
            sk, sv, sva = ws.split_table(
                *elastic._state_view()[:3], n_shards
            )
            for m in range(n_shards):
                t0 = time.perf_counter()
                try:
                    sres = recovery.recover_shard(
                        elastic.journal, snapshot_dir=snapd, shard=m
                    )
                except recovery.RecoveryError as e:
                    common.row("fig12", f"recovery/shard={m}", error=str(e))
                    continue
                t_s = time.perf_counter() - t0
                match = bool(np.array_equal(
                    np.asarray(sres.state.keys), np.asarray(sk[m])
                ))
                common.row(
                    "fig12", f"recovery/shard={m}", recovery_s=t_s,
                    parts=sres.loaded_parts, match=match,
                )
        static.store.close()
        elastic.store.close()
        oracle.store.close()


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--rounds", type=int, default=14)
    p.add_argument("--round-txs", type=int, default=50)
    # Start small enough that the fill workload overflows a static table
    # well inside the sweep; the elastic run must absorb the same load.
    p.add_argument("--n-buckets", type=int, default=256)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--n-shards", type=int, default=4)
    p.add_argument("--grow-free-slots", type=int, default=4)
    p.add_argument("--json", default=None)
    args = p.parse_args(argv)
    run(args.rounds, args.round_txs, args.n_buckets, args.slots,
        args.n_shards, args.grow_free_slots)
    if args.json:
        common.dump_json(args.json)


if __name__ == "__main__":
    main()
    common.print_csv()

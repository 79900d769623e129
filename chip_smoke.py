"""Bring-up smoke of the committing peer on one TPU chip.

Drives ``repro.core.engine.FabricEngine`` through its normal entry points
at the paper's transaction size (``types.PAPER_DIMS``, 2.9 KB) with a
deployment-sized world state (2^20 buckets x 8 slots per hash table), in
three phases fed the same proposal seeds:

  host      FastFabric, blocks committed one by one (``committer``)
  window    FastFabric through ``MeshWindowCommitter`` at depth 8 on a
            1x1 mesh; byte-identical to ``host`` in validity bits, ledger
            head, journal head and world-state digest
  baseline  Fabric 1.2; its endorser-replica digest equals the other two

Every ``verify()`` must be all true and every transaction valid. Any failed
check raises. Wall times are smoke timings, not benchmark numbers.

    PYTHONPATH=src python chip_smoke.py                # one chip
    PYTHONPATH=src python chip_smoke.py --four-chips   # 4-chip paths only

``--four-chips`` runs only the paths that exist across chips: world state
sharded over ``model`` on a (1, 4) mesh, and 4 channels over ``data`` on a
(4, 1) mesh, each against the replicated depth-8 committer on device 0.

Without a TPU the phases still run (the CPU rehearsal) and the script
exits 1. On a TPU its last line is the JSON result, and nothing else.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import endorser, engine, types, unmarshal  # noqa: E402
from repro.core import world_state as ws  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch import fabric_step as fs  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.pipeline import engine_bridge  # noqa: E402

DIMS = types.PAPER_DIMS
SLOTS = 8
N_ACCOUNTS = 1 << 22
ROUND_TXS = 800  # 8 blocks of 100
ROUNDS = 3  # after one warm-up round
DEPTH = 8

_compile_s = [0.0]  # XLA backend compile seconds since the last reset


def _on_event(name, secs, **_):
    if name == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += secs


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def tree_bytes(*trees) -> int:
    return sum(int(x.nbytes) for t in trees for x in jax.tree.leaves(t))


def report(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


# ---------------------------------------------------------------- one chip


def run_engine_phase(name, cfg, n_buckets, window_committer=None) -> dict:
    _compile_s[0] = 0.0
    t_phase = time.perf_counter()
    eng = engine.FabricEngine(cfg, window_committer=window_committer)
    seeds = range(ROUNDS + 1)  # seed 0 is the warm-up round
    walls, n_valid, compiles = [], [], []
    for s in seeds:
        props = eng.make_proposals(ROUND_TXS, seed=s, n_accounts=N_ACCOUNTS)
        c0 = _compile_s[0]
        stats = eng.run_round(props)
        compiles.append(round(_compile_s[0] - c0, 3))
        check(stats.n_valid == ROUND_TXS,
              f"{name}: round {s} validated {stats.n_valid}/{ROUND_TXS}")
        n_valid.append(stats.n_valid)
        walls.append(stats.wall_s)
    t_verify = time.perf_counter()
    verdict = eng.verify()
    t_verify = time.perf_counter() - t_verify
    check(all(verdict.values()), f"{name}: verify() {verdict}")
    eng.store.drain()
    chain = eng.store.chain
    check(len(chain) == (ROUNDS + 1) * ROUND_TXS // 100,
          f"{name}: store holds {len(chain)} blocks")
    out = {
        "valid": np.stack([sb.valid for sb in chain]),
        "block_hash": np.stack([sb.block_hash for sb in chain]),
        # The ledger head is the store chain's head. The host-path peer
        # carries it as ``ledger_head``; the mesh step's own ``ledger_head``
        # is a different fold (over the ordered structured prefix).
        "ledger_head": chain[-1].block_hash,
        "journal_head": eng._peer_journal_head(),
        "state_digest": eng._peer_digest(),
        "endorser_digest": np.asarray(ws.state_digest(eng.endorser_state)),
    }
    if window_committer is None:
        check(np.array_equal(eng._ledger_head(), out["ledger_head"]),
              f"{name}: peer ledger head is not the store chain head")
    state_bytes = tree_bytes([c.peer_state for c in eng.chans],
                             [c.endorser_state for c in eng.chans])
    if window_committer is not None:
        state_bytes += tree_bytes([g.state for g in window_committer.groups])
    report(name, config=cfg.name, n_buckets=n_buckets, slots=SLOTS,
           payload_bytes=DIMS.payload_bytes, state_bytes=state_bytes,
           backend_compile_s=round(_compile_s[0], 3),
           backend_compile_s_by_round=compiles,
           rounds=ROUNDS,
           txs_per_round=ROUND_TXS, valid_per_round=n_valid[1:],
           verify=verdict)
    report(name, smoke_timing_not_a_benchmark="",
           warmup_wall_s=round(walls[0], 4),
           round_wall_s=[round(w, 4) for w in walls[1:]],
           verify_s=round(t_verify, 3),
           phase_s=round(time.perf_counter() - t_phase, 3))
    eng.store.close()
    return out


def one_chip(dev, n_buckets: int) -> None:
    base = dataclasses.replace(engine.FASTFABRIC, dims=DIMS,
                               n_buckets=n_buckets, slots=SLOTS)
    host = run_engine_phase("host", base, n_buckets)
    wc = engine_bridge.MeshWindowCommitter(
        DIMS, fs.FabricStepConfig(pipeline_depth=DEPTH),
        mesh=make_mesh((1, 1), devices=[dev]), n_buckets=n_buckets,
        slots=SLOTS,
    )
    window = run_engine_phase("window", base, n_buckets, wc)
    del wc
    for k in ("valid", "block_hash", "ledger_head", "journal_head",
              "state_digest", "endorser_digest"):
        check(np.array_equal(host[k], window[k]),
              f"window phase differs from host phase in {k}")
    report("window", identical_to_host=True,
           compared="valid,block_hash,ledger_head,journal_head,"
                    "state_digest,endorser_digest")
    v12 = dataclasses.replace(engine.FABRIC_V12, dims=DIMS,
                              n_buckets=n_buckets, slots=SLOTS)
    baseline = run_engine_phase("baseline", v12, n_buckets)
    for other in (host, window):
        check(np.array_equal(baseline["endorser_digest"],
                             other["endorser_digest"]),
              "endorser-replica digests differ across configs")
    report("baseline", endorser_digest_equal_across_phases=True,
           digest=host["endorser_digest"].tolist())


# -------------------------------------------------------------- four chips


def endorsed_windows(n_windows: int, seed: int, n_buckets: int):
    """Pre-endorsed (DEPTH, 100, wire) windows of one block stream, made
    on an endorser replica that applies every block as valid (the stream
    is disjoint transfers; the committers' validity bits are checked)."""
    eng = engine.FabricEngine(dataclasses.replace(
        engine.FASTFABRIC, dims=DIMS, n_buckets=n_buckets, slots=SLOTS,
        store_blocks=False))
    bs = eng.cfg.orderer.block_size
    out = []
    for w in range(n_windows):
        props = eng.make_proposals(DEPTH * bs, seed=seed + w,
                                   n_accounts=N_ACCOUNTS)
        txb = endorser.endorse_jit(eng.endorser_state, props, DIMS,
                                   n_endorsers=eng.cfg.n_endorsers)
        wire = unmarshal.marshal(txb, DIMS)
        out.append((wire.reshape(DEPTH, bs, -1),
                    txb.tx_id.reshape(DEPTH, bs, 2)))
        eng.endorser_state = endorser.apply_validated_jit(
            eng.endorser_state, txb, jnp.ones(DEPTH * bs, bool))
    return out


def compare_committers(name, live, oracle, channel: int) -> None:
    for field, a, b in zip(fs.FabricMeshState._fields,
                           live.channel_state(channel), oracle.state):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              f"{name}: channel {channel} state.{field} differs from oracle")


def shard_layout(arr) -> list:
    """(device id, shard shape) of every shard of ``arr``."""
    return sorted((s.device.id, tuple(s.data.shape))
                  for s in arr.addressable_shards)


def commit_stream(wc, windows) -> tuple:
    valid, hashes = [], []
    for wire, ids in windows:
        res = wc.commit_window(wire, ids)
        valid.append(np.asarray(res.valid))
        hashes.append(res.block_hash)
    return np.stack(valid), np.stack(hashes)


def four_chips(devs, n_buckets: int) -> None:
    oracle_mesh = make_mesh((1, 1), devices=[devs[0]])
    repl = fs.FabricStepConfig(pipeline_depth=DEPTH)
    n_windows = ROUNDS + 1

    # Sharded world state over `model`.
    _compile_s[0] = 0.0
    t0 = time.perf_counter()
    windows = endorsed_windows(n_windows, seed=100, n_buckets=n_buckets)
    live = engine_bridge.MeshWindowCommitter(
        DIMS, fs.FabricStepConfig(shard_state=True, pipeline_depth=DEPTH),
        mesh=make_mesh((1, 4)), n_buckets=n_buckets, slots=SLOTS)
    oracle = engine_bridge.MeshWindowCommitter(
        DIMS, repl, mesh=oracle_mesh, n_buckets=n_buckets, slots=SLOTS)
    v_live, h_live = commit_stream(live, windows)
    v_orc, h_orc = commit_stream(oracle, windows)
    check(v_live.all(), "sharded: a transaction was invalid")
    check(np.array_equal(v_live, v_orc), "sharded: validity bits differ")
    check(np.array_equal(h_live, h_orc), "sharded: block hashes differ")
    compare_committers("sharded", live, oracle, 0)
    for get in ("ledger_head_for", "journal_head_for"):
        check(np.array_equal(getattr(live, get)(0), getattr(oracle, get)(0)),
              f"sharded: {get} differs")
    check(np.array_equal(live.state_digest(0), oracle.state_digest(0)),
          "sharded: state digest differs")
    report("four/sharded", mesh="(1,4)", n_buckets=n_buckets,
           keys_shards=shard_layout(live.state.keys),
           state_bytes=tree_bytes(live.state),
           backend_compile_s=round(_compile_s[0], 3), windows=n_windows,
           blocks=n_windows * DEPTH, valid=int(v_live.sum()),
           identical_to_oracle=True,
           smoke_wall_s=round(time.perf_counter() - t0, 3))
    del live, oracle

    # Four channels over `data`.
    _compile_s[0] = 0.0
    t0 = time.perf_counter()
    streams = [endorsed_windows(n_windows, seed=200 + 10 * c,
                                n_buckets=n_buckets) for c in range(4)]
    live = engine_bridge.MeshWindowCommitter(
        DIMS, repl, mesh=make_mesh((4, 1)), n_buckets=n_buckets,
        slots=SLOTS, n_channels=4)
    v_live, h_live = [], []
    for w in range(n_windows):
        res = live.commit_windows(
            jnp.stack([s[w][0] for s in streams]),
            jnp.stack([s[w][1] for s in streams]))
        v_live.append(np.asarray(res.valid))
        h_live.append(res.block_hash)
    v_live, h_live = np.stack(v_live, 1), np.stack(h_live, 1)  # (C, W, ...)
    check(v_live.all(), "channels: a transaction was invalid")
    layout = shard_layout(live.state.keys)
    for c in range(4):
        oracle = engine_bridge.MeshWindowCommitter(
            DIMS, repl, mesh=oracle_mesh, n_buckets=n_buckets, slots=SLOTS)
        v_orc, h_orc = commit_stream(oracle, streams[c])
        check(np.array_equal(v_live[c], v_orc),
              f"channels: channel {c} validity bits differ")
        check(np.array_equal(h_live[c], h_orc),
              f"channels: channel {c} block hashes differ")
        compare_committers("channels", live, oracle, c)
        for get in ("ledger_head_for", "journal_head_for", "state_digest"):
            check(np.array_equal(getattr(live, get)(c),
                                 getattr(oracle, get)(0)),
                  f"channels: channel {c} {get} differs")
        del oracle
    report("four/channels", mesh="(4,1)", n_channels=4, n_buckets=n_buckets,
           state_bytes=tree_bytes([g.state for g in live.groups]),
           keys_shards=layout,
           backend_compile_s=round(_compile_s[0], 3), windows=n_windows,
           blocks_per_channel=n_windows * DEPTH, valid=int(v_live.sum()),
           identical_to_oracles=True,
           smoke_wall_s=round(time.perf_counter() - t0, 3))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-state and 4-channel paths "
                         "on four devices")
    ap.add_argument("--n-buckets", type=int, default=1 << 20,
                    help="buckets per hash table (default 2^20; smaller "
                         "only to rehearse on the CPU)")
    args = ap.parse_args(argv)

    compile_cache.enable()
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    devs = jax.devices()
    dev = devs[0]
    report("device", platform=dev.platform, device_kind=dev.device_kind,
           count=len(devs))
    if args.four_chips:
        check(len(devs) >= 4, f"--four-chips needs 4 devices, found "
                              f"{len(devs)}")
        four_chips(devs[:4], args.n_buckets)
    else:
        one_chip(dev, args.n_buckets)

    if dev.platform != "tpu":
        print(f"no TPU found (platform {dev.platform!r}): the phases ran "
              f"as a rehearsal; no result", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Fig 10 (beyond-paper) — model-axis sharded world state scaling.

FastFabric's P-I table is capped by one device's fast-memory budget when it
is replicated over the ``model`` axis (kernels/hash_table/ops.py enforces
8 MiB of VMEM per shard). launch/state_sharding partitions the buckets
across ``model`` ranks by high bucket bits, so the aggregate table grows
``model_size``x beyond the single-shard budget while every slice stays
VMEM-resident.

Measured here, per shard count m (powers of two up to the host's devices):
  * ``shard/m=..``  — fabric-step TPS with the state sharded over m ranks,
    on a table whose TOTAL size exceeds the single-shard VMEM budget
    (``fits_budget`` reports whether the per-shard slice fits);
  * ``repl/m=..``   — the replicated oracle on the same mesh/table for
    comparison (every rank carries the full table);
plus an equivalence row: sharded and replicated configs on the same round
must produce byte-identical validity bits and ledger/log heads.

Run with spare host devices to see >1 shard, e.g.:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.fig10_state_scaling
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from benchmarks import common
from repro.core import endorser, engine, types, unmarshal
from repro.kernels.hash_table import ops as ht_ops
from repro.launch import fabric_step as fs
from repro.launch.mesh import make_mesh


def _round_inputs(dims: types.FabricDims, n: int, seed: int = 0):
    eng = engine.FabricEngine(engine.EngineConfig(dims=dims,
                                                  store_blocks=False))
    props = eng.make_proposals(n, seed=seed)
    txb = endorser.execute_and_endorse(eng.endorser_state, props, dims)
    wire = unmarshal.marshal(txb, dims)
    return wire[None], txb.tx_id[None]  # (C=1, B, ...)


def _shard_counts(max_shards: int) -> list[int]:
    out, m = [], 1
    while m <= max_shards:
        out.append(m)
        m *= 2
    return out


def run(n_buckets: int, slots: int, b_round: int, iters: int,
        check_equivalence: bool = True) -> None:
    dims = types.TEST_DIMS
    n_dev = len(jax.devices())
    max_m = 1 << (n_dev.bit_length() - 1)  # largest power of two <= n_dev
    bucket_bytes = slots * (3 + dims.vw) * 4
    total_bytes = n_buckets * bucket_bytes
    common.row(
        "fig10", "table", table_mib=total_bytes / 2**20,
        vmem_budget_mib=ht_ops.VMEM_BUDGET_BYTES / 2**20,
        over_budget=total_bytes > ht_ops.VMEM_BUDGET_BYTES,
    )

    for m in _shard_counts(max_m):
        if b_round % m or n_buckets % m:
            continue
        mesh = make_mesh((1, m))
        wire, ids = _round_inputs(dims, b_round)
        for label, cfg in (
            ("shard", fs.FASTFABRIC_SHARDED_STEP),
            ("repl", fs.FASTFABRIC_STEP),
        ):
            state = fs.create_mesh_state(1, dims, n_buckets=n_buckets,
                                         slots=slots)
            step = jax.jit(fs.make_fabric_step(dims, cfg, mesh))
            t = common.timed(lambda: step(state, wire, ids), iters=iters)
            per_rank = total_bytes // m if label == "shard" else total_bytes
            common.row(
                "fig10", f"{label}/m={m}", tps=b_round / t,
                step_ms=1e3 * t, bytes_per_rank_mib=per_rank / 2**20,
                fits_budget=per_rank <= ht_ops.VMEM_BUDGET_BYTES,
            )

    if check_equivalence:
        # Acceptance: byte-identical validity bits and ledger/log heads.
        mesh = make_mesh((1, max_m))
        wire, ids = _round_inputs(dims, b_round, seed=1)
        outs = {}
        for label, cfg in (("shard", fs.FASTFABRIC_SHARDED_STEP),
                           ("repl", fs.FASTFABRIC_STEP)):
            state = fs.create_mesh_state(1, dims, n_buckets=n_buckets,
                                         slots=slots)
            step = jax.jit(fs.make_fabric_step(dims, cfg, mesh))
            st2, valid = step(state, wire, ids)
            outs[label] = (np.asarray(valid), np.asarray(st2.ledger_head),
                           np.asarray(st2.log_head))
        same = all(
            np.array_equal(a, b) for a, b in zip(outs["shard"], outs["repl"])
        )
        assert same, "sharded and replicated step outputs diverged"
        common.row("fig10", f"equivalence/m={max_m}", identical=same)


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    # Default table: 64 Ki buckets x 8 slots x (3+4) words = 14 MiB total,
    # beyond the 8 MiB single-shard budget; 2+ shards bring each slice under.
    p.add_argument("--n-buckets", type=int, default=1 << 16)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--b-round", type=int, default=256)
    p.add_argument("--iters", type=int, default=3)
    args = p.parse_args(argv)
    run(args.n_buckets, args.slots, args.b_round, args.iters)


if __name__ == "__main__":
    main()
    common.print_csv()

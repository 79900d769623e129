"""Fig 11 (beyond-paper) — device-side block pipeline scaling.

FastFabric's P-II peer keeps many blocks in flight; the mesh step's
``pipeline_depth`` (repro/pipeline) takes a window of D blocks per
invocation, batching the consensus all-gather and the routed cross-shard
MVCC read-version gather to ONE collective each per window instead of one
per block, while commits still apply in block order (byte-identical to the
depth-1 oracle).

Measured per depth D in {1, 2, 4, 8} on replicated and sharded state:
  * ``repl/d=..`` / ``shard/d=..`` — TPS over a D-block window (depth 1
    commits the same blocks through D sequential step invocations);
  * ``coll_per_block`` / ``allreduce_per_block`` / ``allgather_per_block``
    — collective-instruction counts per block, read from the compiled
    dry-run HLO with trip counts multiplied out (launch/hlo_cost, the same
    analyzer roofline.py consumes). The sharded path must show the routed
    gather amortizing: one all-reduce per *window*, not per block;
  * ``commit_scatters`` — state-commit scatter passes in the compiled
    program (scatter instructions / 3 planes, trip-count corrected). The
    fused window commit means exactly ONE per window at any depth — this
    is asserted, not just reported (the pre-fusion schedule paid D);
  * ``repl-ovf/..`` / ``shard-ovf/..`` — the same sweep on a deliberately
    OVERFLOWING table (capacity far below the window's write set), where
    the planner must poison dropped-insert repairs; equivalence to the
    depth-1 oracle is asserted there too and the ``overflow`` column
    records the latched sticky flag;
plus equivalence rows: the deepest pipelined config must be
byte-identical to the depth-1 oracle on validity bits, log/ledger/journal
heads, the sticky overflow flag, and state arrays.

Run with spare host devices to see real routed collectives, e.g.:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.fig11_pipeline
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.analysis import checks as contract_checks
from repro.analysis import contracts
from repro.core import endorser, engine, types, unmarshal
from repro.launch import fabric_step as fs
from repro.launch import hlo_cost
from repro.launch.mesh import make_mesh

# The fused-commit budget comes from the committed program contracts
# (src/repro/analysis/contracts.json) — the same clause the analysis
# gate enforces on every fabric_step program, so an intentional change
# is amended in ONE reviewed file, not here and there.
COMMIT_SCATTER_PASSES = contracts.commit_scatter_passes()


def _window_inputs(dims: types.FabricDims, depth: int, b_round: int,
                   seed: int = 0):
    """A window of ``depth`` blocks of ``b_round`` endorsed transfers each,
    endorsed against a shared replica so later blocks are consistent."""
    eng = engine.FabricEngine(engine.EngineConfig(dims=dims,
                                                  store_blocks=False))
    wires, idss = [], []
    for k in range(depth):
        props = eng.make_proposals(b_round, seed=seed + 7 * k)
        txb = endorser.execute_and_endorse(eng.endorser_state, props, dims)
        wires.append(unmarshal.marshal(txb, dims))
        idss.append(txb.tx_id)
    return jnp.stack(wires), jnp.stack(idss)  # (D, B, WB), (D, B, 2)


def _hlo_counts(jstep, state, wire, ids, nb_local: int, slots: int
                ) -> tuple[dict, float, int]:
    """(collective counts, compiled-HLO scatter count, commit scatter
    passes) of the compiled step. Collectives are trip-count corrected
    (instructions inside scans multiplied out). Commit passes come from
    repro.analysis.checks.table_scatter_passes — the same StableHLO
    counter the contracts gate runs (counted there because CPU XLA
    expands scatters into loops before the final HLO; TPU keeps them,
    and hlo_cost's compiled-HLO ``scatter_count`` is reported
    alongside). Lowering through the same jit wrapper the timing loop
    uses, so each depth compiles exactly once."""
    lowered = jstep.lower(state, wire, ids)
    an = hlo_cost.analyze(lowered.compile().as_text())
    commit_passes = contract_checks.table_scatter_passes(
        lowered.as_text(), nb_local, slots)
    return ({op: v["count"] for op, v in an["collectives"].items()},
            an["scatter_count"], commit_passes)


def _run_depth(dims, mesh, label: str, cfg, depth: int, b_round: int,
               n_buckets: int, iters: int, slots: int = 8):
    wire, ids = _window_inputs(dims, depth, b_round)
    state = fs.create_mesh_state(1, dims, n_buckets=n_buckets, slots=slots)
    dcfg = dataclasses.replace(cfg, pipeline_depth=depth)
    jstep = jax.jit(fs.make_fabric_step(dims, dcfg, mesh))
    nb_local = n_buckets // (mesh.shape["model"] if cfg.shard_state else 1)
    if depth == 1:
        def run():
            # Chain the state block-to-block: this is the real sequential
            # depth-1 path (unchained invocations would be data-independent
            # and async dispatch could overlap them, flattering the
            # baseline the pipeline is measured against).
            st, outs = state, []
            for k in range(wire.shape[0]):
                st, v = jstep(st, wire[k][None], ids[k][None])
                outs.append(v)
            return st, outs

        colls, scat, commits = _hlo_counts(
            jstep, state, wire[0][None], ids[0][None], nb_local, slots)
        n_blocks_compiled = 1
    else:
        def run():
            return jstep(state, wire[None], ids[None])

        colls, scat, commits = _hlo_counts(
            jstep, state, wire[None], ids[None], nb_local, slots)
        n_blocks_compiled = depth
    # The warmup execution doubles as the overflow-flag read (an extra
    # post-timing window run just for one scalar would lengthen the sweep).
    # The field is per-channel lane words ((C, LANES) u32); the row keeps
    # a 0/1 health flag.
    ovf = int(np.asarray(
        jax.block_until_ready(run())[0].overflow)[0].any())
    samples = common.timed_samples(run, warmup=0, iters=iters)
    t = float(np.median(samples))
    # Per-block commit latency percentiles: a window's blocks retire
    # together, so each iteration contributes its amortized wall/D once
    # per block.
    lat = common.latency_hist(
        [s / depth for s in samples for _ in range(depth)])
    total = sum(colls.values())
    # Acceptance: the fused window commit issues exactly the contracted
    # scatter passes (3 planes: keys/versions/values = 1 pass) per
    # compiled program — the pre-fusion schedule paid one per block,
    # i.e. D per window. Budget from contracts.json, clause
    # [programs.fabric_step/*.commit_scatter_passes].
    assert commits == COMMIT_SCATTER_PASSES, (
        f"{label}/d={depth}: expected {COMMIT_SCATTER_PASSES} fused "
        f"commit scatter pass(es) per "
        f"{'window' if depth > 1 else 'block'}, compiled program has "
        f"{commits}"
    )
    common.row(
        "fig11", f"{label}/d={depth}",
        tps=depth * b_round / t, window_ms=1e3 * t,
        coll_per_block=total / n_blocks_compiled,
        allreduce_per_block=colls.get("all-reduce", 0) / n_blocks_compiled,
        allgather_per_block=colls.get("all-gather", 0) / n_blocks_compiled,
        commit_scatters=commits,
        scatter_count_hlo=scat,
        overflow=ovf,
        **common.percentile_cols(lat),
    )


def _check_equivalence(dims, mesh, cfg, depth: int, b_round: int,
                       n_buckets: int, label: str, slots: int = 8) -> None:
    """Acceptance: pipelined == D sequential depth-1 invocations, byte for
    byte (validity bits, log/ledger/journal heads, block_no, the sticky
    overflow flag, and state) — including on overflowing tables."""
    wire, ids = _window_inputs(dims, depth, b_round, seed=3)
    st1 = fs.create_mesh_state(1, dims, n_buckets=n_buckets, slots=slots)
    step1 = jax.jit(fs.make_fabric_step(
        dims, dataclasses.replace(cfg, pipeline_depth=1), mesh))
    valids = []
    for k in range(depth):
        st1, v = step1(st1, wire[k][None], ids[k][None])
        valids.append(np.asarray(v)[0])
    std = fs.create_mesh_state(1, dims, n_buckets=n_buckets, slots=slots)
    stepd = jax.jit(fs.make_fabric_step(
        dims, dataclasses.replace(cfg, pipeline_depth=depth), mesh))
    std, vd = stepd(std, wire[None], ids[None])
    same = np.array_equal(np.stack(valids), np.asarray(vd)[0]) and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(st1, std)
    )
    assert same, f"pipelined {label} d={depth} diverged from depth-1 oracle"
    common.row("fig11", f"equivalence/{label}/d={depth}", identical=same,
               overflow=int(np.asarray(std.overflow)[0].any()))


def _obs_overhead(dims, mesh, cfg, depth: int, b_round: int,
                  n_buckets: int, iters: int,
                  obs_dir: str | None = None) -> None:
    """Instrumentation cost at the deepest pipeline: the SAME window
    committed through MeshWindowCommitter with obs detached vs attached
    (window spans + counters on the hot path). The acceptance bar is
    <= 2% TPS — spans sync only at edges the uninstrumented path already
    syncs (commit_window materializes the chain hashes), so the delta is
    null calls and counter arithmetic.

    With ``obs_dir`` the obs-on run dumps trace.jsonl, trace_chrome.json
    and metrics.json there (the CI smoke artifact)."""
    import os

    from repro import obs as obs_mod
    from repro.pipeline.engine_bridge import MeshWindowCommitter

    wire, ids = _window_inputs(dims, depth, b_round)
    dcfg = dataclasses.replace(cfg, pipeline_depth=depth)
    tps, samples = {}, {}
    handles = {"off": obs_mod.Obs.disabled(), "on": obs_mod.Obs.enabled()}
    for mode, obs in handles.items():
        wc = MeshWindowCommitter(dims, dcfg, mesh, n_buckets=n_buckets)
        if obs.on:
            wc.attach_obs(obs)

        def run_once():
            wc.commit_window(wire, ids)
            return wc.state.ledger_head

        # warmup=2: the first call compiles for the freshly created
        # (unsharded) state, the second for the step's mesh-sharded output
        # layout; steady state starts at the third.
        samples[mode] = common.timed_samples(
            run_once, warmup=2, iters=max(iters, 9))
        tps[mode] = depth * b_round / float(np.median(samples[mode]))
    overhead = 100.0 * (1.0 - tps["on"] / tps["off"])
    on = handles["on"]
    m = on.registry.collect()
    # Percentiles over the TIMED windows only (the registry histogram also
    # holds the warmup/compile windows — right for a live engine, noise
    # for an overhead row).
    lat = common.latency_hist(
        [s / depth for s in samples["on"] for _ in range(depth)])
    # CI keys the fused-commit contract on every non-equivalence /d= row,
    # so this row measures it too — same counting as the depth sweep (one
    # scatter pass per compiled window program).
    nb_local = (n_buckets // mesh.shape["model"] if dcfg.shard_state
                else n_buckets)
    hlo_args = ((wc.state, wire[0][None], ids[0][None]) if depth == 1
                else (wc.state, wire[None], ids[None]))
    _, _, commits = _hlo_counts(wc._step_for(depth, (0,)), *hlo_args,
                                nb_local, 8)
    assert commits == COMMIT_SCATTER_PASSES, (
        f"obs-overhead/d={depth}: expected {COMMIT_SCATTER_PASSES} fused "
        f"commit scatter pass(es), compiled program has {commits}"
    )
    common.row(
        "fig11", f"obs-overhead/d={depth}",
        tps=tps["on"], tps_obs_off=tps["off"],
        overhead_pct=overhead,
        window_commits=m.get("window.commits", 0),
        commit_scatters=commits,
        **common.percentile_cols(lat),
    )
    if obs_dir is not None:
        os.makedirs(obs_dir, exist_ok=True)
        on.tracer.dump_jsonl(os.path.join(obs_dir, "trace.jsonl"))
        on.tracer.dump_chrome(os.path.join(obs_dir, "trace_chrome.json"))
        import json

        with open(os.path.join(obs_dir, "metrics.json"), "w") as f:
            json.dump(m, f, indent=1)
        # The CI smoke contract: the trace holds steady-phase spans and
        # the registry counted the committed windows.
        steady = [r for r in on.tracer.records()
                  if r["name"] == "window.steady"]
        assert len(steady) >= 1, "no window.steady span in the obs trace"
        assert m["window.commits"] > 0, "window.commits is empty"


def _txtrace_overhead(dims, mesh, cfg, depth: int, b_round: int,
                      n_buckets: int, iters: int) -> None:
    """Tx-lifecycle tracing cost on the ENGINE round path at the deepest
    pipeline: the same proposal stream through two engines sharing one
    window-committer shape — obs off (NullTxTracer: no sidecar, no
    stamps) vs obs on (tx-id sidecar + per-block phase stamps folded into
    the tx.phase.* histograms + outcome counters + lifecycle ring).
    Phase timestamps ride sync edges the PR 6 spans already forced, so
    the bar matches the obs-overhead row: the delta is host-side
    arithmetic, not new device syncs."""
    from repro.obs import SLOConfig
    from repro.pipeline.engine_bridge import MeshWindowCommitter

    dcfg = dataclasses.replace(cfg, pipeline_depth=depth)
    tps = {}
    m_on = {}
    wc_on = None
    for mode in ("off", "on"):
        wc = MeshWindowCommitter(dims, dcfg, mesh, n_buckets=n_buckets)
        eng = engine.FabricEngine(
            engine.EngineConfig(
                dims=dims,
                orderer=dataclasses.replace(engine.FASTFABRIC.orderer,
                                            block_size=b_round),
                obs=(mode == "on"), slo=SLOConfig(commit_p95_s=60.0),
                store_blocks=False,
            ),
            window_committer=wc,
        )
        n = depth * b_round  # one full window per round
        for w in range(2):  # compile: fresh state, then sharded layout
            eng.run_round(eng.make_proposals(n, seed=90 + w))
        samples = []
        for i in range(max(iters, 9)):
            samples.append(eng.run_round(
                eng.make_proposals(n, seed=i)).wall_s)
        tps[mode] = n / float(np.median(samples))
        if mode == "on":
            m_on = eng.metrics()
            wc_on = wc
    overhead = 100.0 * (1.0 - tps["on"] / tps["off"])
    # The fused-commit contract is keyed on every non-equivalence /d= row
    # (tests + CI artifact assert), so this row measures it too — same
    # counting as the depth sweep, on the committer the traced engine
    # actually drove.
    wire, ids = _window_inputs(dims, depth, b_round)
    nb_local = (n_buckets // mesh.shape["model"] if dcfg.shard_state
                else n_buckets)
    hlo_args = ((wc_on.state, wire[0][None], ids[0][None]) if depth == 1
                else (wc_on.state, wire[None], ids[None]))
    _, _, commits = _hlo_counts(wc_on._step_for(depth, (0,)), *hlo_args,
                                nb_local, 8)
    assert commits == COMMIT_SCATTER_PASSES, (
        f"txtrace-overhead/d={depth}: expected {COMMIT_SCATTER_PASSES} "
        f"fused commit scatter pass(es), compiled program has {commits}"
    )
    common.row(
        "fig11", f"txtrace-overhead/d={depth}",
        tps=tps["on"], tps_obs_off=tps["off"],
        overhead_pct=overhead,
        commit_scatters=commits,
        txs_valid=m_on.get("tx.outcome{outcome=valid}", 0),
        **common.txphase_cols(m_on),
    )


def run(depths: list[int], b_round: int, n_buckets: int, iters: int,
        ovf_buckets: int = 16, obs_dir: str | None = None) -> None:
    dims = types.TEST_DIMS
    n_dev = len(jax.devices())
    m = 1 << (n_dev.bit_length() - 1)  # largest power of two <= n_dev
    while b_round % m or n_buckets % m or ovf_buckets % m:
        m //= 2
    mesh = make_mesh((1, m))
    common.row("fig11", "mesh", model_ranks=m, b_round=b_round)

    for label, cfg in (("repl", fs.FASTFABRIC_STEP),
                       ("shard", fs.FASTFABRIC_SHARDED_STEP)):
        for d in depths:
            _run_depth(dims, mesh, label, cfg, d, b_round, n_buckets, iters)
        _check_equivalence(dims, mesh, cfg, max(depths), b_round, n_buckets,
                           label)
        # Deliberately overflowing table: capacity ovf_buckets * 2 slots
        # is far below the window's 2 * b_round writes per block, so
        # inserts drop mid-window and the overflow-exact repair is on the
        # measured path (and its equivalence asserted).
        for d in depths:
            _run_depth(dims, mesh, f"{label}-ovf", cfg, d, b_round,
                       ovf_buckets, iters, slots=2)
        _check_equivalence(dims, mesh, cfg, max(depths), b_round,
                           ovf_buckets, f"{label}-ovf", slots=2)
    # Instrumentation overhead at the deepest pipeline (replicated state:
    # the highest-TPS configuration is where overhead shows first). Only
    # this obs-on run exports the trace/metrics artifacts.
    _obs_overhead(dims, mesh, fs.FASTFABRIC_STEP, max(depths), b_round,
                  n_buckets, iters, obs_dir=obs_dir)
    # Tx-lifecycle tracing cost on the engine round path, same depth —
    # the PR 8 counterpart of the span-overhead row above.
    _txtrace_overhead(dims, mesh, fs.FASTFABRIC_STEP, max(depths), b_round,
                      n_buckets, iters)


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--depths", type=int, nargs="+", default=[1, 2, 4, 8])
    p.add_argument("--b-round", type=int, default=128)
    p.add_argument("--n-buckets", type=int, default=1 << 12)
    p.add_argument("--ovf-buckets", type=int, default=16,
                   help="bucket count of the deliberately overflowing "
                        "table (2 slots each)")
    p.add_argument("--iters", type=int, default=3)
    p.add_argument("--json", default=None,
                   help="write the result rows as JSON to this path")
    p.add_argument("--obs-dir", default=None,
                   help="dump the obs-on run's trace.jsonl / "
                        "trace_chrome.json / metrics.json here")
    args = p.parse_args(argv)
    run(args.depths, args.b_round, args.n_buckets, args.iters,
        ovf_buckets=args.ovf_buckets, obs_dir=args.obs_dir)
    if args.json:
        common.dump_json(args.json)


if __name__ == "__main__":
    main()
    common.print_csv()

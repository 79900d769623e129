"""Device-side block pipeline: the depth-D windowed step must be
byte-identical to D sequential invocations of the depth-1 oracle —
validity bits, log/ledger/journal heads, block numbers, and state arrays —
on replicated AND sharded state, including windows with cross-block
read-your-write dependencies (block k reads a key block k-1 wrote) and
windows whose blocks OVERFLOW their buckets (a dropped insert must not be
counted as a version bump, and the sticky overflow flag must latch
identically on both paths).

Runs on whatever host devices exist: with 1 device the sharded path is
exercised degenerately; the CI multi-device job
(XLA_FLAGS=--xla_force_host_platform_device_count=8) runs the >=2-rank
cases for real.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import endorser, engine, types, unmarshal
from repro.launch import fabric_step as fs
from repro.pipeline import engine_bridge
from repro.launch.mesh import make_mesh

DIMS = types.TEST_DIMS
N_DEV = len(jax.devices())
MAX_M = 1 << (N_DEV.bit_length() - 1)  # largest power of two <= N_DEV

multi_device = pytest.mark.skipif(
    N_DEV < 2, reason="needs >=2 devices (CI multi-device job)"
)


def _window(depth, n=32, seed=0, *, read_your_write=False,
            endorser_buckets=None, endorser_slots=8):
    """A (D, B, ...) window of endorsed blocks. With ``read_your_write``
    every block touches the SAME accounts, so block k's reads expect the
    versions block k-1's commits produced — valid only if the pipeline
    preserves commit order. ``endorser_buckets``/``endorser_slots`` shrink
    the endorser replica (overflow tests pair it with an equally tiny peer
    table so both drop the same inserts)."""
    eng = engine.FabricEngine(engine.EngineConfig(
        dims=DIMS, store_blocks=False,
        n_buckets=endorser_buckets or (1 << 12),
        slots=endorser_slots,
    ))
    wires, idss = [], []
    for k in range(depth):
        props = eng.make_proposals(
            n, seed=seed if read_your_write else seed + 11 * k
        )
        if read_your_write:
            props = props._replace(
                nonce=props.nonce + jnp.uint32(k * 100003)
            )
        txb = endorser.execute_and_endorse(eng.endorser_state, props, DIMS)
        wires.append(unmarshal.marshal(txb, DIMS))
        idss.append(txb.tx_id)
        if read_your_write:
            eng.endorser_state = endorser.apply_validated(
                eng.endorser_state, txb, jnp.ones(n, bool)
            )
    return jnp.stack(wires), jnp.stack(idss)


def _oracle(cfg, mesh, wire, ids, n_buckets=256, slots=8):
    """Depth-1 reference: one invocation per block, sequentially."""
    st = fs.create_mesh_state(1, DIMS, n_buckets=n_buckets, slots=slots)
    step = jax.jit(fs.make_fabric_step(
        DIMS, dataclasses.replace(cfg, pipeline_depth=1), mesh))
    valids = []
    for k in range(wire.shape[0]):
        st, v = step(st, wire[k][None], ids[k][None])
        valids.append(np.asarray(v)[0])
    return jax.tree.map(np.asarray, st), np.stack(valids)


def _pipelined(cfg, mesh, wire, ids, depth, n_buckets=256, slots=8):
    st = fs.create_mesh_state(1, DIMS, n_buckets=n_buckets, slots=slots)
    step = jax.jit(fs.make_fabric_step(
        DIMS, dataclasses.replace(cfg, pipeline_depth=depth), mesh))
    st, v = step(st, wire[None], ids[None])
    return jax.tree.map(np.asarray, st), np.asarray(v)[0]


def _assert_identical(cfg, mesh, wire, ids, depth, n_buckets=256, slots=8):
    st1, v1 = _oracle(cfg, mesh, wire, ids, n_buckets, slots)
    st2, v2 = _pipelined(cfg, mesh, wire, ids, depth, n_buckets, slots)
    np.testing.assert_array_equal(v1, v2)
    for name, a, b in zip(fs.FabricMeshState._fields, st1, st2):
        np.testing.assert_array_equal(a, b, err_msg=name)
    return v2, st2


# ------------------------------------------------------- mesh axis types


def test_mesh_axes_auto_chain_hashes_over_step_output():
    """The one mesh constructor makes every axis Auto, so a step output
    (sharded over the mesh) and host-built arrays vmap together in the
    store-chain hash. JAX >= 0.9 defaults ``jax.make_mesh`` axes to
    Explicit, which made this raise "inconsistent axis specs: None vs
    data"; a change of that default, or of the constructor, fails here."""
    mesh = make_mesh((1, 1))
    assert set(mesh.axis_types) == {jax.sharding.AxisType.Auto}
    depth = 2
    wire, ids = _window(depth, n=16, seed=3)
    step = jax.jit(fs.make_fabric_step(
        DIMS, fs.FabricStepConfig(pipeline_depth=depth), mesh),
        donate_argnums=(0,))
    st = fs.create_mesh_state(1, DIMS, n_buckets=256, mesh=mesh)
    st, valid = step(st, wire[None], ids[None])
    bno0 = st.block_no - jnp.uint32(depth)  # output of the step, on mesh
    prev = jnp.stack([jnp.zeros((2,), jnp.uint32)])  # built on the host
    prevs, hashes = engine_bridge._chain_hashes_multi(
        prev, bno0, wire[None], valid)
    want_prevs, want_hashes = engine_bridge._chain_hashes(
        jnp.zeros((2,), jnp.uint32), jnp.uint32(0), wire, valid[0])
    np.testing.assert_array_equal(np.asarray(prevs[0]),
                                  np.asarray(want_prevs))
    np.testing.assert_array_equal(np.asarray(hashes[0]),
                                  np.asarray(want_hashes))


# ------------------------------------------------------- oracle equivalence


@pytest.mark.parametrize("depth", [2, 8])
def test_pipelined_equals_oracle_replicated(depth):
    mesh = make_mesh((1, 1))
    wire, ids = _window(depth, n=16, seed=depth)
    v, st = _assert_identical(fs.FASTFABRIC_STEP, mesh, wire, ids, depth)
    assert int(v.sum()) == v.size  # disjoint accounts: all valid
    assert not np.asarray(st.overflow[0]).any()  # amply sized: flag clear


def test_pipelined_equals_oracle_sharded_degenerate():
    mesh = make_mesh((1, 1))
    wire, ids = _window(2, n=16, seed=9)
    _assert_identical(fs.FASTFABRIC_SHARDED_STEP, mesh, wire, ids, 2)


@multi_device
@pytest.mark.parametrize("depth", [2, 4])
def test_pipelined_equals_oracle_sharded_multi_rank(depth):
    """Acceptance: depth-D window on >=2 model ranks with sharded state is
    byte-identical to the depth-1 oracle — one routed gather per window."""
    mesh = make_mesh((1, min(MAX_M, 4)))
    wire, ids = _window(depth, n=32, seed=depth)
    _assert_identical(fs.FASTFABRIC_SHARDED_STEP, mesh, wire, ids, depth)


def test_pipelined_equals_oracle_baseline_config():
    """The serial fabric-1.2 folds (non-pipelined consensus, sequential
    commit) pipeline too: the schedule reuses the exact per-block math."""
    mesh = make_mesh((1, 1))
    wire, ids = _window(2, n=16, seed=5)
    _assert_identical(fs.FABRIC_V12_STEP, mesh, wire, ids, 2)


# ------------------------------------------- cross-block read-your-write


@pytest.mark.parametrize("depth", [2, 4])
def test_cross_block_read_your_write_commit_order(depth):
    """Block k reads keys block k-1 wrote (expecting the bumped version):
    every transaction is valid ONLY if commits apply in block order and
    the batched fill-time gather is repaired with in-window writes."""
    mesh = make_mesh((1, 1))
    wire, ids = _window(depth, n=16, seed=1, read_your_write=True)
    v, _ = _assert_identical(fs.FASTFABRIC_STEP, mesh, wire, ids, depth)
    assert int(v.sum()) == v.size  # stale fill-time versions would zero
    # the later blocks; all-valid proves the in-window repair is exact.


@multi_device
def test_cross_block_read_your_write_sharded_multi_rank():
    mesh = make_mesh((1, min(MAX_M, 4)))
    wire, ids = _window(4, n=32, seed=2, read_your_write=True)
    v, _ = _assert_identical(fs.FASTFABRIC_SHARDED_STEP, mesh, wire, ids, 4)
    assert int(v.sum()) == v.size


def test_replayed_window_invalidated():
    """Replaying the same window leaves every version stale (the pipeline
    does not leak fill-time versions into the second window)."""
    mesh = make_mesh((1, 1))
    wire, ids = _window(2, n=16, seed=7)
    st = fs.create_mesh_state(1, DIMS, n_buckets=256)
    step = jax.jit(fs.make_fabric_step(
        DIMS, dataclasses.replace(fs.FASTFABRIC_STEP, pipeline_depth=2),
        mesh))
    st, v1 = step(st, wire[None], ids[None])
    st, v2 = step(st, wire[None], ids[None])
    assert int(np.asarray(v1).sum()) == 32
    assert int(np.asarray(v2).sum()) == 0


# ------------------------------- overflow windows (fused commit, exact)


def _overflow_window(depth, n=16, seed=1):
    """Read-your-write blocks against an endorser replica as tiny as the
    peer table below (8 buckets x 2 slots): each block's 2*n writes exceed
    the 16 slots, so inserts drop mid-window and later blocks read keys
    whose source insert was dropped — the repairs that must be poisoned."""
    return _window(depth, n=n, seed=seed, read_your_write=True,
                   endorser_buckets=8, endorser_slots=2)


@pytest.mark.parametrize("depth", [2, 4, 8])
def test_overflow_window_equals_oracle_replicated(depth):
    """Acceptance: overflowing windows stay byte-identical to the depth-1
    oracle (the old window write log counted dropped inserts as version
    bumps, so any in-window read of a dropped key diverged)."""
    mesh = make_mesh((1, 1))
    wire, ids = _overflow_window(depth)
    v, st = _assert_identical(fs.FASTFABRIC_STEP, mesh, wire, ids, depth,
                              n_buckets=8, slots=2)
    assert np.asarray(st.overflow[0]).any()  # sticky bitmask latched on both paths
    assert 0 < int(v.sum()) < v.size  # poisoned repairs invalidate SOME
    # transactions (all-valid would mean the drop was never observed,
    # all-invalid that the window never committed anything)


@pytest.mark.parametrize("depth", [2, 4])
def test_overflow_window_equals_oracle_sharded_degenerate(depth):
    mesh = make_mesh((1, 1))
    wire, ids = _overflow_window(depth)
    _, st = _assert_identical(fs.FASTFABRIC_SHARDED_STEP, mesh, wire, ids,
                              depth, n_buckets=8, slots=2)
    assert np.asarray(st.overflow[0]).any()


@multi_device
@pytest.mark.parametrize("depth", [2, 4])
def test_overflow_window_equals_oracle_sharded_multi_rank(depth):
    """Overflow accounting must survive the routed path: free-slot counts
    gather from the owner shards and the fused commit applies owner-side,
    yet the validity bits and state stay byte-identical to the oracle —
    including the per-shard overflow BITMASK (bit m == shard m filled),
    which the depth-1 routed commit and the pipelined planner must agree
    on without an extra collective."""
    mesh = make_mesh((1, min(MAX_M, 4)))
    wire, ids = _overflow_window(depth, n=16)
    _, st = _assert_identical(fs.FASTFABRIC_SHARDED_STEP, mesh, wire, ids,
                              depth, n_buckets=8, slots=2)
    assert np.asarray(st.overflow[0]).any()


def test_overflow_window_equals_oracle_sequential_baseline():
    """The sequential-commit baseline bumps every duplicate occurrence and
    fills slots in write order; the planner must mirror that flavor too."""
    mesh = make_mesh((1, 1))
    wire, ids = _overflow_window(4)
    _, st = _assert_identical(fs.FABRIC_V12_STEP, mesh, wire, ids, 4,
                              n_buckets=8, slots=2)
    assert np.asarray(st.overflow[0]).any()


def test_overflow_window_store_chain_and_journal():
    """Poisoned repairs must never advance heads incorrectly: the store
    chain and the mesh journal head of an overflowing round are identical
    whether blocks commit one at a time or as one fused window."""
    from repro.core import ledger

    wire, ids = _overflow_window(4)
    results = {}
    for depth in (1, 4):
        wc = engine_bridge.MeshWindowCommitter(
            DIMS, fs.FabricStepConfig(pipeline_depth=depth),
            n_buckets=8, slots=2,
        )
        outs = []
        if depth == 1:
            for k in range(4):
                outs.append(wc.commit_window(wire[k][None], ids[k][None]))
        else:
            outs.append(wc.commit_window(wire, ids))
        store = ledger.BlockStore()
        bno = 0
        for out in outs:
            for k in range(out.valid.shape[0]):
                store.submit(bno, out.prev_hash[k], out.block_hash[k],
                             wire[bno], out.valid[k])
                bno += 1
        store.drain()
        assert store.verify_chain()
        results[depth] = (store, wc)
    s1, wc1 = results[1]
    s4, wc4 = results[4]
    assert wc1.overflow and wc4.overflow
    np.testing.assert_array_equal(wc1.journal_head, wc4.journal_head)
    np.testing.assert_array_equal(wc1.state_digest(), wc4.state_digest())
    for a, b in zip(s1.chain, s4.chain):
        assert a.block_no == b.block_no
        np.testing.assert_array_equal(a.block_hash, b.block_hash)
        np.testing.assert_array_equal(a.valid, b.valid)


def test_engine_overflow_reports_unhealthy(tmp_path):
    """Satellite: an overflowed peer must say so. Both engine paths — the
    per-block committer and the mesh window committer — latch the sticky
    flag and verify() reports overflow_ok=False while the chain itself
    still verifies (the ledger is consistent; the STATE capacity is not)."""
    cfg = engine.EngineConfig(dims=DIMS, n_buckets=8, slots=2)
    e = engine.FabricEngine(cfg)
    e.run_round(e.make_proposals(200, seed=0))
    out = e.verify()
    assert out["overflow_ok"] is False
    assert out["chain_ok"] is True

    wc = engine_bridge.MeshWindowCommitter(
        DIMS, fs.FabricStepConfig(pipeline_depth=4), n_buckets=8, slots=2)
    e_win = engine.FabricEngine(cfg, window_committer=wc)
    e_win.run_round(e_win.make_proposals(200, seed=0))
    out = e_win.verify()
    assert out["overflow_ok"] is False
    assert out["chain_ok"] is True
    # An amply sized engine keeps the bill of health.
    e_ok = engine.FabricEngine(engine.EngineConfig(dims=DIMS))
    e_ok.run_round(e_ok.make_proposals(200, seed=0))
    assert e_ok.verify()["overflow_ok"] is True


# ------------------------------------------------------------ input guards


def test_pipelined_rejects_wrong_window_shape():
    mesh = make_mesh((1, 1))
    wire, ids = _window(2, n=16)
    step = fs.make_fabric_step(
        DIMS, dataclasses.replace(fs.FASTFABRIC_STEP, pipeline_depth=4),
        mesh)
    st = fs.create_mesh_state(1, DIMS, n_buckets=256)
    with pytest.raises(ValueError, match="pipeline_depth=4"):
        step(st, wire[None], ids[None])


# -------------------------------------------------- engine window committer


def test_engine_window_committer_matches_per_block_engine(tmp_path):
    """core/engine.py handing the mesh step a window of blocks per round
    must retire the same blocks as the per-block committer path: same
    valid bits, same store chain, and all durability checks green."""
    cfg = engine.EngineConfig(dims=DIMS, journal_dir=str(tmp_path))
    e_ref = engine.FabricEngine(cfg)
    wc = engine_bridge.MeshWindowCommitter(
        DIMS, fs.FabricStepConfig(pipeline_depth=4),
        n_buckets=cfg.n_buckets, slots=cfg.slots,
    )
    e_win = engine.FabricEngine(
        dataclasses.replace(cfg, journal_dir=str(tmp_path / "win")),
        window_committer=wc,
    )
    for rnd in range(2):
        # 600 txs / block_size 100 = 6 blocks: one full depth-4 window plus
        # a shallower 2-block remainder window.
        s_ref = e_ref.run_round(e_ref.make_proposals(600, seed=rnd))
        s_win = e_win.run_round(e_win.make_proposals(600, seed=rnd))
        assert s_ref.n_valid == s_win.n_valid == 600
        assert s_ref.n_blocks == s_win.n_blocks == 6
    out = e_win.verify()
    assert all(out.values()), out
    e_ref.store.drain()
    e_win.store.drain()
    for a, b in zip(e_ref.store.chain, e_win.store.chain):
        assert a.block_no == b.block_no
        np.testing.assert_array_equal(a.block_hash, b.block_hash)
        np.testing.assert_array_equal(a.valid, b.valid)
    # Journal heads agree between the off-path journal and the mesh state.
    np.testing.assert_array_equal(
        e_win.journal.head, wc.journal_head
    )


def test_engine_window_committer_supports_snapshots(tmp_path):
    """Snapshots used to be rejected with a window committer; the elastic
    refactor made the manifest cover the mesh-backed state instead (full
    durability coverage lives in tests/test_rebalance.py)."""
    wc = engine_bridge.MeshWindowCommitter(
        DIMS, fs.FabricStepConfig(pipeline_depth=2))
    eng = engine.FabricEngine(
        engine.EngineConfig(dims=DIMS, snapshot_every_blocks=4,
                            snapshot_dir=str(tmp_path)),
        window_committer=wc,
    )
    eng.run_round(eng.make_proposals(600, seed=0))
    assert eng.snapshots and eng.snapshots[-1].block_no >= 4
    assert eng.verify()["recovery_ok"]
    eng.store.close()


# -------------------------------------------------------------- benchmark


def test_fig11_benchmark_smoke(capsys, tmp_path):
    from benchmarks import common, fig11_pipeline

    common.ROWS.clear()
    out = tmp_path / "fig11.json"
    fig11_pipeline.main(
        ["--depths", "1", "2", "--b-round", "16", "--n-buckets", "256",
         "--iters", "1", "--json", str(out)]
    )
    names = [r["name"] for r in common.ROWS]
    assert any(n.startswith("repl/d=") for n in names)
    assert any(n.startswith("shard/d=") for n in names)
    assert any(n.startswith("equivalence/") for n in names)
    assert out.exists()
    by_name = {r["name"]: r for r in common.ROWS}
    # The deliberately overflowing rows must latch the sticky flag and
    # still pass their (internally asserted) oracle equivalence.
    assert by_name["shard-ovf/d=2"]["overflow"] == 1
    assert by_name["equivalence/shard-ovf/d=2"]["identical"]
    # Exactly ONE fused commit scatter pass per compiled program at every
    # depth (asserted inside _run_depth too; pinned here for the artifact).
    for n, r in by_name.items():
        if "/d=" in n and "equivalence" not in n:
            assert r["commit_scatters"] == 1, (n, r)
    # Depth 2 halves the collective instructions per block (one window
    # gather instead of one per block) — visible even degenerately as the
    # compiled-program count, and as real collectives on the CI
    # multi-device job.
    if N_DEV >= 2:
        assert (by_name["shard/d=2"]["coll_per_block"]
                < by_name["shard/d=1"]["coll_per_block"])

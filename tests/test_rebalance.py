"""Elastic sharded world state: overflow-driven resize with journal
re-anchoring and per-shard snapshot recovery.

The pins, mirroring the PR-2/PR-3 oracle discipline:

  * GROW is ARRAY-exact: a channel that splits mid-run ends byte-identical
    (state arrays, digest-tree head, ledger/journal heads, validity bits,
    store chain) to an oracle that ran the whole workload on the
    post-split layout from block 0 — at pipeline depths 1 and 4,
    replicated and sharded.
  * The butterfly neighbor-exchange resize inside shard_map equals the
    host-side ``world_state.resize`` of the merged table, shard by shard.
  * Journal re-anchor records make verify/replay cross resize epochs and
    survive spill + cold load; tampering with any re-anchor field breaks
    the chain.
  * Per-shard recovery rebuilds ONE bucket shard from 2^epochs snapshot
    parts (never the full table), array-exact, across grow re-anchors.
  * The engine's between-rounds policy absorbs a fill workload that
    overflows a static table, keeps every durability check green, and a
    peer that DID overflow, snapshotted and restarted still reports
    ``overflow_ok=False`` (the sticky bitmask is persisted).

Runs on whatever host devices exist; the >=2-rank cases need the CI
multi-device job (XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import endorser, engine, types, unmarshal
from repro.core import world_state as ws
from repro.launch import fabric_step as fs
from repro.launch import state_sharding
from repro.pipeline import engine_bridge
from repro.storage import journal as journal_mod
from repro.storage import recovery, snapshot
from repro.launch.mesh import make_mesh

DIMS = types.TEST_DIMS
N_DEV = len(jax.devices())
MAX_M = 1 << (N_DEV.bit_length() - 1)  # largest power of two <= N_DEV

multi_device = pytest.mark.skipif(
    N_DEV < 2, reason="needs >=2 devices (CI multi-device job)"
)


def _filled(n_buckets=256, slots=8, blocks=4, seed=0):
    """A table populated by a block history, plus the history itself."""
    rng = np.random.default_rng(seed)
    st = ws.create(n_buckets, slots, DIMS.vw)
    history = []
    for _ in range(blocks):
        wk = jnp.asarray(
            rng.integers(1, 1 << 32, (16, DIMS.wk, 2), dtype=np.uint32))
        wv = jnp.asarray(
            rng.integers(0, 1 << 32, (16, DIMS.wk, DIMS.vw),
                         dtype=np.uint32))
        valid = jnp.asarray(rng.random(16) < 0.9)
        history.append((wk, wv, valid))
        r = ws.commit_vectorized(st, wk, wv, valid)
        assert not bool(r.overflow)
        st = r.state
    return st, history


# ------------------------------------------------------------ ws.resize


def test_resize_validates_bucket_count():
    st = ws.create(64, 4, DIMS.vw)
    with pytest.raises(ValueError, match="power of two"):
        ws.resize(st, 48)


def test_resize_grow_is_array_exact_vs_post_split_history():
    """Splitting mid-history == running the whole history on the big
    table from the start, byte for byte (the insertion-order compaction
    theorem in the resize docstring)."""
    st, history = _filled(blocks=6)
    small = ws.create(256, 8, DIMS.vw)
    for wk, wv, valid in history[:3]:
        small = ws.commit_vectorized(small, wk, wv, valid).state
    res = ws.resize(small, 512)
    assert not bool(res.overflow)
    grown = res.state
    for wk, wv, valid in history[3:]:
        grown = ws.commit_vectorized(grown, wk, wv, valid).state
    oracle = ws.create(512, 8, DIMS.vw)
    for wk, wv, valid in history:
        oracle = ws.commit_vectorized(oracle, wk, wv, valid).state
    for name, a, b in zip(ws.HashState._fields, grown, oracle):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=name)


def test_resize_shrink_content_exact_and_overflow_flag():
    st, history = _filled()
    keys = jnp.concatenate([h[0].reshape(-1, 2) for h in history])
    res = ws.resize(st, 128)
    assert not bool(res.overflow)
    before, after = ws.lookup(st, keys), ws.lookup(res.state, keys)
    np.testing.assert_array_equal(
        np.asarray(before.found), np.asarray(after.found))
    np.testing.assert_array_equal(
        np.asarray(before.versions), np.asarray(after.versions))
    np.testing.assert_array_equal(
        np.asarray(before.values), np.asarray(after.values))
    # Content digest is layout-invariant across the resize.
    np.testing.assert_array_equal(
        np.asarray(ws.state_digest(st)),
        np.asarray(ws.state_digest(res.state)))
    # Shrinking far below the live entry count must raise the flag.
    tiny = ws.resize(st, 4)
    assert bool(tiny.overflow)


def test_shard_pressure_stats():
    st, _ = _filled()
    occ = np.asarray(ws.shard_occupancy(st, 4))
    assert occ.sum() == int(ws.occupancy(st))
    free = np.asarray(ws.shard_min_free(st, 4))
    assert ((0 <= free) & (free <= st.slots)).all()


def test_resize_property_partition_bijection_and_lookups():
    """Satellite: halve/double of nb_loc is a partition bijection
    (shard_of/owned_mask cover every bucket exactly once before and
    after) and lookups of all pre-resize keys return identical
    (version, value) after the resize."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st_

    @settings(max_examples=20, deadline=None)
    @given(
        nb_pow=st_.integers(min_value=4, max_value=7),
        m_pow=st_.integers(min_value=0, max_value=3),
        grow=st_.booleans(),
        seed=st_.integers(min_value=0, max_value=2**16),
    )
    def prop(nb_pow, m_pow, grow, seed):
        nb, m = 1 << nb_pow, 1 << m_pow
        new_nb = nb * 2 if grow else max(nb // 2, m)
        # Partition bijection before AND after: synthesize one key per
        # global bucket; every bucket has exactly one owner shard and
        # each shard owns exactly nb/M contiguous buckets.
        for n in (nb, new_nb):
            bkeys = jnp.stack(
                [jnp.arange(n, dtype=jnp.uint32),
                 jnp.ones(n, jnp.uint32)], axis=-1)
            owners = np.asarray(ws.shard_of(n, m, bkeys))
            counts = np.bincount(owners, minlength=m)
            assert (counts == n // m).all()
            # Contiguous high-bit ranges: owner of bucket b is b//(n/m).
            np.testing.assert_array_equal(
                owners, np.arange(n) // (n // m))
        rng = np.random.default_rng(seed)
        st = ws.create(nb, 8, DIMS.vw)
        wk = jnp.asarray(
            rng.integers(1, 1 << 32, (12, DIMS.wk, 2), dtype=np.uint32))
        wv = jnp.asarray(
            rng.integers(0, 1 << 32, (12, DIMS.wk, DIMS.vw),
                         dtype=np.uint32))
        st = ws.commit_vectorized(st, wk, wv, jnp.ones(12, bool)).state
        res = ws.resize(st, new_nb)
        if bool(res.overflow):
            return  # dropped entries: lookup identity does not apply
        keys = wk.reshape(-1, 2)
        a, b = ws.lookup(st, keys), ws.lookup(res.state, keys)
        np.testing.assert_array_equal(
            np.asarray(a.versions), np.asarray(b.versions))
        np.testing.assert_array_equal(
            np.asarray(a.values), np.asarray(b.values))

    prop()


# ------------------------------------------ sharded butterfly exchange


def _mesh_resize(full, m, new_nb_loc, nb_glob):
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh((1, m))

    def body(keys, vers, vals):
        local = ws.HashState(keys, vers, vals)
        res = state_sharding.resize_sharded(local, new_nb_loc, nb_glob, m)
        return (res.state.keys, res.state.versions, res.state.values,
                res.shard_overflow.astype(jnp.uint32)[None])

    prog = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("model"), P("model"), P("model")),
        out_specs=(P("model"), P("model"), P("model"), P("model")),
        check_vma=False,
    )
    k, v, va, ovf = jax.jit(prog)(full.keys, full.versions, full.values)
    return ws.HashState(np.asarray(k), np.asarray(v), np.asarray(va)), ovf


@multi_device
@pytest.mark.parametrize("direction", ["grow", "shrink"])
def test_resize_sharded_equals_host_resize(direction):
    """The two-ppermute butterfly exchange rebuilds exactly the table the
    host-side resize of the merged arrays produces — per shard, array for
    array — and the post-resize digest tree equals a fresh tree of the
    rebuilt table."""
    m = min(MAX_M, 4)
    nb = 256
    full, _ = _filled(n_buckets=nb, seed=3)
    nb_loc = nb // m
    new_nb_loc = nb_loc * 2 if direction == "grow" else nb_loc // 2
    got, ovf = _mesh_resize(full, m, new_nb_loc, nb)
    want = ws.resize(full, new_nb_loc * m)
    assert not np.asarray(ovf).any() and not bool(want.overflow)
    for name, a, b in zip(ws.HashState._fields, got, want.state):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=name)
    # Tree head of the resized table == fresh tree of the rebuilt table.
    def tree(state):
        sk, sv, sva = ws.split_table(
            jnp.asarray(state.keys), jnp.asarray(state.versions),
            jnp.asarray(state.values), m)
        return np.asarray(ws.shard_digest_tree(jnp.stack([
            ws.state_digest(ws.HashState(sk[i], sv[i], sva[i]))
            for i in range(m)
        ])))

    np.testing.assert_array_equal(tree(got), tree(want.state))


def test_resize_sharded_rejects_non_step():
    st = ws.create(64, 4, DIMS.vw)
    with pytest.raises(ValueError, match="2x only"):
        state_sharding.resize_sharded(st, 64, 64, 1)


# ------------------------- acceptance: mid-run split == post-split oracle


def _windows(n_windows, depth, n=16, seed=0):
    eng = engine.FabricEngine(
        engine.EngineConfig(dims=DIMS, store_blocks=False))
    outs = []
    for w in range(n_windows):
        wires, idss = [], []
        for k in range(depth):
            props = eng.make_proposals(n, seed=seed + 31 * (w * depth + k))
            txb = endorser.execute_and_endorse(
                eng.endorser_state, props, DIMS)
            wires.append(unmarshal.marshal(txb, DIMS))
            idss.append(txb.tx_id)
            eng.endorser_state = endorser.apply_validated(
                eng.endorser_state, txb, jnp.ones(n, bool))
        outs.append((jnp.stack(wires), jnp.stack(idss)))
    return outs


def _split_mid_run(shard_state, depth, m):
    """Live: 2 windows at 128 buckets, split to 256, 2 windows. Oracle:
    all 4 windows on 256 from block 0. Everything must match."""
    mesh = make_mesh((1, m))
    cfg = fs.FabricStepConfig(shard_state=shard_state, pipeline_depth=depth)
    wins = _windows(4, depth, seed=5)
    live = engine_bridge.MeshWindowCommitter(
        DIMS, cfg, mesh, n_buckets=128, slots=8)
    valid_live = []
    for w in range(2):
        valid_live.append(live.commit_window(*wins[w]).valid)
    info = live.resize(256)
    assert (info.old_n_buckets, info.new_n_buckets) == (128, 256)
    assert info.block_no == 2 * depth - 1  # the drained window boundary
    for w in range(2, 4):
        valid_live.append(live.commit_window(*wins[w]).valid)
    oracle = engine_bridge.MeshWindowCommitter(
        DIMS, cfg, mesh, n_buckets=256, slots=8)
    valid_oracle = [oracle.commit_window(*wins[w]).valid for w in range(4)]
    for a, b in zip(valid_live, valid_oracle):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name, a, b in zip(fs.FabricMeshState._fields, live.state,
                          oracle.state):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=name)
    np.testing.assert_array_equal(live.tree_head(), oracle.tree_head())
    np.testing.assert_array_equal(
        np.asarray(live.prev_hash), np.asarray(oracle.prev_hash))


@pytest.mark.parametrize("depth", [1, 4])
def test_split_mid_run_equals_post_split_oracle_replicated(depth):
    _split_mid_run(False, depth, 1)


@pytest.mark.parametrize("depth", [1, 4])
def test_split_mid_run_equals_post_split_oracle_sharded_degenerate(depth):
    _split_mid_run(True, depth, 1)


@multi_device
@pytest.mark.parametrize("depth", [1, 4])
def test_split_mid_run_equals_post_split_oracle_sharded_multi_rank(depth):
    """Acceptance: the butterfly resize under a live pipeline, on real
    model ranks, at depth 1 and 4 — state arrays, digest tree head,
    ledger/journal heads and validity bits all byte-identical to the
    post-split-layout oracle."""
    _split_mid_run(True, depth, min(MAX_M, 4))


# --------------------------------------------- journal re-anchor records


def _journal_with_resize(seed=0):
    rng = np.random.default_rng(seed)
    j = journal_mod.StateJournal(DIMS)
    st = ws.create(256, 8, DIMS.vw)

    def block(b, st):
        wk = jnp.asarray(
            rng.integers(1, 1 << 30, (8, DIMS.wk, 2), dtype=np.uint32))
        wv = jnp.asarray(
            rng.integers(0, 1 << 30, (8, DIMS.wk, DIMS.vw),
                         dtype=np.uint32))
        valid = jnp.asarray(rng.random(8) < 0.8)
        j.append_writes(b, wk, wv, valid)
        return ws.commit_vectorized(st, wk, wv, valid).state

    def reanchor(st, new_nb, bno):
        st2 = ws.resize(st, new_nb).state
        sk, sv, sva = ws.split_table(st2.keys, st2.versions, st2.values, 4)
        tree = ws.shard_digest_tree(jnp.stack([
            ws.state_digest(ws.HashState(sk[i], sv[i], sva[i]))
            for i in range(4)
        ]))
        j.append_reanchor(bno, old_n_buckets=st.n_buckets,
                          new_n_buckets=new_nb, n_shards=4,
                          tree_head=np.asarray(tree))
        return st2

    for b in range(3):
        st = block(b, st)
    st = reanchor(st, 512, 2)
    for b in range(3, 5):
        st = block(b, st)
    return j, st


def test_journal_replay_and_verify_cross_reanchor():
    j, live = _journal_with_resize()
    assert j.verify_chain()
    rep = j.replay(ws.create(256, 8, DIMS.vw), check_reanchors=True)
    assert rep.overflow is False  # amply sized: no replayed drop
    replayed = rep.state
    for name, a, b in zip(ws.HashState._fields, replayed, live):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=name)


@pytest.mark.parametrize("field,value", [
    ("new_n_buckets", 1024),
    ("block_no", 1),
    ("overflow_bits", 1),
    ("tree_head", np.ones(2, np.uint32)),
])
def test_journal_reanchor_tamper_detected(field, value):
    j, _ = _journal_with_resize()
    j.reanchors[0] = j.reanchors[0]._replace(**{field: value})
    assert not j.verify_chain()


def test_journal_reanchor_requires_drained_tip():
    j, _ = _journal_with_resize()
    with pytest.raises(ValueError, match="tip"):
        j.append_reanchor(2, old_n_buckets=512, new_n_buckets=1024,
                          n_shards=4, tree_head=np.zeros(2, np.uint32))


def test_journal_reanchor_spill_load_and_prune(tmp_path):
    spill = tmp_path / "journal"
    spill.mkdir()
    rng = np.random.default_rng(4)
    j = journal_mod.StateJournal(DIMS, spill_dir=str(spill))
    st = ws.create(64, 8, DIMS.vw)
    for b in range(3):
        wk = jnp.asarray(
            rng.integers(1, 1 << 30, (4, DIMS.wk, 2), dtype=np.uint32))
        wv = jnp.asarray(
            rng.integers(0, 1 << 30, (4, DIMS.wk, DIMS.vw),
                         dtype=np.uint32))
        j.append_writes(b, wk, wv, jnp.ones(4, bool))
        st = ws.commit_vectorized(st, wk, wv, jnp.ones(4, bool)).state
        if b == 1:
            st = ws.resize(st, 128).state
            j.append_reanchor(1, old_n_buckets=64, new_n_buckets=128,
                              n_shards=1,
                              tree_head=np.asarray(ws.state_digest(st)),
                              overflow_bits=1)
    j2 = journal_mod.StateJournal.load(DIMS, str(spill))
    assert j2.verify_chain()
    assert len(j2.reanchors) == 1
    assert j2.reanchors[0].overflow_bits == 1
    np.testing.assert_array_equal(j2.reanchor_head, j.reanchor_head)
    replayed = j2.replay(ws.create(64, 8, DIMS.vw)).state
    np.testing.assert_array_equal(
        np.asarray(ws.state_digest(replayed)),
        np.asarray(ws.state_digest(st)))
    # Pruning drops covered re-anchors (and their spill files) with the
    # block records; the chains re-anchor at the stored bases.
    j2.prune_upto(1)
    assert not j2.reanchors
    assert j2.verify_chain()
    names = sorted(p.name for p in spill.iterdir())
    assert names == ["journal_00000002.npz"]
    j3 = journal_mod.StateJournal.load(DIMS, str(spill))
    assert [r.block_no for r in j3.records] == [2]


def test_journal_pre_genesis_reanchor_replayed_and_verified():
    """Regression: a resize BEFORE the first block (boundary -1) must be
    part of the from-genesis suffix — replayed, authenticated, and
    tamper-detected — not silently skipped (genesis is not a snapshot)."""
    rng = np.random.default_rng(13)
    j = journal_mod.StateJournal(DIMS)
    grown = ws.create(128, 8, DIMS.vw)
    j.append_reanchor(-1, old_n_buckets=64, new_n_buckets=128, n_shards=1,
                      tree_head=np.asarray(ws.tree_head(grown, 1)))
    wk = jnp.asarray(
        rng.integers(1, 1 << 30, (8, DIMS.wk, 2), dtype=np.uint32))
    wv = jnp.asarray(
        rng.integers(0, 1 << 30, (8, DIMS.wk, DIMS.vw), dtype=np.uint32))
    j.append_writes(0, wk, wv, jnp.ones(8, bool))
    live = ws.commit_vectorized(grown, wk, wv, jnp.ones(8, bool)).state
    assert j.verify_chain()
    rep = j.replay(ws.create(64, 8, DIMS.vw), check_reanchors=True)
    assert rep.state.n_buckets == 128
    for name, a, b in zip(ws.HashState._fields, rep.state, live):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=name)
    rec = recovery.recover(j, n_buckets=64, slots=8, value_width=DIMS.vw)
    assert rec.n_buckets == 128 and rec.crossed_reanchors == 1
    j.reanchors[0] = j.reanchors[0]._replace(new_n_buckets=256)
    assert not j.verify_chain()


def test_recovery_relatches_overflow_from_replayed_suffix():
    """Regression: overflow that strikes AFTER the last snapshot persisted
    its mask is re-derived by the suffix replay — the recovered peer must
    not report healthy while its replay reproduced a dropped insert."""
    rng = np.random.default_rng(17)
    j = journal_mod.StateJournal(DIMS)
    st = ws.create(8, 2, DIMS.vw)  # 16 slots: one block overflows it
    wk = jnp.asarray(
        rng.integers(1, 1 << 30, (16, DIMS.wk, 2), dtype=np.uint32))
    wv = jnp.asarray(
        rng.integers(0, 1 << 30, (16, DIMS.wk, DIMS.vw), dtype=np.uint32))
    j.append_writes(0, wk, wv, jnp.ones(16, bool))
    res = ws.commit_vectorized(st, wk, wv, jnp.ones(16, bool))
    assert bool(res.overflow)
    rec = recovery.recover(j, n_buckets=8, slots=2, value_width=DIMS.vw)
    assert rec.overflow_bits != 0


# ----------------------------------------------- per-shard recovery


def test_recover_shard_across_grow_reanchor(tmp_path):
    """Acceptance: per-shard snapshot + journal suffix across a re-anchor
    reproduces the live shard WITHOUT materializing the full table — a
    shard rebuilds from 2^epochs parts of the M on disk."""
    m = 8
    rng = np.random.default_rng(9)
    j = journal_mod.StateJournal(DIMS)
    st = ws.create(256, 8, DIMS.vw)

    def block(b, st):
        wk = jnp.asarray(
            rng.integers(1, 1 << 30, (8, DIMS.wk, 2), dtype=np.uint32))
        wv = jnp.asarray(
            rng.integers(0, 1 << 30, (8, DIMS.wk, DIMS.vw),
                         dtype=np.uint32))
        valid = jnp.asarray(rng.random(8) < 0.8)
        j.append_writes(b, wk, wv, valid)
        return ws.commit_vectorized(st, wk, wv, valid).state

    for b in range(2):
        st = block(b, st)
    snap = snapshot.take(
        st, block_no=1, journal_head=j.head,
        ledger_head=np.zeros(2, np.uint32), n_shards=m,
        reanchor_head=j.reanchor_head,
    )
    snapshot.save(str(tmp_path), snap)
    for b in (2, 3):
        st = block(b, st)
    st2 = ws.resize(st, 512).state
    sk, sv, sva = ws.split_table(st2.keys, st2.versions, st2.values, m)
    tree = ws.shard_digest_tree(jnp.stack([
        ws.state_digest(ws.HashState(sk[i], sv[i], sva[i]))
        for i in range(m)
    ]))
    j.append_reanchor(3, old_n_buckets=256, new_n_buckets=512, n_shards=m,
                      tree_head=np.asarray(tree))
    st = st2
    for b in (4, 5):
        st = block(b, st)

    sk, sv, sva = ws.split_table(st.keys, st.versions, st.values, m)
    for shard in range(m):
        res = recovery.recover_shard(
            j, snapshot_dir=str(tmp_path), shard=shard)
        assert res.loaded_parts == 2  # one grow epoch: 2 of 8 parts
        assert res.crossed_reanchors == 1 and res.block_no == 5
        np.testing.assert_array_equal(
            np.asarray(res.state.keys), np.asarray(sk[shard]))
        np.testing.assert_array_equal(
            np.asarray(res.state.versions), np.asarray(sv[shard]))
        np.testing.assert_array_equal(
            np.asarray(res.state.values), np.asarray(sva[shard]))
        np.testing.assert_array_equal(res.journal_head, j.head)


def test_recover_shard_across_shrink_reanchor(tmp_path):
    """Per-shard recovery across a SHRINK epoch: the post-shrink shard's
    preimage is TWO sibling ranges of the pre-shrink table; recovery
    loads both parts, folds them exactly like the full-table halve
    (low fragment first — the flat rehash order), and replays the
    suffix byte-identically, lossy drops included."""
    m = 8
    rng = np.random.default_rng(13)
    j = journal_mod.StateJournal(DIMS)
    st = ws.create(512, 8, DIMS.vw)

    def block(b, st):
        wk = jnp.asarray(
            rng.integers(1, 1 << 30, (8, DIMS.wk, 2), dtype=np.uint32))
        wv = jnp.asarray(
            rng.integers(0, 1 << 30, (8, DIMS.wk, DIMS.vw),
                         dtype=np.uint32))
        valid = jnp.asarray(rng.random(8) < 0.8)
        j.append_writes(b, wk, wv, valid)
        return ws.commit_vectorized(st, wk, wv, valid).state

    for b in range(2):
        st = block(b, st)
    snap = snapshot.take(
        st, block_no=1, journal_head=j.head,
        ledger_head=np.zeros(2, np.uint32), n_shards=m,
        reanchor_head=j.reanchor_head,
    )
    snapshot.save(str(tmp_path), snap)
    for b in (2, 3):
        st = block(b, st)
    st2 = ws.resize(st, 256).state  # SHRINK: 512 -> 256
    sk, sv, sva = ws.split_table(st2.keys, st2.versions, st2.values, m)
    tree = ws.shard_digest_tree(jnp.stack([
        ws.state_digest(ws.HashState(sk[i], sv[i], sva[i]))
        for i in range(m)
    ]))
    j.append_reanchor(3, old_n_buckets=512, new_n_buckets=256, n_shards=m,
                      tree_head=np.asarray(tree))
    st = st2
    for b in (4, 5):
        st = block(b, st)

    sk, sv, sva = ws.split_table(st.keys, st.versions, st.values, m)
    for shard in range(m):
        res = recovery.recover_shard(
            j, snapshot_dir=str(tmp_path), shard=shard)
        assert res.loaded_parts == 2  # one shrink epoch: the 2 siblings
        assert res.crossed_reanchors == 1 and res.block_no == 5
        np.testing.assert_array_equal(
            np.asarray(res.state.keys), np.asarray(sk[shard]))
        np.testing.assert_array_equal(
            np.asarray(res.state.versions), np.asarray(sv[shard]))
        np.testing.assert_array_equal(
            np.asarray(res.state.values), np.asarray(sva[shard]))
        np.testing.assert_array_equal(res.journal_head, j.head)


def test_recover_shard_refuses_inconsistent_reanchor_epochs(tmp_path):
    """A re-anchor whose old_n_buckets contradicts the epoch it follows
    (rewritten history) must be refused, not silently recovered."""
    j, _ = _journal_with_resize(seed=11)
    snapshot.save(str(tmp_path), snapshot.take(
        ws.create(256, 8, DIMS.vw), block_no=-1,
        journal_head=journal_mod.GENESIS_HEAD,
        ledger_head=np.zeros(2, np.uint32), n_shards=4,
    ))
    forged = journal_mod.StateJournal(DIMS)
    forged.records = j.records
    forged.reanchors = [
        j.reanchors[0]._replace(old_n_buckets=512, new_n_buckets=256)
    ]
    with pytest.raises(recovery.RecoveryError):
        recovery.recover_shard(forged, snapshot_dir=str(tmp_path), shard=0)


# ------------------------------------------------- engine policy + restart


def _engine_cfg(**kw):
    return engine.EngineConfig(
        dims=DIMS,
        orderer=dataclasses.replace(
            engine.FASTFABRIC.orderer, block_size=50),
        **kw,
    )


def test_engine_policy_absorbs_fill_that_overflows_static():
    """Acceptance (engine layer): the same fill workload overflows the
    static peer but the elastic peer splits ahead of pressure, stays
    healthy, and every durability check — including chain replay ACROSS
    the re-anchors — holds."""
    static = engine.FabricEngine(_engine_cfg(n_buckets=128, slots=8))
    elastic = engine.FabricEngine(_engine_cfg(
        n_buckets=128, slots=8,
        resize_policy=engine.ResizePolicy(grow_free_slots=4),
    ))
    for i in range(10):
        static.run_round(static.make_proposals(50, seed=i))
        elastic.run_round(elastic.make_proposals(50, seed=i))
    assert static.verify()["overflow_ok"] is False
    out = elastic.verify()
    assert all(out.values()), out
    assert elastic.n_buckets > 128
    assert len(elastic.reanchor_log) == len(elastic.journal.reanchors) \
        if elastic.journal else True
    static.store.close()
    elastic.store.close()


def test_engine_manual_resize_shrink_and_verify():
    eng = engine.FabricEngine(_engine_cfg(n_buckets=1 << 10))
    eng.run_round(eng.make_proposals(100, seed=0))
    eng.resize(1 << 11)
    eng.run_round(eng.make_proposals(100, seed=1))
    eng.resize(1 << 10)  # shrink back: still plenty of room
    # Second resize at the SAME boundary: verify()'s chain replay must
    # apply both steps in order, not their net composition.
    eng.resize(1 << 11)
    eng.run_round(eng.make_proposals(100, seed=2))
    out = eng.verify()
    assert all(out.values()), out
    assert eng.n_buckets == 1 << 11
    assert [r["new_n_buckets"] for r in eng.reanchor_log] == [
        2048, 1024, 2048]
    assert eng.reanchor_log[0]["block_no"] == eng.reanchor_log[1][
        "block_no"] - 2  # two resizes share the later boundary
    assert all("hot_shard" in r for r in eng.reanchor_log)
    eng.store.close()


def test_engine_restart_keeps_sticky_overflow(tmp_path):
    """Satellite: overflow -> snapshot -> restart must still report
    overflow_ok=False (the flag rides the snapshot manifest + re-anchor
    records instead of host memory)."""
    cfg = _engine_cfg(
        n_buckets=8, slots=2, snapshot_every_blocks=3,
        snapshot_dir=str(tmp_path / "snap"),
        journal_dir=str(tmp_path / "jrnl"),
        resize_policy=engine.ResizePolicy(
            grow_free_slots=0, grow_on_overflow=True),
    )
    eng = engine.FabricEngine(cfg)
    eng.run_round(eng.make_proposals(150, seed=0))
    assert eng.verify()["overflow_ok"] is False
    nb_repaired = eng.n_buckets
    assert nb_repaired == 16  # one overflow-triggered repair, not per-round
    eng.run_round(eng.make_proposals(150, seed=5))
    eng.run_round(eng.make_proposals(150, seed=6))
    assert eng.n_buckets == nb_repaired  # the sticky flag fires ONCE
    man = snapshot.latest_manifest(str(tmp_path / "snap"))
    assert man.overflow is True  # persisted, not host memory
    bits = man.overflow_bits
    eng.store.drain()
    eng.store.close()

    restored = engine.FabricEngine.restore(cfg)
    out = restored.verify()
    assert out["overflow_ok"] is False
    assert out["recovery_ok"] and out["replica_ok"]
    # The persisted mask keeps its which-shard bits across the restart,
    # and the restored flag counts as already repaired: restarting an
    # overflowed peer must NOT double the table once per boot.
    assert restored.overflow_bits() == bits
    nb = restored.n_buckets
    restored.run_round(restored.make_proposals(150, seed=1))
    assert restored.n_buckets == nb
    restored.store.drain()
    restored.store.close()


def test_engine_restart_resumes_post_resize_layout(tmp_path):
    cfg = _engine_cfg(
        n_buckets=128, slots=8, snapshot_every_blocks=3,
        snapshot_dir=str(tmp_path / "snap"),
        journal_dir=str(tmp_path / "jrnl"),
        resize_policy=engine.ResizePolicy(grow_free_slots=4),
    )
    eng = engine.FabricEngine(cfg)
    for i in range(6):
        eng.run_round(eng.make_proposals(50, seed=i))
    assert eng.n_buckets > 128
    nb, digest = eng.n_buckets, eng._peer_digest()
    bno = eng._next_block_no
    eng.store.drain()
    eng.store.close()
    restored = engine.FabricEngine.restore(cfg)
    assert restored.n_buckets == nb
    assert restored._next_block_no == bno
    np.testing.assert_array_equal(restored._peer_digest(), digest)
    assert all(restored.verify().values())
    restored.store.close()


def test_engine_window_committer_snapshots_and_recovers(tmp_path):
    """The window-committer engine now supports the durability layer: the
    manifest covers the mesh-backed state (per-shard for sharded configs)
    and recovery reproduces the committer's digest + journal head."""
    wc = engine_bridge.MeshWindowCommitter(
        DIMS, fs.FabricStepConfig(pipeline_depth=4), n_buckets=1 << 10)
    eng = engine.FabricEngine(
        _engine_cfg(
            n_buckets=1 << 10, snapshot_every_blocks=3,
            snapshot_dir=str(tmp_path), journal_dir=str(tmp_path / "j"),
        ),
        window_committer=wc,
    )
    for i in range(2):
        eng.run_round(eng.make_proposals(200, seed=i))
    out = eng.verify()
    assert all(out.values()), out
    assert eng.snapshots
    rec = eng.recover()
    np.testing.assert_array_equal(rec.state_digest, wc.state_digest())
    np.testing.assert_array_equal(rec.journal_head, wc.journal_head)
    eng.store.close()


def test_engine_policy_resizes_through_window_committer():
    wc = engine_bridge.MeshWindowCommitter(
        DIMS, fs.FabricStepConfig(pipeline_depth=4), n_buckets=128)
    eng = engine.FabricEngine(
        _engine_cfg(
            n_buckets=128,
            resize_policy=engine.ResizePolicy(grow_free_slots=4),
        ),
        window_committer=wc,
    )
    for i in range(8):
        eng.run_round(eng.make_proposals(50, seed=i))
    out = eng.verify()
    assert all(out.values()), out
    assert wc.n_buckets > 128 and eng.n_buckets == wc.n_buckets
    assert eng.reanchor_log
    eng.store.close()


# -------------------------------------------------------------- benchmark


def test_fig12_benchmark_smoke(capsys):
    from benchmarks import common, fig12_rebalance

    common.ROWS.clear()
    fig12_rebalance.main(
        ["--rounds", "6", "--round-txs", "30", "--n-buckets", "64",
         "--slots", "8", "--n-shards", "2", "--grow-free-slots", "4"]
    )
    by = {r["name"]: r for r in common.ROWS}
    assert by["elastic/final"]["n_resizes"] >= 1
    assert by["elastic/final"]["overflow_ok"]
    assert by["equivalence/elastic"]["identical"]
    assert any(n.startswith("recovery/shard=") for n in by)

"""Model-axis sharded world state: the sharded fabric step must be
byte-identical to the replicated oracle, and the hash-table ops dispatch
must route over-budget tables through the sharded path.

Runs on whatever host devices exist: with 1 device the sharded path is
exercised degenerately (psum over one rank); the CI multi-device job
(XLA_FLAGS=--xla_force_host_platform_device_count=8) runs the >=2-rank
cases for real.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import endorser, engine, types, unmarshal
from repro.core import world_state as ws
from repro.kernels.hash_table import ops as ht_ops
from repro.kernels.hash_table import ref as ht_ref
from repro.launch import fabric_step as fs
from repro.launch import state_sharding
from repro.launch.mesh import make_mesh

DIMS = types.TEST_DIMS
N_DEV = len(jax.devices())
MAX_M = 1 << (N_DEV.bit_length() - 1)  # largest power of two <= N_DEV

multi_device = pytest.mark.skipif(
    N_DEV < 2, reason="needs >=2 devices (CI multi-device job)"
)


def _round(n=32, seed=0):
    eng = engine.FabricEngine(engine.EngineConfig(dims=DIMS,
                                                  store_blocks=False))
    props = eng.make_proposals(n, seed=seed)
    txb = endorser.execute_and_endorse(eng.endorser_state, props, DIMS)
    wire = unmarshal.marshal(txb, DIMS)
    return wire[None], txb.tx_id[None]  # (C=1, B, ...)


def _run_step(cfg, mesh, wire, ids, n_buckets=256):
    state = fs.create_mesh_state(1, DIMS, n_buckets=n_buckets)
    step = jax.jit(fs.make_fabric_step(DIMS, cfg, mesh))
    st2, valid = step(state, wire, ids)
    return jax.tree.map(np.asarray, st2), np.asarray(valid)


# ------------------------------------------------------------ shard routing


def test_shard_of_high_bits_and_local_bucket_low_bits():
    nb, m = 64, 4
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(1, 1 << 32, (100, 2), dtype=np.uint32))
    owner = np.asarray(ws.shard_of(nb, m, keys))
    gb = np.asarray(ws.bucket_of(nb, keys))
    nb_loc = nb // m
    np.testing.assert_array_equal(owner, gb // nb_loc)
    # Local probe index (low bits) recombines with the owner to the global
    # bucket: the contiguous reshape IS the partition.
    lb = np.asarray(ws.bucket_of(nb_loc, keys))
    np.testing.assert_array_equal(owner * nb_loc + lb, gb)


def test_shard_buckets_validation():
    assert ws.shard_buckets(64, 4) == 16
    with pytest.raises(ValueError, match="power of two"):
        ws.shard_buckets(64, 3)
    with pytest.raises(ValueError, match="divisible"):
        ws.shard_buckets(64, 128)


def test_create_shard_local_table():
    """create(n_shards=M) yields one shard's local slice of the global
    table — same shapes as a split of the replicated creation."""
    local = ws.create(64, 4, DIMS.vw, n_shards=4)
    assert local.n_buckets == 16 and local.slots == 4
    full = ws.create(64, 4, DIMS.vw)
    sk, sv, sva = state_sharding.split_table(
        full.keys, full.versions, full.values, 4
    )
    assert sk.shape[1:] == local.keys.shape
    assert sva.shape[1:] == local.values.shape
    with pytest.raises(ValueError, match="power of two"):
        ws.create(64, 4, DIMS.vw, n_shards=3)


def test_split_merge_roundtrip_is_high_bit_partition():
    st = ws.create(16, 2, 1)
    keys = st.keys.at[:, 0, 0].set(jnp.arange(16, dtype=jnp.uint32))
    sk, sv, sva = state_sharding.split_table(keys, st.versions, st.values, 4)
    assert sk.shape == (4, 4, 2, 2)
    # Shard m holds buckets [m*4, (m+1)*4).
    np.testing.assert_array_equal(
        np.asarray(sk[2, :, 0, 0]), np.arange(8, 12)
    )
    mk, mv, mva = state_sharding.merge_table(sk, sv, sva)
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(keys))


def test_shard_digest_tree_deterministic_and_xor_decomposition():
    rng = np.random.default_rng(1)
    txb = types.make_transfer_batch(DIMS, 32, seed=2)
    full = ws.commit_vectorized(
        ws.create(64, 8, DIMS.vw), txb.write_keys, txb.write_vals,
        jnp.ones(32, bool),
    ).state
    sk, sv, sva = state_sharding.split_table(
        full.keys, full.versions, full.values, 4
    )
    per_shard = jnp.stack(
        [ws.state_digest(ws.HashState(sk[m], sv[m], sva[m]))
         for m in range(4)]
    )
    # XOR of per-shard digests == full-table digest (shard-decomposable).
    np.testing.assert_array_equal(
        np.bitwise_xor.reduce(np.asarray(per_shard), axis=0),
        np.asarray(ws.state_digest(full)),
    )
    # The tree head is deterministic and shard-order-sensitive.
    t1 = np.asarray(ws.shard_digest_tree(per_shard))
    t2 = np.asarray(ws.shard_digest_tree(per_shard))
    np.testing.assert_array_equal(t1, t2)
    assert not np.array_equal(
        t1, np.asarray(ws.shard_digest_tree(per_shard[::-1]))
    )


# ----------------------------------------------- sharded step == replicated


def _assert_equivalent(m, n=32, seed=0):
    mesh = make_mesh((1, m))
    wire, ids = _round(n=n, seed=seed)
    st_r, v_r = _run_step(fs.FASTFABRIC_STEP, mesh, wire, ids)
    st_s, v_s = _run_step(fs.FASTFABRIC_SHARDED_STEP, mesh, wire, ids)
    np.testing.assert_array_equal(v_r, v_s)
    for a, b in zip(st_r, st_s):
        np.testing.assert_array_equal(a, b)
    assert int(v_s.sum()) == n
    return v_s


def test_sharded_equals_replicated_degenerate():
    _assert_equivalent(1)


@multi_device
def test_sharded_equals_replicated_multi_rank():
    """Acceptance: identical validity bits, ledger/log heads, and state
    arrays (concatenated shards == replicated table) on >=2 model ranks."""
    _assert_equivalent(min(MAX_M, 4), n=32, seed=1)


@multi_device
def test_sharded_replay_round_invalidated():
    """Version checks still work when the versions live on remote shards."""
    mesh = make_mesh((1, min(MAX_M, 4)))
    wire, ids = _round(seed=3)
    state = fs.create_mesh_state(1, DIMS, n_buckets=256)
    step = jax.jit(fs.make_fabric_step(DIMS, fs.FASTFABRIC_SHARDED_STEP,
                                       mesh))
    st1, v1 = step(state, wire, ids)
    st2, v2 = step(st1, wire, ids)
    assert int(np.asarray(v1).sum()) == 32
    assert int(np.asarray(v2).sum()) == 0  # stale versions everywhere


@multi_device
def test_sharded_digest_head_identical_on_all_ranks():
    from jax.sharding import PartitionSpec as P

    m = min(MAX_M, 4)
    mesh = make_mesh((1, m))
    txb = types.make_transfer_batch(DIMS, 64, seed=4)
    full = ws.commit_vectorized(
        ws.create(256, 8, DIMS.vw), txb.write_keys, txb.write_vals,
        jnp.ones(64, bool),
    ).state

    def head(keys, vers, vals):
        local = ws.HashState(keys, vers, vals)
        return state_sharding.sharded_digest(local)[None]

    shard = jax.shard_map(
        head, mesh=mesh,
        in_specs=(P("model"), P("model"), P("model")),
        out_specs=P("model"), check_vma=False,
    )
    heads = np.asarray(
        shard(full.keys, full.versions, full.values)
    ).reshape(m, 2)
    # Same head on every rank, equal to the host-side tree computation.
    sk, sv, sva = state_sharding.split_table(
        full.keys, full.versions, full.values, m
    )
    want = np.asarray(ws.shard_digest_tree(jnp.stack(
        [ws.state_digest(ws.HashState(sk[i], sv[i], sva[i]))
         for i in range(m)]
    )))
    for h in heads:
        np.testing.assert_array_equal(h, want)


def test_shard_state_rejects_indivisible_buckets():
    if N_DEV < 2:
        pytest.skip("needs >=2 devices to build a >1 model axis")
    mesh = make_mesh((1, 2))
    wire, ids = _round()
    state = fs.create_mesh_state(1, DIMS, n_buckets=256)
    odd = state._replace(keys=state.keys[:, :100])  # 100 % 2 == 0 but not
    step = fs.make_fabric_step(DIMS, fs.FASTFABRIC_SHARDED_STEP, mesh)
    with pytest.raises(ValueError, match="power of two"):
        step(odd, wire, ids)


# ------------------------------------------------- ops.py budget dispatch


def test_ops_dispatch_over_budget_lookup_and_commit(monkeypatch):
    """Tables above the VMEM budget are sharded, not rejected, and the
    sharded kernel path matches the reference exactly."""
    monkeypatch.setattr(ht_ops, "VMEM_BUDGET_BYTES", 4096)
    # Packed, 32 buckets of 4 slots share one 4 KiB (8, 128) tile: the
    # 64-bucket table takes 8 KiB > 4 KiB -> 2 shards of one tile each.
    nb, s, vw = 64, 4, 2
    rng = np.random.default_rng(5)
    tk = jnp.zeros((nb, s, 2), jnp.uint32)
    tv = jnp.zeros((nb, s), jnp.uint32)
    tva = jnp.zeros((nb, s, vw), jnp.uint32)
    assert ht_ops._n_shards(tk, tva) == 2
    wk = jnp.asarray(rng.integers(1, 1 << 32, (50, 2), dtype=np.uint32))
    wv = jnp.asarray(rng.integers(0, 1 << 32, (50, vw), dtype=np.uint32))
    act = jnp.asarray(rng.random(50) < 0.9)
    got = ht_ops.commit(tk, tv, tva, wk, wv, act, use_pallas=True,
                        interpret=True)
    want = ht_ref.commit_ref(tk, tv, tva, wk, wv, act)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    queries = jnp.concatenate(
        [wk[:30],
         jnp.asarray(rng.integers(1, 1 << 32, (20, 2), dtype=np.uint32))]
    )
    got_l = ht_ops.lookup(got[0], got[1], got[2], queries, use_pallas=True,
                          interpret=True)
    want_l = ht_ref.lookup_ref(want[0], want[1], want[2], queries)
    for g, w in zip(got_l, want_l):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_ops_dispatch_under_budget_unchanged():
    nb, s, vw = 16, 4, 1
    tk = jnp.zeros((nb, s, 2), jnp.uint32)
    tva = jnp.zeros((nb, s, vw), jnp.uint32)
    assert ht_ops._n_shards(tk, tva) == 1


def test_shards_for_budget():
    assert state_sharding.shards_for_budget(100, 200, 64) == 1
    assert state_sharding.shards_for_budget(1000, 200, 64) == 8
    # Cannot shard below one bucket.
    assert state_sharding.shards_for_budget(1 << 20, 1, 4) == 4


# -------------------------------------------------------------- benchmark


def test_fig10_benchmark_smoke(capsys):
    from benchmarks import common, fig10_state_scaling

    common.ROWS.clear()
    fig10_state_scaling.main(
        ["--n-buckets", "256", "--b-round", "32", "--iters", "1"]
    )
    names = [r["name"] for r in common.ROWS]
    assert any(n.startswith("shard/m=") for n in names)
    assert any(n.startswith("equivalence/") for n in names)
    assert all(
        r["tps"] > 0 for r in common.ROWS if r.get("tps") is not None
    )

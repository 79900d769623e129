"""Distributed fabric step (degenerate 1x1 mesh): semantics must match the
single-host engine. The multi-device sharding itself is proven by the
production-mesh dry-run (launch/dryrun.py --fabric)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import endorser, engine, types, unmarshal
from repro.core import world_state as ws
from repro.launch import fabric_step as fs
from repro.launch.mesh import make_mesh

DIMS = types.TEST_DIMS


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1))


def _round(n=32, seed=0):
    eng = engine.FabricEngine(engine.EngineConfig(dims=DIMS,
                                                  store_blocks=False))
    props = eng.make_proposals(n, seed=seed)
    txb = endorser.execute_and_endorse(eng.endorser_state, props, DIMS)
    wire = unmarshal.marshal(txb, DIMS)
    return wire[None], txb.tx_id[None]  # (C=1, B, ...)


def test_configs_agree_on_state(mesh):
    wire, ids = _round()
    digests = []
    for cfg in (fs.FASTFABRIC_STEP, fs.FABRIC_V12_STEP):
        state = fs.create_mesh_state(1, DIMS, n_buckets=256)
        step = jax.jit(fs.make_fabric_step(DIMS, cfg, mesh))
        st2, valid = step(state, wire, ids)
        assert int(np.asarray(valid).sum()) == 32
        digests.append(np.asarray(ws.state_digest(
            ws.HashState(st2.keys[0], st2.versions[0], st2.values[0]))))
    np.testing.assert_array_equal(digests[0], digests[1])


def test_matches_single_host_committer(mesh):
    """Mesh-step world state == engine commit of the same ordered round."""
    wire, ids = _round(seed=1)
    state = fs.create_mesh_state(1, DIMS, n_buckets=256)
    step = jax.jit(fs.make_fabric_step(DIMS, fs.FASTFABRIC_STEP, mesh))
    st2, valid = step(state, wire, ids)

    from repro.core import committer, orderer
    order = orderer.consensus_order(ids[0])
    pstate = committer.create_peer_state(DIMS, n_buckets=256)
    res = committer.commit_block(pstate, wire[0][order], DIMS,
                                 committer.FASTFABRIC_PEER)
    d_mesh = np.asarray(ws.state_digest(
        ws.HashState(st2.keys[0], st2.versions[0], st2.values[0])))
    d_eng = np.asarray(ws.state_digest(res.state.hash_state))
    np.testing.assert_array_equal(d_mesh, d_eng)
    assert int(np.asarray(valid).sum()) == int(res.valid.sum())


def test_corrupt_payload_flagged(mesh):
    wire, ids = _round(seed=2)
    wire_np = np.asarray(wire).copy()
    wire_np[0, 5, 60] ^= 0xFF  # flip a byte in tx 5's opaque body
    state = fs.create_mesh_state(1, DIMS, n_buckets=256)
    step = jax.jit(fs.make_fabric_step(DIMS, fs.FASTFABRIC_STEP, mesh))
    _, valid = step(state, jnp.asarray(wire_np), ids)
    assert int(np.asarray(valid).sum()) == 31  # exactly the corrupt tx


def test_replay_round_invalidated(mesh):
    wire, ids = _round(seed=3)
    state = fs.create_mesh_state(1, DIMS, n_buckets=256)
    step = jax.jit(fs.make_fabric_step(DIMS, fs.FASTFABRIC_STEP, mesh))
    st1, v1 = step(state, wire, ids)
    st2, v2 = step(st1, wire, ids)  # identical round replayed
    assert int(np.asarray(v1).sum()) == 32
    assert int(np.asarray(v2).sum()) == 0  # stale versions everywhere

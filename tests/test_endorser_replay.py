"""The endorser replica's round replay: ``endorser.replay_blocks`` applies
a round's retired blocks in one program, with the replica donated.

  * It is byte-identical to the per-block ``unmarshal`` +
    ``apply_validated`` sequence, with the same valid count: a key
    rewritten by a later block of the round ends as that block left it,
    invalid transactions stay masked, a short round tail and a replicated
    4-device mesh give the same replica.
  * Through the engine, after several rounds the replica equals the
    committed table on the host path, the depth-8 window path and the
    2-channel meshed path; ``replay.dispatches`` counts one program call
    per channel and round; a repeated same-shape round compiles nothing;
    a resize and a restore after donated replays still verify.

The mesh case needs 4 devices: on a 1-device host it reruns this file in
a subprocess with 4 forced CPU devices.
"""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import endorser, engine, types, unmarshal
from repro.core import world_state as ws
from repro.launch import fabric_step as fs
from repro.launch.mesh import make_mesh
from repro.pipeline import engine_bridge

DIMS = types.TEST_DIMS
BS = 16
N_BUCKETS = 256
SLOTS = 8
N_ACCOUNTS = 600
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _proposals(n: int, seed: int) -> endorser.Proposal:
    """Transfers between distinct accounts of a small pool, so a round
    both updates keys an earlier round wrote and inserts new ones."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(N_ACCOUNTS)[: 2 * n].astype(np.uint32)
    return endorser.Proposal(
        src=perm[:n], dst=perm[n:],
        amount=rng.integers(1, 1000, size=n, dtype=np.uint32),
        client=rng.integers(0, 64, size=n, dtype=np.uint32),
        nonce=np.arange(n, dtype=np.uint32) + np.uint32(seed << 16),
    )


def _endorsed_round(state, prop: endorser.Proposal, n_blocks: int):
    txb = endorser.execute_and_endorse(
        state, endorser.Proposal(*map(jnp.asarray, prop)), DIMS)
    return unmarshal.marshal(txb, DIMS).reshape(n_blocks, BS, -1)


def _copy(state: ws.HashState) -> ws.HashState:
    return ws.HashState(*(jnp.array(a, copy=True) for a in state))


def _per_block(state, wires, valids):
    for wire_b, valid in zip(wires, valids):
        state = endorser.apply_validated(
            state, unmarshal.unmarshal(wire_b, DIMS).txb, valid)
    return state, int(np.asarray(valids).sum())


def _check(case: str) -> None:
    """Run one equivalence case; raises AssertionError on a mismatch."""
    n_blocks = {"tail3": 3, "rewrite": 4}.get(case, 8)
    # A replica with history: one earlier all-valid round of 2 blocks.
    pre = ws.create(N_BUCKETS, SLOTS, DIMS.vw)
    first = _endorsed_round(pre, _proposals(2 * BS, seed=1), 2)
    pre, _ = _per_block(pre, first, jnp.ones((2, BS), bool))

    prop = _proposals(n_blocks * BS, seed=2)
    if case in ("rewrite", "mesh4"):
        # Block 3 rewrites the key block 0 wrote at tx 0 (as source) and
        # block 2 the one block 0 wrote at tx 1 (as destination).
        prop.src[3 * BS] = prop.src[0]
        prop.dst[2 * BS + 1] = prop.dst[1]
    wires = _endorsed_round(pre, prop, n_blocks)
    rng = np.random.default_rng(3)
    if case in ("masked", "mesh4"):
        valids = jnp.asarray(rng.random((n_blocks, BS)) < 0.7)
    else:
        valids = jnp.ones((n_blocks, BS), bool)

    want, want_n = _per_block(pre, wires, valids)
    start = _copy(pre)
    if case == "mesh4":
        mesh = make_mesh((4, 1))
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        start, wires, valids = jax.device_put((start, wires, valids), rep)
    got, got_n = endorser.replay_blocks_jit(start, wires, valids, DIMS)
    assert int(got_n) == want_n
    for name, a, b in zip(ws.HashState._fields, got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    if case == "rewrite":
        # The later block's write is the one that stays.
        wk = unmarshal.unmarshal(wires[3], DIMS).txb
        look = ws.lookup(got, wk.write_keys[:1, 0])
        assert int(look.values[0, 0]) == int(wk.write_vals[0, 0, 0])
        assert int(look.versions[0]) == 2  # inserted by block 0, bumped once


@pytest.mark.parametrize("case", ["rewrite", "masked", "tail3", "mesh4"])
def test_replay_blocks_equals_per_block_apply(case):
    if case == "mesh4" and len(jax.devices()) < 4:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=4")
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (src, env.get("PYTHONPATH")) if p)
        out = subprocess.run([sys.executable, __file__, case], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stdout + out.stderr
        return
    _check(case)


# ---------------------------------------------------------------- engine


class _Compiles:
    """Counts programs compiled (or loaded from a cache) while open."""

    def __init__(self):
        self.n = 0

    def _on(self, name, _secs, **_):
        if name == COMPILE_EVENT:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def _engine(path: str, tmp_path, n_buckets: int = N_BUCKETS):
    n_channels = 2 if path == "meshed2ch" else 1
    cfg = dataclasses.replace(
        engine.FASTFABRIC, dims=DIMS,
        orderer=dataclasses.replace(engine.FASTFABRIC.orderer,
                                    block_size=BS),
        n_buckets=n_buckets, slots=SLOTS, n_channels=n_channels, obs=True,
        # One snapshot, after round 3: the resize after round 4 falls
        # between snapshots (a resize in a snapshot's own round is a
        # recovery gap of the durability layer, not of the replay).
        snapshot_every_blocks=24,
        journal_dir=str(tmp_path / "j"), snapshot_dir=str(tmp_path / "s"),
        block_dir=str(tmp_path / "b"),
    )
    wc = None
    if path != "host":
        wc = engine_bridge.MeshWindowCommitter(
            DIMS, fs.FabricStepConfig(pipeline_depth=8), make_mesh((1, 1)),
            n_buckets=n_buckets, slots=SLOTS, n_channels=n_channels)
    return engine.FabricEngine(cfg, window_committer=wc), cfg


def _round(eng, r: int):
    props = [_proposals(8 * BS, seed=100 + 10 * r + c)
             for c in range(eng.n_channels)]
    props = [endorser.Proposal(*map(jnp.asarray, p)) for p in props]
    if eng.n_channels == 1:
        return [eng.run_round(props[0])]
    return eng.run_rounds(props)


def _committed(eng, c: int) -> ws.HashState:
    wc = eng.window_committer
    if wc is not None:
        return wc.hash_state(c)
    return eng.chans[c].peer_state.hash_state


def _assert_replica_is_table(eng) -> None:
    for c in range(eng.n_channels):
        replica = eng.chans[c].endorser_state
        for name, a, b in zip(ws.HashState._fields, replica,
                              _committed(eng, c)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (c, name)


@pytest.mark.parametrize("path", ["host", "window8", "meshed2ch"])
def test_engine_replay_matches_table_one_dispatch_per_round(path, tmp_path):
    eng, cfg = _engine(path, tmp_path)
    # The window path's ordering program settles its input layouts in the
    # third round; the fourth repeats every shape.
    n_rounds = 4
    for r in range(n_rounds - 1):
        _round(eng, r)
    with _Compiles() as compiles:
        stats = _round(eng, n_rounds - 1)
    assert compiles.n == 0
    assert all(s.n_valid == 8 * BS for s in stats)
    _assert_replica_is_table(eng)
    m = eng.metrics()
    assert m["replay.dispatches"] == n_rounds * eng.n_channels
    if eng.n_channels > 1:
        for c in range(eng.n_channels):
            assert m[f"replay.dispatches{{channel={c}}}"] == n_rounds

    # A resize after donated replays: the replica follows the table.
    eng.resize(2 * N_BUCKETS, channel=0)
    _round(eng, n_rounds)
    _assert_replica_is_table(eng)
    for c, verdicts in eng.verify_all().items():
        assert all(verdicts.values()), (c, verdicts)
    eng.store.drain()
    eng.store.close()

    # A restart from the snapshots + journals: the restored replica takes
    # donated replays too.
    if path != "host":
        return  # restore rebuilds the host-path peer
    restored = engine.FabricEngine.restore(cfg)
    _round(restored, n_rounds + 1)
    _assert_replica_is_table(restored)
    assert all(restored.verify().values())
    restored.store.close()


if __name__ == "__main__":
    _check(sys.argv[1])

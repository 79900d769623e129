"""Multi-channel scale-out: N independent channels vmapped over the
`data` axis, per-channel journals/snapshots/resize epochs, ONE
BlockStore writer multiplexing every channel's chain.

The pins, extending the PR-2..PR-5 oracle discipline across channels:

  * An N-channel committer run (N >= 2, sharded over >= 2 `data` ranks,
    pipeline depth >= 2, with a mid-run resize on ONE channel) is
    byte-identical PER CHANNEL to N single-channel oracle runs — state
    arrays, ledger/journal heads, validity bits, digest-tree heads and
    sticky overflow bitmasks all match, and the resized channel's
    epoch never perturbs its neighbors.
  * Channels are failure-isolated end to end: tampering with channel
    i's journal (or store chain) flips channel i's verify() verdicts
    ONLY; every other channel stays green.
  * One BlockStore writer thread serves every channel: channel-tagged
    submits land on per-channel chains, spill into per-channel
    directories (``ledger.channel_dir``), and verify/replay/resume are
    strictly per channel.
  * ``FabricEngine.restore`` rebuilds a channel whose latest snapshot
    TRAILS the journal tip: the suffix's ledger head is recomputed from
    the block spill and re-verified against the chain rule.

Runs on whatever host devices exist; the >=2-data-rank acceptance case
needs the CI multi-device job (XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import endorser, engine, ledger, types, unmarshal
from repro.launch import fabric_step as fs
from repro.pipeline import engine_bridge
from repro.launch.mesh import make_mesh

DIMS = types.TEST_DIMS
N_DEV = len(jax.devices())

needs_4_devices = pytest.mark.skipif(
    N_DEV < 4, reason="needs >=4 devices (CI multi-device job)"
)


def _engine_cfg(**kw):
    return engine.EngineConfig(
        dims=DIMS,
        orderer=dataclasses.replace(
            engine.FASTFABRIC.orderer, block_size=32),
        **kw,
    )


def _windows(n_windows, depth, n=16, seed=0):
    """Pre-endorsed wire windows, shaped (depth, n, wire) per window."""
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


# --------------- acceptance: N channels == N oracles, mid-run resize


def _multichannel_vs_oracles(shard_state, depth, data, model):
    """Live: C=2 channels lockstep, channel 1 resizes 128->256 after two
    windows. Oracles: each channel's exact per-channel history replayed
    on a single-channel committer. Everything must match, per channel."""
    mesh = make_mesh((data, model))
    cfg = fs.FabricStepConfig(shard_state=shard_state, pipeline_depth=depth)
    streams = [_windows(4, depth, seed=5), _windows(4, depth, seed=77)]

    live = engine_bridge.MeshWindowCommitter(
        DIMS, cfg, mesh, n_buckets=128, slots=8, n_channels=2)
    valid_live = []
    for w in range(2):
        wires = jnp.stack([s[w][0] for s in streams])
        ids = jnp.stack([s[w][1] for s in streams])
        valid_live.append(live.commit_windows(wires, ids).valid)
    info = live.resize(256, channel=1)
    assert (info.channel, info.old_n_buckets, info.new_n_buckets) == (
        1, 128, 256)
    assert info.block_no == 2 * depth - 1  # the drained window boundary
    assert live.n_buckets_for(0) == 128 and live.n_buckets_for(1) == 256
    for w in range(2, 4):
        wires = jnp.stack([s[w][0] for s in streams])
        ids = jnp.stack([s[w][1] for s in streams])
        valid_live.append(live.commit_windows(wires, ids).valid)

    for c, wins in enumerate(streams):
        oracle = engine_bridge.MeshWindowCommitter(
            DIMS, cfg, mesh, n_buckets=128, slots=8)
        for w in range(4):
            if c == 1 and w == 2:  # channel 1's mid-run epoch, replayed
                oracle.resize(256)
            v = oracle.commit_window(*wins[w]).valid
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(valid_live[w][c]),
                err_msg=f"ch{c} window{w} validity")
        lc = live.channel_state(c)
        for name, a, b in zip(fs.FabricMeshState._fields, lc,
                              oracle.state):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"ch{c}:{name}")
        np.testing.assert_array_equal(
            live.tree_head(c), oracle.tree_head(), err_msg=f"ch{c} tree")
        np.testing.assert_array_equal(
            live.journal_head_for(c), np.asarray(oracle.journal_head),
            err_msg=f"ch{c} journal head")
        np.testing.assert_array_equal(
            live.ledger_head_for(c), oracle.ledger_head_for(0),
            err_msg=f"ch{c} ledger head")
        assert live.overflow_bits_for(c) == oracle.overflow_bits


def test_multichannel_equals_oracles_replicated():
    _multichannel_vs_oracles(False, 2, 1, 1)


def test_multichannel_equals_oracles_sharded_degenerate():
    _multichannel_vs_oracles(True, 2, 1, 1)


@needs_4_devices
def test_multichannel_equals_oracles_sharded_data_ranks():
    """ACCEPTANCE: 2 channels sharded over 2 `data` ranks x 2 model
    ranks, pipeline depth 2, channel 1 resizes mid-run — byte-identical
    per channel to the single-channel oracles."""
    _multichannel_vs_oracles(True, 2, 2, 2)


@needs_4_devices
def test_multichannel_four_channels_two_data_ranks():
    """4 channels over 2 data ranks (2 local channels per rank): the
    vmap-inside-shard_map layout, no resize — quick layout pin."""
    mesh = make_mesh((2, 2))
    cfg = fs.FabricStepConfig(shard_state=True, pipeline_depth=2)
    streams = [_windows(2, 2, seed=11 * (c + 1)) for c in range(4)]
    live = engine_bridge.MeshWindowCommitter(
        DIMS, cfg, mesh, n_buckets=128, slots=8, n_channels=4)
    for w in range(2):
        live.commit_windows(
            jnp.stack([s[w][0] for s in streams]),
            jnp.stack([s[w][1] for s in streams]))
    for c, wins in enumerate(streams):
        oracle = engine_bridge.MeshWindowCommitter(
            DIMS, cfg, mesh, n_buckets=128, slots=8)
        for w in range(2):
            oracle.commit_window(*wins[w])
        np.testing.assert_array_equal(
            live.tree_head(c), oracle.tree_head(), err_msg=f"ch{c}")
        for name, a, b in zip(fs.FabricMeshState._fields,
                              live.channel_state(c), oracle.state):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"ch{c}:{name}")


# ------------------------------- engine: lockstep rounds + isolation


def test_engine_multichannel_meshed_rounds_verify_all(tmp_path):
    mesh = make_mesh((1, 1))
    wc = engine_bridge.MeshWindowCommitter(
        DIMS, fs.FabricStepConfig(pipeline_depth=2), mesh,
        n_buckets=256, slots=8, n_channels=2)
    eng = engine.FabricEngine(
        _engine_cfg(
            n_channels=2, n_buckets=256,
            journal_dir=str(tmp_path / "j"),
            snapshot_dir=str(tmp_path / "s"),
            block_dir=str(tmp_path / "b"),
        ),
        window_committer=wc,
    )
    for r in range(2):
        props = [eng.make_proposals(64, seed=100 + 7 * r + c)
                 for c in range(2)]
        stats = eng.run_rounds(props)
        assert [s.n_txs for s in stats] == [64, 64]
        # Lockstep rounds share ONE wall clock across channels.
        assert stats[0].wall_s == stats[1].wall_s
    out = eng.verify_all()
    assert set(out) == {0, 1}
    for c, verdicts in out.items():
        assert all(verdicts.values()), (c, verdicts)
    # Per-channel block spill directories exist (channel 0 = base dir).
    assert (tmp_path / "b" / "block_00000000.npz").exists()
    assert (tmp_path / "b" / "channel_0001" / "block_00000000.npz").exists()
    eng.store.close()


def test_engine_multichannel_mismatched_committer_raises():
    wc = engine_bridge.MeshWindowCommitter(
        DIMS, fs.FabricStepConfig(pipeline_depth=1), n_buckets=128,
        n_channels=1)
    with pytest.raises(ValueError, match="channels"):
        engine.FabricEngine(
            _engine_cfg(n_channels=2, n_buckets=128), window_committer=wc)


def test_engine_journal_tamper_flips_only_that_channel(tmp_path):
    """Cross-channel isolation: corrupt channel 1's journal; channel 1's
    verify fails, channel 0 stays green — and vice versa for the store
    chain."""
    eng = engine.FabricEngine(
        _engine_cfg(n_channels=2, journal_dir=str(tmp_path / "j")))
    for r in range(2):
        eng.run_rounds([eng.make_proposals(64, seed=200 + 3 * r + c)
                        for c in range(2)])
    assert all(all(v.values()) for v in eng.verify_all().values())
    rec = eng.chans[1].journal.records[2]
    eng.chans[1].journal.records[2] = rec._replace(
        write_vals=rec.write_vals + 1)
    v0, v1 = eng.verify(0), eng.verify(1)
    assert all(v0.values()), v0
    assert not all(v1.values()), v1
    # Restore channel 1's record; now tamper channel 0's store chain.
    eng.chans[1].journal.records[2] = rec
    assert all(eng.verify(1).values())
    sb = eng.store.chains[0][1]
    eng.store.chains[0][1] = sb._replace(
        block_hash=sb.block_hash ^ np.uint32(1))
    v0, v1 = eng.verify(0), eng.verify(1)
    assert not all(v0.values()), v0
    assert all(v1.values()), v1
    eng.store.close()


def test_engine_multichannel_per_channel_resize(tmp_path):
    """A between-rounds resize of ONE channel re-anchors only that
    channel's journal; both channels keep verifying and the bucket
    counts diverge."""
    eng = engine.FabricEngine(
        _engine_cfg(n_channels=2, n_buckets=128,
                    journal_dir=str(tmp_path / "j")))
    eng.run_rounds([eng.make_proposals(64, seed=c) for c in range(2)])
    info = eng.resize(256, channel=1)
    assert info["channel"] == 1
    eng.run_rounds([eng.make_proposals(64, seed=10 + c) for c in range(2)])
    assert eng.chans[0].n_buckets == 128
    assert eng.chans[1].n_buckets == 256
    assert len(eng.chans[0].journal.reanchors) == 0
    assert len(eng.chans[1].journal.reanchors) == 1
    for c, verdicts in eng.verify_all().items():
        assert all(verdicts.values()), (c, verdicts)
    eng.store.close()


def test_overflow_cap_raise_names_channels():
    """>64 model ranks is a hard cap; in a multi-channel mesh the raise
    must say WHICH channels' state hit it."""
    from repro.launch import state_sharding

    flags = jnp.zeros(state_sharding.MAX_OVERFLOW_SHARDS + 1, bool)
    with pytest.raises(ValueError, match=r"channel \(1, 3\)"):
        state_sharding.overflow_bits(flags, channel=(1, 3))


# --------------------------- storage: ONE writer, N channel chains


def _chain_blocks(n_blocks, batch=8, seed=0):
    prev = jnp.zeros((2,), jnp.uint32)
    out = []
    for b in range(n_blocks):
        txb = types.make_transfer_batch(DIMS, batch, seed=seed + b)
        wire = unmarshal.marshal(txb, DIMS)
        valid = jnp.ones(batch, bool)
        digest = ledger.block_body_digest(wire, valid)
        bh = ledger.append_hash(prev, jnp.uint32(b), digest)
        out.append((b, prev, bh, wire, valid))
        prev = bh
    return out


def test_blockstore_multiplexes_channels(tmp_path):
    store = ledger.BlockStore(spill_dir=str(tmp_path))
    chans = {c: _chain_blocks(3, seed=40 * (c + 1)) for c in range(3)}
    # Interleave submits across channels through the one writer thread.
    for b in range(3):
        for c, blocks in chans.items():
            store.submit(*blocks[b], channel=c)
    store.drain()
    for c, blocks in chans.items():
        assert store.verify_chain(c)
        assert [sb.block_no for sb in store.chains[c]] == [0, 1, 2]
        loaded = ledger.load_spilled_blocks(str(tmp_path), 0, channel=c)
        assert [sb.block_no for sb in loaded] == [0, 1, 2]
        for sb, (bno, prev, bh, wire, valid) in zip(loaded, blocks):
            np.testing.assert_array_equal(sb.block_hash, np.asarray(bh))
    # Pruning channel 1 re-anchors channel 1 only.
    store.prune_upto(1, channel=1)
    assert store.base_block_nos[1] == 1
    assert store.base_block_nos[0] == -1 and store.base_block_nos[2] == -1
    assert all(store.verify_chain(c) for c in range(3))
    # A bad cross-channel splice fails that channel's verify only.
    store.chains[2][1] = store.chains[0][1]
    assert store.verify_chain(0) and store.verify_chain(1)
    assert not store.verify_chain(2)
    store.close()


# ----------------- restore: snapshot TRAILING the journal tip


def test_restore_from_snapshot_trailing_journal_tip(tmp_path):
    """5 rounds with a snapshot cadence that leaves blocks AFTER the last
    snapshot: restore must rebuild the suffix's ledger head from the
    block spill and end at the live digest + block number."""
    cfg = _engine_cfg(
        n_buckets=256, snapshot_every_blocks=4,
        snapshot_dir=str(tmp_path / "s"),
        journal_dir=str(tmp_path / "j"),
        block_dir=str(tmp_path / "b"),
    )
    eng = engine.FabricEngine(cfg)
    for i in range(5):
        eng.run_rounds([eng.make_proposals(64, seed=i)])
    digest, bno = eng._peer_digest(), eng._next_block_no
    head = eng._ledger_head()
    snap_bno = eng.snapshots[-1].block_no
    assert snap_bno < bno - 1  # the journal tip really trails
    eng.store.drain()
    eng.store.close()

    restored = engine.FabricEngine.restore(cfg)
    assert restored._next_block_no == bno
    np.testing.assert_array_equal(restored._peer_digest(), digest)
    np.testing.assert_array_equal(restored._ledger_head(), head)
    assert all(restored.verify().values())
    restored.store.close()


def test_restore_trailing_snapshot_requires_block_spill(tmp_path):
    cfg = _engine_cfg(
        n_buckets=256, snapshot_every_blocks=4,
        snapshot_dir=str(tmp_path / "s"),
        journal_dir=str(tmp_path / "j"),
    )
    eng = engine.FabricEngine(cfg)
    for i in range(5):
        eng.run_rounds([eng.make_proposals(64, seed=i)])
    assert eng.snapshots[-1].block_no < eng._next_block_no - 1
    eng.store.drain()
    eng.store.close()
    with pytest.raises(RuntimeError, match="block_dir"):
        engine.FabricEngine.restore(cfg)


def test_restore_multichannel_with_divergent_epochs(tmp_path):
    """2 channels, channel 1 resized mid-history: restore brings BOTH
    back (per-channel snapshots + journals + block spill), with the
    divergent bucket counts intact and every verdict green."""
    cfg = _engine_cfg(
        n_channels=2, n_buckets=128, snapshot_every_blocks=4,
        snapshot_dir=str(tmp_path / "s"),
        journal_dir=str(tmp_path / "j"),
        block_dir=str(tmp_path / "b"),
    )
    eng = engine.FabricEngine(cfg)
    eng.run_rounds([eng.make_proposals(64, seed=c) for c in range(2)])
    eng.resize(256, channel=1)
    for i in range(2):
        eng.run_rounds([eng.make_proposals(64, seed=10 + 2 * i + c)
                        for c in range(2)])
    digests = [eng._peer_digest(c) for c in range(2)]
    bnos = [eng.chans[c].next_block_no for c in range(2)]
    eng.store.drain()
    eng.store.close()

    restored = engine.FabricEngine.restore(cfg)
    assert restored.chans[0].n_buckets == 128
    assert restored.chans[1].n_buckets == 256
    for c in range(2):
        assert restored.chans[c].next_block_no == bnos[c]
        np.testing.assert_array_equal(
            restored._peer_digest(c), digests[c], err_msg=f"ch{c}")
    for c, verdicts in restored.verify_all().items():
        assert all(verdicts.values()), (c, verdicts)
    restored.store.close()

"""Engine ↔ mesh-step bridge: commit windows of blocks per round.

``core/engine.py`` is the single-host engine; its committer role used to
push one block at a time through ``committer.commit_block``. This adapter
lets the engine hand the MESH step (launch/fabric_step) a window of
``pipeline_depth`` blocks per invocation instead — the device-side block
pipeline — while still producing everything the storage role needs per
block (prev/block chain hashes for ``BlockStore.verify_chain``, per-tx
validity bits for the journal and the endorser-replica update).

The committer now drives N independent CHANNELS (the paper's deployment
unit — FastFabric's numbers are per channel): one ``FabricMeshState``
carries a group of channels with a leading channel dim sharded over the
mesh ``data`` axis, and the step vmaps the per-channel math so a whole
group commits in ONE dispatch. Because each channel resizes on its own
epoch schedule, channels are partitioned into *shape groups* by bucket
count: a resize drains the mesh, splits its channel out of its group, runs
the butterfly exchange on that channel alone, and re-merges it with any
group already at the new layout. Groups whose size divides the data axis
shard channels across ranks; odd-sized groups (transient, post-resize)
replicate over ``data`` until they merge back.

The engine stays the orchestrator: it orders each channel's round, slices
it into windows, ships each retired block to the store (channel-tagged),
and runs its usual durability checks against the per-channel
``state_digest`` / ``journal_head``.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_mod
from repro.core import ledger, types
from repro.core import world_state as ws
from repro.launch import fabric_step as fs
from repro.launch import state_sharding
from repro.launch.mesh import make_mesh

U32 = jnp.uint32


class ReanchorInfo(NamedTuple):
    """What one resize epoch commits to the journal (storage/journal
    append_reanchor): the boundary block, the layout change, the
    post-resize digest-tree head, and the sticky overflow bitmask."""

    block_no: int  # last committed block — the resize lands after it
    old_n_buckets: int  # global bucket count before
    new_n_buckets: int  # ... and after
    n_shards: int
    tree_head: np.ndarray  # (2,) u32 — shard_digest_tree of the new table
    overflow_bits: int  # sticky per-shard overflow bitmask at the boundary
    channel: int = 0  # which channel's table the epoch resized


class WindowResult(NamedTuple):
    """Per-block outputs of one committed window (block-major)."""

    valid: jnp.ndarray  # (D, B) bool, block order == input order
    prev_hash: np.ndarray  # (D, 2) u32 — store-chain prev per block
    block_hash: np.ndarray  # (D, 2) u32 — store-chain hash per block


class MultiWindowResult(NamedTuple):
    """Per-channel, per-block outputs of one multi-channel window."""

    valid: jnp.ndarray  # (C, D, B) bool
    prev_hash: np.ndarray  # (C, D, 2) u32
    block_hash: np.ndarray  # (C, D, 2) u32


@jax.jit
def _chain_hashes(prev_hash, block_no0, wire, valid):
    """Store-chain hashes for a window: (prev (D, 2), hash (D, 2))."""

    def link(prev, xs):
        wire_b, valid_b, k = xs
        digest = ledger.block_body_digest(wire_b, valid_b)
        bh = ledger.append_hash(prev, block_no0 + k, digest)
        return bh, (prev, bh)

    _, (prevs, hashes) = jax.lax.scan(
        link, prev_hash,
        (wire, valid, jnp.arange(wire.shape[0], dtype=U32)),
    )
    return prevs, hashes


@jax.jit
def _chain_hashes_multi(prev_hash, block_no0, wire, valid):
    """Channel-batched store-chain hashes: (C, D, 2) prevs and hashes."""
    return jax.vmap(_chain_hashes)(prev_hash, block_no0, wire, valid)


def make_stats_program(n_shards: int):
    """Per-group shard-stats pass (unjitted): vmapped occupancy +
    min-free reductions over a group's stacked state. Module-level so the
    committer's jit cache and the contract analyzer's registration lower
    the SAME program (repro.analysis registers it as
    ``pipeline/stats_pass``)."""

    def prog(keys, vers, vals):
        def one(k, v, va):
            st = ws.HashState(k, v, va)
            return (ws.shard_occupancy(st, n_shards),
                    ws.shard_min_free(st, n_shards))

        return jax.vmap(one)(keys, vers, vals)

    return prog


def make_resize_program(cfg: fs.FabricStepConfig, mesh, old_nb: int,
                        new_nb: int):
    """Halve/double of ONE channel's state (C=1) for ``mesh`` (unjitted).
    Sharded configs run the butterfly neighbor exchange inside shard_map;
    replicated configs resize every rank's copy locally. Module-level for
    the same reason as :func:`make_stats_program` (registered as
    ``pipeline/resize_exchange``)."""
    msize = mesh.shape["model"]
    if cfg.shard_state:
        new_nb_loc = new_nb // msize

        def body(keys, vers, vals):
            local = ws.HashState(keys[0], vers[0], vals[0])
            res = state_sharding.resize_sharded(
                local, new_nb_loc, old_nb, msize
            )
            bits = state_sharding.overflow_bits(res.shard_overflow)
            return (res.state.keys[None], res.state.versions[None],
                    res.state.values[None], bits[None])

        # A lone channel replicates over `data` (channels_over_data
        # False) — on a 1-rank data axis this is the old spec exactly.
        spec = fs.state_specs(mesh, shard_state=True,
                              channels_over_data=False)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(spec.keys, spec.versions, spec.values),
            out_specs=(spec.keys, spec.versions, spec.values,
                       spec.overflow),
            check_vma=False,
        )

    def prog_fn(keys, vers, vals):
        res = jax.vmap(
            lambda k, v, va: ws.resize(ws.HashState(k, v, va), new_nb)
        )(keys, vers, vals)
        bits = jax.vmap(
            lambda o: state_sharding.overflow_bits(o[None])
        )(res.overflow)  # (C, LANES)
        return (res.state.keys, res.state.versions,
                res.state.values, bits)

    return prog_fn


class _ChannelGroup:
    """Channels sharing one bucket layout, stacked in one mesh state."""

    __slots__ = ("channels", "state")

    def __init__(self, channels: tuple[int, ...], state: fs.FabricMeshState):
        self.channels = channels
        self.state = state

    @property
    def n_buckets(self) -> int:
        return self.state.keys.shape[1]


def _take_channels(state: fs.FabricMeshState, idx: list[int]
                   ) -> fs.FabricMeshState:
    """Host-side gather of a channel subset (resize boundaries only)."""
    arrs = jax.device_get(tuple(state))
    return fs.FabricMeshState(*(jnp.asarray(a[idx]) for a in arrs))


def _concat_channels(states: list[fs.FabricMeshState]) -> fs.FabricMeshState:
    arrs = [jax.device_get(tuple(s)) for s in states]
    return fs.FabricMeshState(
        *(jnp.asarray(np.concatenate([a[i] for a in arrs]))
          for i in range(len(fs.FabricMeshState._fields)))
    )


class MeshWindowCommitter:
    """The committer role backed by the mesh fabric step, windowed.

    One instance owns ``n_channels`` independent channels (grouped by
    bucket layout, each group one ``FabricMeshState``) and feeds them
    windows of up to ``cfg.pipeline_depth`` blocks; remainder windows at a
    round's tail compile a shallower step once and reuse it. Depth-1
    windows take the single-block oracle path, so an engine driving this
    committer at depth 1 is byte-identical to depth D in every output —
    and every channel is byte-identical to a single-channel committer fed
    the same block stream (tests/test_multichannel.py).

    The single-channel surface (``commit_window``, ``state``,
    ``journal_head``, ``overflow_bits``, ``resize(nb)``...) is unchanged
    when ``n_channels == 1``; multi-channel callers use
    ``commit_windows`` and the ``*_for(channel)`` accessors.
    """

    def __init__(self, dims: types.FabricDims, cfg: fs.FabricStepConfig,
                 mesh=None, *, n_buckets: int = 1 << 12, slots: int = 8,
                 n_channels: int = 1):
        if mesh is None:
            mesh = make_mesh((1, 1))
        if n_channels < 1:
            raise ValueError(f"n_channels must be >= 1, got {n_channels}")
        self.dims = dims
        self.cfg = cfg
        self.mesh = mesh
        self.n_channels = n_channels
        self.slots = slots
        self.groups: list[_ChannelGroup] = [
            _ChannelGroup(
                tuple(range(n_channels)),
                fs.create_mesh_state(
                    n_channels, dims, n_buckets=n_buckets, slots=slots,
                    mesh=mesh, shard_state=cfg.shard_state,
                    channels_over_data=n_channels % mesh.shape["data"] == 0,
                ),
            )
        ]
        self._prev_hash: list = [jnp.zeros((2,), U32)
                                 for _ in range(n_channels)]
        self._steps: dict = {}
        self._resizes: dict = {}
        self._stats: dict = {}
        self.obs = obs_mod.Obs.disabled()
        self._auditor = None

    def attach_retrace_auditor(self, auditor) -> None:
        """Route every jit this committer builds (window steps, resize
        exchange, stats pass) through ``auditor.wrap`` (repro.analysis.
        retrace.RetraceAuditor) — the contracts gate drives a live
        workload this way and fails on any trace outside the allowed
        key set. Attach BEFORE the first commit; already-built jits are
        not retrofitted."""
        self._auditor = auditor

    def _jit(self, name: str, fn, **jit_kwargs):
        """``jax.jit`` with optional cache-miss auditing under ``name``."""
        if self._auditor is not None:
            return self._auditor.wrap(name, fn, **jit_kwargs)
        return jax.jit(fn, **jit_kwargs)

    def attach_obs(self, obs) -> None:
        """Route window spans + metrics through ``obs`` (repro.obs.Obs).

        Span boundaries per window (see repro.obs.trace): ``window.fill``
        covers the async dispatch of the step AND the store-chain hash
        fold (host enqueue only), ``window.steady`` blocks until the
        device finishes the window's validate/commit work,
        ``window.drain`` covers the host transfer of the per-block
        hashes. With obs detached nothing syncs that didn't before, and
        with it attached nothing serializes that overlapped before."""
        self.obs = obs

    # -- channel bookkeeping -----------------------------------------------

    def _locate(self, channel: int) -> tuple[_ChannelGroup, int]:
        for g in self.groups:
            if channel in g.channels:
                return g, g.channels.index(channel)
        raise ValueError(
            f"channel {channel} out of range for {self.n_channels} channels"
        )

    def _channels_over_data(self, n: int) -> bool:
        return n % self.mesh.shape["data"] == 0

    @property
    def depth(self) -> int:
        return max(self.cfg.pipeline_depth, 1)

    @property
    def n_shards(self) -> int:
        """Bucket shards of a channel state: the mesh ``model`` size when
        the state is sharded, else 1 (replicated)."""
        return self.mesh.shape["model"] if self.cfg.shard_state else 1

    @property
    def prev_hash(self):
        """Channel 0's store-chain head (single-channel compat)."""
        return self._prev_hash[0]

    @property
    def state(self) -> fs.FabricMeshState:
        """THE mesh state — defined only while every channel shares one
        layout (always true for ``n_channels == 1``, the pre-multi-channel
        surface)."""
        if len(self.groups) != 1:
            raise ValueError(
                "channels hold different bucket layouts: use "
                "channel_state(c) instead of .state"
            )
        return self.groups[0].state

    def channel_state(self, channel: int) -> fs.FabricMeshState:
        """ONE channel's mesh state, with a singleton channel dim — shaped
        exactly like a single-channel committer's ``.state`` (the oracle
        the isolation tests compare against)."""
        g, pos = self._locate(channel)
        return fs.FabricMeshState(
            *(a[pos:pos + 1] for a in g.state)
        )

    @property
    def n_buckets(self) -> int:
        """CURRENT global bucket count of channel 0 (resize epochs move
        it); per-channel layouts via :meth:`n_buckets_for`."""
        return self.n_buckets_for(0)

    def n_buckets_for(self, channel: int) -> int:
        g, _ = self._locate(channel)
        return g.n_buckets

    # -- the window step ----------------------------------------------------

    def _step_for(self, d: int, channels: tuple):
        c_g = len(channels)
        over = self._channels_over_data(c_g)
        # ``channel`` only names the group's channels in shape-cap raises
        # (e.g. >64 model ranks) — it never enters the traced math, so the
        # cache stays keyed by shape alone and ignores channel identity.
        key = (d, c_g, over)
        if key not in self._steps:
            cfg = dataclasses.replace(self.cfg, pipeline_depth=d)
            chan = None if self.n_channels == 1 else channels
            # donate_argnums=(0,): the window step consumes the group
            # state in place — XLA aliases the table planes and heads
            # instead of allocating a second copy per window (the
            # contract analyzer's donation verifier pins that the alias
            # actually happens). Callers never reuse a pre-step state:
            # commit_windows reassigns g.state from the step's output
            # before anything else reads it.
            self._steps[key] = self._jit(
                f"pipeline/window_step/d{d}",
                fs.make_fabric_step(
                    self.dims, cfg, self.mesh, channels_over_data=over,
                    channel=chan,
                ),
                donate_argnums=(0,),
            )
        return self._steps[key]

    def commit_window(self, wire: jnp.ndarray, tx_ids: jnp.ndarray
                      ) -> WindowResult:
        """Commit ``wire`` (D, B, WB) / ``tx_ids`` (D, B, 2), D <= depth.
        Single-channel surface: requires ``n_channels == 1``."""
        if self.n_channels != 1:
            raise ValueError(
                "commit_window drives one channel: use commit_windows "
                f"for {self.n_channels} channels"
            )
        res = self.commit_windows(wire[None], tx_ids[None])
        return WindowResult(
            valid=res.valid[0], prev_hash=res.prev_hash[0],
            block_hash=res.block_hash[0],
        )

    def commit_windows(self, wires: jnp.ndarray, tx_ids: jnp.ndarray
                       ) -> MultiWindowResult:
        """Commit one window on EVERY channel: ``wires`` (C, D, B, WB) /
        ``tx_ids`` (C, D, B, 2), D <= depth. One mesh dispatch per shape
        group (one total while no channel has diverged its layout)."""
        if wires.shape[0] != self.n_channels:
            raise ValueError(
                f"expected {self.n_channels} channel windows, "
                f"got {wires.shape[0]}"
            )
        d = wires.shape[1]
        tracer, reg = self.obs.tracer, self.obs.registry
        step_by_group = [self._step_for(d, g.channels)
                         for g in self.groups]
        valid_by_channel: list = [None] * self.n_channels
        prevs_by_channel: list = [None] * self.n_channels
        hashes_by_channel: list = [None] * self.n_channels
        with tracer.span("window.fill", depth=d):
            # Async dispatch only: the span measures host enqueue time of
            # the whole window — every group's step AND the store-chain
            # hash folds (dispatching all before any sync preserves the
            # overlap the uninstrumented path has).
            for g, step in zip(self.groups, step_by_group):
                chans = list(g.channels)
                wire_g = wires[jnp.asarray(chans)]
                ids_g = tx_ids[jnp.asarray(chans)]
                if d == 1:
                    g.state, valid = step(g.state, wire_g[:, 0],
                                          ids_g[:, 0])
                    valid = valid[:, None]  # (C_g, 1, B)
                else:
                    g.state, valid = step(g.state, wire_g, ids_g)
                # The step donated (and so invalidated) the pre-step
                # state; derive the window's first block number from the
                # post-step counter instead of reading it up front.
                bno0 = g.state.block_no - jnp.uint32(d)  # (C_g,)
                prev = jnp.stack([self._prev_hash[c] for c in chans])
                prevs, hashes = _chain_hashes_multi(
                    prev, bno0, wire_g, valid
                )
                for i, c in enumerate(chans):
                    self._prev_hash[c] = hashes[i, -1]
                    valid_by_channel[c] = valid[i]
                    prevs_by_channel[c] = prevs[i]
                    hashes_by_channel[c] = hashes[i]
        with tracer.span("window.steady", depth=d,
                         sync=lambda: [g.state.ledger_head
                                       for g in self.groups]):
            pass  # device executes the dispatched window inside this span
        with tracer.span("window.drain", depth=d):
            # Host transfer of the per-block chain hashes (the storage
            # role's input). This is the sync the obs-off path pays too.
            prevs = np.stack([np.asarray(p) for p in prevs_by_channel])
            hashes = np.stack([np.asarray(h) for h in hashes_by_channel])
        reg.counter("window.commits").inc()
        reg.counter("blocks.committed").inc(d * self.n_channels)
        if self.n_channels > 1:
            for c in range(self.n_channels):
                reg.counter("blocks.committed", channel=c).inc(d)
        return MultiWindowResult(
            valid=jnp.stack(valid_by_channel), prev_hash=prevs,
            block_hash=hashes,
        )

    # -- elastic state: resize epochs --------------------------------------

    def _resize_program(self, old_nb: int, new_nb: int):
        """Jitted halve/double of ONE channel's state (C=1) for THIS mesh
        (:func:`make_resize_program`). Sharded configs run the butterfly
        neighbor exchange inside shard_map; replicated configs resize
        every rank's copy locally."""
        key = (old_nb, new_nb)
        if key not in self._resizes:
            self._resizes[key] = self._jit(
                "pipeline/resize_exchange",
                make_resize_program(self.cfg, self.mesh, old_nb, new_nb),
            )
        return self._resizes[key]

    def resize(self, new_n_buckets: int, channel: int = 0) -> ReanchorInfo:
        """Halve/double ONE channel's world state between windows.

        The epoch boundary of the elastic state: drains the in-flight
        window (the window write log assumes one partition per window, so
        with ``pipeline_depth > 1`` a resize may only land here, between
        ``commit_window(s)`` calls), splits the channel out of its shape
        group, exchanges/compacts its bucket shards, re-merges it with any
        group already at the new layout, latches any shrink overflow
        sticky, and returns the :class:`ReanchorInfo` the engine must
        commit to that channel's journal. Other channels' states, heads
        and windows are untouched — a resize drains and re-anchors only
        its own channel. The next window re-jits for the new group shapes
        automatically (jit caches per input shape).
        """
        g, pos = self._locate(channel)
        old_nb = g.n_buckets
        if new_n_buckets == old_nb:
            raise ValueError(f"resize to current size {old_nb}")
        self.block_until_ready()  # window boundary: nothing in flight
        # Split the channel out of its group (host-side; epoch-rare).
        if len(g.channels) > 1:
            rest = [i for i in range(len(g.channels)) if i != pos]
            g_state = _take_channels(g.state, rest)
            lone = _take_channels(g.state, [pos])
            g.state = g_state
            g.channels = tuple(c for c in g.channels if c != channel)
        else:
            lone = g.state
            self.groups.remove(g)
        keys, vers, vals, bits = self._resize_program(
            old_nb, new_n_buckets
        )(lone.keys, lone.versions, lone.values)
        lone = lone._replace(
            keys=keys, versions=vers, values=vals,
            overflow=lone.overflow | bits,
        )
        # Merge with an existing group at the new layout (keeps the group
        # count — and so dispatches per window — minimal).
        target = next(
            (h for h in self.groups if h.n_buckets == new_n_buckets), None
        )
        if target is None:
            self.groups.append(_ChannelGroup((channel,), lone))
        else:
            order = sorted(
                range(len(target.channels) + 1),
                key=lambda i: (target.channels + (channel,))[i],
            )
            merged = _concat_channels([target.state, lone])
            target.state = _take_channels(merged, order)
            target.channels = tuple(
                sorted(target.channels + (channel,))
            )
        g2, pos2 = self._locate(channel)
        info = ReanchorInfo(
            block_no=int(np.asarray(g2.state.block_no[pos2])) - 1,
            old_n_buckets=old_nb,
            new_n_buckets=new_n_buckets,
            n_shards=self.n_shards,
            tree_head=self.tree_head(channel),
            overflow_bits=state_sharding.bits_to_int(
                g2.state.overflow[pos2]
            ),
            channel=channel,
        )
        self.obs.tracer.event(
            "reanchor.epoch", block_no=info.block_no, channel=channel,
            old_n_buckets=old_nb, new_n_buckets=new_n_buckets,
            overflow_bits=info.overflow_bits,
        )
        return info

    # -- durability-check surface (engine.verify) --------------------------

    def _stats_program(self, c_g: int, nb: int):
        """Jitted per-group shard stats (:func:`make_stats_program`).
        Output is tiny ((C_g, M) ints), so the host read that follows is
        a few words — NOT the full-table device_get ``hash_state`` pays."""
        key = (c_g, nb)
        if key not in self._stats:
            self._stats[key] = self._jit(
                "pipeline/stats_pass", make_stats_program(self.n_shards)
            )
        return self._stats[key]

    def shard_stats(self, channels) -> dict:
        """channel -> (per-shard occupancy ``(M,)``, min free slots,
        per-shard slot capacity, sticky overflow bits) in ONE stacked
        read per shape group — the vectorized resize-policy /
        health-rollup feed (the serial path synced the host once per
        channel per round)."""
        want = set(channels)
        out = {}
        for g in self.groups:
            sel = [i for i, c in enumerate(g.channels) if c in want]
            if not sel:
                continue
            occ, mf = self._stats_program(len(g.channels), g.n_buckets)(
                g.state.keys, g.state.versions, g.state.values
            )
            occ, mf, ovf = jax.device_get((occ, mf, g.state.overflow))
            cap = g.n_buckets // self.n_shards * self.slots
            for i in sel:
                out[g.channels[i]] = (
                    np.asarray(occ[i]),
                    int(np.asarray(mf[i]).min()),
                    cap,
                    state_sharding.bits_to_int(ovf[i]),
                )
        return out

    def hash_state(self, channel: int = 0) -> ws.HashState:
        """A channel's committed world state as a single-host table
        (global view: for sharded configs the channel's concatenated
        bucket shards ARE the full table — the high-bit partition)."""
        g, pos = self._locate(channel)
        # device_get: the channel axis may be sharded over `data`, and the
        # digest reductions downstream run eagerly — a single-host copy
        # keeps them off the (unsupported) cross-device reduce path. These
        # accessors are cold (verify/snapshot), not the commit loop.
        return ws.HashState(
            keys=jnp.asarray(jax.device_get(g.state.keys[pos])),
            versions=jnp.asarray(jax.device_get(g.state.versions[pos])),
            values=jnp.asarray(jax.device_get(g.state.values[pos])),
        )

    def state_digest(self, channel: int = 0) -> np.ndarray:
        return np.asarray(ws.state_digest(self.hash_state(channel)))

    def tree_head(self, channel: int = 0) -> np.ndarray:
        """(2,) u32 digest-tree head over the per-shard digests — the
        layout-binding commitment re-anchor records and snapshot manifests
        carry (world_state.tree_head)."""
        return np.asarray(
            ws.tree_head(self.hash_state(channel), self.n_shards)
        )

    @property
    def journal_head(self) -> np.ndarray:
        return self.journal_head_for(0)

    def journal_head_for(self, channel: int) -> np.ndarray:
        g, pos = self._locate(channel)
        return np.asarray(g.state.journal_head[pos])

    def ledger_head_for(self, channel: int) -> np.ndarray:
        g, pos = self._locate(channel)
        return np.asarray(g.state.ledger_head[pos])

    @property
    def overflow(self) -> bool:
        """Sticky: any commit on ANY channel ever dropped a write on a
        full bucket — that channel's version accounting can no longer be
        trusted and ``FabricEngine.verify()`` reports it unhealthy."""
        return any(
            bool(np.asarray(g.state.overflow).any()) for g in self.groups
        )

    @property
    def overflow_bits(self) -> int:
        """Channel 0's sticky per-shard bitmask as one host int (lane
        words folded by state_sharding.bits_to_int; bit m == shard m ever
        filled)."""
        return self.overflow_bits_for(0)

    def overflow_bits_for(self, channel: int) -> int:
        g, pos = self._locate(channel)
        return state_sharding.bits_to_int(g.state.overflow[pos])

    @property
    def shard_overflow(self) -> np.ndarray:
        """(M,) bool — WHICH bucket shards of channel 0 ever filled,
        decoded from the sticky bitmask. The resize policy splits while
        this is still all False (pressure-triggered) or repairs capacity
        once a bit sets."""
        bits = self.overflow_bits
        return np.array(
            [(bits >> m) & 1 for m in range(self.n_shards)], dtype=bool
        )

    def hot_shard(self, channel: int = 0) -> int:
        """The shard a grow should relieve (recorded in the engine's
        re-anchor log): the first overflowed shard if any bit is set,
        else the fullest shard by occupancy (world_state.hot_shard)."""
        return ws.hot_shard(
            self.overflow_bits_for(channel),
            ws.shard_occupancy(self.hash_state(channel), self.n_shards),
        )

    def block_no_for(self, channel: int) -> int:
        g, pos = self._locate(channel)
        return int(np.asarray(g.state.block_no[pos]))

    def block_until_ready(self) -> None:
        jax.block_until_ready([g.state.ledger_head for g in self.groups])


# ---------------------------------------------------------------------------
# Contract-analyzer registrations (repro.analysis): the committer's two
# non-step jitted programs, built by the SAME module-level constructors
# its jit cache uses, lowered at BuildContext sizing.
# ---------------------------------------------------------------------------

from repro.analysis import registry as _areg  # noqa: E402


@_areg.register(
    "pipeline/stats_pass",
    description="stacked per-group shard occupancy/min-free reductions",
)
def _build_stats_pass(ctx):
    msize = ctx.mesh.shape["model"]
    fn = jax.jit(make_stats_program(msize))
    nb, s = ctx.n_buckets, ctx.slots
    c = max(ctx.n_channels, 1)
    sd = jax.ShapeDtypeStruct
    args = (
        sd((c, nb, s, 2), jnp.uint32),
        sd((c, nb, s), jnp.uint32),
        sd((c, nb, s, ctx.dims.vw), jnp.uint32),
    )
    return _areg.BuiltProgram(
        name="pipeline/stats_pass", fn=fn, args=args,
        meta={"n_shards": msize},
    )


@_areg.register(
    "pipeline/resize_exchange",
    description="butterfly bucket-shard exchange of one channel's table",
)
def _build_resize_exchange(ctx):
    cfg = fs.FASTFABRIC_SHARDED_STEP
    nb, s = ctx.n_buckets, ctx.slots
    fn = jax.jit(make_resize_program(cfg, ctx.mesh, nb, 2 * nb))
    sd = jax.ShapeDtypeStruct
    args = (
        sd((1, nb, s, 2), jnp.uint32),
        sd((1, nb, s), jnp.uint32),
        sd((1, nb, s, ctx.dims.vw), jnp.uint32),
    )
    return _areg.BuiltProgram(
        name="pipeline/resize_exchange", fn=fn, args=args,
        meta={"old_n_buckets": nb, "new_n_buckets": 2 * nb},
    )

"""End-to-end FastFabric engine: client -> endorse -> order -> commit -> store.

This is the single-host engine used by examples and the Table I end-to-end
benchmark. It wires the roles exactly like the paper's §IV-D setup:

  client (synthetic proposals)
    -> endorser cluster (execute transfer chaincode on the state replica)
    -> orderer (O-I/O-II per config; blocks of ``block_size``)
    -> committer peer (P-I/II/III validation pipeline)
    -> block store (async, off the critical path)  +  endorser replica update

The distributed (mesh-role) version used by the dry-run lives in
launch/fabric_step.py; semantics are identical.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_mod
from repro.core import (
    committer,
    endorser,
    ledger,
    orderer,
    types,
    unmarshal,
)
from repro.core import world_state as ws
from repro.storage import journal as state_journal
from repro.storage import recovery, snapshot

U32 = jnp.uint32


@dataclasses.dataclass(frozen=True)
class ResizePolicy:
    """Between-rounds elastic-state policy: when to halve/double the table.

    Checked after every round (and so, with a window committer, always on
    a window boundary — the window write log assumes one partition per
    window). Overflow strikes when a single BUCKET fills, so the grow
    triggers watch per-shard minimum free slots (the early-warning signal)
    and the sticky overflow bitmask (the repair signal: migrate the hot
    shard's bucket range into a bigger table instead of fail-stopping the
    channel), not just mean occupancy.
    """

    grow_free_slots: int = 1  # double when any shard's fullest bucket has
    # <= this many empty slots left (0 disables the pressure trigger)
    grow_fill: float = 0.0  # ... or when any shard's occupancy fraction
    # exceeds this (0 disables)
    grow_on_overflow: bool = True  # ... or when the sticky bitmask sets
    # (capacity repair; the flag itself stays latched — health is honest)
    shrink_fill: float = 0.0  # halve when TOTAL occupancy drops below this
    # fraction of the halved table (0 disables shrinking)
    max_buckets: int = 1 << 24
    min_buckets: int = 8


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    dims: types.FabricDims = types.TEST_DIMS
    orderer: orderer.OrdererConfig = orderer.OrdererConfig()
    peer: committer.PeerConfig = committer.FASTFABRIC_PEER
    n_buckets: int = 1 << 12
    slots: int = 8
    n_endorsers: int = 3
    store_blocks: bool = True
    # Multi-channel scale-out: N independent Fabric channels (the paper's
    # numbers are per channel; production deployments multiply throughput
    # by running many). Every channel gets its own world state, heads,
    # journal, snapshots and resize epochs; ONE BlockStore writer
    # multiplexes their chains, and a mesh window committer vmaps their
    # validation over the `data` axis. Channel 0 is the implicit channel
    # of the whole single-channel API.
    n_channels: int = 1
    # Block spill directory for the storage role (per-channel subdirs via
    # ledger.channel_dir): enables restore() from a snapshot that TRAILS
    # the journal tip by rebuilding the suffix's ledger head from the
    # spilled blocks.
    block_dir: str | None = None
    # Durability layer (storage/): snapshot every N committed blocks
    # (0 = off), optionally persisted to snapshot_dir; journal_dir spills
    # journal records for cold-start recovery (StateJournal.load);
    # prune_chain compacts the block chain + journal up to each snapshot
    # (the statejournal storage win — history before a snapshot is no
    # longer replayed).
    snapshot_every_blocks: int = 0
    snapshot_dir: str | None = None
    journal_dir: str | None = None
    prune_chain: bool = True
    # Elastic state: between-rounds halve/double of the world-state table,
    # journaled as re-anchor records (None = static table, the old
    # fail-stop-on-overflow behavior). snapshot_shards partitions each
    # snapshot into per-shard files (a mesh-backed committer overrides it
    # with its own shard count).
    resize_policy: ResizePolicy | None = None
    snapshot_shards: int = 1
    # Observability (repro/obs): True builds a per-engine tracer + metrics
    # registry and instruments the round path (per-stage spans, also on
    # the jax.profiler clock; tx/overflow/journal counters, resize
    # events, per-tx lifecycle tracing). False routes every probe to the
    # shared no-op sinks — the hot path gains only null calls, no device
    # syncs. An obs.Obs instance is also accepted (benchmarks sharing one
    # registry across engines).
    obs: bool | object = False
    # Tracer memory bound when the engine builds its own tracer
    # (obs=True): drop-oldest past this many records, evictions counted
    # in trace.dropped_events. None = unbounded (short runs export their
    # complete trace; soak runs should bound it).
    trace_max_events: int | None = None
    # Flight recorder (repro/obs/recorder): always on, fixed memory. A
    # fault edge (verify() contract failure, new sticky overflow latch,
    # resize refusal, exception escaping run_rounds) auto-dumps the
    # recorder's window — trace JSONL + Chrome trace + metrics snapshot +
    # last-N tx lifecycles — into recorder_dir (None: trip is logged,
    # dump stays manual via engine.recorder.dump(dir)).
    recorder_dir: str | None = None
    # Health/SLO rollup objectives (repro/obs/health.SLOConfig); None
    # uses the loose defaults. FabricEngine.health() evaluates them.
    slo: object | None = None

    @property
    def name(self) -> str:
        return f"{self.orderer.name}/{self.peer.name}"


FASTFABRIC = EngineConfig()
FABRIC_V12 = EngineConfig(
    orderer=orderer.OrdererConfig(
        separate_metadata=False, pipelined=False, block_size=100
    ),
    peer=committer.FABRIC_V12_PEER,
)


class RoundStats(NamedTuple):
    n_txs: int
    n_blocks: int
    n_valid: int
    wall_s: float

    @property
    def tps(self) -> float:
        return self.n_txs / self.wall_s if self.wall_s else float("inf")


class _Channel:
    """One channel's mutable engine-side state (world state replicas,
    heads, durability layer, resize history). ``FabricEngine`` holds one
    per configured channel; channel 0 doubles as the target of the whole
    single-channel API (property shims on the engine)."""

    __slots__ = (
        "peer_state", "endorser_state", "log_head", "journal", "snapshots",
        "next_block_no", "overflow", "n_buckets", "reanchor_log",
        "repaired_bits", "restored_overflow_bits", "obs_seen_bits",
        "total_valid", "total_txs",
    )

    def __init__(self, cfg: EngineConfig, journal):
        self.peer_state = committer.create_peer_state(
            cfg.dims, n_buckets=cfg.n_buckets, slots=cfg.slots
        )
        self.endorser_state = ws.create(
            cfg.n_buckets, cfg.slots, cfg.dims.vw
        )
        self.log_head = jnp.zeros((2,), U32)
        self.journal = journal
        self.snapshots: list[snapshot.Snapshot] = []
        self.next_block_no = 0
        self.overflow = jnp.asarray(False)
        self.n_buckets = cfg.n_buckets
        self.reanchor_log: list = []
        self.repaired_bits = 0
        self.restored_overflow_bits = 0
        self.obs_seen_bits = 0
        self.total_valid = 0
        self.total_txs = 0


class FabricEngine:
    """Single-host engine holding all roles (the paper's 15-server testbed
    collapsed onto one device; role separation is preserved logically and
    exercised at scale by the mesh-role dry-run)."""

    def __init__(self, cfg: EngineConfig, *, window_committer=None):
        if cfg.snapshot_every_blocks and not (
            cfg.store_blocks and cfg.peer.journal and cfg.peer.hash_state
        ):
            raise ValueError(
                "snapshot_every_blocks requires store_blocks=True and a "
                "peer config with journal=True and hash_state=True (P-I): "
                "snapshots cover the hash-table state and recovery replays "
                "the journal the storage role materializes"
            )
        self.cfg = cfg
        # Observability handle: per-engine tracer + registry, or the shared
        # no-op pair. The window committer (if any) reports through the
        # same handle, so one collect() covers the whole engine.
        if isinstance(cfg.obs, obs_mod.Obs):
            self.obs = cfg.obs
        else:
            self.obs = (obs_mod.Obs.enabled(max_events=cfg.trace_max_events)
                        if cfg.obs else obs_mod.Obs.disabled())
        if window_committer is not None and self.obs.on:
            window_committer.attach_obs(self.obs)
        # Always-on flight recorder: bounded rings of recent records,
        # tx lifecycles and periodic metric snapshots; fault edges trip
        # it (and auto-dump when cfg.recorder_dir is set). Taps the live
        # tracer as a sink; with obs off it still logs trips/notes.
        self.recorder = obs_mod.FlightRecorder(
            dump_dir=cfg.recorder_dir, registry=self.obs.registry
        )
        self.recorder.attach(self.obs.tracer)
        # Per-transaction lifecycle tracing rides the obs switch: the
        # sidecar stamps only existing sync edges, but materializing the
        # tx-id sidecar is a (small) host transfer obs-off should skip.
        self.txtrace = (
            obs_mod.TxTracer(self.obs.registry, recorder=self.recorder)
            if self.obs.on else obs_mod.NULL_TXTRACER
        )
        # Health/SLO rollup: host-side per-round buckets, works obs-off.
        self.health_rollup = obs_mod.HealthRollup(
            cfg.slo, n_channels=cfg.n_channels
        )
        # Optional device-side block pipeline: an adapter (see
        # repro/pipeline/engine_bridge.MeshWindowCommitter) that commits a
        # WINDOW of pipeline-depth blocks per mesh-step invocation instead
        # of one block per commit_block call — for multi-channel engines
        # it commits ALL channels' windows per invocation (vmapped over
        # the mesh `data` axis). The engine still orders each round and
        # ships every retired block to the storage role.
        self.window_committer = window_committer
        if (window_committer is not None
                and getattr(window_committer, "n_channels", 1)
                != cfg.n_channels):
            raise ValueError(
                f"window committer drives "
                f"{window_committer.n_channels} channels, engine is "
                f"configured for {cfg.n_channels}"
            )
        if cfg.n_channels < 1:
            raise ValueError(f"n_channels must be >= 1, got {cfg.n_channels}")
        # Journal materialization rides the storage role's writer thread —
        # attached only when the durability layer is configured (a snapshot
        # cadence or an on-disk journal), so engines that never asked for a
        # restart story keep the seed's storage-role cost and memory profile.
        # The commit-path head (PeerConfig.journal) is independent and cheap.
        # Each channel journals independently (spill namespaced per channel
        # via ledger.channel_dir).
        want_journal = (cfg.store_blocks and cfg.peer.journal
                        and (cfg.snapshot_every_blocks > 0
                             or cfg.journal_dir is not None))

        def make_journal(c: int):
            if not want_journal:
                return None
            spill = (ledger.channel_dir(cfg.journal_dir, c)
                     if cfg.journal_dir is not None else None)
            return state_journal.StateJournal(
                cfg.dims, spill_dir=spill, metrics=self.obs.registry
            )

        self.chans = [
            _Channel(cfg, make_journal(c)) for c in range(cfg.n_channels)
        ]
        if window_committer is not None:
            for ch in self.chans:
                ch.n_buckets = window_committer.n_buckets
        # ONE store multiplexes every channel (channel-tagged submits,
        # per-channel chains + journals — the paper's storage cluster).
        if cfg.store_blocks:
            if cfg.block_dir is not None:
                os.makedirs(cfg.block_dir, exist_ok=True)
            self.store = ledger.BlockStore(
                cfg.block_dir, journal=self.chans[0].journal,
                tracer=self.obs.tracer,
            )
            for c in range(1, cfg.n_channels):
                if self.chans[c].journal is not None:
                    self.store.set_journal(c, self.chans[c].journal)
        else:
            self.store = None
        self.total_valid = 0
        self.total_txs = 0

    # -- channel-0 shims: the single-channel API is channel 0's view ---------
    # (tests/examples predating multi-channel read AND write these).

    peer_state = property(
        lambda self: self.chans[0].peer_state,
        lambda self, v: setattr(self.chans[0], "peer_state", v),
        doc="Channel 0's committer-peer state.",
    )
    endorser_state = property(
        lambda self: self.chans[0].endorser_state,
        lambda self, v: setattr(self.chans[0], "endorser_state", v),
    )
    log_head = property(
        lambda self: self.chans[0].log_head,
        lambda self, v: setattr(self.chans[0], "log_head", v),
    )
    journal = property(
        lambda self: self.chans[0].journal,
        lambda self, v: setattr(self.chans[0], "journal", v),
    )
    snapshots = property(
        lambda self: self.chans[0].snapshots,
        lambda self, v: setattr(self.chans[0], "snapshots", v),
    )
    reanchor_log = property(
        lambda self: self.chans[0].reanchor_log,
        lambda self, v: setattr(self.chans[0], "reanchor_log", v),
    )
    n_buckets = property(
        lambda self: self.chans[0].n_buckets,
        lambda self, v: setattr(self.chans[0], "n_buckets", v),
        doc="Channel 0's CURRENT table layout (resize epochs move it).",
    )
    _next_block_no = property(
        lambda self: self.chans[0].next_block_no,
        lambda self, v: setattr(self.chans[0], "next_block_no", v),
    )
    # Sticky commit-overflow flag (device scalar, ORed lazily so block
    # commits stay async; materialized by verify()). A dropped insert
    # never bumped its key's version, so an overflowed peer must report
    # unhealthy instead of silently miscounting — and the flag is
    # PERSISTED via the snapshot manifest / re-anchor records, so a
    # peer that overflows, snapshots and restarts stays unhealthy.
    _overflow = property(
        lambda self: self.chans[0].overflow,
        lambda self, v: setattr(self.chans[0], "overflow", v),
    )
    # Overflow bits an overflow-triggered grow already repaired: the
    # sticky mask never un-latches, so the repair trigger compares
    # against this to fire once per NEWLY overflowed shard (not once
    # per process, and not once per round).
    _repaired_bits = property(
        lambda self: self.chans[0].repaired_bits,
        lambda self, v: setattr(self.chans[0], "repaired_bits", v),
    )
    _restored_overflow_bits = property(
        lambda self: self.chans[0].restored_overflow_bits,
        lambda self, v: setattr(self.chans[0], "restored_overflow_bits", v),
    )
    _obs_seen_bits = property(
        lambda self: self.chans[0].obs_seen_bits,
        lambda self, v: setattr(self.chans[0], "obs_seen_bits", v),
    )

    @property
    def n_channels(self) -> int:
        return self.cfg.n_channels

    # -- client --------------------------------------------------------------

    def make_proposals(self, n: int, *, seed: int = 0,
                       n_accounts: int = 1 << 16) -> endorser.Proposal:
        """Synthetic transfer proposals with disjoint account pairs (the
        paper's all-valid, non-conflicting worst case)."""
        rng = np.random.default_rng(seed)
        perm = rng.permutation(max(n_accounts, 2 * n))[: 2 * n].astype(
            np.uint32
        )
        return endorser.Proposal(
            src=jnp.asarray(perm[:n]),
            dst=jnp.asarray(perm[n:]),
            amount=jnp.asarray(
                rng.integers(1, 1000, size=n, dtype=np.uint32)
            ),
            client=jnp.asarray(rng.integers(0, 64, size=n, dtype=np.uint32)),
            nonce=jnp.arange(n, dtype=jnp.uint32) + jnp.uint32(seed << 16),
        )

    # -- one full round --------------------------------------------------------

    def run_round(self, proposals: endorser.Proposal,
                  channel: int = 0) -> RoundStats:
        """One round on ``channel``: endorse (untimed) -> order -> commit
        -> retire.

        Timing boundary follows the paper's §IV-D measurement: the client
        sends *pre-endorsed* transactions, so endorsement/marshaling is
        client/endorser-cluster work outside the peer-throughput window;
        the endorser-replica update after validation runs on the endorser
        cluster's hardware (P-II role separation) and is applied after
        ``RoundStats.wall_s`` ends here (block handoff itself is async).
        It is still inside the round, so a client that times its commits
        end to end pays for it.

        A multi-channel engine backed by a mesh window committer commits
        all channels per dispatch — drive it with :meth:`run_rounds`;
        per-channel rounds there would serialize the mesh per channel.
        Host-path engines (no committer) run any channel's round alone.
        """
        if self.window_committer is not None and self.cfg.n_channels > 1:
            raise ValueError(
                "multi-channel window committer commits all channels per "
                "dispatch: drive rounds with run_rounds(proposals_by_"
                "channel)"
            )
        try:
            return self._round(proposals, channel)
        except Exception as e:
            # Fault edge: an escaping exception mid-round is exactly the
            # moment the flight recorder's last window matters.
            self._fault("exception", where="run_round", channel=channel,
                        error=repr(e))
            raise

    def run_rounds(self, proposals_by_channel: list) -> list[RoundStats]:
        """One lockstep round on EVERY channel (entry c drives channel c).

        With a mesh window committer the channels' windows commit in ONE
        device dispatch per window (vmapped over the mesh `data` axis) —
        the multi-channel scale-out path; rounds must therefore be
        shape-uniform across channels (same tx count and block size — pad
        light channels with filler streams, as the fairness benchmark
        does). Without a committer this is just the per-channel host path
        run back to back under one wall clock. Returns per-channel
        :class:`RoundStats` whose ``wall_s`` is the SHARED round wall (the
        channels ran concurrently), so per-channel TPS = that channel's
        txs over the common wall."""
        if len(proposals_by_channel) != self.cfg.n_channels:
            raise ValueError(
                f"expected {self.cfg.n_channels} proposal batches, got "
                f"{len(proposals_by_channel)}"
            )
        try:
            if self.window_committer is None:
                t0 = time.perf_counter()
                stats = [self._round(p, c)
                         for c, p in enumerate(proposals_by_channel)]
                wall = time.perf_counter() - t0
                return [s._replace(wall_s=wall) for s in stats]
            return self._rounds_meshed(proposals_by_channel)
        except Exception as e:
            self._fault("exception", where="run_rounds", error=repr(e))
            raise

    def _round(self, proposals: endorser.Proposal, channel: int
               ) -> RoundStats:
        cfg = self.cfg
        ch = self.chans[channel]
        n = int(proposals.src.shape[0])
        bs = cfg.orderer.block_size
        if n % bs:
            raise ValueError(f"round of {n} txs not a multiple of {bs}")

        tracer = self.obs.tracer
        # Endorse (endorser cluster; separate hardware under P-II). The
        # replica must reflect all previously retired blocks first.
        with tracer.span("round.endorse", channel=channel):
            txb = endorser.endorse_jit(
                ch.endorser_state, proposals, cfg.dims,
                n_endorsers=cfg.n_endorsers,
            )
            wire = jax.block_until_ready(unmarshal.marshal(txb, cfg.dims))
        # Tx-lifecycle sidecar: tx-ids assigned at submission (the wire
        # is ready — the endorser's content hashes ARE the ids). The
        # sidecar transfer is the obs-on cost; obs-off passes None.
        txr = self.txtrace.begin_round(
            channel, np.asarray(txb.tx_id) if self.obs.on else None,
            bs, ch.next_block_no,
        )
        t0 = time.perf_counter()

        # Order.
        txr.order_start()
        with tracer.span("round.order", channel=channel,
                         sync=lambda: blocks.log_head):
            blocks = orderer.order_batch_jit(
                wire, txb.tx_id, txb.client, ch.log_head, cfg.orderer
            )
            ch.log_head = blocks.log_head
        txr.ordered()

        if self.window_committer is not None:
            # Device-side block pipeline: hand the mesh step a window of
            # blocks per invocation (depth blocks in flight ON device,
            # batched consensus + MVCC gathers) instead of per-block
            # dispatch.
            with tracer.span("round.commit", n_blocks=blocks.wire.shape[0],
                             channel=channel):
                retired = self._commit_windows(blocks, channel, txr)
                self.window_committer.block_until_ready()
        else:
            # Commit block by block; up to pipeline_depth blocks in flight
            # (JAX async dispatch = the paper's block-shepherd goroutines).
            # Note: commits donate the previous peer state, so anything a
            # block needs after retirement (its number, the pre-commit
            # head) is carried host-side / copied — the in-flight tuple
            # never references donated buffers.
            n_blocks = blocks.wire.shape[0]
            with tracer.span("round.commit", n_blocks=n_blocks,
                             channel=channel,
                             sync=lambda: ch.peer_state.ledger_head):
                in_flight = []
                retired = []  # per-block validity bits, for the replay
                for b in range(n_blocks):
                    bno = ch.next_block_no
                    ch.next_block_no += 1
                    prev_head = jnp.array(ch.peer_state.ledger_head,
                                          copy=True)
                    res = committer.commit_block(
                        ch.peer_state, blocks.wire[b], cfg.dims, cfg.peer
                    )
                    ch.peer_state = res.state
                    ch.overflow = ch.overflow | res.overflow
                    in_flight.append((blocks.wire[b], bno, prev_head,
                                      res.block_hash, res.valid))
                    retired.append(res.valid)
                    if len(in_flight) >= max(cfg.peer.pipeline_depth, 1):
                        self._ship(*in_flight.pop(0), channel=channel)
                while in_flight:
                    self._ship(*in_flight.pop(0), channel=channel)

                jax.block_until_ready(ch.peer_state.ledger_head)
                txr.validated(0, n_blocks)
        wall = time.perf_counter() - t0

        # Post-window: endorser-cluster replica updates (their hardware).
        n_valid, valids = self._endorser_replay(
            blocks.wire, retired, channel, collect_valid=self.obs.on
        )
        txr.committed()
        self._policy_pass((channel,))
        self._maybe_snapshot(channel)
        new_bits = self._count_round(channel, n, n_valid, wall,
                                     blocks.wire.shape[0])
        txr.finish(valids, overflow_latched=bool(new_bits))
        return RoundStats(
            n_txs=n, n_blocks=blocks.wire.shape[0], n_valid=n_valid,
            wall_s=wall,
        )

    def _rounds_meshed(self, proposals_by_channel: list) -> list[RoundStats]:
        """The multi-channel mesh round: order every channel, then commit
        all channels' windows in lockstep — one ``commit_windows`` device
        dispatch per window position covers every channel."""
        cfg = self.cfg
        tracer = self.obs.tracer
        wires, blocks_by_ch = [], []
        for c, proposals in enumerate(proposals_by_channel):
            ch = self.chans[c]
            n = int(proposals.src.shape[0])
            if n % cfg.orderer.block_size:
                raise ValueError(
                    f"channel {c}: round of {n} txs not a multiple of "
                    f"{cfg.orderer.block_size}"
                )
            with tracer.span("round.endorse", channel=c):
                txb = endorser.endorse_jit(
                    ch.endorser_state, proposals, cfg.dims,
                    n_endorsers=cfg.n_endorsers,
                )
                wires.append(
                    jax.block_until_ready(unmarshal.marshal(txb, cfg.dims))
                )
            blocks_by_ch.append((txb, wires[-1]))
        shapes = {w.shape for w in wires}
        if len(shapes) > 1:
            raise ValueError(
                f"lockstep rounds need shape-uniform channels, got {shapes}"
            )
        txrs = [
            self.txtrace.begin_round(
                c,
                np.asarray(blocks_by_ch[c][0].tx_id) if self.obs.on
                else None,
                cfg.orderer.block_size, self.chans[c].next_block_no,
            )
            for c in range(cfg.n_channels)
        ]
        t0 = time.perf_counter()
        ordered = []
        for txr in txrs:
            txr.order_start()
        with tracer.span("round.order", channels=cfg.n_channels,
                         sync=lambda: [b.log_head for b in ordered]):
            for c, (txb, wire) in enumerate(blocks_by_ch):
                ch = self.chans[c]
                blocks = orderer.order_batch_jit(
                    wire, txb.tx_id, txb.client, ch.log_head, cfg.orderer
                )
                ch.log_head = blocks.log_head
                ordered.append(blocks)
        for txr in txrs:
            txr.ordered()

        wc = self.window_committer
        n_blocks = ordered[0].wire.shape[0]
        retired: list[list] = [[] for _ in range(cfg.n_channels)]
        with tracer.span("round.commit", n_blocks=n_blocks,
                         channels=cfg.n_channels):
            for lo in range(0, n_blocks, wc.depth):
                hi = min(lo + wc.depth, n_blocks)
                wire_w = jnp.stack([b.wire[lo:hi] for b in ordered])
                ids_w = jnp.stack([b.tx_ids[lo:hi] for b in ordered])
                res = wc.commit_windows(wire_w, ids_w)
                # commit_windows host-synced the window's chain hashes in
                # its drain span: blocks [lo, hi) cleared validation for
                # every channel on that existing edge.
                for txr in txrs:
                    txr.validated(lo, hi)
                for c in range(cfg.n_channels):
                    ch = self.chans[c]
                    retired[c].append(res.valid[c])
                    for k in range(hi - lo):
                        bno = ch.next_block_no
                        ch.next_block_no += 1
                        self._ship(
                            ordered[c].wire[lo + k], bno,
                            res.prev_hash[c, k], res.block_hash[c, k],
                            res.valid[c, k], channel=c,
                        )
            wc.block_until_ready()
        wall = time.perf_counter() - t0

        replayed = []
        for c in range(cfg.n_channels):
            n_valid, valids = self._endorser_replay(
                ordered[c].wire, retired[c], c, collect_valid=self.obs.on
            )
            txrs[c].committed()
            replayed.append((n_valid, valids))
        # ONE stacked stats read drives every channel's policy decision
        # (satellite: the old per-channel _maybe_resize loop synced the
        # host once per channel per round).
        self._policy_pass(range(cfg.n_channels))
        stats = []
        for c in range(cfg.n_channels):
            n = int(proposals_by_channel[c].src.shape[0])
            n_valid, valids = replayed[c]
            self._maybe_snapshot(c)
            new_bits = self._count_round(c, n, n_valid, wall, n_blocks)
            txrs[c].finish(valids, overflow_latched=bool(new_bits))
            stats.append(RoundStats(
                n_txs=n, n_blocks=n_blocks, n_valid=n_valid, wall_s=wall,
            ))
        return stats

    def _endorser_replay(self, wires, retired: list, channel: int,
                         collect_valid: bool = False) -> tuple:
        """Endorser-cluster replica update (their hardware) for one
        channel's round: ``wires`` (n_blocks, B, W) are the round's
        blocks, ``retired`` their validity bits, per block (B,) or per
        window (k, B). One program applies every block in order; returns
        ``(n_valid, valid_by_block)``. ``valid_by_block`` is one host-side
        bool array per block when ``collect_valid`` (the tx-outcome feed),
        else None — the obs-off path keeps its scalar-only host transfer."""
        ch = self.chans[channel]
        with self.obs.tracer.span(
            "round.endorser_replay", channel=channel,
            sync=lambda: ch.endorser_state.versions,
        ):
            if retired[0].ndim == 1:  # the host path's per-block bits
                valids = jnp.stack(retired)
            else:  # one window returns its own bits, with no copy
                valids = jnp.concatenate(retired)
            ch.endorser_state, n_valid = endorser.replay_blocks_jit(
                ch.endorser_state, wires, valids, self.cfg.dims
            )
            reg = self.obs.registry
            reg.counter("replay.dispatches").inc()
            if self.cfg.n_channels > 1:
                reg.counter("replay.dispatches", channel=channel).inc()
            by_block = list(np.asarray(valids)) if collect_valid else None
            n_valid = int(n_valid)
        return n_valid, by_block

    def _count_round(self, channel: int, n: int, n_valid: int,
                     wall_s: float, n_blocks: int) -> int:
        """Fold one round into the totals, the health rollup's bucket
        ring, and (obs on) the overflow gauges + periodic recorder
        snapshot. Returns the NEWLY latched sticky overflow bits (0 with
        obs off) — a non-zero return is a fault edge."""
        ch = self.chans[channel]
        ch.total_valid += n_valid
        ch.total_txs += n
        self.total_valid += n_valid
        self.total_txs += n
        reg = self.obs.registry
        reg.counter("txs.valid").inc(n_valid)
        reg.counter("txs.invalid").inc(n - n_valid)
        if self.cfg.n_channels > 1:
            # Per-channel demand: makes hot channels visible in
            # stats_text() / collect() next to the aggregate counters.
            reg.counter("txs.valid", channel=channel).inc(n_valid)
            reg.counter("txs.invalid", channel=channel).inc(n - n_valid)
        self.health_rollup.push_round(
            channel, n_txs=n, n_valid=n_valid, wall_s=wall_s,
            n_blocks=n_blocks,
        )
        new_bits = 0
        if self.obs.on:
            new_bits = self._record_overflow_metrics(channel)
            self.recorder.snapshot_registry()
            if new_bits:
                self._fault("overflow_latch", channel=channel,
                            bits=new_bits)
        return new_bits

    def _commit_windows(self, blocks, channel: int = 0,
                        txr=None) -> list:
        """Slice the ordered round into pipeline-depth windows and hand
        each to the window committer; ship every block to the store with
        the committer's chain hashes. A round tail shorter than the depth
        becomes one shallower window (compiled once, reused). Returns each
        window's (k, B) validity bits."""
        wc = self.window_committer
        ch = self.chans[channel]
        retired = []
        n_blocks = blocks.wire.shape[0]
        for lo in range(0, n_blocks, wc.depth):
            hi = min(lo + wc.depth, n_blocks)
            res = wc.commit_window(blocks.wire[lo:hi], blocks.tx_ids[lo:hi])
            if txr is not None:
                # commit_window host-synced the window's chain hashes in
                # its drain span — blocks [lo, hi) validated on that edge.
                txr.validated(lo, hi)
            retired.append(res.valid)
            for k in range(hi - lo):
                bno = ch.next_block_no
                ch.next_block_no += 1
                self._ship(
                    blocks.wire[lo + k], bno, res.prev_hash[k],
                    res.block_hash[k], res.valid[k], channel=channel,
                )
        return retired

    def _ship(self, wire_b, bno: int, prev_head, block_hash, valid,
              channel: int = 0):
        """Block leaves the pipeline: async handoff to the storage role."""
        if self.store is not None:
            with self.obs.tracer.span("block.ship", block_no=bno,
                                      channel=channel):
                self.store.submit(bno, prev_head, block_hash, wire_b,
                                  valid, channel=channel)

    # -- observability ---------------------------------------------------------

    def metrics(self) -> dict:
        """One-call snapshot of every engine metric (repro.obs Registry
        collect): counters/gauges as numbers, histograms as
        count/sum/mean/p50/p95/p99 dicts. Empty when obs is off."""
        return self.obs.registry.collect()

    def stats_text(self) -> str:
        """Prometheus text exposition of the engine metrics. Multi-channel
        engines label per-channel series (``txs.valid{channel="c"}``,
        ``state.shard_overflow{channel="c",shard="m"}``), so hot channels
        read straight off the scrape."""
        return self.obs.registry.to_prometheus()

    @property
    def tracer(self):
        return self.obs.tracer

    def _fault(self, reason: str, **ctx) -> None:
        """One engine fault edge fired: trip the flight recorder (which
        auto-dumps the post-mortem when ``cfg.recorder_dir`` is set) and
        surface the trip on the trace."""
        path = self.recorder.trip(reason, **ctx)
        self.obs.tracer.event("engine.fault", reason=reason,
                              dump=path or "")

    def health(self) -> "obs_mod.HealthVerdict":
        """The peer's SLO verdict NOW: ``healthy | degraded | critical``
        with per-channel / per-shard reasons (repro.obs.health).

        Feeds the rollup the live sticky overflow bits and per-shard
        occupancy fractions (one stacked :meth:`_shard_stats` read — NOT
        one sync per channel), evaluates the rolling round window, and
        mirrors the verdict onto ``health.status`` /
        ``health.channel{channel=c}`` gauges for :meth:`stats_text` when
        observability is on. Works with observability off too: the rollup
        runs on host-side round accounting, so the serving layer's
        backpressure can poll it on any engine."""
        chans = range(self.cfg.n_channels)
        stats = self._shard_stats(chans)
        for c in chans:
            occ, _min_free, cap, bits = stats[c]
            self.health_rollup.set_overflow(c, bits)
            self.health_rollup.set_occupancy(
                c, [int(o) / cap for o in occ]
            )
        verdict = self.health_rollup.evaluate()
        if self.obs.on:
            reg = self.obs.registry
            reg.gauge("health.status").set(
                obs_mod.STATUS_RANK[verdict.status]
            )
            for c, info in verdict.channels.items():
                reg.gauge("health.channel", channel=c).set(
                    obs_mod.STATUS_RANK[info["status"]]
                )
        return verdict

    def _record_overflow_metrics(self, channel: int = 0) -> int:
        """Per-shard overflow bits as a labeled gauge + a latch counter
        that fires once per NEWLY set bit. Gauges are keyed
        ``{channel=c, shard=m}`` — one channel's full shard can't hide
        behind (or masquerade as) another's. One tiny host transfer per
        round; only runs with obs on. Returns the newly latched bits (the
        round-level fault-edge signal)."""
        ch = self.chans[channel]
        bits = self.overflow_bits(channel)
        reg = self.obs.registry
        new = bits & ~ch.obs_seen_bits
        if new:
            reg.counter("overflow.latches").inc(bin(new).count("1"))
            ch.obs_seen_bits |= bits
        for m in range(self.n_shards):
            reg.gauge("state.shard_overflow", channel=channel,
                      shard=m).set((bits >> m) & 1)
        return new

    # -- elastic state (resize epochs) -----------------------------------------

    @property
    def n_shards(self) -> int:
        """Bucket shards of snapshot manifests / digest trees: the mesh
        committer's shard count when one is attached, else the configured
        host-side partition."""
        if self.window_committer is not None:
            return self.window_committer.n_shards
        return self.cfg.snapshot_shards

    def _state_view(self, channel: int = 0) -> ws.HashState:
        return (self.window_committer.hash_state(channel)
                if self.window_committer is not None
                else self.chans[channel].peer_state.hash_state)

    def _tree_head(self, state: ws.HashState | None = None,
                   channel: int = 0) -> np.ndarray:
        st = self._state_view(channel) if state is None else state
        return np.asarray(ws.tree_head(st, self.n_shards))

    def overflow_bits(self, channel: int = 0) -> int:
        """Sticky per-shard overflow bitmask of one channel (bit m ==
        shard m filled). Restored bits (a restart re-latching a persisted
        mask) OR in, so a mesh peer's which-shard information survives a
        host-side restore."""
        ch = self.chans[channel]
        if self.window_committer is not None:
            bits = self.window_committer.overflow_bits_for(channel)
        else:
            bits = int(bool(np.asarray(ch.overflow)))
        return bits | ch.restored_overflow_bits

    def _shard_stats(self, channels) -> dict:
        """channel -> (per-shard occupancy ``(M,)``, min free slots,
        per-shard slot capacity, sticky overflow bits) for every requested
        channel in ONE stacked device read — the committer runs a tiny
        jitted reduction per shape group, the host path device_gets one
        lazy tuple tree. Restored overflow bits are OR-ed in, matching
        :meth:`overflow_bits`."""
        channels = list(channels)
        if self.window_committer is not None:
            stats = self.window_committer.shard_stats(channels)
            return {
                c: (occ, mf, cap,
                    bits | self.chans[c].restored_overflow_bits)
                for c, (occ, mf, cap, bits) in stats.items()
            }
        m = self.n_shards
        lazy = {}
        for c in channels:
            st = self.chans[c].peer_state.hash_state
            lazy[c] = (ws.shard_occupancy(st, m),
                       ws.shard_min_free(st, m), self.chans[c].overflow)
        host = jax.device_get(lazy)
        out = {}
        for c in channels:
            st = self.chans[c].peer_state.hash_state
            occ, mf, ovf = host[c]
            out[c] = (
                np.asarray(occ), int(np.asarray(mf).min()),
                st.n_buckets // m * st.slots,
                int(bool(ovf)) | self.chans[c].restored_overflow_bits,
            )
        return out

    def _policy_pass(self, channels) -> dict:
        """The between-rounds policy trigger, vectorized: ONE stacked
        stats read (:meth:`_shard_stats`) drives every channel's
        grow/shrink decision — grow under bucket pressure or after an
        overflow (capacity repair instead of fail-stop), shrink a mostly-
        empty table — plus the per-channel ``state.occupancy`` /
        ``state.health`` gauges and the health rollup's occupancy feed,
        all from the same pass. Rounds are window boundaries, so a window
        committer is always drained here. No policy, no device read.
        Returns ``{channel: resize info}`` for channels that resized."""
        pol = self.cfg.resize_policy
        if pol is None:
            return {}
        channels = list(channels)
        stats = self._shard_stats(channels)
        reg = self.obs.registry
        if self.obs.on:
            reg.counter("resize.policy_checks").inc(len(channels))
        out = {}
        for c in channels:
            ch = self.chans[c]
            occ, min_free, cap, bits = stats[c]
            fills = [int(o) / cap for o in occ]
            self.health_rollup.set_occupancy(c, fills)
            pressure = bool(
                (pol.grow_free_slots and min_free <= pol.grow_free_slots)
                or (pol.grow_fill and max(fills) >= pol.grow_fill)
            )
            if self.obs.on:
                reg.gauge("state.occupancy", channel=c).set(max(fills))
                # 2 = overflowed (fail-stop shard), 1 = under grow
                # pressure, 0 = headroom — the at-a-glance shard health.
                reg.gauge("state.health", channel=c).set(
                    2 if bits else (1 if pressure else 0)
                )
            # Capacity repair: one overflow-triggered grow per NEWLY
            # latched shard bit (the bitmask is sticky, so comparing
            # against the repaired mask keeps a later overflow of a
            # different shard repairable without re-firing every round).
            if pressure or (pol.grow_on_overflow
                            and bits & ~ch.repaired_bits):
                if ch.n_buckets * 2 <= pol.max_buckets:
                    self.obs.tracer.event(
                        "resize.decision", action="grow",
                        min_free=min_free, overflow_bits=bits,
                        n_buckets=ch.n_buckets, channel=c,
                    )
                    ch.repaired_bits |= bits
                    out[c] = self.resize(ch.n_buckets * 2, c)
                elif bits & ~ch.repaired_bits:
                    # Overflowed at the policy's capacity ceiling: the
                    # repair cannot run — a fault edge (fail-stop shard
                    # with no recourse). Latch the bits as repaired so the
                    # refusal trips once, not every following round.
                    ch.repaired_bits |= bits
                    self._fault(
                        "resize_refused", channel=c,
                        n_buckets=ch.n_buckets,
                        max_buckets=pol.max_buckets, overflow_bits=bits,
                    )
                continue
            if (pol.shrink_fill and ch.n_buckets // 2 >= pol.min_buckets
                    and int(occ.sum()) < pol.shrink_fill
                    * (ch.n_buckets // 2) * self.cfg.slots):
                self.obs.tracer.event(
                    "resize.decision", action="shrink",
                    occupancy=int(occ.sum()), n_buckets=ch.n_buckets,
                    channel=c,
                )
                out[c] = self.resize(ch.n_buckets // 2, c)
        return out

    def _maybe_resize(self, channel: int = 0) -> dict | None:
        """Single-channel policy hook (back-compat surface): the round
        paths batch every channel through :meth:`_policy_pass` now."""
        return self._policy_pass((channel,)).get(channel)

    def resize(self, new_n_buckets: int, channel: int = 0) -> dict:
        """Halve/double ONE channel's world state NOW (between rounds) and
        commit a re-anchor record for the epoch — to that channel's
        journal; other channels' tables, heads and journals are untouched.
        The channel's endorser replica follows (its capacity must track
        the peer's or the replicas diverge on which inserts drop), and the
        journal is re-anchored at the drained boundary so verify/replay
        cross the resize."""
        if self.store is not None:
            self.store.drain()  # journal tip must be at the boundary
        ch = self.chans[channel]
        old_nb = ch.n_buckets
        hot = (self.window_committer.hot_shard(channel)
               if self.window_committer is not None
               else self._hot_shard(channel))
        if self.window_committer is not None:
            try:
                info = self.window_committer.resize(new_n_buckets, channel)
            except ValueError as e:
                # The committer refused the epoch (e.g. a no-op resize to
                # the current layout): a fault edge — the caller believed
                # a capacity change was needed and none happened.
                self._fault("resize_refused", channel=channel,
                            n_buckets=old_nb, requested=new_n_buckets,
                            error=str(e))
                raise
            tree, bits = info.tree_head, info.overflow_bits
        else:
            res = ws.resize(ch.peer_state.hash_state, new_n_buckets)
            ch.peer_state = ch.peer_state._replace(hash_state=res.state)
            ch.overflow = ch.overflow | res.overflow
            tree, bits = None, None
        eres = ws.resize(ch.endorser_state, new_n_buckets)
        ch.endorser_state = eres.state
        ch.n_buckets = new_n_buckets
        if tree is None:
            tree, bits = (self._tree_head(channel=channel),
                          self.overflow_bits(channel))
        if ch.journal is not None:
            ch.journal.append_reanchor(
                ch.next_block_no - 1,
                old_n_buckets=old_nb, new_n_buckets=new_n_buckets,
                n_shards=self.n_shards, tree_head=tree, overflow_bits=bits,
            )
        info = {
            "block_no": ch.next_block_no - 1, "old_n_buckets": old_nb,
            "new_n_buckets": new_n_buckets, "overflow_bits": bits,
            "hot_shard": hot, "channel": channel,
        }
        ch.reanchor_log.append(info)
        self.obs.registry.counter(
            "resize.grow" if new_n_buckets > old_nb else "resize.shrink"
        ).inc()
        self.obs.tracer.event("resize.epoch", **info)
        return info

    def _hot_shard(self, channel: int = 0) -> int:
        return ws.hot_shard(
            self.overflow_bits(channel),
            ws.shard_occupancy(self._state_view(channel), self.n_shards),
        )

    # -- durability layer (storage/) -------------------------------------------

    def _maybe_snapshot(self, channel: int = 0) -> None:
        """Snapshot cadence: dump one channel's world state every
        ``snapshot_every_blocks`` committed blocks (per-channel block
        counts — channels snapshot on their own schedules); prune that
        channel's chain + journal with a one-snapshot lag (the previous
        snapshot stays fully recoverable even if the newest one is lost or
        torn). Snapshots are per-shard files + manifest, and the manifest
        persists the sticky overflow bitmask + re-anchor head."""
        cfg = self.cfg
        if not cfg.snapshot_every_blocks:
            return
        ch = self.chans[channel]
        last = ch.snapshots[-1].block_no if ch.snapshots else -1
        tip = ch.next_block_no - 1  # last committed block
        if tip - last < cfg.snapshot_every_blocks:
            return
        self.store.drain()  # journal must cover every shipped block
        with self.obs.tracer.span("snapshot.take", block_no=tip,
                                  channel=channel):
            snap = snapshot.take(
                self._state_view(channel),
                block_no=tip,
                journal_head=self._peer_journal_head(channel),
                ledger_head=self._ledger_head(channel),
                n_shards=self.n_shards,
                overflow_bits=self.overflow_bits(channel),
                reanchor_head=(ch.journal.reanchor_head
                               if ch.journal is not None else None),
            )
        ch.snapshots.append(snap)
        if cfg.snapshot_dir is not None:
            sdir = ledger.channel_dir(cfg.snapshot_dir, channel)
            snapshot.save(sdir, snap, registry=self.obs.registry)
            snapshot.gc(sdir, keep=2, registry=self.obs.registry)
        if cfg.prune_chain and len(ch.snapshots) >= 2:
            base = ch.snapshots[-2].block_no
            self.store.prune_upto(base, channel)
            ch.journal.prune_upto(base)
            ch.snapshots = ch.snapshots[-2:]

    def recover(self, channel: int = 0) -> recovery.RecoveryResult:
        """Cold-start recovery of one channel from its latest snapshot +
        journal suffix (crossing any resize re-anchors in it)."""
        ch = self.chans[channel]
        if ch.journal is None:
            raise recovery.RecoveryError("engine has no journal")
        self.store.drain()
        return recovery.recover(
            ch.journal,
            snapshot=ch.snapshots[-1] if ch.snapshots else None,
            n_buckets=self.cfg.n_buckets,
            slots=self.cfg.slots,
            value_width=self.cfg.dims.vw,
            channel=channel,
        )

    @classmethod
    def restore(cls, cfg: EngineConfig) -> "FabricEngine":
        """Restart a peer from its persisted snapshots + journal spills
        (every configured channel restores from its own namespaced dirs).

        Requires ``journal_dir`` and ``snapshot_dir``. When the latest
        complete snapshot covers the journal tip (the engine snapshots
        after the round that produced the tip, so a crash between rounds
        restores exactly), the snapshot's heads restore directly. When the
        snapshot TRAILS the tip (crash between the journal write and the
        snapshot), the suffix's state replays from the journal and its
        ledger head rebuilds from the ``block_dir`` block spill — the
        spilled blocks must chain from the snapshot's head, and they
        re-seed the store so ``verify()`` replays the same suffix. The
        restored peer re-latches the persisted sticky overflow bitmask —
        overflowing, snapshotting and restarting no longer launders the
        health flag — and resumes on the persisted (post-resize) layout.
        """
        if cfg.journal_dir is None or cfg.snapshot_dir is None:
            raise recovery.RecoveryError(
                "restore requires journal_dir and snapshot_dir"
            )
        eng = cls(cfg)
        for c in range(cfg.n_channels):
            eng._restore_channel(c)
        return eng

    def _restore_channel(self, channel: int) -> None:
        cfg = self.cfg
        ch = self.chans[channel]
        jrnl = state_journal.StateJournal.load(
            cfg.dims, ledger.channel_dir(cfg.journal_dir, channel),
            metrics=self.obs.registry,
        )
        ch.journal = jrnl
        if self.store is not None:
            if channel == cfg.n_channels - 1:
                # Writer swap once, after the last channel's journal loads:
                # the fresh store multiplexes every restored journal.
                self.store.close()
                store = ledger.BlockStore(cfg.block_dir,
                                          journal=self.chans[0].journal,
                                          tracer=self.obs.tracer)
                for c2 in range(1, cfg.n_channels):
                    if self.chans[c2].journal is not None:
                        store.set_journal(c2, self.chans[c2].journal)
                # Re-seed the already-restored channels' bases and chains.
                for c2 in range(channel):
                    old = self.store
                    store.chains[c2] = old.chains.get(c2, [])
                    store.base_block_nos[c2] = old.base_block_nos.get(
                        c2, -1)
                    store.base_hashes[c2] = old.base_hashes.get(
                        c2, np.zeros(2, np.uint32))
                self.store = store
        snap = snapshot.latest(ledger.channel_dir(cfg.snapshot_dir, channel))
        if snap is None:
            raise recovery.RecoveryError(
                f"no complete snapshot for channel {channel} in "
                f"{cfg.snapshot_dir}"
            )
        rec = recovery.recover(
            jrnl, snapshot=snap, n_buckets=cfg.n_buckets, slots=cfg.slots,
            value_width=cfg.dims.vw,
        )
        suffix: list[ledger.StoredBlock] = []
        if rec.block_no != snap.block_no:
            # The snapshot trails the journal tip: the journal already
            # replayed the suffix's STATE, but the ledger head only lives
            # in the block chain — rebuild it from the spilled blocks,
            # verifying they chain from the snapshot's head.
            if cfg.block_dir is None:
                raise recovery.RecoveryError(
                    f"journal tip {rec.block_no} past the latest snapshot "
                    f"{snap.block_no}: the suffix's ledger head is not "
                    "recoverable without the block spill (cfg.block_dir)"
                )
            suffix = ledger.load_spilled_blocks(
                cfg.block_dir, snap.block_no + 1, channel
            )
            suffix = [sb for sb in suffix if sb.block_no <= rec.block_no]
            if not suffix or suffix[-1].block_no != rec.block_no:
                have = suffix[-1].block_no if suffix else snap.block_no
                raise recovery.RecoveryError(
                    f"block spill covers channel {channel} only up to "
                    f"block {have}, journal tip is {rec.block_no}"
                )
            prev = np.asarray(snap.ledger_head)
            for sb in suffix:
                if not np.array_equal(sb.prev_hash, prev):
                    raise recovery.RecoveryError(
                        f"spilled block {sb.block_no} does not chain from "
                        "the snapshot's ledger head (corrupt or tampered)"
                    )
                expect = ledger.append_hash(
                    jnp.asarray(prev), jnp.uint32(sb.block_no),
                    ledger.block_body_digest(
                        jnp.asarray(sb.wire), jnp.asarray(sb.valid)),
                )
                if not np.array_equal(np.asarray(expect), sb.block_hash):
                    raise recovery.RecoveryError(
                        f"spilled block {sb.block_no} fails its chain "
                        "hash (corrupt or tampered)"
                    )
                prev = sb.block_hash
            ledger_head = prev
            # Resize epochs inside the suffix must re-enter the replay
            # log, or verify()'s chain replay lands on the wrong layout.
            for r in jrnl.suffix_reanchors(snap.block_no):
                ch.reanchor_log.append({
                    "block_no": r.block_no,
                    "old_n_buckets": r.old_n_buckets,
                    "new_n_buckets": r.new_n_buckets,
                    "overflow_bits": r.overflow_bits,
                    "hot_shard": -1,  # not persisted; advisory only
                    "channel": channel,
                })
        else:
            ledger_head = np.asarray(snap.ledger_head)
        ch.snapshots = [snap]
        ch.peer_state = ch.peer_state._replace(
            hash_state=rec.state,
            ledger_head=jnp.asarray(ledger_head),
            journal_head=jnp.asarray(rec.journal_head),
            block_no=jnp.uint32(rec.block_no + 1),
        )
        ch.endorser_state = ws.HashState(
            keys=jnp.array(rec.state.keys, copy=True),
            versions=jnp.array(rec.state.versions, copy=True),
            values=jnp.array(rec.state.values, copy=True),
        )
        ch.n_buckets = rec.n_buckets
        # Re-latch the persisted mask WITH its which-shard bits, and mark
        # those bits as already repaired: the pre-crash policy (or its
        # operator) had its chance — a restart must not trigger one more
        # doubling per boot on bits that can never un-latch. A shard that
        # newly overflows AFTER the restart still fires the repair.
        ch.restored_overflow_bits = rec.overflow_bits
        ch.repaired_bits = rec.overflow_bits
        ch.next_block_no = rec.block_no + 1
        if self.store is not None:
            # The chain re-anchors at the snapshot; a rebuilt suffix
            # re-enters it so verify() replays the same blocks recovery
            # replayed from the journal.
            self.store.base_block_nos[channel] = snap.block_no
            self.store.base_hashes[channel] = np.asarray(snap.ledger_head)
            self.store.chains[channel] = list(suffix)

    # -- durability checks (used by tests/examples) ----------------------------

    def _peer_digest(self, channel: int = 0) -> np.ndarray:
        """Digest of a channel's committed world state — from the
        mesh-backed window committer when one is attached, else the peer
        state."""
        if self.window_committer is not None:
            return self.window_committer.state_digest(channel)
        return np.asarray(
            ws.state_digest(self.chans[channel].peer_state.hash_state)
        )

    def _peer_journal_head(self, channel: int = 0) -> np.ndarray:
        if self.window_committer is not None:
            return self.window_committer.journal_head_for(channel)
        return np.asarray(self.chans[channel].peer_state.journal_head)

    def _ledger_head(self, channel: int = 0) -> np.ndarray:
        if self.window_committer is not None:
            return self.window_committer.ledger_head_for(channel)
        return np.asarray(self.chans[channel].peer_state.ledger_head)

    def overflowed(self, channel: int = 0) -> bool:
        """Sticky: any committed block of the channel ever dropped a write
        on a full bucket (mesh-backed committer or the single-host peer
        path)."""
        return bool(self.overflow_bits(channel))

    def verify(self, channel: int = 0) -> dict:
        """Drain storage, verify ONE channel's chain, check its replica
        consistency, check that none of its commits ever overflowed a
        bucket, and prove its recovery path reproduces the live peer.
        Strictly per-channel state: tampering with channel i's chain or
        journal flips channel i's verdicts only (``verify_all`` sweeps
        every channel)."""
        ch = self.chans[channel]
        out = {"chain_ok": True, "replica_ok": True, "replay_ok": True,
               "recovery_ok": True,
               "overflow_ok": not self.overflowed(channel)}
        if self.store is not None:
            self.store.drain()
            out["chain_ok"] = self.store.verify_chain(channel)
            start = None
            missing_base = False
            base_bno = self.store.base_block_nos.get(channel, -1)
            if base_bno >= 0:
                # Chain pruned at a snapshot boundary: replay resumes from
                # the snapshot that covers the compacted prefix. The list
                # may no longer hold it (pruned snapshots, reloaded dir) —
                # that is a verification failure, not a crash: without the
                # covering snapshot the compacted prefix cannot be
                # re-authenticated or replayed.
                base = next(
                    (s for s in ch.snapshots if s.block_no == base_bno),
                    None,
                )
                if base is None:
                    missing_base = True
                else:
                    start = snapshot.to_state(base)
            if missing_base:
                out["chain_ok"] = False
                out["replay_ok"] = False
            else:
                # Replay crosses resize epochs: the recorded halve/doubles
                # apply at their boundaries, so the replayed table lands on
                # the live (post-resize) layout.
                replay_from = base_bno if start is not None else -1
                resize_at: dict = {}
                for r in ch.reanchor_log:
                    if r["block_no"] > replay_from:
                        resize_at.setdefault(r["block_no"], []).append(
                            r["new_n_buckets"])
                replayed = self.store.replay_state(
                    self.cfg.dims, self.cfg.n_buckets, self.cfg.slots,
                    start_state=start, resize_at=resize_at,
                    channel=channel,
                )
                out["replay_ok"] = bool(
                    np.array_equal(
                        np.asarray(ws.state_digest(replayed)),
                        self._peer_digest(channel),
                    )
                ) if self.cfg.peer.hash_state else True
        if ch.journal is not None and self.cfg.peer.hash_state:
            try:
                rec = self.recover(channel)
                out["recovery_ok"] = bool(
                    np.array_equal(rec.state_digest,
                                   self._peer_digest(channel))
                    and np.array_equal(
                        rec.journal_head, self._peer_journal_head(channel)
                    )
                )
            except recovery.RecoveryError:
                out["recovery_ok"] = False
        if self.cfg.peer.hash_state:
            out["replica_ok"] = bool(
                np.array_equal(
                    np.asarray(ws.state_digest(ch.endorser_state)),
                    self._peer_digest(channel),
                )
            )
        if not all(out.values()):
            # Fault edge: the durability contract broke. Trip the flight
            # recorder with the verdict — plus WHICH journal record broke
            # the chain when the journal can say (verify_chain_reason).
            ctx = {"channel": channel,
                   "verdict": {k: bool(v) for k, v in out.items()}}
            if ch.journal is not None:
                jok, why = ch.journal.verify_chain_reason()
                if not jok:
                    ctx["journal_reason"] = why
            self._fault("verify_contract", **ctx)
        return out

    def verify_all(self) -> dict[int, dict]:
        """Per-channel :meth:`verify` verdicts for every channel."""
        return {c: self.verify(c) for c in range(self.cfg.n_channels)}

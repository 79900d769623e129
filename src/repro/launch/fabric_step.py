"""Distributed FastFabric step over the production mesh (shard_map).

Topology mapping (DESIGN.md §2/§5): N independent *channels* sharded over
the ``data`` axis (the paper's future-work "separate ordering and fast
peer per channel" — each data rank holds C/data_size local channels,
vmapped inside the body), and the ``model`` axis inside a channel is the
orderer-replica/validation-worker cluster. Per step and channel:

  1. ingest      — each model rank holds B_loc client proposals (payloads
                   stay put for the whole step: the O-I invariant);
                   syntactic checksum runs locally (P-II parallel
                   validation: each worker validates what it ingested);
  2. consensus   — the log is replicated to every orderer replica:
                   all-gather over ``model`` of the FULL wire (baseline) or
                   only the structured prefix (O-I: IDs + rw sets + tags,
                   ~structure bytes instead of payload bytes) + chain hash;
  3. order       — deterministic interleave by ID hash (identical on every
                   replica, consensus-free);
  4. validate    — endorsement MACs on local txs (parallel), validity bits
                   all-gathered (1 word/tx); MVCC runs on the replicated
                   structured sets — the sequential scan every replica
                   executes identically;
  5. commit      — the channel's world state (replicated over ``model``,
                   sharded over ``data``) applies valid write sets.

The collective-byte asymmetry (payload vs structure bytes over the
``model`` axis) is the paper's Opt O-I, visible directly in the dry-run
HLO — benchmarks/fabric_roofline.py reads it out.

With ``FabricStepConfig.pipeline_depth > 1`` the step takes a WINDOW of D
blocks per invocation and software-pipelines them through the stages
(repro/pipeline/schedule.py): one consensus all-gather, one routed fill
gather (read/write versions + bucket free slots) and ONE fused window
commit scatter instead of one of each per block, with blocks still taking
effect in block order. Depth 1 is this module's single-block body below —
the byte-identical oracle the pipelined path is pinned against, including
windows whose blocks overflow their buckets (tests/test_pipeline.py); both
paths latch the commit overflow flag sticky on the mesh state.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import orderer, types, unmarshal
from repro.core import world_state as ws
from repro.launch import state_sharding
from repro.pipeline import stages

U32 = jnp.uint32


class FabricMeshState(NamedTuple):
    """Per-channel peer state, channel dim leading (sharded over `data`)."""

    keys: jnp.ndarray  # (C, NB, S, 2)
    versions: jnp.ndarray  # (C, NB, S)
    values: jnp.ndarray  # (C, NB, S, VW)
    log_head: jnp.ndarray  # (C, 2)
    ledger_head: jnp.ndarray  # (C, 2)
    journal_head: jnp.ndarray  # (C, 2) — state-journal digest chain
    block_no: jnp.ndarray  # (C,) — next block number (journal chain input)
    overflow: jnp.ndarray  # (C, LANES) u32 — STICKY per-shard BITMASK in
    # state_sharding.OVERFLOW_LANES lane words: bit m of lane m//32 set ==
    # shard m (bit 0 for replicated state) ever dropped a write because
    # a bucket ran out of slots. An overflowed channel's version accounting
    # is no longer trustworthy (the dropped insert never bumped), so
    # FabricEngine.verify() reports it unhealthy — and the elastic-state
    # resize policy reads the hot shard straight off the set bits
    # (state_sharding.overflow_bits; both step paths produce identical
    # masks, pinned by the oracle-equivalence tests).


def create_mesh_state(n_channels: int, dims: types.FabricDims,
                      n_buckets: int = 1 << 10, slots: int = 8, *,
                      mesh=None, shard_state: bool = False,
                      channels_over_data: bool = True) -> FabricMeshState:
    """An empty channel group. With ``mesh`` every array is created in
    place with its :func:`state_specs` sharding (a sharded table is never
    whole on one device); without, on the default device."""
    z = lambda *s: jnp.zeros(s, U32)

    def build():
        return FabricMeshState(
            keys=z(n_channels, n_buckets, slots, 2),
            versions=z(n_channels, n_buckets, slots),
            values=z(n_channels, n_buckets, slots, dims.vw),
            log_head=z(n_channels, 2),
            ledger_head=z(n_channels, 2),
            journal_head=z(n_channels, 2),
            block_no=z(n_channels),
            overflow=z(n_channels, state_sharding.OVERFLOW_LANES),
        )

    if mesh is None:
        return build()
    specs = state_specs(mesh, shard_state=shard_state,
                        channels_over_data=channels_over_data)
    return jax.jit(build, out_shardings=FabricMeshState(
        *(NamedSharding(mesh, s) for s in specs)))()


def state_specs(mesh, *, shard_state: bool = False,
                channels_over_data: bool = True) -> FabricMeshState:
    """Channel dim over `data`. World-state arrays are replicated over
    `model` (replica cluster) by default; with ``shard_state`` their bucket
    dim splits over `model` instead — the high-bit bucket partition of
    launch/state_sharding. Heads stay replicated (identical on every
    rank). With ``channels_over_data=False`` the channel dim replicates
    over `data` instead of sharding it — the fallback for channel groups
    whose size does not divide the data axis (every data rank computes
    every channel of the group; correct, not work-minimal)."""
    d = "data" if channels_over_data else None
    c = lambda nd: P(d, *((None,) * nd))
    s = lambda nd: P(d, "model", *((None,) * (nd - 1)))
    st = s if shard_state else c
    return FabricMeshState(
        keys=st(3), versions=st(2), values=st(3), log_head=c(1),
        ledger_head=c(1), journal_head=c(1), block_no=c(0), overflow=c(1),
    )


def make_fabric_step(dims: types.FabricDims, cfg: "FabricStepConfig", mesh,
                     *, channels_over_data: bool = True, channel=None):
    """Build the jit-able sharded step for C independent channels.

    Inputs (global shapes), with D = ``cfg.pipeline_depth``:
      state: FabricMeshState with C channels leading
      depth 1:  wire (C, B_round, WB) u8, ids (C, B_round, 2) u32
      depth D>1: wire (C, D, B_round, WB) u8, ids (C, D, B_round, 2) u32
    where B_round is one whole channel block; each model rank ingests
    B_round/model_size per block. Returns (state, valid) with valid
    (C, B_round) at depth 1 and (C, D, B_round) at depth D.

    The channel dim shards over `data` ranks when ``channels_over_data``
    (C must be a multiple of the data axis size; each rank holds
    C/data_size local channels) and replicates otherwise. Inside the
    shard_map body the per-channel math is vmapped over the local channel
    axis, so any C_loc >= 1 runs in ONE dispatch — channels share the
    step's collectives but no state, heads, or validity bits (the
    cross-channel isolation the multi-channel tests pin). ``channel``
    (static id or tuple of ids) names the channel(s) in shape-cap raises.

    With ``cfg.shard_state`` the world-state bucket dim is partitioned over
    ``model`` (each rank holds NB/model_size buckets, the high-bit bucket
    partition); reads route to their owner rank via masked-psum gather and
    commits apply only on the owning shard. The replicated path stays as
    the oracle — both must produce byte-identical validity bits and
    ledger/log heads. Depth D > 1 pipelines the window's blocks
    (repro/pipeline/schedule.py) and must be byte-identical to D
    invocations of the depth-1 step.
    """
    msize = mesh.shape["model"]
    if cfg.pipeline_depth > 1:
        return _make_pipelined(dims, cfg, mesh, msize,
                               channels_over_data=channels_over_data,
                               channel=channel)
    spw = unmarshal.struct_prefix_words(dims)

    def chan_body(keys, vers, vals, log_head, ledger_head, journal_head,
                  bno, ovf, wire, ids):
        # ONE channel's local shapes: (NB, S, 2), ..., (B_loc, WB). The
        # shard_map body below vmaps this over the local channel axis.
        b_loc = wire.shape[0]

        # --- 1. local syntactic verification (P-II: validate-where-ingested)
        words, txb_loc, checksum_ok = stages.stage_syntax(wire, dims)
        # Local endorsement verification (worst case: every tag checked).
        endorse_ok = stages.stage_endorse(txb_loc)
        ok_loc = checksum_ok & endorse_ok

        # --- 2. consensus replication over the `model` replica cluster.
        published = (words[:, :spw] if cfg.separate_metadata else words)
        log_glob = jax.lax.all_gather(
            published, "model", axis=0, tiled=True
        )  # (B_round, spw|W)
        log_head = stages.fold_log_head(log_head, log_glob, cfg)

        # --- 3. deterministic order over the channel round.
        ids_glob = jax.lax.all_gather(ids, "model", axis=0, tiled=True)
        order = orderer.consensus_order(ids_glob)

        # --- 4. replicated validation state: flags + structured sets.
        ok_glob = jax.lax.all_gather(ok_loc, "model", axis=0, tiled=True)
        ordered_words = log_glob[order]
        txb = stages.decode_published(
            ordered_words, dims, cfg.separate_metadata
        )
        ok_ord = ok_glob[order]

        st = ws.HashState(keys=keys, versions=vers, values=vals)
        if cfg.shard_state:
            # Routed path: `st` is this rank's bucket shard; reads gather
            # (found, version, value) from the owner rank by masked psum.
            nb_glob = st.n_buckets * msize
            cur = state_sharding.sharded_lookup(
                st, txb.read_keys.reshape(-1, 2), nb_glob, msize
            ).versions.reshape(txb.batch, -1)
        else:
            nb_glob = st.n_buckets
            cur = ws.lookup(
                st, txb.read_keys.reshape(-1, 2)
            ).versions.reshape(txb.batch, -1)

        # --- 5. MVCC + commit (sharded: owner ranks only; else every
        # replica applies the same deltas). The overflow bitmask latches
        # sticky: a dropped insert silently miscounted versions before,
        # and bit m names the hot shard the resize policy should split.
        st2, valid, blk_ovf = stages.stage_mvcc_commit(
            st, txb, ok_ord, cur, cfg,
            n_buckets_global=nb_glob, n_shards=msize, channel=channel,
        )
        ovf = ovf | blk_ovf

        # Ledger append over the ordered round (content + validity), and
        # the state-journal head over the validated write sets.
        led = stages.fold_ledger_head(ledger_head, ordered_words, valid, cfg)
        jrn = stages.advance_journal_head(journal_head, bno, txb, valid)

        # Un-order validity back to ingest layout, return this rank's slice.
        inv = jnp.argsort(order)
        valid_ingest = valid[inv]
        rank = jax.lax.axis_index("model")
        mine = jax.lax.dynamic_slice_in_dim(
            valid_ingest, rank * b_loc, b_loc
        )
        return (
            st2.keys, st2.versions, st2.values,
            log_head, led, jrn, bno + jnp.uint32(1), ovf, mine,
        )

    def step_local(*args):
        # Channels are independent: vmap the per-channel body over the
        # local channel axis (C_loc = C / data_size when sharded, C when
        # replicated). Collectives inside the body batch over channels.
        return jax.vmap(chan_body)(*args)

    cspec = state_specs(mesh, shard_state=cfg.shard_state,
                        channels_over_data=channels_over_data)
    cd = "data" if channels_over_data else None
    io_spec = P(cd, "model", None)
    step = jax.shard_map(
        step_local,
        mesh=mesh,
        in_specs=(cspec.keys, cspec.versions, cspec.values,
                  cspec.log_head, cspec.ledger_head, cspec.journal_head,
                  cspec.block_no, cspec.overflow, io_spec, io_spec),
        out_specs=(cspec.keys, cspec.versions, cspec.values, cspec.log_head,
                   cspec.ledger_head, cspec.journal_head, cspec.block_no,
                   cspec.overflow, P(cd, "model")),
        check_vma=False,
    )

    def window_step(state: FabricMeshState, wire, ids):
        if cfg.shard_state:
            ws.shard_buckets(state.keys.shape[1], msize)  # validate split
        out = step(
            state.keys, state.versions, state.values, state.log_head,
            state.ledger_head, state.journal_head, state.block_no,
            state.overflow, wire, ids,
        )
        return FabricMeshState(*out[:-1]), out[-1]

    return window_step


def _make_pipelined(dims: types.FabricDims, cfg: "FabricStepConfig", mesh,
                    msize: int, *, channels_over_data: bool = True,
                    channel=None):
    """Window variant: D blocks in flight per invocation (schedule.py)."""
    from repro.pipeline import schedule  # local: keeps layering one-way

    depth = cfg.pipeline_depth
    body = schedule.make_window_body(dims, cfg, msize, depth,
                                     channel=channel)

    def step_local(*args):
        # vmap the single-channel window body over the local channel axis.
        return jax.vmap(body)(*args)

    cspec = state_specs(mesh, shard_state=cfg.shard_state,
                        channels_over_data=channels_over_data)
    cd = "data" if channels_over_data else None
    io_spec = P(cd, None, "model", None)  # (C, D, B_round, ...)
    step = jax.shard_map(
        step_local,
        mesh=mesh,
        in_specs=(cspec.keys, cspec.versions, cspec.values,
                  cspec.log_head, cspec.ledger_head, cspec.journal_head,
                  cspec.block_no, cspec.overflow, io_spec, io_spec),
        out_specs=(cspec.keys, cspec.versions, cspec.values, cspec.log_head,
                   cspec.ledger_head, cspec.journal_head, cspec.block_no,
                   cspec.overflow, P(cd, None, "model")),
        check_vma=False,
    )

    def window_step(state: FabricMeshState, wire, ids):
        if cfg.shard_state:
            ws.shard_buckets(state.keys.shape[1], msize)  # validate split
        if wire.ndim != 4 or wire.shape[1] != depth:
            raise ValueError(
                f"pipeline_depth={depth} expects wire (C, {depth}, B, WB); "
                f"got {wire.shape}"
            )
        out = step(
            state.keys, state.versions, state.values, state.log_head,
            state.ledger_head, state.journal_head, state.block_no,
            state.overflow, wire, ids,
        )
        return FabricMeshState(*out[:-1]), out[-1]

    return window_step


@dataclasses.dataclass(frozen=True)
class FabricStepConfig:
    separate_metadata: bool = True  # O-I
    pipelined: bool = True  # O-II
    sequential_commit: bool = False  # paper-faithful serial commit if True
    tree_hash: bool = False  # beyond-paper: O(log B) consensus-log fold
    # (replaces the serial 1600-step chain with a Merkle-style pairwise
    # reduction — different but equally deterministic log head; §Perf)
    shard_state: bool = False  # beyond-paper: world state sharded over
    # `model` by high bucket bits (launch/state_sharding) — the table grows
    # model_size x beyond one device's VMEM budget; replicated path is the
    # oracle (byte-identical validity bits and ledger/log heads).
    pipeline_depth: int = 1  # P-II device-side block pipeline: blocks in
    # flight per step invocation (repro/pipeline). Depth 1 is the
    # single-block path above; depth D takes a (C, D, B, ...) window,
    # issues ONE consensus gather + ONE routed fill gather + ONE fused
    # window commit scatter, and must stay byte-identical to D depth-1
    # invocations — including when blocks overflow their buckets.

    @property
    def name(self) -> str:
        base = "fastfabric" if self.separate_metadata else "fabric-1.2"
        return (base + ("+tree" if self.tree_hash else "")
                + ("+shard" if self.shard_state else "")
                + (f"+pipe{self.pipeline_depth}"
                   if self.pipeline_depth > 1 else ""))


FASTFABRIC_STEP = FabricStepConfig()
FASTFABRIC_SHARDED_STEP = FabricStepConfig(shard_state=True)
FASTFABRIC_PIPELINED_STEP = FabricStepConfig(shard_state=True,
                                             pipeline_depth=8)
FABRIC_V12_STEP = FabricStepConfig(
    separate_metadata=False, pipelined=False, sequential_commit=True
)


def input_specs(mesh, dims: types.FabricDims, b_loc: int = 100,
                pipeline_depth: int = 1, n_channels: int | None = None):
    """ShapeDtypeStructs for the dry-run: one round of B_loc txs per device
    (per block; ``pipeline_depth`` blocks per window when > 1).
    ``n_channels`` defaults to one channel per data rank."""
    c = n_channels if n_channels is not None else mesh.shape["data"]
    m = mesh.shape["model"]
    b_round = b_loc * m
    wb = 4 * dims.payload_words
    if pipeline_depth > 1:
        d = pipeline_depth
        return (
            jax.ShapeDtypeStruct((c, d, b_round, wb), jnp.uint8),
            jax.ShapeDtypeStruct((c, d, b_round, 2), U32),
        )
    return (
        jax.ShapeDtypeStruct((c, b_round, wb), jnp.uint8),
        jax.ShapeDtypeStruct((c, b_round, 2), U32),
    )


# ---------------------------------------------------------------------------
# Contract-analyzer registrations (repro.analysis): each step variant
# self-registers a builder the gate AOT-lowers with the SAME jit wrapper
# and donation the live committer uses — no workload runs.
# ---------------------------------------------------------------------------

from repro.analysis import registry as _areg  # noqa: E402


def _register_step(name: str, cfg: FabricStepConfig, depth: int,
                   n_channels: int = 1, description: str = "") -> None:
    @_areg.register(name, description=description)
    def _build(ctx, cfg=cfg, depth=depth, n_channels=n_channels):
        dcfg = dataclasses.replace(cfg, pipeline_depth=depth)
        step = jax.jit(
            make_fabric_step(ctx.dims, dcfg, ctx.mesh), donate_argnums=(0,)
        )
        state = jax.eval_shape(lambda: create_mesh_state(
            n_channels, ctx.dims, n_buckets=ctx.n_buckets, slots=ctx.slots
        ))
        wire_s, ids_s = input_specs(
            ctx.mesh, ctx.dims, b_loc=ctx.b_loc, pipeline_depth=depth,
            n_channels=n_channels,
        )
        nb_local = ctx.n_buckets // (
            ctx.mesh.shape["model"] if dcfg.shard_state else 1
        )
        return _areg.BuiltProgram(
            name=name, fn=step, args=(state, wire_s, ids_s),
            donate_argnums=(0,), nb_local=nb_local, slots=ctx.slots,
            meta={"depth": depth, "n_channels": n_channels,
                  "config": dcfg.name},
        )


_register_step("fabric_step/repl/d1", FASTFABRIC_STEP, 1,
               description="replicated-state single-block step (the oracle)")
_register_step("fabric_step/shard/d1", FASTFABRIC_SHARDED_STEP, 1,
               description="bucket-sharded single-block step (routed MVCC)")
_register_step("fabric_step/shard/d8", FASTFABRIC_PIPELINED_STEP, 8,
               description="sharded depth-8 window step (fused commit)")
_register_step("fabric_step/shard/d4/c2", FASTFABRIC_SHARDED_STEP, 4,
               n_channels=2,
               description="two channels vmapped through a depth-4 window")

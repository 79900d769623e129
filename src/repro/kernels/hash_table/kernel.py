"""Pallas TPU kernels for the in-VMEM world-state hash table (Opt P-I).

Hardware adaptation (DESIGN.md §2): the paper moves world state up the
memory hierarchy (disk -> RAM). On TPU the same move is HBM -> VMEM: the
state shard *stays VMEM-resident across the whole grid* (its BlockSpec
pins block 0), so every probe is a VMEM access instead of an HBM gather.

VMEM layout (:func:`pack`): vector memory is tiled (8 sublanes x 128
lanes of 32-bit words), so a table laid out (NB, S, 2) would pad every
bucket row to a whole tile. The kernels instead see the table as
``(n_tiles, P, 128)`` int32: P = 3 + VW planes on sublanes (key lo, key
hi, version, value words) and 128 // S buckets side by side on the lanes
of one tile, each bucket's S slots on S adjacent lanes. A probe reads the
bucket's tile by a dynamic leading index, masks the bucket's lanes and
reduces. Scalars (query keys, write keys and values) live in SMEM.

Sizing rule (ops.py enforces): the packed table, :func:`vmem_bytes`, must
fit the VMEM budget per kernel invocation; larger states are sharded by
high bucket bits — over mesh 'model' ranks in the distributed step
(launch/state_sharding), or by ops.py's per-slice dispatch on a single
device — never over sequential grid steps, because the table is mutable
state and grid-step sharding would re-stream HBM, which is exactly what
P-I is designed to avoid.

Kernels:
  * lookup:  grid over query tiles; table resident; one tile read, lane
    mask and reduction per query.
  * commit:  single grid step; sequential fori_loop applies insert-or-update
    write-by-write (the paper's "must be updated sequentially"); the table
    is aliased input->output so the update is in-place in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# NOTE: constants are constructed *inside* kernel bodies — module-level jnp
# constants would be captured as tracer consts, which pallas_call rejects.

U32 = jnp.uint32
I32 = jnp.int32
LANES = 128


def _i32(x):
    return jax.lax.bitcast_convert_type(x, I32)


def _u32(x):
    return jax.lax.bitcast_convert_type(x, U32)


def vmem_bytes(nb: int, s: int, vw: int) -> int:
    """VMEM bytes of the packed (n_tiles, P, 128) table, padding included."""
    if LANES % s:
        raise ValueError(f"slots={s} must divide {LANES}")
    n_tiles = -(-nb * s // LANES)
    sublanes = -(-(3 + vw) // 8) * 8
    return n_tiles * sublanes * LANES * 4


def pack(tkeys, tvers, tvals):
    """(NB,S,2),(NB,S),(NB,S,VW) u32 -> (n_tiles, 3+VW, 128) int32."""
    nb, s, vw = tvals.shape
    if LANES % s:
        raise ValueError(f"slots={s} must divide {LANES}")
    planes = jnp.concatenate(
        [jnp.moveaxis(tkeys, 2, 0), tvers[None], jnp.moveaxis(tvals, 2, 0)]
    )  # (P, NB, S)
    flat = planes.reshape(3 + vw, nb * s)
    flat = jnp.pad(flat, ((0, 0), (0, (-nb * s) % LANES)))
    return _i32(jnp.moveaxis(flat.reshape(3 + vw, -1, LANES), 1, 0))


def unpack(tbl, nb: int, s: int):
    """Inverse of :func:`pack`: (keys, vers, vals) u32."""
    p = tbl.shape[1]
    planes = jnp.moveaxis(_u32(tbl), 0, 1).reshape(p, -1)[:, :nb * s]
    planes = planes.reshape(p, nb, s)
    return (jnp.moveaxis(planes[:2], 0, 2), planes[2],
            jnp.moveaxis(planes[3:], 0, 2))


def _bucket_tile(k0, nb: int, s: int):
    """(tile index, (1, 128) lane mask of the bucket's S slots)."""
    b = jnp.bitwise_and(k0, nb - 1)
    per_tile = LANES // s
    lo = (b % per_tile) * s
    lane = jax.lax.broadcasted_iota(I32, (1, LANES), 1)
    return b // per_tile, (lane >= lo) & (lane < lo + s), lane


def _match(tile, k0, k1, in_bucket):
    nonempty = tile[0:1] != 0
    return (tile[0:1] == k0) & (tile[1:2] == k1) & nonempty & in_bucket, \
        nonempty


def _lookup_kernel(q_ref, tbl_ref, out_ref, *, nb: int, s: int):
    """One grid step: probe TQ queries against the VMEM-resident table.
    ``q`` holds every query (SMEM); ``out`` row i is the matching slot's
    planes (all zero on a miss)."""
    tq = out_ref.shape[0]
    base = pl.program_id(0) * tq

    def body(i, _):
        k0 = q_ref[2 * (base + i)]
        k1 = q_ref[2 * (base + i) + 1]
        t, in_bucket, _lane = _bucket_tile(k0, nb, s)
        tile = tbl_ref[t]  # (P, 128)
        hit, _ = _match(tile, k0, k1, in_bucket)
        out_ref[i] = jnp.sum(jnp.where(hit, tile, 0), axis=1, keepdims=True)
        return 0

    jax.lax.fori_loop(0, tq, body, 0)


@functools.partial(jax.jit, static_argnames=("q_tile", "interpret"))
def lookup(tkeys, tvers, tvals, queries, *, q_tile: int = 128,
           interpret: bool = False):
    """Batched probe. queries (Q,2); Q padded to q_tile multiples.

    Returns (found (Q,) bool, versions (Q,), values (Q,VW)).
    """
    q = queries.shape[0]
    nb, s, _ = tvals.shape
    tbl = pack(tkeys, tvers, tvals)
    p = tbl.shape[1]
    pad = (-q) % q_tile
    qp = _i32(jnp.pad(queries, ((0, pad), (0, 0)))).reshape(-1)
    n_q = qp.shape[0] // 2
    out = pl.pallas_call(
        functools.partial(_lookup_kernel, nb=nb, s=s),
        grid=(n_q // q_tile,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(tbl.shape, lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((q_tile, p, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_q, p, 1), I32),
        interpret=interpret,
    )(qp, tbl)
    out = _u32(out[:q, :, 0])
    # A live key has a non-zero low word, so a hit returns it non-zero.
    return out[:, 0] != 0, out[:, 2], out[:, 3:]


def _commit_kernel(wk_ref, wv_ref, act_ref, _tbl_ref, tbl_ref, ovf_ref, *,
                   nb: int, s: int):
    """Sequential insert-or-update; table aliased in-place (VMEM-resident).

    ``_tbl_ref`` is the aliased input ref — the kernel works on the output
    ref, which shares its memory (input_output_aliases)."""
    p = tbl_ref.shape[1]
    vw = p - 3
    k = act_ref.shape[0]
    ovf_ref[0] = 0

    def body(i, _):
        k0 = wk_ref[2 * i]
        k1 = wk_ref[2 * i + 1]
        a = (act_ref[i] != 0) & (k0 != 0)
        t, in_bucket, lane = _bucket_tile(k0, nb, s)
        tile = tbl_ref[t]  # (P, 128)
        match, nonempty = _match(tile, k0, k1, in_bucket)
        empty = ~nonempty & in_bucket
        exists = jnp.max(match.astype(I32)) > 0
        has_empty = jnp.max(empty.astype(I32)) > 0
        # Slot: the match if present, else the first empty slot.
        first_empty = jnp.min(jnp.where(empty, lane, LANES))
        hit_lane = jnp.max(jnp.where(match, lane, -1))
        slot = jnp.where(exists, hit_lane, first_empty)
        ok = a & (exists | has_empty)
        ovf_ref[0] = ovf_ref[0] | (a & ~exists & ~has_empty).astype(I32)
        old_ver = jnp.sum(jnp.where(match, tile[2:3], 0))
        new_ver = jnp.where(exists, old_ver + 1, 1)
        # The new slot contents as a column over the planes.
        plane = jax.lax.broadcasted_iota(I32, (p, 1), 0)
        col = jnp.where(plane == 0, k0, k1)
        col = jnp.where(plane == 2, new_ver, col)
        for v in range(vw):
            col = jnp.where(plane == 3 + v, wv_ref[i * vw + v], col)
        write = (lane == slot) & ok
        tbl_ref[t] = jnp.where(write, col, tile)
        return 0

    jax.lax.fori_loop(0, k, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def commit(tkeys, tvers, tvals, wkeys, wvals, active, *,
           interpret: bool = False):
    """Sequential commit of K writes. Returns (keys, vers, vals, overflow)."""
    nb, s, _ = tvals.shape
    tbl = pack(tkeys, tvers, tvals)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    whole = pl.BlockSpec(tbl.shape, lambda: (0, 0, 0))
    tbl, ovf = pl.pallas_call(
        functools.partial(_commit_kernel, nb=nb, s=s),
        in_specs=[smem, smem, smem, whole],
        out_specs=[whole, smem],
        out_shape=[
            jax.ShapeDtypeStruct(tbl.shape, I32),
            jax.ShapeDtypeStruct((1,), I32),
        ],
        input_output_aliases={3: 0},
        interpret=interpret,
    )(_i32(wkeys).reshape(-1), _i32(wvals).reshape(-1),
      active.astype(I32), tbl)
    return (*unpack(tbl, nb, s), ovf[0] != 0)

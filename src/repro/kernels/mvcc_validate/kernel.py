"""Pallas TPU kernel for MVCC block validation.

The paper's "must be sequential" step (§III-D), restructured for TPU
(DESIGN.md §2): the pairwise conflict matrix — does tx j's write set touch
tx i's read+write set — is dense vectorized VPU work computed *in parallel*
inside VMEM; the irreducibly sequential part shrinks to a B-step boolean
scan that propagates one validity bit per transaction:

    valid[i] = ok0[i] & vers_ok[i] & !any_{j<i}(valid[j] & conflict[j, i])

Grid: one step per block (multiple blocks pipeline through the kernel, the
paper's multi-block validation pipeline). The wrapper lays keys out with
transactions on lanes (rows) or sublanes (columns), so the conflict matrix
is a column-against-row broadcast and the scan reads one row of it per
step. Per-block VMEM: the (B, B) i32 conflict matrix plus the key planes —
B=512, RK=WK=4 is ~1.3 MiB, comfortably resident.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

U32 = jnp.uint32


def _mvcc_kernel(tcol_ref, wrow_ref, vrow_ref, valid_ref, conf_ref):
    """One block. Inputs are laid out for the vector unit by
    :func:`validate_blocks` (leading block dim 1):

      tcol (B, 2T)      read+write keys of tx i, i on sublanes
      wrow (2WK, B)     write keys of tx j, j on lanes
      vrow (3RK+1, B)   read-key lo words, read versions, current
                        versions, ok0 — txs on lanes
    """
    tcol = tcol_ref[0]
    wrow = wrow_ref[0]
    vrow = vrow_ref[0]
    bsz = tcol.shape[0]
    rk = (vrow.shape[0] - 1) // 3

    # --- Parallel part 1: read-set freshness (one lane per tx). ---
    ok = vrow[3 * rk:3 * rk + 1] != jnp.uint32(0)
    for r in range(rk):
        active = vrow[r:r + 1] != jnp.uint32(0)
        fresh = vrow[2 * rk + r:2 * rk + r + 1] == vrow[rk + r:rk + r + 1]
        ok = ok & (~active | fresh)

    # --- Parallel part 2: conflict matrix conf[i, j] = tx j's writes touch
    # tx i's read+write set (VPU broadcast of a column against a row). ---
    conf = jnp.zeros((bsz, bsz), bool)
    for w in range(wrow.shape[0] // 2):
        w0 = wrow[2 * w:2 * w + 1]
        w1 = wrow[2 * w + 1:2 * w + 2]
        nonempty = w0 != jnp.uint32(0)
        for t in range(tcol.shape[1] // 2):
            conf = conf | ((tcol[:, 2 * t:2 * t + 1] == w0)
                           & (tcol[:, 2 * t + 1:2 * t + 2] == w1) & nonempty)
    conf_ref[...] = conf.astype(jnp.int32)

    # --- Sequential part: one validity bit per step. ---
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, bsz), 1)
    ok_i32 = ok.astype(jnp.int32)

    def body(i, valid):
        earlier = (lane < i).astype(jnp.int32)
        blocked = jnp.max(conf_ref[pl.ds(i, 1), :] * valid * earlier)
        ok_i = jnp.max(jnp.where(lane == i, ok_i32, 0))
        return jnp.where(lane == i, ok_i * (1 - blocked), valid)

    valid = jax.lax.fori_loop(0, bsz, body, jnp.zeros((1, bsz), jnp.int32))
    valid_ref[0] = valid.astype(U32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def validate_blocks(read_keys, read_vers, write_keys, current_versions, ok0,
                    *, interpret: bool = False):
    """Validate NB blocks of B txs each. Inputs (NB, B, ...); out (NB, B) bool."""
    nb, b, rk, _ = read_keys.shape
    wk = write_keys.shape[2]
    lanes = lambda x: jnp.swapaxes(x, 1, 2)  # (NB, B, K) -> (NB, K, B)
    tcol = jnp.concatenate([read_keys, write_keys], axis=2).reshape(
        nb, b, 2 * (rk + wk))
    wrow = lanes(write_keys.reshape(nb, b, 2 * wk))
    vrow = jnp.concatenate([
        lanes(read_keys[..., 0]), lanes(read_vers), lanes(current_versions),
        ok0.astype(U32)[:, None, :],
    ], axis=1)
    spec = lambda *s: pl.BlockSpec((1, *s), lambda i: (i,) + (0,) * len(s))
    valid = pl.pallas_call(
        _mvcc_kernel,
        grid=(nb,),
        in_specs=[spec(b, 2 * (rk + wk)), spec(2 * wk, b),
                  spec(3 * rk + 1, b)],
        out_specs=spec(1, b),
        out_shape=jax.ShapeDtypeStruct((nb, 1, b), U32),
        scratch_shapes=[pltpu.VMEM((b, b), jnp.int32)],
        interpret=interpret,
    )(tcol, wrow, vrow)
    return valid[:, 0].astype(bool)

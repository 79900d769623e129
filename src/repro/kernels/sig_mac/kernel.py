"""Pallas TPU kernel for the Carter-Wegman endorsement MAC.

The endorsement-policy check (§III-H) verifies every transaction's tags on
the critical path. The MAC is a degree-W polynomial over GF(2^31-1)
evaluated by Horner's rule: sequential in W (the polynomial chain) but
embarrassingly parallel across transactions — the kernel maps transactions
to VPU lanes and walks the message words with a fori_loop, the message
tile VMEM-resident and the endorser keys in SMEM.

Mersenne-31 modular multiply uses 16-bit limb decomposition (see
repro.core.crypto): TPUs have no 64-bit integer units, so 32x32 products
are assembled from 16x16 partials that each fit u32 — every op here is a
native VPU u32 op.

Block shape: (W, TB) message tiles (transposed, transactions on lanes);
all NE endorser keys are verified in one pass per tile (grid = tx tiles).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

U32 = jnp.uint32


def _mod31(x):
    p = jnp.uint32((1 << 31) - 1)
    x = (x & p) + (x >> 31)
    x = (x & p) + (x >> 31)
    return jnp.where(x == p, jnp.uint32(0), x)


def _addmod31(a, b):
    return _mod31(a + b)


def _mulmod31(a, b):
    m16 = jnp.uint32(0xFFFF)
    m15 = jnp.uint32(0x7FFF)
    ah, al = a >> 16, a & m16
    bh, bl = b >> 16, b & m16
    hi2 = _mod31((ah * bh) << 1)  # *2^32 == *2 (mod p)

    def shift16(x):  # (x * 2^16) mod p for x < 2^31
        x = _mod31(x)
        return _mod31(((x & m15) << 16) + (x >> 15))

    mid = _addmod31(shift16(ah * bl), shift16(al * bh))
    lo = _mod31(al * bl)
    return _addmod31(_addmod31(hi2, mid), lo)


def _mac_kernel(r_ref, s_ref, msg_ref, tag_ref):
    """r/s (NE,) endorser keys in SMEM; msg (W, TB) with transactions on
    lanes; tag (NE, TB). Endorsers are a static loop, words a fori_loop
    over sublane rows."""
    w, tb = msg_ref.shape
    for e in range(tag_ref.shape[0]):
        r = jnp.full((1, tb), r_ref[e], U32)

        def body(i, acc, r=r):
            m = _mod31(msg_ref[pl.ds(i, 1), :])
            return _addmod31(_mulmod31(acc, r), m)

        acc = jax.lax.fori_loop(0, w, body, jnp.zeros((1, tb), U32))
        tag_ref[pl.ds(e, 1), :] = _addmod31(
            acc, jnp.full((1, tb), s_ref[e], U32))


@functools.partial(jax.jit, static_argnames=("tx_tile", "interpret"))
def mac_many(msg, rs, ss, *, tx_tile: int = 256, interpret: bool = False):
    """Tags for all endorsers: (B, W) x (NE,) -> (B, NE) u32.

    The kernel sees the messages transposed, (W, B): transactions on the
    128-wide lane axis, so ``tx_tile`` is a multiple of 128 on a TPU."""
    b, w = msg.shape
    ne = rs.shape[0]
    pad = (-b) % tx_tile
    msgp = jnp.pad(msg, ((0, pad), (0, 0)))
    bp = msgp.shape[0]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tags = pl.pallas_call(
        _mac_kernel,
        grid=(bp // tx_tile,),
        in_specs=[smem, smem,
                  pl.BlockSpec((w, tx_tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((ne, tx_tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((ne, bp), U32),
        interpret=interpret,
    )(rs, ss, msgp.T)
    return tags.T[:b]

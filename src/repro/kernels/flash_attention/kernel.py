"""Pallas TPU flash attention (forward), GQA-aware.

§Perf motivation: the train/prefill roofline is dominated by attention
score traffic — XLA materializes (B,H,S,S) f32 tiles at fusion boundaries
even under the chunked-scan formulation (EXPERIMENTS.md Cell A iter 3).
The VMEM-resident online-softmax kernel is the TPU-native fix: one
(q_block x kv_block) tile lives in VMEM per grid step, HBM sees only
Q/K/V/O.

Layout: grid (batch, q_heads, q_blocks); each step streams KV chunks for
its (batch, kv_head = q_head // group) through a fori_loop carrying the
(acc, m, l) online-softmax state. Causal masking prunes the KV loop bound
per q block (exact N^2/2 work). MXU-aligned tiles: q_block/kv_block
multiples of 128 on real hardware (tests use smaller interpret-mode
tiles).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, causal: bool,
                  kv_block: int, q_block: int, seq_kv: int):
    qb = q_ref.shape[0]
    d = q_ref.shape[1]
    iq = pl.program_id(2)
    scale = 1.0 / math.sqrt(d)
    q = q_ref[...].astype(jnp.float32) * scale  # (qb, d)

    nk = seq_kv // kv_block
    if causal:
        # KV blocks strictly after this q block's last row are fully masked.
        hi = jnp.minimum(((iq + 1) * q_block + kv_block - 1) // kv_block, nk)
    else:
        hi = nk

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[pl.dslice(j * kv_block, kv_block), :].astype(jnp.float32)
        v = v_ref[pl.dslice(j * kv_block, kv_block), :].astype(jnp.float32)
        s = q @ k.T  # (qb, kb)
        if causal:
            qpos = iq * q_block + jax.lax.broadcasted_iota(
                jnp.int32, (qb, kv_block), 0
            )
            kpos = j * kv_block + jax.lax.broadcasted_iota(
                jnp.int32, (qb, kv_block), 1
            )
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_new, l

    acc0 = jnp.zeros((qb, d), jnp.float32)
    m0 = jnp.full((qb,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((qb,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l[:, None], 1e-37)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_block", "kv_block", "interpret"),
)
def flash_attention(q, k, v, *, causal: bool = True, q_block: int = 256,
                    kv_block: int = 256, interpret: bool = False):
    """q (B,S,H,D), k/v (B,Skv,Hkv,D) -> (B,S,H,D). GQA by head grouping."""
    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    group = h // hkv
    q_block = min(q_block, sq)
    kv_block = min(kv_block, skv)
    if sq % q_block or skv % kv_block:
        raise ValueError("sequence not divisible by block size")
    grid = (b, h, sq // q_block)

    kernel = functools.partial(
        _flash_kernel, causal=causal, kv_block=kv_block, q_block=q_block,
        seq_kv=skv,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, q_block, None, d),
                         lambda bi, hi, qi: (bi, qi, hi, 0)),
            pl.BlockSpec((None, skv, None, d),
                         lambda bi, hi, qi: (bi, 0, hi // group, 0)),
            pl.BlockSpec((None, skv, None, d),
                         lambda bi, hi, qi: (bi, 0, hi // group, 0)),
        ],
        out_specs=pl.BlockSpec((None, q_block, None, d),
                               lambda bi, hi, qi: (bi, qi, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq, h, d), q.dtype),
        interpret=interpret,
    )(q, k, v)
    return out

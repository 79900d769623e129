"""Dispatch for flash attention: the Pallas kernel when the caller asks
for it (``interpret`` only without a TPU), else the XLA paths."""

from __future__ import annotations

from repro.kernels.flash_attention import kernel, ref
from repro.models import layers


def attention(q, k, v, *, causal: bool = True, use_pallas: bool = False,
              interpret: bool = False, q_block: int = 256,
              kv_block: int = 256):
    """Self-attention core. The Pallas flash kernel with ``use_pallas``;
    otherwise the exact-causal chunked-scan XLA formulation
    (models/layers.attn_chunked) for long sequences and naive scores for
    short ones."""
    if use_pallas:
        return kernel.flash_attention(
            q, k, v, causal=causal, q_block=q_block, kv_block=kv_block,
            interpret=interpret,
        )
    if q.shape[1] > 2 * q_block:
        return layers.attn_chunked(q, k, v, causal=causal,
                                   q_chunk=q_block, kv_chunk=kv_block)
    return ref.flash_attention_ref(q, k, v, causal=causal)

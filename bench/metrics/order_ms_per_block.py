"""Ordering (``core/orderer.order_batch_jit``): the ``round.order`` spans
of the window over its blocks. Moves ``committed_tps``."""


def read(ctx):
    return ctx.span_ms_per_block("round.order")

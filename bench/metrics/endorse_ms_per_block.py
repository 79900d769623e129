"""Endorsement and marshalling (``core/endorser.endorse_jit`` and
``unmarshal.marshal``): the ``round.endorse`` spans of the window over its
blocks. Moves ``committed_tps``."""


def read(ctx):
    return ctx.span_ms_per_block("round.endorse")

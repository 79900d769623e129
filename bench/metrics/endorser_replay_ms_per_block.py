"""Endorser-replica update (``engine._endorser_replay``): the
``round.endorser_replay`` spans of the window over its blocks. Moves
``committed_tps``."""


def read(ctx):
    return ctx.span_ms_per_block("round.endorser_replay")

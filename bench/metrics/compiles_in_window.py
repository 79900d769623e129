"""Programs compiled or loaded from the compile cache inside the window
(``jax.monitoring`` backend-compile events). Moves
``commit_latency_p95_ms``: a compile is a stall of the round it hits."""


def read(ctx):
    return ctx.compiles

"""Share of the traced window in which no XLA op ran on the device, the
mean over the chips the cell uses (profiler trace). Moves
``committed_tps``."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace["idle_pct"]

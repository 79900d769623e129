"""Peer throughput inside the paper's window (order + commit, §IV-D):
the window's transactions over the sum of the engine's own round walls
(``RoundStats.wall_s``). Moves ``committed_tps``."""


def read(ctx):
    wall = sum(r.wall_s for r in ctx.rounds)
    return sum(r.n_txs for r in ctx.rounds) / wall if wall > 0 else None

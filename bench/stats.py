"""End-to-end numbers from the window's round records (host clock)."""

from __future__ import annotations

import math
from typing import NamedTuple


class RoundRecord(NamedTuple):
    """One closed-loop round as the client saw it."""

    t_send: float  # perf_counter when the round was handed to the engine
    t_done: float  # ... and when the call returned
    n_txs: int  # transactions of every channel in the round
    n_valid: int
    wall_s: float  # the engine's own order+commit wall (RoundStats)


def committed_tps(rounds: list, window_s: float) -> float:
    """Transactions of all rounds completed in the window over the
    window's wall time (which ends when the store has drained)."""
    return sum(r.n_txs for r in rounds) / window_s


def latency_percentile_ms(rounds: list, q: float) -> float:
    """The ``q``-th percentile (nearest rank) over every transaction of
    the window, each taking its round's send-to-return time."""
    lat = sorted((r.t_done - r.t_send, r.n_txs) for r in rounds)
    total = sum(n for _, n in lat)
    rank = max(1, math.ceil(q / 100.0 * total))
    seen = 0
    for dt, n in lat:
        seen += n
        if seen >= rank:
            return dt * 1e3
    raise ValueError("no rounds")

"""Block store writer (``core/ledger.BlockStore``): per block, the time
from its submit to the end of its ``store.append`` span (queued plus host
copy, spill and journal), nearest-rank 95th percentile over the window's
blocks. Moves ``committed_tps``: the window closes when the store drains."""

import math


def read(ctx):
    lags = sorted(r["args"]["queued_s"] + r["dur"] for r in ctx.spans
                  if r["name"] == "store.append")
    if not lags:
        return None
    return 1e3 * lags[max(1, math.ceil(0.95 * len(lags))) - 1]

"""Shared benchmark helpers: timing, workload construction, CSV rows."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import endorser, engine, types, unmarshal

ROWS: list[dict] = []


def row(bench: str, name: str, tps: float = None, **extra) -> dict:
    r = {"bench": bench, "name": name, "tps": tps, **extra}
    ROWS.append(r)
    keys = [k for k in ("tps", *extra.keys()) if r.get(k) is not None]

    def fmt(v):
        if isinstance(v, float) and abs(v) < 100:
            return f"{v:.3g}"
        if isinstance(v, (int, float)):
            return f"{v:,.0f}"
        return str(v)

    body = " ".join(f"{k}={fmt(r[k])}" for k in keys)
    print(f"  {bench:14s} {name:28s} {body}")
    return r


def print_csv() -> None:
    cols = sorted({k for r in ROWS for k in r})
    print(",".join(cols))
    for r in ROWS:
        print(",".join(str(r.get(c, "")) for c in cols))


def dump_json(path: str) -> None:
    """Write the collected rows as JSON (CI uploads these as artifacts so
    the per-PR perf trajectory is tracked)."""
    import json
    import os

    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)

    def clean(v):
        if isinstance(v, (np.integer,)):
            return int(v)
        if isinstance(v, (np.floating,)):
            return float(v)
        if isinstance(v, (np.bool_,)):
            return bool(v)
        return v

    with open(path, "w") as f:
        json.dump([{k: clean(v) for k, v in r.items()} for r in ROWS],
                  f, indent=1)


def make_endorsed_wire(dims: types.FabricDims, n: int, *, seed: int = 0,
                       state=None):
    """N endorsed transfer txs, marshaled. Returns (wire, tx_ids, clients)."""
    from repro.core import world_state as ws

    if state is None:
        state = ws.create(1 << 10, 8, dims.vw)
    rng = np.random.default_rng(seed)
    n_acct = max(2 * n, 4)
    perm = rng.permutation(n_acct)[: 2 * n].astype(np.uint32)
    props = endorser.Proposal(
        src=jnp.asarray(perm[:n]),
        dst=jnp.asarray(perm[n:]),
        amount=jnp.asarray(rng.integers(1, 1000, n, dtype=np.uint32)),
        client=jnp.asarray(rng.integers(0, 64, n, dtype=np.uint32)),
        nonce=jnp.arange(n, dtype=jnp.uint32),
    )
    txb = endorser.execute_and_endorse(state, props, dims)
    wire = unmarshal.marshal(txb, dims)
    return jax.block_until_ready(wire), txb.tx_id, txb.client


def timed_samples(fn, *args, warmup: int = 1, iters: int = 3) -> list[float]:
    """Wall-time samples of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return ts


def timed(fn, *args, warmup: int = 1, iters: int = 3):
    """Median wall time of fn(*args) with block_until_ready."""
    return float(np.median(timed_samples(fn, *args, warmup=warmup,
                                         iters=iters)))


def latency_hist(samples):
    """Fold wall-clock samples (seconds) into a repro.obs Histogram —
    benchmarks report percentiles through the same fixed-bucket log2
    semantics the engine registry uses, so rows and live metrics agree."""
    from repro.obs.metrics import Histogram

    h = Histogram()
    for s in samples:
        h.record(float(s))
    return h


def percentile_cols(hist, prefix: str = "commit") -> dict:
    """p50/p95/p99 columns (ms) from an obs Histogram."""
    return {
        f"{prefix}_p50_ms": 1e3 * hist.percentile(50),
        f"{prefix}_p95_ms": 1e3 * hist.percentile(95),
        f"{prefix}_p99_ms": 1e3 * hist.percentile(99),
    }


def metrics_cols(collected: dict, name: str, prefix: str) -> dict:
    """Absorb one histogram out of a ``Registry.collect()`` snapshot into
    row columns (p50/p95/p99 in ms + count). Empty when the metric is
    absent (obs off or the path never recorded)."""
    snap = collected.get(name)
    if not snap or not snap.get("count"):
        return {}
    return {
        f"{prefix}_p50_ms": 1e3 * snap["p50"],
        f"{prefix}_p95_ms": 1e3 * snap["p95"],
        f"{prefix}_p99_ms": 1e3 * snap["p99"],
        f"{prefix}_n": snap["count"],
    }


def txphase_cols(collected: dict) -> dict:
    """Per-tx lifecycle decomposition columns out of a registry snapshot:
    p50/p95/p99 (ms) for each tx.phase.* histogram plus tx.e2e, and the
    p99 commit bucket's most recent exemplar tx-id — a p99 row always
    names a concrete transaction (repro.obs.txtrace's contract). Columns
    are ``tx_``-prefixed: the plain ``commit_*`` columns are the
    round-level commit latency, a different measurement. Empty when tx
    tracing never ran (obs off)."""
    out = {}
    for p in ("queue", "order", "validate", "commit"):
        cols = metrics_cols(collected, f"tx.phase.{p}", f"tx_{p}")
        cols.pop(f"tx_{p}_n", None)  # every phase shares the e2e count
        out.update(cols)
    out.update(metrics_cols(collected, "tx.e2e", "tx_e2e"))
    ex = (collected.get("tx.phase.commit") or {}).get("p99_exemplars")
    if ex:
        out["p99_exemplar_tx"] = ex[-1]["tx_id"]
    return out

"""The per-layer metric files of ``BENCHMARK.json``, read from a
synthetic ``Context`` as the harness builds it after a traced run."""

import pytest

from bench import harness, spec
from bench.tests.test_bench_harness import ROOT


def _span(name, dur, **args):
    return {"name": name, "ts": 0.0, "dur": dur, "depth": 0,
            "parent": None, "tid": 1, "args": args}


def _context():
    spans = ([_span("round.endorse", 0.004, channel=0)] * 2
             + [_span("round.order", 0.001, channel=0)]
             + [_span("store.append", 0.001 * k, block_no=k, channel=0,
                      queued_s=0.010) for k in range(1, 21)])
    return harness.Context([], spans, 8, 0, None)


@pytest.mark.parametrize("name, want", [
    ("endorse_ms_per_block", 1.0),  # 2 x 4 ms over 8 blocks
    ("store_lag_ms_p95", 29.0),  # 19th of 20 lags, 10 ms queued + 19 ms
])
def test_new_metric_reads_its_span(name, want):
    read = spec.metric_reader(ROOT, name)
    assert read(_context()) == pytest.approx(want)
    # A program without the span reads nothing rather than raising.
    assert read(harness.Context([], [], 8, 0, None)) is None

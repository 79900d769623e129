"""The idle-share and per-program reduction of a profiler trace."""

import json
import os

import numpy as np
import pytest

from bench import devtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _synthetic():
    # Window [1000, 2000) ns. Chip 0 runs ops [1100, 1300) and [1250, 1500)
    # (overlapping: busy 400) and [1900, 2100) (clipped: busy 100); chip 1
    # one op [1000, 1100).
    return {
        "devices": {
            "0": {"ops": [[1100, 200, "a"], [1250, 250, "b"],
                          [1900, 200, "c"]],
                  "modules": [[1100, 400, "jit_step(7)"],
                              [1900, 200, "jit_other(9)"]]},
            "1": {"ops": [[1000, 100, "a"]],
                  "modules": [[1000, 100, "jit_step(7)"]]},
            "2": {"ops": [], "modules": []},  # a chip the cell did not use
        },
        "host": [[1000, 1000, "bench.window"], [1000, 900, "bench.round"],
                 [1500, 300, "PjitFunction(order)"],
                 [1900, 100, "bench.generate"]],
    }


def test_union_of_busy_intervals_inside_the_window():
    r = devtrace.reduce(_synthetic())
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["chips"]["0"]["busy_s"] == pytest.approx(500e-9)
    assert r["chips"]["0"]["idle_pct"] == pytest.approx(50.0)
    assert r["chips"]["1"]["idle_pct"] == pytest.approx(90.0)
    assert set(r["chips"]) == {"0", "1"}
    assert r["idle_pct"] == pytest.approx(70.0)
    assert r["busy_s"] == pytest.approx(300e-9)


def test_programs_and_gaps_are_named():
    r = devtrace.reduce(_synthetic())
    assert r["device_ops"][0] == ["jit_step", pytest.approx(500e-9)]
    assert r["device_ops"][1] == ["jit_other", pytest.approx(100e-9)]
    # Chip 1 idles [1100, 2000); chip 0's longest gap is [1500, 1900),
    # while the host dispatched the orderer inside bench.round.
    assert r["idle_gaps"][0] == ["chip1:bench.round/PjitFunction(order)",
                                 pytest.approx(900e-9)]
    assert r["idle_gaps"][1] == ["chip0:bench.round/PjitFunction(order)",
                                 pytest.approx(400e-9)]


def test_no_window_or_no_device_op_reads_nothing():
    ev = _synthetic()
    assert devtrace.reduce(dict(ev, host=ev["host"][1:])) is None
    assert devtrace.reduce(dict(ev, devices={"0": {"ops": [],
                                                   "modules": []}})) is None


def test_recorded_chip_trace():
    """10 ms of a host-path round traced on one v5e chip (op names cut to
    their first word): the union of op intervals agrees with a timeline
    painted op by op, and the program that took most device time is the
    per-block commit."""
    ev = json.load(open(os.path.join(DATA, "trace_ff_host_10ms.json")))
    r = devtrace.reduce(ev)
    (lo, dur, _), = [e for e in ev["host"] if e[2] == devtrace.WINDOW]
    busy = np.zeros(dur, bool)
    for s, d, _ in ev["devices"]["0"]["ops"]:
        busy[max(s - lo, 0):max(min(s + d - lo, dur), 0)] = True
    assert r["window_s"] == pytest.approx(dur / 1e9)
    assert r["busy_s"] == pytest.approx(busy.sum() / 1e9)
    assert r["idle_pct"] == pytest.approx(100 * (1 - busy.mean()))
    assert 0 < r["idle_pct"] < 100
    assert r["device_ops"][0][0] == "jit_commit_block_fused"
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) == 10
    gaps = [g for _, g in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert all(name.startswith("chip0:bench.round")
               for name, _ in r["idle_gaps"])

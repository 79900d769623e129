"""Observability layer: histogram math, span tracing, engine metrics.

Percentile properties run under hypothesis when it is installed and fall
back to a seeded random sweep otherwise (same property, fixed seeds).
"""

import json
import os
import time

import numpy as np
import pytest

from repro.launch import state_sharding
from repro.obs import Obs
from repro.obs.metrics import (
    Histogram,
    NULL_REGISTRY,
    Registry,
)
from repro.obs.trace import NULL_TRACER, Tracer

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# Histogram percentiles vs numpy (property)
# ---------------------------------------------------------------------------


def _check_percentiles(samples):
    """The pinned bound: nearest-rank numpy percentile <= histogram
    percentile <= 2x (one log2 bucket ratio), for samples inside the
    histogram's finite range."""
    h = Histogram()
    for v in samples:
        h.record(v)
    for q in (50.0, 90.0, 95.0, 99.0):
        true = float(np.percentile(samples, q, method="inverted_cdf"))
        est = h.percentile(q)
        assert true <= est <= 2.0 * true, (q, true, est)


def _log_uniform(rng, n):
    # Strictly inside (lo, hi): the bound needs value > lo (bucket 0
    # reports lo itself) and value <= hi (overflow reports inf).
    return np.exp(rng.uniform(np.log(2e-7), np.log(5e2), size=n))


if HAVE_HYPOTHESIS:

    @settings(max_examples=50, deadline=None)
    @given(
        st.lists(
            st.floats(min_value=2e-7, max_value=5e2,
                      allow_nan=False, allow_infinity=False),
            min_size=1, max_size=300,
        )
    )
    def test_percentiles_within_bucket_ratio(samples):
        _check_percentiles(np.asarray(samples))

else:

    @pytest.mark.parametrize("seed", range(20))
    def test_percentiles_within_bucket_ratio(seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 300))
        _check_percentiles(_log_uniform(rng, n))


def test_percentile_edges():
    h = Histogram()
    assert np.isnan(h.percentile(50))  # empty
    h.record(1e-9)  # below lo -> bucket 0, reported as lo
    assert h.percentile(50) == h.lo
    h2 = Histogram()
    h2.record(1e9)  # past hi -> overflow bucket, reported as inf
    assert h2.percentile(50) == float("inf")
    h3 = Histogram()
    h3.record(1.0)
    snap = h3.snapshot()
    assert snap["count"] == 1 and snap["sum"] == 1.0
    assert 1.0 <= snap["p50"] <= 2.0


def test_merge_is_exact():
    """Merged histogram == histogram of the pooled samples, bucket for
    bucket — the property that makes per-shard percentile merges exact."""
    rng = np.random.default_rng(7)
    a, b = _log_uniform(rng, 200), _log_uniform(rng, 133)
    ha, hb, hp = Histogram(), Histogram(), Histogram()
    for v in a:
        ha.record(v)
        hp.record(v)
    for v in b:
        hb.record(v)
        hp.record(v)
    ha.merge(hb)
    assert ha.counts == hp.counts
    assert ha.count == hp.count
    assert ha.percentile(95) == hp.percentile(95)
    with pytest.raises(ValueError):
        ha.merge(Histogram(lo=1e-6))  # different edges: not exact


def test_registry_semantics():
    reg = Registry()
    reg.counter("c").inc(3)
    reg.counter("c").inc()
    assert reg.collect()["c"] == 4
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)
    with pytest.raises(TypeError):
        reg.gauge("c")  # kind mismatch on the same name
    reg.gauge("g", shard=3).set(7)
    reg.gauge("g", shard=1).set(5)
    col = reg.collect()
    assert col["g{shard=3}"] == 7 and col["g{shard=1}"] == 5
    reg.histogram("h").record(0.5)
    text = reg.to_prometheus()
    assert "# TYPE c counter" in text
    assert 'g{shard="3"} 7' in text
    assert 'h_bucket{le="+Inf"} 1' in text
    # null registry absorbs everything and stays empty
    NULL_REGISTRY.counter("x").inc(10)
    assert NULL_REGISTRY.collect() == {}
    assert NULL_REGISTRY.to_prometheus() == ""


# ---------------------------------------------------------------------------
# Span tracer: nesting, ordering, export formats
# ---------------------------------------------------------------------------


def test_span_nesting_and_chrome_export(tmp_path):
    tr = Tracer()
    with tr.span("outer", kind="round"):
        with tr.span("inner_a"):
            time.sleep(0.002)
        tr.event("marker", n=3)
        with tr.span("inner_b"):
            time.sleep(0.001)
    recs = tr.records()
    by_name = {r["name"]: r for r in recs}
    assert by_name["outer"]["depth"] == 0
    assert by_name["outer"]["parent"] is None
    for child in ("inner_a", "inner_b", "marker"):
        assert by_name[child]["depth"] == 1
        assert by_name[child]["parent"] == "outer"
    # records() orders by start time: outer first, then children in order
    names = [r["name"] for r in recs]
    assert names == ["outer", "inner_a", "marker", "inner_b"]
    # children are contained in the parent's interval
    o = by_name["outer"]
    for child in ("inner_a", "inner_b"):
        c = by_name[child]
        assert o["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= o["ts"] + o["dur"] + 1e-9
    # chrome export: spans are "X" complete events (us), events are "i"
    ev = {e["name"]: e for e in tr.chrome_events()}
    assert ev["outer"]["ph"] == "X"
    assert ev["outer"]["dur"] == pytest.approx(o["dur"] * 1e6)
    assert ev["marker"]["ph"] == "i"
    assert ev["marker"]["args"] == {"n": 3}
    # both dump formats round-trip as JSON
    jl, cj = tmp_path / "t.jsonl", tmp_path / "t.json"
    tr.dump_jsonl(str(jl))
    lines = [json.loads(x) for x in jl.read_text().splitlines()]
    assert [x["name"] for x in lines] == names
    tr.dump_chrome(str(cj))
    doc = json.loads(cj.read_text())
    assert len(doc["traceEvents"]) == 4


def test_span_sync_callable_and_set_sync():
    tr = Tracer()
    hit = []
    with tr.span("s", sync=lambda: hit.append("exit") or None):
        hit.append("body")
    assert hit == ["body", "exit"]  # sync resolved at exit, after the body
    with tr.span("s2") as sp:
        sp.set_sync(lambda: hit.append("late") or None)
    assert hit[-1] == "late"
    # an exception skips the sync but still pops/emits the span
    with pytest.raises(RuntimeError):
        with tr.span("s3", sync=lambda: hit.append("never")):
            raise RuntimeError("boom")
    assert "never" not in hit
    assert tr._stack() == []
    assert {r["name"] for r in tr.records()} == {"s", "s2", "s3"}


def test_null_tracer_never_syncs():
    hit = []
    with NULL_TRACER.span("x", sync=lambda: hit.append("sync")):
        pass
    assert hit == []  # obs-off must not add the span-edge device sync
    assert NULL_TRACER.records() == []


def _host_events(trace_dir) -> list:
    """``(start_ns, dur_ns, name, line)`` of every host event in the one
    ``.xplane.pb`` that ``jax.profiler`` wrote under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return [(e.start_ns, e.duration_ns, e.name, i)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for i, line in enumerate(plane.lines) for e in line.events]


def test_live_span_is_a_profiler_host_event(tmp_path):
    """A live span is written into a real ``jax.profiler`` trace under its
    own name, nested on the same thread inside the annotation that
    encloses it, and covers its exit sync; the null tracer writes none."""
    import jax
    import jax.numpy as jnp

    tr = Tracer()
    x = jnp.arange(4096.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.outer"):
            with tr.span("test.live", sync=lambda: y):
                y = jnp.sin(x) * 2
            with NULL_TRACER.span("test.null"):
                jnp.cos(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    (o_s, o_d, _, o_line), = [e for e in ev if e[2] == "test.outer"]
    (s_s, s_d, _, s_line), = [e for e in ev if e[2] == "test.live"]
    assert s_line == o_line
    assert o_s <= s_s and s_s + s_d <= o_s + o_d
    assert not [e for e in ev if e[2] == "test.null"]
    rec, = tr.records()
    assert rec["name"] == "test.live"
    # the annotation closes after the sync, so it spans the tracer's record
    assert s_d >= 0.5 * rec["dur"] * 1e9


def test_live_span_annotation_closes_when_the_body_raises(tmp_path):
    """A span whose body raises still closes its profiler annotation: the
    event is in the trace, and the next annotation on the thread is not
    nested inside it."""
    import jax

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with pytest.raises(ValueError):
            with tr.span("test.raises"):
                raise ValueError("body")
        with jax.profiler.TraceAnnotation("test.after"):
            pass
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    (r_s, r_d, _, r_line), = [e for e in ev if e[2] == "test.raises"]
    (a_s, _, _, a_line), = [e for e in ev if e[2] == "test.after"]
    assert a_line == r_line
    assert a_s >= r_s + r_d
    assert tr._stack() == []


def test_engine_round_spans_are_profiler_host_events(tmp_path):
    """With obs on, one engine round writes its layer spans into a
    ``jax.profiler`` trace inside the caller's annotation, on the caller's
    thread; the store writer's ``store.append`` spans sit on another."""
    import jax

    from repro.core import engine as eng_mod
    from repro.core import types

    eng = eng_mod.FabricEngine(eng_mod.EngineConfig(
        dims=types.TEST_DIMS, n_buckets=1 << 12, obs=True))
    bs = eng.cfg.orderer.block_size
    props = eng.make_proposals(2 * bs, seed=3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.round"):
            eng.run_round(props)
        eng.store.drain()
    finally:
        jax.profiler.stop_trace()
    eng.store.close()
    ev = _host_events(tmp_path)
    (o_s, o_d, _, o_line), = [e for e in ev if e[2] == "test.round"]
    for name in ("round.endorse", "round.order", "round.commit",
                 "round.endorser_replay"):
        hits = [e for e in ev if e[2] == name]
        assert hits, name
        assert all(line == o_line and o_s <= s and s + d <= o_s + o_d
                   for s, d, _, line in hits), name
    appends = [e for e in ev if e[2] == "store.append"]
    assert len(appends) == 2
    assert {line for *_, line in appends} != {o_line}


def test_block_store_traces_each_append():
    """``BlockStore(tracer=...)`` records one ``store.append`` per block on
    its writer thread, with the block's number, its channel and the time
    it waited in the queue."""
    import threading

    from repro.core import ledger

    tr = Tracer()
    store = ledger.BlockStore(tracer=tr)
    blocks = [(b, c) for b in range(3) for c in (0, 2)]
    for bno, c in blocks:
        store.submit(bno, np.zeros(2, np.uint32),
                     np.full(2, bno + 1, np.uint32),
                     np.zeros((4, 8), np.uint32), np.ones(4, bool),
                     channel=c)
    store.drain()
    store.close()
    recs = tr.records()
    assert [r["name"] for r in recs] == ["store.append"] * len(blocks)
    assert sorted((r["args"]["block_no"], r["args"]["channel"])
                  for r in recs) == sorted(blocks)
    assert all(r["args"]["queued_s"] >= 0 and r["dur"] >= 0 for r in recs)
    assert {r["tid"] for r in recs} == {store._t.ident}
    assert store._t.ident != threading.get_ident()
    assert [len(store.chains[c]) for c in (0, 2)] == [3, 3]


def test_obs_handle():
    off = Obs.disabled()
    assert not off.on
    on = Obs.enabled()
    assert on.on
    with on.tracer.span("a"):
        on.registry.counter("c").inc()
    assert on.registry.collect()["c"] == 1
    assert off.registry.collect() == {}


# ---------------------------------------------------------------------------
# Overflow bitmask lanes: >32 shards (the widened mask regression)
# ---------------------------------------------------------------------------


def test_overflow_bits_two_lanes():
    import jax.numpy as jnp

    for m, set_bits in ((40, [0, 5, 31, 32, 39]), (64, [33, 63])):
        flags = np.zeros(m, bool)
        flags[set_bits] = True
        lanes = np.asarray(state_sharding.overflow_bits(jnp.asarray(flags)))
        assert lanes.shape == (state_sharding.OVERFLOW_LANES,)
        assert lanes.dtype == np.uint32
        bits = state_sharding.bits_to_int(lanes)
        assert bits == sum(1 << b for b in set_bits)
        # bits above rank 31 live in lane 1, not truncated
        assert any(b >= 32 for b in set_bits) == (lanes[1] != 0)


def test_bits_int_lanes_roundtrip():
    rng = np.random.default_rng(11)
    for _ in range(50):
        bits = int(rng.integers(0, 1 << 63, dtype=np.uint64))
        lanes = state_sharding.int_to_lanes(bits)
        assert lanes.dtype == np.uint32
        assert state_sharding.bits_to_int(lanes) == bits


def test_overflow_bits_over_max_raises():
    import jax.numpy as jnp

    flags = jnp.zeros(state_sharding.MAX_OVERFLOW_SHARDS + 1, bool)
    with pytest.raises(ValueError):
        state_sharding.overflow_bits(flags)


def test_reanchor_head_binds_high_lane():
    """The re-anchor chain link must see shard bits past rank 31 — a
    lower-word-only fold would let two different overflow states share a
    head."""
    from repro.storage.journal import reanchor_head_update

    common = dict(
        prev_reanchor=np.zeros(2, np.uint32),
        prev_head=np.zeros(2, np.uint32),
        block_no=3, old_n_buckets=64, new_n_buckets=128, n_shards=40,
        tree_head=np.zeros(2, np.uint32),
    )
    lo = reanchor_head_update(overflow_bits=1 << 3, **common)
    hi = reanchor_head_update(overflow_bits=1 << 35, **common)
    none = reanchor_head_update(overflow_bits=0, **common)
    assert not np.array_equal(hi, none)  # high lane is bound
    assert not np.array_equal(hi, lo)
    # deterministic
    assert np.array_equal(hi, reanchor_head_update(
        overflow_bits=np.uint64(1 << 35), **common))


# ---------------------------------------------------------------------------
# Engine metrics: stability across snapshot / restore
# ---------------------------------------------------------------------------


def test_engine_metrics_stable_across_restore(tmp_path):
    """A restored engine starts a fresh registry: reloading the journal
    and snapshot must not replay appends/commits into the metrics (no
    double counting), and post-restore rounds count from zero."""
    from repro.core import engine as eng_mod
    from repro.core import types

    cfg = eng_mod.EngineConfig(
        dims=types.TEST_DIMS, n_buckets=1 << 12,
        snapshot_every_blocks=2,
        snapshot_dir=str(tmp_path / "snap"),
        journal_dir=str(tmp_path / "jrnl"),
        obs=True,
    )
    eng = eng_mod.FabricEngine(cfg)
    bs = cfg.orderer.block_size
    eng.run_round(eng.make_proposals(4 * bs, seed=1))
    eng.store.drain()
    m = eng.metrics()
    assert m["journal.appends"] == 4
    assert m["txs.valid"] == 4 * bs
    assert m["snapshot.saves"] == 1
    names = {r["name"] for r in eng.tracer.records()}
    assert {"round.endorse", "round.order", "round.commit",
            "round.endorser_replay", "block.ship", "store.append",
            "snapshot.take"} <= names
    eng.store.close()

    eng2 = eng_mod.FabricEngine.restore(cfg)
    m2 = eng2.metrics()
    assert m2.get("journal.appends", 0) == 0  # reload is not an append
    assert "txs.valid" not in m2
    eng2.run_round(eng2.make_proposals(2 * bs, seed=2))
    m3 = eng2.metrics()
    assert m3["journal.appends"] == 2
    assert m3["txs.valid"] == 2 * bs
    assert all(eng2.verify().values())


def test_engine_obs_off_is_empty():
    from repro.core import engine as eng_mod
    from repro.core import types

    eng = eng_mod.FabricEngine(eng_mod.EngineConfig(dims=types.TEST_DIMS))
    eng.run_round(eng.make_proposals(2 * eng.cfg.orderer.block_size))
    assert eng.metrics() == {}
    assert eng.tracer.records() == []


# ---------------------------------------------------------------------------
# benchmarks/perf_gate.py — the CI perf-trajectory gate's join semantics
# ---------------------------------------------------------------------------


def test_perf_gate_compare():
    from benchmarks.perf_gate import compare

    base = [
        {"bench": "fig11", "name": "pipe/d=8", "tps": 1000.0,
         "commit_scatters": 1, "commit_p95_ms": 2.0},
        {"bench": "fig12", "name": "elastic/final", "tps": 500.0,
         "overflow_ok": True},
        {"bench": "fig11", "name": "equivalence/d=8", "identical": True},
        {"bench": "fig4", "name": "O-I@512B", "tps": 200.0},
    ]
    # Self-compare: clean.
    failures, _ = compare(base, base)
    assert failures == []
    # Within-tolerance dip: note, not failure; improvements never fail.
    cur = [dict(r) for r in base]
    cur[0]["tps"] = 900.0
    cur[3]["tps"] = 400.0
    failures, notes = compare(base, cur)
    assert failures == []
    assert any("within tolerance" in n for n in notes)
    # Past-tolerance TPS regression fails.
    cur[0]["tps"] = 700.0
    failures, _ = compare(base, cur)
    assert any("pipe/d=8" in f and "regression" in f for f in failures)
    # Contract flip fails even with healthy TPS.
    cur[0]["tps"] = 1000.0
    cur[1]["overflow_ok"] = False
    cur[2]["identical"] = False
    failures, _ = compare(base, cur)
    assert any("overflow_ok flipped" in f for f in failures)
    assert any("identical flipped" in f for f in failures)
    # Missing contract row fails; missing plain row only notes.
    failures, notes = compare(base, [base[0], base[1], base[3]])
    assert any("equivalence/d=8" in f and "missing" in f for f in failures)
    failures, notes = compare(base, base[:3])
    assert failures == []
    assert any("O-I@512B" in n and "missing" in n for n in notes)
    # Latency drift >2x is reported, never gated.
    cur = [dict(r) for r in base]
    cur[1]["overflow_ok"] = True
    cur[0]["commit_p95_ms"] = 5.0
    failures, notes = compare(base, cur)
    assert failures == []
    assert any("commit_p95_ms" in n for n in notes)


def test_perf_gate_main(tmp_path):
    import json as _json

    from benchmarks.perf_gate import main

    rows = [{"bench": "fig11", "name": "pipe/d=8", "tps": 1000.0,
             "commit_scatters": 1}]
    bad = [{"bench": "fig11", "name": "pipe/d=8", "tps": 100.0,
            "commit_scatters": 1}]
    b, c = tmp_path / "base.json", tmp_path / "cur.json"
    b.write_text(_json.dumps(rows))
    c.write_text(_json.dumps(bad))
    assert main([str(b), str(b)]) == 0
    assert main([str(b), str(c)]) == 1
    assert main([str(b), str(c), "--tps-tolerance", "0.95"]) == 0


# ---------------------------------------------------------------------------
# PR 8: Ring / bounded tracer / exemplars / tx tracing / recorder / health
# ---------------------------------------------------------------------------


def test_ring_drop_oldest():
    from repro.obs.trace import Ring

    r = Ring(3)
    for i in range(7):
        r.push(i)
    assert r.items() == [4, 5, 6]  # newest kept, oldest dropped
    assert r.dropped == 4  # evictions counted exactly, never silent
    assert len(r) == 3
    r.clear()
    assert r.items() == [] and r.dropped == 0
    unbounded = Ring(None)
    for i in range(100):
        unbounded.push(i)
    assert len(unbounded) == 100 and unbounded.dropped == 0


def test_tracer_bounded_ring_and_drop_counter():
    from repro.obs.trace import NullTracer

    tr = Tracer(max_events=3)
    for i in range(5):
        tr.event(f"e{i}")
    recs = tr.records()
    assert [r["name"] for r in recs] == ["e2", "e3", "e4"]
    assert tr.dropped_events == 2
    # Obs.enabled(max_events=...) wires evictions to a registry counter.
    o = Obs.enabled(max_events=2)
    for i in range(5):
        o.tracer.event(f"x{i}")
    assert o.registry.collect()["trace.dropped_events"] == 3
    # Default enabled() keeps the unbounded complete trace (no counter).
    o2 = Obs.enabled()
    o2.tracer.event("y")
    assert "trace.dropped_events" not in o2.registry.collect()
    # NullTracer surface is unchanged: never syncs, never buffers.
    nt = NullTracer()
    assert nt.dropped_events == 0
    nt.add_sink(lambda rec: (_ for _ in ()).throw(AssertionError))
    nt.event("never")
    assert nt.records() == []


def test_recorder_sink_survives_tracer_eviction(tmp_path):
    """The flight recorder taps the tracer as a sink, so its window is
    independent of the tracer's own (possibly tighter) ring."""
    from repro.obs.recorder import FlightRecorder

    tr = Tracer(max_events=2)
    rec = FlightRecorder(capacity=64)
    rec.attach(tr)
    for i in range(6):
        tr.event(f"e{i}")
    assert len(tr.records()) == 2  # tracer ring is tight...
    names = [r["name"] for r in rec.spans.items()]
    assert names == [f"e{i}" for i in range(6)]  # ...recorder kept all


def test_histogram_exemplars_bounded_and_overflow_labeled():
    h = Histogram(max_exemplars=2)
    for i in range(5):
        h.record(0.5, exemplar={"tx_id": f"t{i}"})
    snap = h.exemplar_snapshot()
    (bucket,) = [k for k in snap if k != "overflow"]
    # Bounded per bucket: only the K most recent exemplars are retained.
    assert [e["tx_id"] for e in snap[bucket]] == ["t3", "t4"]
    # Clamp-bucket exemplars are labeled "overflow", not a bucket index.
    h.record(1e9, exemplar={"tx_id": "huge"})
    assert [e["tx_id"] for e in h.exemplar_snapshot()["overflow"]] == [
        "huge"]
    # exemplars_for(q) returns the payloads in the percentile's bucket.
    assert [e["tx_id"] for e in h.exemplars_for(50)] == ["t3", "t4"]
    assert "p99_exemplars" in h.snapshot()
    assert Histogram().exemplars_for(99) == []  # empty -> no exemplars


def test_txtrace_phase_accounting_and_outcomes():
    """Unit-level lifecycle: queue+order+validate+commit == e2e exactly,
    outcomes partition the round, lifecycles sample valid + invalid."""
    from repro.obs.txtrace import TxTracer

    reg = Registry()
    tt = TxTracer(reg, lifecycle_capacity=4)
    ids = np.arange(16, dtype=np.uint32).reshape(8, 2)
    rt = tt.begin_round(0, ids, 4, block_no0=10)
    rt.order_start()
    rt.ordered()
    rt.validated(0, 1)
    time.sleep(0.002)
    rt.validated(1, 2)
    rt.committed()
    valid = [np.array([True] * 4), np.array([True, False, True, True])]
    rt.finish(valid)
    m = reg.collect()
    for p in ("queue", "order", "validate", "commit"):
        assert m[f"tx.phase.{p}"]["count"] == 8  # weighted by block size
    s = sum(m[f"tx.phase.{p}"]["sum"]
            for p in ("queue", "order", "validate", "commit"))
    assert s == pytest.approx(m["tx.e2e"]["sum"], abs=1e-12)
    assert m["tx.outcome{outcome=valid}"] == 7
    assert m["tx.outcome{outcome=mvcc_conflict}"] == 1
    # Lifecycles: first tx per block + first invalid of block 1.
    lcs = tt.lifecycles.items()
    assert len(lcs) == 3
    assert {lc["outcome"] for lc in lcs} == {"valid", "mvcc_conflict"}
    assert {lc["block_no"] for lc in lcs} == {10, 11}
    assert all(len(lc["tx_id"]) == 16 for lc in lcs)
    # Overflow-tainted round: valid txs downgrade to overflow_dropped.
    rt2 = tt.begin_round(0, ids, 4, block_no0=12)
    rt2.order_start(); rt2.ordered(); rt2.committed()
    rt2.finish([np.ones(4, bool), np.ones(4, bool)],
               overflow_latched=True)
    m = reg.collect()
    assert m["tx.outcome{outcome=overflow_dropped}"] == 8
    assert m["tx.outcome{outcome=valid}"] == 7  # unchanged


def test_txtrace_null_is_inert():
    from repro.obs.txtrace import NULL_TXTRACER

    rt = NULL_TXTRACER.begin_round(0, None, 100, 0)
    rt.order_start(); rt.ordered(); rt.validated(0, 4); rt.committed()
    rt.finish(None)  # no registry, no sidecar, no stamps


def test_engine_tx_phase_decomposition():
    """Engine-level acceptance: per-tx phase histograms sum to e2e, the
    outcome counters match RoundStats, and the p99 commit bucket carries
    a concrete exemplar tx-id."""
    from repro.core import engine as eng_mod
    from repro.core import types

    eng = eng_mod.FabricEngine(
        eng_mod.EngineConfig(dims=types.TEST_DIMS, obs=True))
    bs = eng.cfg.orderer.block_size
    total = 0
    for seed in range(2):
        st = eng.run_round(eng.make_proposals(2 * bs, seed=seed))
        total += st.n_txs
    m = eng.metrics()
    for p in ("queue", "order", "validate", "commit"):
        assert m[f"tx.phase.{p}"]["count"] == total
    s = sum(m[f"tx.phase.{p}"]["sum"]
            for p in ("queue", "order", "validate", "commit"))
    assert s == pytest.approx(m["tx.e2e"]["sum"], rel=1e-9)
    valid = m.get("tx.outcome{outcome=valid}", 0)
    conflicts = m.get("tx.outcome{outcome=mvcc_conflict}", 0)
    assert valid == eng.total_valid
    assert valid + conflicts == eng.total_txs == total
    exemplars = m["tx.phase.commit"]["p99_exemplars"]
    assert exemplars and all(len(e["tx_id"]) == 16 for e in exemplars)
    assert len(eng.txtrace.lifecycles) >= 2
    eng.store.close()


def test_engine_obs_off_txtrace_inert():
    """Obs-off engines take the NullTxTracer path: no sidecar transfer,
    no lifecycle ring, empty registry — and health() still answers."""
    from repro.core import engine as eng_mod
    from repro.core import types
    from repro.obs.txtrace import NullTxTracer

    eng = eng_mod.FabricEngine(eng_mod.EngineConfig(dims=types.TEST_DIMS))
    assert isinstance(eng.txtrace, NullTxTracer)
    eng.run_round(eng.make_proposals(2 * eng.cfg.orderer.block_size))
    assert eng.metrics() == {}
    v = eng.health()
    assert v.status == "healthy"
    assert eng.metrics() == {}  # health() must not create gauges obs-off
    eng.store.close()


def test_recorder_auto_dump_on_verify_fault(tmp_path):
    """Fault-edge acceptance: tamper a journal record, verify() trips the
    flight recorder, and the auto-dump is a complete post-mortem (spans,
    metrics snapshot, >=1 full tx lifecycle, trip reason with the
    journal's failure reason)."""
    from repro.core import engine as eng_mod
    from repro.core import types

    dump = tmp_path / "dump"
    eng = eng_mod.FabricEngine(eng_mod.EngineConfig(
        dims=types.TEST_DIMS, obs=True,
        snapshot_every_blocks=4, prune_chain=False,
        snapshot_dir=str(tmp_path / "snap"),
        journal_dir=str(tmp_path / "jrnl"),
        recorder_dir=str(dump),
    ))
    bs = eng.cfg.orderer.block_size
    for seed in range(3):
        eng.run_round(eng.make_proposals(2 * bs, seed=seed))
    eng.store.drain()
    assert not eng.recorder.tripped
    # Tamper a record in the post-snapshot suffix (block 5 or 6): the
    # recovery path must re-authenticate it and fail.
    rec = eng.journal.records[-1]
    vals = rec.write_vals.copy()
    vals[0, 0, 0] ^= 1
    eng.journal.records[-1] = rec._replace(write_vals=vals)
    out = eng.verify()
    assert not all(out.values())
    assert eng.recorder.tripped
    trip = eng.recorder.trips[-1]
    assert trip["reason"] == "verify_contract"
    assert "recomputed head mismatch" in trip["ctx"]["journal_reason"]
    # The dump landed and is complete.
    for f in ("trace.jsonl", "trace_chrome.json", "metrics.json",
              "lifecycles.json", "meta.json"):
        assert (dump / f).exists(), f
    lcs = json.loads((dump / "lifecycles.json").read_text())
    assert len(lcs) >= 1
    assert all(
        {"tx_id", "phases", "outcome", "e2e"} <= set(lc) for lc in lcs)
    metrics = json.loads((dump / "metrics.json").read_text())
    assert metrics["latest"]["txs.valid"] == eng.total_valid
    assert len(metrics["periodic"]) >= 1  # per-round registry snapshots
    meta = json.loads((dump / "meta.json").read_text())
    assert meta["trips"][-1]["reason"] == "verify_contract"
    spans = [json.loads(x)
             for x in (dump / "trace.jsonl").read_text().splitlines()]
    assert any(r["name"] == "round.commit" for r in spans)
    assert any(
        r["name"] == "flightrec.trip.verify_contract" for r in spans)
    eng.store.close()


def test_recorder_trips_with_obs_off(tmp_path):
    """The recorder is ALWAYS on: an obs-off engine still records fault
    trips (notes + trip log + dump), just without span/metric content."""
    from repro.core import engine as eng_mod
    from repro.core import types

    dump = tmp_path / "dump"
    eng = eng_mod.FabricEngine(eng_mod.EngineConfig(
        dims=types.TEST_DIMS, snapshot_every_blocks=4, prune_chain=False,
        snapshot_dir=str(tmp_path / "snap"),
        journal_dir=str(tmp_path / "jrnl"),
        recorder_dir=str(dump),
    ))
    bs = eng.cfg.orderer.block_size
    for seed in range(3):
        eng.run_round(eng.make_proposals(2 * bs, seed=seed))
    eng.store.drain()
    rec = eng.journal.records[-1]
    vals = rec.write_vals.copy()
    vals[0, 0, 0] ^= 1
    eng.journal.records[-1] = rec._replace(write_vals=vals)
    assert not all(eng.verify().values())
    assert eng.recorder.tripped
    meta = json.loads((dump / "meta.json").read_text())
    assert meta["trips"][0]["reason"] == "verify_contract"
    assert eng.metrics() == {}  # still obs-off
    eng.store.close()


def test_engine_exception_fault_edge():
    from repro.core import engine as eng_mod
    from repro.core import types

    eng = eng_mod.FabricEngine(
        eng_mod.EngineConfig(dims=types.TEST_DIMS, obs=True))
    with pytest.raises(ValueError, match="multiple"):
        eng.run_round(eng.make_proposals(77))  # not a block multiple
    assert eng.recorder.tripped
    assert eng.recorder.trips[-1]["reason"] == "exception"
    assert "ValueError" in eng.recorder.trips[-1]["ctx"]["error"]
    eng.store.close()


def test_health_rollup_transitions():
    from repro.obs import CRITICAL, DEGRADED, HEALTHY, HealthRollup
    from repro.obs.health import SLOConfig

    slo = SLOConfig(commit_p95_s=0.1, min_validity_rate=0.9,
                    critical_validity_rate=0.5, max_occupancy=0.8,
                    window_rounds=4)
    hr = HealthRollup(slo, n_channels=2)
    for c in range(2):
        hr.push_round(c, n_txs=100, n_valid=100, wall_s=0.01, n_blocks=2)
    assert hr.evaluate().status == HEALTHY
    # Validity dips below the objective on channel 1 only.
    hr.push_round(1, n_txs=100, n_valid=70, wall_s=0.01, n_blocks=2)
    v = hr.evaluate()
    assert v.status == DEGRADED
    assert v.channels[0]["status"] == HEALTHY
    assert any("validity" in r for r in v.channels[1]["reasons"])
    # Sticky overflow: critical, with the per-shard reason.
    hr.set_overflow(1, 0b100)
    v = hr.evaluate()
    assert v.status == CRITICAL
    assert any("shard 2" in r and "overflow" in r
               for r in v.channels[1]["reasons"])
    hr.set_overflow(1, 0)
    # Latency over the window p95 objective.
    for _ in range(4):
        hr.push_round(0, n_txs=10, n_valid=10, wall_s=1.0, n_blocks=2)
    assert any("commit p95" in r for r in hr.evaluate().channels[0][
        "reasons"])
    # Occupancy headroom, per shard.
    hr.set_occupancy(0, [0.2, 0.95])
    assert any("shard 1" in r and "occupancy" in r
               for r in hr.evaluate().channels[0]["reasons"])


def test_engine_health_critical_on_overflow_healthy_when_elastic(
        tmp_path):
    """The fig12 scenario in miniature: a static undersized table latches
    overflow -> health() critical with a per-shard reason; the elastic
    twin repairs capacity and stays healthy."""
    import dataclasses as _dc

    from repro.core import engine as eng_mod
    from repro.core import types
    from repro.obs import SLOConfig

    base_cfg = eng_mod.EngineConfig(
        dims=types.TEST_DIMS, obs=True, n_buckets=8, slots=2,
        slo=SLOConfig(commit_p95_s=60.0),
    )
    static = eng_mod.FabricEngine(base_cfg)
    static.run_round(static.make_proposals(200, seed=0))
    assert static.overflowed()
    v = static.health()
    assert v.status == "critical"
    assert any("shard" in r and "overflow" in r for r in v.reasons)
    assert static.metrics()["health.status"] == 2
    assert static.recorder.tripped  # the latch is a fault edge
    assert any(t["reason"] == "overflow_latch"
               for t in static.recorder.trips)
    static.store.close()

    elastic = eng_mod.FabricEngine(_dc.replace(
        base_cfg, n_buckets=1 << 10, slots=8,
        resize_policy=eng_mod.ResizePolicy(
            grow_free_slots=2, grow_on_overflow=True),
    ))
    for seed in range(3):
        elastic.run_round(elastic.make_proposals(200, seed=seed))
    assert not elastic.overflowed()
    assert elastic.health().status == "healthy"
    assert elastic.metrics()["health.status"] == 0
    elastic.store.close()


def test_policy_pass_vectorized_multichannel():
    """Satellite: ONE policy pass covers every channel per round —
    resize.policy_checks counts channels, per-channel state.health /
    state.occupancy gauges come from the same pass, and resizes still
    fire per channel."""
    from repro.core import engine as eng_mod
    from repro.core import types

    eng = eng_mod.FabricEngine(eng_mod.EngineConfig(
        dims=types.TEST_DIMS, obs=True, n_channels=2, n_buckets=1 << 10,
        slots=8,
        resize_policy=eng_mod.ResizePolicy(grow_fill=0.04,
                                           max_buckets=1 << 14),
    ))
    bs = eng.cfg.orderer.block_size
    for r in range(2):
        eng.run_rounds([eng.make_proposals(2 * bs, seed=10 * r + c)
                        for c in range(2)])
    m = eng.metrics()
    assert m["resize.policy_checks"] == 4  # 2 channels x 2 rounds
    for c in range(2):
        assert f"state.health{{channel={c}}}" in m
        assert f"state.occupancy{{channel={c}}}" in m
    assert m.get("resize.grow", 0) >= 1  # the trigger still fires
    assert not eng.overflowed(0) and not eng.overflowed(1)
    eng.store.close()

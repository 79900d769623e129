"""One run of one cell: set-up, warm-up, the measured window, the check.

``run_cell`` is the whole run after the device check; ``bench/run.py``
is its command line. The loop is closed: each round is one client batch
per channel, made before it is sent, handed to the engine, and the next
is sent when it returns. The window ends once ``seconds`` have passed at
a round's return and the block store has drained.
"""

from __future__ import annotations

import gc
import glob
import shutil
import tempfile
import time

import jax

from bench import check, devtrace, reference, spec, stats
from bench.generator import Generator
from bench.system import System

MAX_WARMUP_ROUNDS = 8
TRACE_SECONDS = 3.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts programs compiled or loaded from the cache while open."""

    def __init__(self):
        self.n = 0

    def _on(self, name, _secs, **_):
        if name == COMPILE_EVENT:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


class Context:
    """What a per-layer metric reader may read of one traced run."""

    def __init__(self, rounds, spans, n_blocks, compiles, trace):
        self.rounds = rounds  # stats.RoundRecord per window round
        self.spans = spans  # the engine tracer's records of the window
        self.n_blocks = n_blocks  # blocks of every channel in the window
        self.compiles = compiles  # compile events inside the window
        self.trace = trace  # devtrace.reduce(...) or None

    def span_ms_per_block(self, name: str):
        durs = [r["dur"] for r in self.spans if r["name"] == name]
        if not durs or not self.n_blocks:
            return None
        return 1e3 * sum(durs) / self.n_blocks


def end_to_end(name: str, rounds, window_s: float, setup_s: float) -> float:
    if name == "committed_tps":
        return stats.committed_tps(rounds, window_s)
    if name == "commit_latency_p50_ms":
        return stats.latency_percentile_ms(rounds, 50)
    if name == "commit_latency_p95_ms":
        return stats.latency_percentile_ms(rounds, 95)
    if name == "setup_s":
        return setup_s
    raise KeyError(f"no end-to-end metric {name!r}")


def _warm_up(system, gen, compiles, log) -> int:
    """Rounds until one compiles nothing. A cache load fires a compile
    event too, so a fresh process always warms up two rounds or more."""
    warm = 0
    while True:
        before = compiles.n
        t = time.perf_counter()
        system.run(system.prepare(gen.next_round()))
        warm += 1
        log(f"warmup round {warm}: {time.perf_counter() - t} s, "
            f"{compiles.n - before} compiles")
        if compiles.n == before:
            return warm
        if warm >= MAX_WARMUP_ROUNDS:
            log(f"warm-up: round {warm} still compiled; going on")
            return warm


class FullCollections:
    """Durations of the garbage collector's full (generation 2) passes
    while open: a stall in the window that matches one is the collector's."""

    def __init__(self):
        self.secs = []
        self._t = None

    def _on(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.secs.append(time.perf_counter() - self._t)
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)


class _Profile:
    """The profiler trace of the window's first ``TRACE_SECONDS``, with
    the traced stretch marked by the host span ``bench.window``."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation(devtrace.WINDOW)
        self.span.__enter__()

    def stop(self) -> None:
        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None
            jax.profiler.stop_trace()

    def reduce(self):
        paths = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace under {self.dir}: "
                               f"{paths}")
        out = devtrace.reduce(devtrace.extract(paths[0]))
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def _window(system, gen, seconds: float, profile) -> tuple:
    """The closed loop: rounds until ``seconds`` have passed at a round's
    return, then the store's drain. Returns the rounds, the wall, and the
    CPU seconds of each round, of this thread and of the whole process
    (for the log: a stall that spends neither waited on the device or the
    OS)."""
    props = system.prepare(gen.next_round())
    rounds, cpu = [], []
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.round"):
            c_send = time.thread_time(), time.process_time()
            t_send = time.perf_counter()
            res = system.run(props)
            t_done = time.perf_counter()
            cpu.append((time.thread_time() - c_send[0],
                        time.process_time() - c_send[1]))
        rounds.append(stats.RoundRecord(
            t_send, t_done, sum(r.n_txs for r in res),
            sum(r.n_valid for r in res), res[0].wall_s))
        if profile is not None and t_done - t0 >= min(TRACE_SECONDS,
                                                      seconds):
            profile.stop()
        if t_done - t0 >= seconds:
            break
        with jax.profiler.TraceAnnotation("bench.generate"):
            props = system.prepare(gen.next_round())
    with jax.profiler.TraceAnnotation("bench.drain"):
        system.drain()
    return rounds, time.perf_counter() - t0, cpu


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devices: list, *, t_start: float, log=print) -> dict:
    """Run ``cell`` once and return its result line as a dict."""
    cfg = cell.config
    with CompileCounter() as compiles:
        t_build = time.perf_counter()
        system = System(cfg, devices, obs=trace)
        gen = Generator(cell.traffic, cfg["n_accounts"], cfg["n_channels"],
                        seed)
        log(f"imports_s={t_build - t_start} "
            f"build_s={time.perf_counter() - t_build}")
        warm = _warm_up(system, gen, compiles, log)
        system.drain()
        setup_s = time.perf_counter() - t_start
        log(f"setup_s={setup_s} warmup_rounds={warm} "
            f"compiles_in_setup={compiles.n}")
        profile = None
        if trace:
            system.tracer.clear()
            profile = _Profile()
        in_setup = compiles.n
        with FullCollections() as full_gc:
            rounds, window_s, cpu = _window(system, gen, seconds, profile)
        in_window = compiles.n - in_setup

    memory_peak = system.memory_peak_bytes(devices)
    spans = system.tracer.records()
    outputs = system.outputs()
    system.close()
    n_txs = sum(r.n_txs for r in rounds)
    n_valid = sum(r.n_valid for r in rounds)
    slow = sorted(((r.t_done - r.t_send, i) for i, r in enumerate(rounds)),
                  reverse=True)[:5]
    log(f"window_s={window_s} rounds={len(rounds)} txs={n_txs} "
        f"valid={n_valid} compiles_in_window={in_window} "
        f"memory_peak_bytes={memory_peak} full_gc_s="
        + ",".join(f"{t:.4f}" for t in full_gc.secs) + " slowest_rounds="
        + ",".join(f"{i}:{dt:.4f}s(cpu {cpu[i][0]:.4f}/{cpu[i][1]:.4f}s)"
                   for dt, i in slow))

    result = {"correct": None, "attempted": n_txs,
              "failed": n_txs - n_valid, "metrics": {}}
    d0 = devices[0]
    result["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                        "count": len(devices),
                        "memory_peak_bytes": memory_peak}
    if trace:
        reduced = profile.reduce()
        blocks = cfg["n_channels"] * (cell.traffic["txs_per_round"]
                                      // cfg["block_txs"]) * len(rounds)
        ctx = Context(rounds, spans, blocks, in_window, reduced)
        for m in cell.per_layer:
            value = spec.metric_reader(cell.root, m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if reduced is not None:
            result["device"]["busy_s"] = reduced["busy_s"]
            result["device"]["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
            result["idle_pct_by_chip"] = {
                c: v["idle_pct"] for c, v in reduced["chips"].items()}
    else:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {
                "value": end_to_end(m["name"], rounds, window_s, setup_s),
                "unit": m["unit"]}

    t_ref = time.perf_counter()
    counts = check.compare(outputs, reference.replay(
        cfg, cell.traffic, seed, warm + len(rounds)))
    log(f"reference_s={time.perf_counter() - t_ref}")
    result["correct"] = check.verdict(counts)
    result["check"] = {k: {"value": v, "limit": check.LIMITS[k]}
                       for k, v in counts.items()}
    return result

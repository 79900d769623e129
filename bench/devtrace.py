"""Device busy time, idle share and per-program time from a profiler trace.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict of events (the form the tests record); ``reduce`` turns that
into the numbers the run reports. The traced window is the host span
``bench.window``. A chip is busy where any XLA op runs on it; the union of
those intervals inside the window is its busy time, and every stretch of
the window outside them is an idle gap, named by what the host was doing
at its midpoint (the benchmark's own ``bench.*`` span, and the innermost
host event there).
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW = "bench.window"


def extract(path: str) -> dict:
    """``{"devices": {chip: {"ops": [...], "modules": [...]}}, "host":
    [...]}``; every event is ``[start_ns, dur_ns, name]``. Host events are
    those of the threads that opened a ``bench.`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    chip[key] = [[e.start_ns, e.duration_ns, e.name]
                                 for e in line.events]
            out["devices"][m.group(1)] = chip
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [[e.start_ns, e.duration_ns, e.name]
                       for e in line.events]
                if any(name.startswith("bench.") for _, _, name in evs):
                    out["host"].extend(evs)
    return out


def _union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end] intervals clipped to [lo, hi]."""
    merged = []
    for s, e in sorted((max(s, lo), min(s + d, hi)) for s, d, _ in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _label(host: list, t: float) -> str:
    """The innermost ``bench.`` span and innermost other host event that
    contain time ``t``."""
    bench, other = None, None
    for s, d, name in host:
        if s <= t <= s + d and name != WINDOW:
            slot = "b" if name.startswith("bench.") else "o"
            cur = bench if slot == "b" else other
            if cur is None or d < cur[1]:
                if slot == "b":
                    bench = (name, d)
                else:
                    other = (name, d)
    parts = [p[0] for p in (bench, other) if p is not None]
    return "/".join(parts) if parts else "host.idle"


def _program(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def reduce(events: dict, top: int = 10) -> dict:
    """Busy and idle per chip inside the traced window, their means over
    the chips, the programs that took most device time, and the longest
    idle gaps. ``None`` when the trace holds no window or no device op."""
    wins = [(s, s + d) for s, d, name in events["host"] if name == WINDOW]
    chips = {c: ev for c, ev in events["devices"].items()
             if ev["ops"] or ev["modules"]}
    if not wins or not chips:
        return None
    lo, hi = wins[0]
    span = hi - lo
    per_chip, gaps, programs = {}, [], {}
    for c, ev in sorted(chips.items(), key=lambda kv: int(kv[0])):
        busy = _union(ev["ops"] or ev["modules"], lo, hi)
        busy_ns = sum(e - s for s, e in busy)
        per_chip[c] = {"busy_s": busy_ns / 1e9,
                       "idle_pct": 100.0 * (1.0 - busy_ns / span)}
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, c))
        for s, d, name in ev["modules"]:
            clip = min(s + d, hi) - max(s, lo)
            if clip > 0:
                prog = _program(name)
                programs[prog] = programs.get(prog, 0) + clip
    gaps.sort(reverse=True)
    n = len(per_chip)
    return {
        "window_s": span / 1e9,
        "busy_s": sum(v["busy_s"] for v in per_chip.values()) / n,
        "idle_pct": sum(v["idle_pct"] for v in per_chip.values()) / n,
        "chips": per_chip,
        "device_ops": [[name, ns / 1e9] for name, ns in sorted(
            programs.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[f"chip{c}:" + _label(events["host"], s + g / 2),
                       g / 1e9] for g, s, c in gaps[:top]],
    }

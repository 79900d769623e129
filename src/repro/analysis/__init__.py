"""Program-contract static analysis for the hot-path programs.

Performance invariants used to be enforced ad hoc (a ``commit_scatters``
assert in fig11); this package turns each of them into a *declarative,
committed contract* checked against the compiled artifact of every
registered hot-path program — without running a workload:

  * :mod:`repro.analysis.registry`  — program registry: each jitted hot
    path (the fabric step per (depth, shards, channels), the stacked
    stats pass, the resize butterfly exchange, the serving decode step)
    self-registers a builder the analyzer AOT-lowers.
  * :mod:`repro.analysis.contracts` — loads ``contracts.json``, the one
    source of truth for budgets (fig11 and CI consume the same file).
  * :mod:`repro.analysis.checks`    — compiled-artifact checks:
    per-program collective budgets (count by type + wire bytes),
    forbidden-op scan (host-callback custom-calls, dtype widening to
    f64/s64/u64), the donation/aliasing verifier (a donated argument
    that does not alias was silently copied), and the table-shaped
    StableHLO scatter counter (the fused window-commit contract).
  * :mod:`repro.analysis.retrace`   — runtime-boundary jit cache-miss
    auditor: wraps registered entry points and fails when a round
    triggers a trace outside the allowed key set (a genuinely new
    shape/sharding signature: first window, post-resize window).
  * :mod:`repro.analysis.lint`      — AST-level source lint flagging
    ``block_until_ready`` / ``device_get`` / ``pure_callback`` /
    ``io_callback`` outside the allowlisted phase-edge sites.
  * :mod:`repro.analysis.gate`      — ``python -m repro.analysis.gate``:
    per-program report, nonzero exit on any violation; CI runs it next
    to ``benchmarks/perf_gate.py`` and uploads the JSON report.

This is the machine-checked prerequisite for ROADMAP item 3 (async
double-buffered dispatch + compiled-program cache): donation, aliasing
and retrace behavior must be verified before windows overlap.
"""

from .checks import Violation  # noqa: F401
from .registry import BuildContext, BuiltProgram, register  # noqa: F401

__all__ = ["Violation", "BuildContext", "BuiltProgram", "register"]

"""The contracts gate: ``python -m repro.analysis.gate``.

Runs the whole analyzer and exits nonzero on ANY violation:

  1. **static** — discover the registered hot-path programs
     (:mod:`repro.analysis.registry`), AOT-lower and compile each at a
     small but structurally faithful sizing, and check the artifact
     against its committed contract (collective budgets, fused-commit
     scatter count, forbidden ops, dtype widening, donation aliasing).
     No workload runs; this is pure compile-and-inspect.
  2. **retrace** — drive a small LIVE workload (windows, a stats read,
     a resize epoch, more windows) through a ``MeshWindowCommitter``
     with the jit cache-miss auditor attached; any trace outside the
     allowed key set (first window, sharded-layout window, post-resize
     window) fails.
  3. **lint** — AST scan of ``src/repro/`` for host-sync calls outside
     the allowlisted phase-edge sites.

``--json PATH`` writes the full per-program report (CI uploads it next
to the bench artifacts). Budgets are ceilings, so the same contracts
pass at 1 CPU device (collectives elided) and at 8 forced host devices
(real collectives) — CI runs both.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import jax

from repro.analysis import checks, contracts, lint, registry
from repro.analysis.retrace import RetraceAuditor
from repro.core import types
from repro.launch import mesh as mesh_lib


def make_mesh():
    """(1, M) mesh with M the largest power of two <= device count —
    the same shape fig11 sweeps; data=1 keeps every registered channel
    count valid."""
    n = len(jax.devices())
    m = 1 << (n.bit_length() - 1)
    return mesh_lib.make_mesh((1, m))


def build_context(mesh=None) -> registry.BuildContext:
    return registry.BuildContext(
        mesh=mesh if mesh is not None else make_mesh(),
        dims=types.TEST_DIMS,
    )


# ---------------------------------------------------------------------------
# 1. Static: compile every registered program, check its artifact
# ---------------------------------------------------------------------------


def run_static(ctx: registry.BuildContext, only: set | None = None
               ) -> tuple[dict, list[checks.Violation]]:
    report: dict = {}
    viols: list[checks.Violation] = []
    for name, reg in registry.discover().items():
        if only is not None and name not in only:
            continue
        built = reg.builder(ctx)
        lowered = built.fn.lower(*built.args)
        stablehlo = lowered.as_text()
        hlo = lowered.compile().as_text()
        donated = checks.donated_param_ids(built.args, built.donate_argnums)
        art = checks.Artifact(
            name=name, hlo_text=hlo, stablehlo_text=stablehlo,
            donated=donated, nb_local=built.nb_local, slots=built.slots,
        )
        measured, v = checks.check_artifact(art, contracts.for_program(name))
        report[name] = {
            "description": reg.description,
            "measured": measured,
            "violations": [str(x) for x in v],
        }
        viols += v
    return report, viols


# ---------------------------------------------------------------------------
# 2. Retrace: a small live workload under the cache-miss auditor
# ---------------------------------------------------------------------------


def run_retrace(mesh, dims) -> RetraceAuditor:
    """Windows -> stats -> resize -> windows on an audited committer.

    Every jit in this sequence is allowed its enumerable signatures
    (fresh state, sharded-output layout, one per resize) and nothing
    else; an accidental per-round retrace anywhere in the committer
    surfaces here as a violation.
    """
    import jax.numpy as jnp

    from repro.launch import fabric_step as fs
    from repro.pipeline.engine_bridge import MeshWindowCommitter

    auditor = RetraceAuditor()
    msize = mesh.shape["model"]
    cfg = dataclasses.replace(fs.FASTFABRIC_SHARDED_STEP, pipeline_depth=2)
    nb = 16 * msize
    wc = MeshWindowCommitter(dims, cfg, mesh, n_buckets=nb, slots=4)
    wc.attach_retrace_auditor(auditor)
    d, b_round = 2, 4 * msize
    wire = jnp.zeros((1, d, b_round, 4 * dims.payload_words), jnp.uint8)
    ids = jnp.zeros((1, d, b_round, 2), jnp.uint32)
    for _ in range(3):  # trace, sharded-layout trace, cache hit
        wc.commit_windows(wire, ids)
    wc.shard_stats([0])
    wc.shard_stats([0])  # second read must hit the stats cache
    wc.resize(2 * nb)  # epoch: butterfly exchange + new table layout
    for _ in range(2):  # post-resize trace(s), then steady state
        wc.commit_windows(wire, ids)
    wc.block_until_ready()
    return auditor


# ---------------------------------------------------------------------------
# 3. Lint
# ---------------------------------------------------------------------------


def run_lint() -> list[checks.Violation]:
    allow = contracts.load().get("lint", {}).get("allow", [])
    return lint.lint_tree(lint.default_root(), allow)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default=None,
                    help="write the full report as JSON to this path")
    ap.add_argument("--only", nargs="+", default=None,
                    help="restrict the static pass to these program names")
    ap.add_argument("--skip-retrace", action="store_true")
    ap.add_argument("--skip-lint", action="store_true")
    ap.add_argument("--list", action="store_true",
                    help="list registered programs and exit")
    args = ap.parse_args(argv)

    if args.list:
        for name, reg in registry.discover().items():
            print(f"{name:32s} {reg.description}")
        return 0

    mesh = make_mesh()
    ctx = build_context(mesh)
    report = {
        "n_devices": len(jax.devices()),
        "mesh": dict(mesh.shape),
        "programs": {},
        "retrace": {},
        "lint": [],
    }
    all_viols: list[checks.Violation] = []

    only = set(args.only) if args.only else None
    report["programs"], viols = run_static(ctx, only)
    all_viols += viols
    for name, rec in report["programs"].items():
        ok = "ok " if not rec["violations"] else "FAIL"
        m = rec["measured"]
        colls = ",".join(f"{k}={v:g}" for k, v in
                         sorted(m["collectives"].items())) or "-"
        csp = m.get("commit_scatter_passes")
        print(f"[{ok}] {name:28s} collectives: {colls:40s}"
              f" aliased {len(m['aliased_params'])}/{len(m['donated_params'])}"
              + (f"  commit_passes={csp:g}" if csp is not None else ""))

    if not args.skip_retrace:
        auditor = run_retrace(mesh, types.TEST_DIMS)
        report["retrace"] = auditor.report()
        all_viols += auditor.violations
        for name, rec in report["retrace"].items():
            ok = "ok " if not rec["violations"] else "FAIL"
            print(f"[{ok}] retrace {name:28s} calls={rec['calls']}"
                  f" traces={rec['traces']} signatures={rec['signatures']}")

    if not args.skip_lint:
        lviols = run_lint()
        report["lint"] = [str(v) for v in lviols]
        all_viols += lviols
        print(f"[{'ok ' if not lviols else 'FAIL'}] lint src/repro: "
              f"{len(lviols)} host-sync call(s) outside allowlisted sites")

    report["violations"] = [str(v) for v in all_viols]
    report["ok"] = not all_viols
    if args.json:
        import os

        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
    if all_viols:
        print(f"\n{len(all_viols)} contract violation(s):", file=sys.stderr)
        for v in all_viols:
            print(f"  {v}", file=sys.stderr)
        return 1
    print(f"\nall contracts hold "
          f"({len(report['programs'])} programs, "
          f"{len(report['retrace'])} audited entry points)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Prints one JSON object as the last line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``
and, last, ``check``: each number compared with the reference beside its
limit. Without a TPU, or with fewer chips than the cell asks for, it
prints no result and exits with 2. Progress goes to standard error, each
line naming the platform, device kind and device count.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import devices, spec

    cell = spec.load_cell(ROOT, args.workload)
    import jax

    jax.config.update(
        "jax_compilation_cache_dir",
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    tag = (f"[{devs[0].platform} {devs[0].device_kind} x{len(devs)}] "
           f"{cell.name}:")

    def log(msg: str) -> None:
        print(tag, msg, file=sys.stderr, flush=True)

    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"needs {cell.chips} TPU chip(s); no result")
        return 2
    hbm = devices.facts(devs[0].device_kind)["hbm_bytes"]

    from bench import harness

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devs[:cell.chips],
                              t_start=T_START, log=log)
    log(f"memory_peak_share_of_hbm="
        f"{result['device']['memory_peak_bytes'] / hbm}")
    for name, c in result["check"].items():
        log(f"check {name}={c['value']} limit={c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark suite entrypoint — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig4,table1] \
        [--json out/bench.json]

Prints per-benchmark rows as they complete and a final CSV (optionally a
JSON dump — CI uploads it as an artifact to track the perf trajectory per
PR). The roofline section summarizes the dry-run artifacts if present (run
``python -m repro.launch.dryrun --all --fabric`` first to regenerate).
"""

from __future__ import annotations

import argparse
import time

from benchmarks import common
from repro.launch import compile_cache

ALL = ("fig3", "fig4", "fig5_6", "fig7", "fig8", "fig9", "fig10", "fig11",
       "fig12", "table1", "roofline")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(ALL))
    ap.add_argument("--json", default=None,
                    help="write all result rows as JSON to this path")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized figures (currently scales fig11 down "
                         "to a smoke run; other figures keep defaults)")
    ap.add_argument("--obs-dir", default=None,
                    help="dump fig11's obs trace + metrics snapshot here "
                         "(trace.jsonl / trace_chrome.json / metrics.json)")
    args = ap.parse_args()
    compile_cache.enable()
    which = args.only.split(",") if args.only else list(ALL)

    t0 = time.time()
    if "fig3" in which:
        from benchmarks import fig3_transfer
        print("== Fig 3: block transfer (network is not the bottleneck) ==")
        fig3_transfer.run()
    if "fig4" in which:
        from benchmarks import fig4_orderer
        print("== Fig 4: orderer TPS vs payload size ==")
        fig4_orderer.run()
    if "fig5_6" in which:
        from benchmarks import fig5_6_peer
        print("== Fig 5/6: peer latency & throughput, opts stacked ==")
        fig5_6_peer.run()
    if "fig7" in which:
        from benchmarks import fig7_sensitivity
        print("== Fig 7: parallelism sensitivity ==")
        fig7_sensitivity.run()
    if "fig8" in which:
        from benchmarks import fig8_blocksize
        print("== Fig 8: block size scan ==")
        fig8_blocksize.run()
    if "fig9" in which:
        from benchmarks import fig9_recovery
        print("== Fig 9: crash recovery (replay vs snapshot+journal) ==")
        fig9_recovery.main([])
    if "fig10" in which:
        from benchmarks import fig10_state_scaling
        print("== Fig 10: model-axis sharded world state ==")
        fig10_state_scaling.main([])
    if "fig11" in which:
        from benchmarks import fig11_pipeline
        print("== Fig 11: device-side block pipeline ==")
        # --quick keeps the full depth sweep (the CI artifact asserts the
        # fused commit at depth 8) on a small block/table size.
        fig11_args = (
            ["--depths", "1", "2", "8", "--b-round", "32",
             "--n-buckets", "1024", "--iters", "1"] if args.quick else []
        )
        if args.obs_dir:
            fig11_args += ["--obs-dir", args.obs_dir]
        fig11_pipeline.main(fig11_args)
    if "fig12" in which:
        from benchmarks import fig12_rebalance
        print("== Fig 12: elastic state (overflow-driven shard split) ==")
        # --quick shrinks the sweep but keeps the static-overflows /
        # elastic-stays-healthy contrast the CI artifact asserts.
        fig12_rebalance.main(
            ["--rounds", "10", "--round-txs", "50", "--n-buckets", "128",
             "--slots", "8", "--n-shards", "2", "--grow-free-slots", "4"]
            if args.quick else []
        )
    if "table1" in which:
        from benchmarks import table1_endtoend
        print("== Table I: end-to-end + multi-channel scale-out ==")
        # --quick shrinks round/window sizes but keeps every multi-channel
        # contract row (per-channel identical, channels_x_tps aggregate,
        # fairness under uniform + Zipf load) the CI artifact asserts.
        table1_endtoend.run(quick=args.quick)
    if "roofline" in which:
        from benchmarks import roofline
        print("== Roofline (from dry-run artifacts) ==")
        roofline.run()

    print(f"\n== CSV ({time.time() - t0:.0f}s total) ==")
    common.print_csv()
    if args.json:
        common.dump_json(args.json)
        print(f"rows written to {args.json}")


if __name__ == "__main__":
    main()

"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--full]

Default is --quick sizing (CI-friendly); --full reproduces the paper-scale
2-hour trace segments.  Output: ``name,value,derived...`` CSV lines +
JSON artifacts under experiments/bench/.
"""

import argparse
import sys
import time
import traceback

from benchmarks import (bench_engine, bench_fault_handling, bench_integrity,
                        bench_kernels, bench_migration, bench_motivation,
                        bench_obs, bench_recovery, bench_response_length,
                        bench_scenarios, bench_seeding_ablation,
                        bench_static_instances, bench_streaming,
                        bench_trace_throughput, bench_transfer,
                        bench_weight_transfer, roofline)
from repro.launch.compile_cache import setup_compile_cache

BENCHES = [
    ("fig2_motivation", bench_motivation.main),
    ("fig8_10_trace_throughput", bench_trace_throughput.main),
    ("fig11_static_instances", bench_static_instances.main),
    ("fig12_seeding_ablation", bench_seeding_ablation.main),
    ("fig13_response_length", bench_response_length.main),
    ("fig14_17_weight_transfer", bench_weight_transfer.main),
    ("transfer_plane", bench_transfer.main),
    ("engine_horizon", bench_engine.main),
    ("migration", bench_migration.main),
    ("fig15_fault_handling", bench_fault_handling.main),
    ("availability_scenarios", bench_scenarios.main),
    ("recovery_plane", bench_recovery.main),
    ("fig16_integrity", bench_integrity.main),
    ("streaming_collection", bench_streaming.main),
    ("obs_flight_recorder", bench_obs.main),
    ("kernels", bench_kernels.main),
    ("roofline", roofline.main),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale runs (2h virtual traces)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizing (the default; explicit flag for "
                         "make/CI entry points)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    assert not (args.full and args.quick), "--full and --quick conflict"
    quick = not args.full
    setup_compile_cache()
    failures = 0
    for name, fn in BENCHES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        print(f"# === {name} (quick={quick}) ===", flush=True)
        try:
            fn(quick=quick)
        except Exception:
            failures += 1
            traceback.print_exc()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

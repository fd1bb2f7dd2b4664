"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only name ...]

Output: ``name,us_per_call,derived`` CSV (assignment format).  Scale with
REPRO_BENCH_SCALE=small|full (default small: minutes on 1 CPU).

Paper artifact -> module map (DESIGN.md §7):
  Fig 4      bench_trace       Table 3/1b  bench_storage
  Table 4/F7 bench_latency     Table 6     bench_cache_sweep
  Fig 9/11   bench_tuning      Fig 10      bench_spillover
  Fig 8      bench_cost        Fig 12      bench_fidelity
  Table 1c   bench_decode      kernels     bench_kernels
  §Roofline  roofline_report   fault tol.  bench_resilience
  serving    bench_runtime     (QoS/SLO load sweep)
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks.common import Rows
from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    "bench_trace", "bench_storage", "bench_decode", "bench_kernels",
    "bench_cost", "bench_cache_sweep", "bench_tuning", "bench_spillover",
    "bench_latency", "bench_fidelity", "bench_regen",
    "bench_resilience", "bench_runtime", "roofline_report",
]


def trajectory() -> None:
    """Perf-trajectory mode: write ``BENCH_decode.json`` +
    ``BENCH_kernels.json`` + ``BENCH_storage.json`` at the repo root
    (versioned, unlike the artifacts/ scratch) — per-bucket per-image
    decode ms, fast-path speedups, kernel-vs-oracle errors and traffic
    wins, pixel-tier bytes/object, the durable store's measured
    on-disk savings / recovery ms / compaction write amplification, and
    (``BENCH_resilience.json``) the replicated cluster's hedged-tail,
    failover, and restart-recovery numbers, and
    (``BENCH_runtime.json``) the serving runtime's per-class tails and
    SLO attainment at three load factors with QoS on/off, and
    (``BENCH_fidelity.json``) the rate-distortion ladder's per-rung
    storage savings vs PSNR/SSIM, floor-gated, and
    (``BENCH_cost.json``) Fig. 8 cost projections plus the trace-driven
    $-per-million-requests A-B-C (static-small / static-peak /
    autoscaled) at a fixed 250 ms SLO — so later checkouts have a trend
    to regress against."""
    from benchmarks import (bench_cost, bench_decode, bench_fidelity,
                            bench_kernels, bench_resilience, bench_runtime,
                            bench_storage)
    bench_decode.trajectory().print()
    bench_kernels.trajectory().print()
    bench_storage.trajectory().print()
    bench_resilience.trajectory().print()
    bench_runtime.trajectory(smoke=True).print()
    bench_fidelity.trajectory(smoke=True).print()
    bench_cost.trajectory(smoke=True).print()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--trajectory", action="store_true",
                    help="write BENCH_decode.json + BENCH_kernels.json at "
                         "the repo root and exit")
    args = ap.parse_args()
    enable_compile_cache()
    if args.trajectory:
        trajectory()
        return
    mods = args.only or MODULES

    all_rows = Rows()
    failures = 0
    for name in mods:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            rows = mod.run()
            all_rows.extend(rows)
            print(f"# {name}: ok in {time.time() - t0:.1f}s",
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — keep the harness running
            failures += 1
            all_rows.add(f"{name}.FAILED", derived=type(e).__name__)
            print(f"# {name}: FAILED {e}", file=sys.stderr)
            traceback.print_exc()
    all_rows.print()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

"""Benchmark entry point: ``python -m benchmarks.run [--full]``.

Emits ``name,us_per_call,derived`` CSV rows:
  * selection/* — paper Figures 2/3/4 analogues (one per table family)
  * kernel/*    — oracle/attention kernel micro-benchmarks
  * roofline    — §Roofline table from the dry-run artifacts (if present)
"""

import argparse
import sys

from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale dataset sizes")
    ap.add_argument("--skip-selection", action="store_true")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    from benchmarks import bench_kernels
    bench_kernels.run()
    if not args.skip_selection:
        from benchmarks import bench_selection
        bench_selection.run(full=args.full)
    from benchmarks import bench_roofline
    bench_roofline.run()


if __name__ == '__main__':
    main()

"""Benchmark orchestrator — one module per paper table/figure.

  python -m benchmarks.run [--quick] [--only titan23,ispd98,...]

Prints ``table,name,...`` CSV blocks per benchmark; partition-quality
tables additionally report the paper's Norm. Avg. rows.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.env import enable_compile_cache
    enable_compile_cache()

    from benchmarks import titan23, ispd98, jumping, largek, kernels_bench
    from benchmarks import roofline

    suites = [
        ("kernels", lambda: kernels_bench.run(quick=args.quick)),
        ("titan23", lambda: titan23.run(quick=args.quick)),
        ("ispd98", lambda: ispd98.run(quick=args.quick)),
        ("jumping", lambda: jumping.run(quick=args.quick)),
        ("largek", lambda: largek.run(quick=args.quick)),
        ("roofline", roofline.main),
    ]
    for name, fn in suites:
        if only and name not in only:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.perf_counter()
        try:
            fn()
            print(f"# {name} done in {time.perf_counter() - t0:.0f}s",
                  flush=True)
        except Exception as e:  # keep the suite going; report at the end
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
            raise


if __name__ == "__main__":
    main()

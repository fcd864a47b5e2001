"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes ``BENCH_*.json``
artifacts (per-module payloads plus a ``BENCH_summary.json`` of every
row).  ``--smoke`` runs the CI-sized variant: same code paths, reduced
shapes/steps, hard-failing on any exception so the bench-smoke job
gates regressions.

  bench_estimators  -- Fig. 3 (Eq. 7 condition), Theorem 2 variance
  bench_memory      -- Table 2 (activation memory), Fig. 6 (max batch)
  bench_convergence -- Table 1 (accuracy), Fig. 7 (budget), Fig. 8
                       (estimator ablation), fixed-vs-adaptive budgets
  bench_roofline    -- roofline terms per (arch x shape x mesh) cell
  bench_serving     -- continuous batching vs sequential: requests/s,
                       p50/p99 latency under a Poisson open-loop trace
"""
import argparse
import importlib
import os
import sys
import traceback

# `python benchmarks/run.py` puts benchmarks/ (not the repo root) on
# sys.path; the intra-package `benchmarks.*` imports need the root.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

MODULES = ["bench_estimators", "bench_memory", "bench_convergence",
           "bench_roofline", "bench_serving"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module suffixes")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: reduced shapes/steps, same paths")
    ap.add_argument("--out-dir", default=".",
                    help="directory for BENCH_*.json artifacts")
    ap.add_argument("--list", action="store_true",
                    help="import each module, print its name and first "
                         "docstring line, and exit (CI smoke for the "
                         "harness wiring — no benchmark runs)")
    args = ap.parse_args()

    if args.list:
        for m in MODULES:
            mod = importlib.import_module(f"benchmarks.{m}")
            doc = (mod.__doc__ or "").strip().splitlines()
            print(f"{m}: {doc[0] if doc else ''}")
        return

    from benchmarks import common
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    common.set_smoke(args.smoke)
    common.set_out_dir(args.out_dir)

    mods = MODULES
    if args.only:
        keep = args.only.split(",")
        mods = [m for m in MODULES if any(k in m for k in keep)]
    print("name,us_per_call,derived")
    errors = {}
    for m in mods:
        try:
            importlib.import_module(f"benchmarks.{m}").run()
        except Exception:
            errors[m] = traceback.format_exc()
            print(f"{m},0.0,ERROR")
            traceback.print_exc(file=sys.stderr)
    common.emit_json("summary", {
        "smoke": args.smoke,
        "modules": mods,
        "rows": common.RESULTS,
        "errors": errors,
    })
    if errors:
        sys.exit(1)


if __name__ == '__main__':
    main()

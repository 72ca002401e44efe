"""Benchmark harness: one module per paper table/figure + kernels + roofline.

Prints ``name,us_per_call,derived`` CSV (one line per measurement).
``--json PATH`` additionally writes the rows as a JSON baseline (e.g.
``--only kernels --json benchmarks/BENCH_kernels.json``) so the perf
trajectory is tracked in-repo from PR to PR.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only table1,fig1,...]
      [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
import traceback

SUITES = [
    ("table1", "benchmarks.bench_table1_stability"),
    ("table2", "benchmarks.bench_table2_pareto"),
    ("fig1", "benchmarks.bench_fig1_variance"),
    ("fig2", "benchmarks.bench_fig2_mixed_seqlen"),
    ("fig3", "benchmarks.bench_fig3_pacing"),
    ("table4", "benchmarks.bench_table4_gpt3recipe"),
    ("a2", "benchmarks.bench_a2_lr_decay"),
    ("optim", "benchmarks.bench_optim"),
    ("kernels", "benchmarks.bench_kernels"),
    ("serve", "benchmarks.bench_serve"),
    ("roofline", "benchmarks.bench_roofline"),
    ("chaos", "benchmarks.bench_chaos"),
    ("gns", "benchmarks.bench_gns"),
]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true")
    p.add_argument("--only", default="",
                   help="comma-separated suite keys (default: all)")
    p.add_argument("--json", default="",
                   help="also write the rows to this path as a JSON baseline")
    args = p.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    import importlib
    print("name,us_per_call,derived")
    failures = 0
    all_rows = []
    for key, module_name in SUITES:
        if only is not None and key not in only:
            continue
        try:
            mod = importlib.import_module(module_name)
            t0 = time.time()
            rows = mod.run(quick=args.quick)
            for name, us, derived in rows:
                print(f'{name},{us:.1f},"{derived}"', flush=True)
                all_rows.append({"name": name, "us_per_call": round(us, 1),
                                 "derived": derived})
            print(f'_suite/{key},{(time.time()-t0)*1e6:.0f},"suite wall time"',
                  flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f'{key}/ERROR,0,"{type(e).__name__}: {e}"', flush=True)
    if args.json and failures:
        # never clobber a tracked baseline with a partial row set
        print(f'_json,{0:.1f},"skipped {args.json}: {failures} suite '
              f'failure(s)"', flush=True)
    elif args.json:
        import jax
        baseline = {
            "meta": {
                "backend": jax.default_backend(),
                "jax": jax.__version__,
                "python": platform.python_version(),
                "suites": sorted(only) if only else [k for k, _ in SUITES],
                "note": ("interpret-mode timings on CPU measure plumbing, "
                         "not TPU speed; derived columns carry max-err vs "
                         "the oracles and analytic TPU flops"),
            },
            "rows": all_rows,
        }
        with open(args.json, "w") as f:
            json.dump(baseline, f, indent=2)
            f.write("\n")
        print(f'_json,{0:.1f},"wrote {args.json}"', flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())

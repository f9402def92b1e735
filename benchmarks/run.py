"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV; detailed per-point CSVs land in
``artifacts/bench/``.  Run: ``PYTHONPATH=src python -m benchmarks.run``.

Flags:
  --quick        tiny shape set (CI smoke; seconds, not minutes)
  --json PATH    also dump the rows as a JSON artifact
  --only NAME    run a single benchmark by substring match

The persistent compile cache is on (``repro.launch.compile_cache``), so a
repeat run reads cached compiles: the "cold" serving rows (legacy cold
TTFT, ``serve_paged_long`` cold tok/s and TTFT) measure a cold compile
only on the first run into an empty cache.  Point
``JAX_COMPILATION_CACHE_DIR`` at an empty directory to time them cold.
"""
from __future__ import annotations

import argparse
import functools
import json


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny shape set for CI smoke runs")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="dump results as JSON to PATH")
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark names")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks.paper_figures import (bench_fig4_speedup, bench_fig5_edp,
                                          bench_fig6_redas,
                                          bench_fig7_casestudy,
                                          bench_table2_shapes,
                                          bench_table3_area_energy)
    from benchmarks.kernel_bench import bench_grouped_kernels, bench_kernels
    from benchmarks.multi_tenant_bench import bench_multi_tenant
    from benchmarks.serve_bench import (bench_serving,
                                        bench_serving_archs,
                                        bench_serving_frontend,
                                        bench_serving_paged,
                                        bench_serving_sharded,
                                        bench_serving_slo)
    from benchmarks.slab_ablation import bench_slab_ablation

    benches = [bench_table2_shapes, bench_table3_area_energy,
               bench_fig4_speedup, bench_fig5_edp, bench_fig6_redas,
               bench_fig7_casestudy, bench_kernels, bench_grouped_kernels,
               bench_slab_ablation, bench_multi_tenant, bench_serving,
               bench_serving_paged, bench_serving_frontend,
               bench_serving_slo, bench_serving_sharded,
               bench_serving_archs]
    if args.quick:
        # CI smoke: the analytic benches are already fast; skip the slow
        # interpret-mode kernel sweep and shrink the packing/grouped
        # scenarios.  This set (with committed baseline.json) feeds the
        # bench-regression gate — scripts/check_bench.py.
        benches = [bench_table2_shapes, bench_table3_area_energy,
                   functools.partial(bench_grouped_kernels, quick=True),
                   functools.partial(bench_multi_tenant, quick=True),
                   functools.partial(bench_serving, quick=True),
                   functools.partial(bench_serving_paged, quick=True),
                   functools.partial(bench_serving_frontend, quick=True),
                   functools.partial(bench_serving_slo, quick=True),
                   functools.partial(bench_serving_sharded, quick=True),
                   functools.partial(bench_serving_archs, quick=True)]

    def _name(b) -> str:
        fn = b.func if isinstance(b, functools.partial) else b
        return getattr(fn, "__name__", repr(fn))

    if args.only:
        benches = [b for b in benches if args.only in _name(b)]

    results = []
    print("name,us_per_call,derived")
    for bench in benches:
        for (name, us, derived) in bench():
            print(f"{name},{us:.1f},{derived}")
            results.append({"name": name, "us_per_call": us,
                            "derived": derived})
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"quick": args.quick, "results": results}, f, indent=2)


if __name__ == "__main__":
    main()

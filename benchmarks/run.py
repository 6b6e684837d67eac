"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per benchmark line.

  fig8/fig9    bench_dse_sweep       (algorithmic DSE, Pareto)
  fig10        bench_sampling        (metrics vs S)
  table1/2     bench_quantization    (fp32 vs bf16 vs int8)
  table3       bench_resource_model  (DSP + TPU memory model accuracy)
  table4       bench_latency         (CPU measured + FPGA/TPU modeled)
  table5/6     bench_opt_modes       (optimization framework outputs)
  kernels      bench_kernels         (fused vs unfused)
  streaming    bench_streaming       (stateful session serving sweep)
  controlplane bench_controlplane    (admission, snapshot/restore, pad waste)
  sharding     bench_sharding        (tokens/s vs device count, data plane)
  controller   bench_controller      (decision overhead, SLO recovery)
  fleet        bench_fleet           (multi-tenant co-batching, fair drain)
  early_exit   bench_early_exit      (adaptive sampling speedup + quality)
  distill      bench_distill         (student frontier, escalation, quality)
  roofline     roofline              (dry-run derived terms, all 40 cells)

``--only`` filters by suite name (substring, repeatable); ``--json PATH``
additionally writes every emitted record as JSON — CI uses
``--only controlplane --only controller --json BENCH_serving.json`` to pin
the serving-stack baseline.
"""

import argparse
import json
import sys
import traceback


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    from benchmarks import (bench_controller, bench_controlplane,
                            bench_distill, bench_dse_sweep, bench_early_exit,
                            bench_fleet, bench_kernels, bench_latency,
                            bench_opt_modes, bench_quantization,
                            bench_resource_model, bench_sampling,
                            bench_sharding, bench_streaming, common, roofline)
    benches = [
        ("dse_sweep", bench_dse_sweep),
        ("sampling", bench_sampling),
        ("quantization", bench_quantization),
        ("resource_model", bench_resource_model),
        ("latency", bench_latency),
        ("opt_modes", bench_opt_modes),
        ("kernels", bench_kernels),
        ("streaming", bench_streaming),
        ("controlplane", bench_controlplane),
        ("sharding", bench_sharding),
        ("controller", bench_controller),
        ("fleet", bench_fleet),
        ("early_exit", bench_early_exit),
        ("distill", bench_distill),
        ("roofline", roofline),
    ]
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", action="append", default=None,
                    help="run only suites whose name contains this "
                    "substring (repeatable)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write every emitted record as JSON "
                    "(the machine-readable baseline, e.g. "
                    "BENCH_serving.json)")
    args = ap.parse_args()
    if args.only:
        benches = [(n, m) for n, m in benches
                   if any(pat in n for pat in args.only)]
        if not benches:
            sys.exit(f"--only {args.only} matches no suite")
    failed = 0
    for name, mod in benches:
        print(f"# --- {name} ---", flush=True)
        try:
            mod.run()
        except Exception:
            failed += 1
            print(f"{name},0.0,ERROR", flush=True)
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"suites": [n for n, _ in benches],
                       "records": common.RECORDS}, f, indent=1)
        print(f"# wrote {len(common.RECORDS)} records -> {args.json}",
              flush=True)
    if failed:
        sys.exit(1)


if __name__ == '__main__':
    main()

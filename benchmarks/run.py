"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run`` runs everything and prints both
human-readable tables and a machine-readable CSV block (name,<row...>).
``--json PATH`` additionally writes every table to one JSON document — the
schema is documented in benchmarks/README.md:

    {"tables": [{"name": str, "cols": [str], "rows": [[cell, ...]]}],
     "failures": [[benchmark_name, error_str]]}
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write all tables as one JSON document")
    ap.add_argument("--only", metavar="NAME", default=None,
                    help="run a single benchmark by name (e.g. fig9, table2)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (ablation, bootup_breakdown, engine_measured,
                            expert_remap, expert_skew, fleet, granularity,
                            kv_pressure, latency_breakdown, memory_vs_ep,
                            overlap, peak_memory, scaledown_latency,
                            scaleup_latency, slo_compliance, slo_dynamics,
                            throughput_windows, trace_overhead)
    modules = [
        ("fig1", granularity),
        ("fig4a", bootup_breakdown),
        ("fig4b", memory_vs_ep),
        ("fig7", scaleup_latency),
        ("fig8", peak_memory),
        ("fig9", slo_dynamics),
        ("fig10", slo_compliance),
        ("fig11", latency_breakdown),
        ("fig12", scaledown_latency),
        ("table1+3", ablation),
        ("table2", throughput_windows),
        ("kv_pressure", kv_pressure),
        # chunked-prefill ITL flatness A/B (same module, own entry so CI
        # can smoke it via --only without the slower admission sweep)
        ("chunked_itl", kv_pressure),
        # int8 KV pool vs bf16 under the same burst (same module, own
        # entry: pool capacity ~2x at halved block bytes, DESIGN.md §11)
        ("quant_kv", kv_pressure),
        ("expert_remap", expert_remap),
        # skew-aware rebalancing A/B: Zipf routing, replicate-hot /
        # demote-cold mid-serving, scale-event pricing with the cold tier
        ("expert_skew", expert_skew),
        ("overlap", overlap),
        # measured drain-vs-migrate scale-down on the real engine (the
        # fig12 entry above is the cost-model projection)
        ("scaledown_migrate", scaledown_latency),
        ("measured", engine_measured),
        # tracing disabled-vs-enabled throughput A/B + trace artifact
        ("trace_overhead", trace_overhead),
        # shared-pool fleet vs static per-model pools A/B with
        # scale-to-zero (park/unpark) on anti-correlated diurnal demand
        ("fleet", fleet),
    ]
    if args.only:
        modules = [(n, m) for n, m in modules if n == args.only]
        if not modules:
            raise SystemExit(f"unknown benchmark {args.only!r}")
    tables = []
    failures = []
    for name, mod in modules:
        t0 = time.perf_counter()
        print(f"\n{'=' * 72}\n[{name}] {mod.__doc__.splitlines()[0]}")
        try:
            if mod is slo_dynamics:
                outs = [mod.run(True), mod.run(False), mod.run_closed_loop()]
            elif name == "scaledown_migrate":
                outs = [mod.run_measured()]
            elif name == "chunked_itl":
                outs = [mod.run_itl()]
            elif name == "quant_kv":
                outs = [mod.run_quant()]
            else:
                out = mod.run()
                outs = out if isinstance(out, list) else [out]
            for t in outs:
                if t is not None:
                    t.show()
                    tables.append(t)
            print(f"[{name}] done in {time.perf_counter() - t0:.1f}s")
        except Exception as e:
            traceback.print_exc()
            failures.append((name, str(e)))
    print(f"\n{'=' * 72}\n# CSV")
    print("table,row...")
    for t in tables:
        for line in t.csv_rows():
            print(line)
    if args.json:
        doc = {"tables": [{"name": t.name, "cols": t.cols, "rows": t.rows}
                          for t in tables],
               "failures": [list(f) for f in failures]}
        with open(args.json, "w") as fh:
            json.dump(doc, fh, indent=1, default=str)
        print(f"\nwrote {len(tables)} tables -> {args.json}")
    if failures:
        print(f"\n{len(failures)} benchmark(s) FAILED: {failures}")
        raise SystemExit(1)
    print(f"\nall {len(modules)} benchmarks passed "
          f"({len(tables)} tables)")


if __name__ == "__main__":
    main()

"""Benchmark runner: one module per paper table/figure + roofline summary.

    PYTHONPATH=src python -m benchmarks.run [--only fig4,fig13]

Prints ``name,us_per_call,derived`` CSV lines per benchmark.
"""
import argparse
import importlib
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    "fig1_ber",
    "fig4_bitlevel",
    "fig5_timestep",
    "fig6_block",
    "fig7_selfcorrection",
    "table1_quality_efficiency",
    "fig11_tradeoff",
    "fig12_comparison",
    "fig13_ablation",
    "fig14_dse",
    "table2_taylorseer",
    "roofline_summary",
    "serving_telemetry",
    "ar_serving",
    "offload_overlap",
    "trace_forensics",
    "energy_slo",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    enable_compile_cache()
    only = [s.strip() for s in args.only.split(",") if s.strip()]

    failures = []
    for name in MODULES:
        if only and not any(name == o or name.startswith(o + "_")
                            for o in only):
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            mod.main()
            print(f"[{name}] done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        print(f"\nFAILED benchmarks: {failures}")
        sys.exit(1)
    print("\nall benchmarks passed")


if __name__ == "__main__":
    main()

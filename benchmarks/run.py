"""Benchmark orchestrator — one entry per paper figure/table + roofline.

Usage:
    PYTHONPATH=src python -m benchmarks.run                # all
    PYTHONPATH=src python -m benchmarks.run fig7 table1    # subset
    REPRO_BENCH_FULL=1 ... python -m benchmarks.run        # paper-scale (50/10)

Emits ``figure,series,x,metric,value`` rows to results/benchmarks.csv and a
pass/fail summary line per benchmark.
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    import os

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        agg_backends, beyond_paper, cifar_task, fault_tolerance, figures,
        kernels_bench, lm_throughput, moe_ablation, participation,
        roofline_report, serving_continuous, serving_federated,
        straggler_wallclock, throughput,
    )

    registry = {
        "fig4_5": figures.fig4_5_convergence_vs_baselines,
        "fig6": figures.fig6_comm_rate,
        "fig7": figures.fig7_tau1,
        "fig8": figures.fig8_topology_alpha,
        "fig9": figures.fig9_noniid,
        "fig10": figures.fig10_async,
        "fig11": figures.fig11_lr_imbalance,
        "table1": figures.table1_latency,
        "kernels": kernels_bench.main,
        "agg_backends": agg_backends.main,
        "straggler_wallclock": straggler_wallclock.main,
        "participation": participation.main,
        "throughput": throughput.main,
        "lm_throughput": lm_throughput.main,
        "serving_federated": serving_federated.main,
        "serving_continuous": serving_continuous.main,
        "fault_tolerance": fault_tolerance.main,
        "roofline": roofline_report.main,
        "beyond_torus": beyond_paper.main,
        "cifar": cifar_task.main,
        "moe_ablation": moe_ablation.main,
    }
    default = [k for k in registry
               if k != "cifar" or os.environ.get("REPRO_BENCH_FULL") == "1"]
    wanted = sys.argv[1:] or default
    failures = []
    for name in wanted:
        fn = registry[name]
        t0 = time.time()
        print(f"==== {name} ====")
        try:
            out = fn()
            printable = {k: (round(v, 4) if isinstance(v, float) else v)
                         for k, v in (out or {}).items()}
            print(f"PASS {name} ({time.time() - t0:.1f}s): {printable}")
        except Exception as e:
            traceback.print_exc()
            failures.append(name)
            print(f"FAIL {name}: {type(e).__name__}: {e}")
    print(f"==== done: {len(wanted) - len(failures)}/{len(wanted)} passed ====")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

"""Benchmark harness entrypoint: one module per paper table/figure.

Usage:
    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig7,fig8,...]

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/README contract).
The quick mode (default) uses reduced rates/durations sized for a single-core
CPU container; --full uses paper-scale sweeps.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import CsvReporter


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", type=str, default="")
    ap.add_argument("--skip", type=str, default="")
    args = ap.parse_args()

    from benchmarks import (churn_scale, fig1_burst, fig7_coldstart,
                            fig8_warmstart, fig9_10_azure, fig11_failover,
                            kernel_bench, registration, scalability)
    modules = {
        "fig1": fig1_burst,
        "fig7": fig7_coldstart,
        "fig8": fig8_warmstart,
        "azure": fig9_10_azure,
        "fig11": fig11_failover,
        "registration": registration,
        "scalability": scalability,
        "churn": churn_scale,
        "kernels": kernel_bench,
    }
    only = set(filter(None, args.only.split(",")))
    skip = set(filter(None, args.skip.split(",")))

    rep = CsvReporter()
    rep.header()
    failed = []
    for name, mod in modules.items():
        if only and name not in only:
            continue
        if name in skip:
            continue
        t0 = time.time()
        try:
            mod.run(rep, quick=not args.full)
            print(f"# {name} finished in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # keep the harness going; report the failure
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
            import traceback
            traceback.print_exc()
            failed.append(name)
    if failed:
        sys.exit(f"# failed modules: {', '.join(failed)}")


if __name__ == "__main__":
    main()

"""Benchmark runner. Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--full] [--quick] [--only table1,fig9]

Default mode uses reduced sizes so the whole suite finishes on one CPU core;
--full matches the paper's settings (K=3965 alignment, sweeps to 2048);
--quick runs the ~30-second CI smoke subset (kernel model + batched decode)."""

import argparse
import sys
import traceback

from repro.runtime.compile_cache import enable_compile_cache

QUICK_SUITES = ["fig10", "fig12", "fig13"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke subset (~30 s)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    from . import (table1_overall, fig7_scaling, fig8_density, fig9_beam,
                   fig10_kernel, fig11_streaming, fig12_batch,
                   fig13_constrained, roofline_table)
    suites = {
        "table1": table1_overall.run,
        "fig7": fig7_scaling.run,
        "fig8": fig8_density.run,
        "fig9": fig9_beam.run,
        "fig10": fig10_kernel.run,
        "fig11": fig11_streaming.run,
        "fig12": fig12_batch.run,
        "fig13": fig13_constrained.run,
        "roofline": roofline_table.run,
    }
    if args.only:
        picked = args.only.split(",")
    elif args.quick:
        picked = QUICK_SUITES
    else:
        picked = list(suites)
    print("name,us_per_call,derived")
    failed = []
    for name in picked:
        try:
            suites[name](full=args.full)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

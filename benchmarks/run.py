"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--full] [--smoke] [--only NAME]

Prints ``name,us_per_call,derived`` CSV.

``--smoke`` runs every suite in a tiny configuration (a couple of cells,
short sequences) and never rewrites the committed BENCH_*.json trajectory
files — it exists so tier-1 CI can prove the benchmark scripts still run
between the real (weekly / manual) sweeps.  The serving_load smoke
additionally guards the plan subsystem: it autotunes one tiny cell and
fails loudly if the result fails ``ServingPlan.validate()`` or the plan
JSON schema drifts from the dataclass fields (see
``serving_load._check_plan_surface``).
"""

from __future__ import annotations

import argparse
import inspect
import sys
import traceback

from repro.compile_cache import enable_compile_cache

from benchmarks import (
    chaos,
    collectives,
    decode_hotpath,
    energy,
    fig4_fragmentation,
    kernel_tiles,
    roofline_table,
    serving_load,
    table6_deepbench,
    table7_dse,
)

SUITES = {
    "table6_deepbench": table6_deepbench,
    "table7_dse": table7_dse,
    "fig4_fragmentation": fig4_fragmentation,
    "energy": energy,
    "roofline_table": roofline_table,
    "kernel_tiles": kernel_tiles,
    "serving_load": serving_load,
    "decode_hotpath": decode_hotpath,
    "chaos": chaos,
    "collectives": collectives,
}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full timesteps for measured benchmarks")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny configs, no BENCH_*.json writes (CI guard)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    print("name,us_per_call,derived")
    failed = []
    for name, mod in SUITES.items():
        if args.only and args.only != name:
            continue
        kwargs = {"fast": not args.full}
        if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
            kwargs["smoke"] = True
        try:
            for row in mod.run(**kwargs):
                print(row.csv(), flush=True)
        except Exception as e:  # noqa: BLE001 — keep the harness running
            failed.append(name)
            print(f"{name}/ERROR,0.0,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()

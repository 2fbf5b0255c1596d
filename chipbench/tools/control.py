"""Read a cell's compared number for the program and for its control on
several seeds, in one process: each seed is a whole run of the cell
(set-up, window, check) with the control in the program's place: the
compared number holds the control's reading, so ``correct`` reads false,
and the program's reading is printed beside it (``program_*``).  The
lower reading of a limit is the largest program number over the seeds,
the upper the smallest control number.

    python3 chipbench/tools/control.py --workload <cell> \
        --seeds 11,12,13 --seconds 30
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench.core import device, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    root = harness.ROOT
    sys.path.insert(0, str(root / "src"))
    harness.enable_compile_cache(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    chips = {c["name"]: c for c in bench["workloads"]}[args.workload]["chips"]
    devices = device.require_tpu(int(chips))
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        ctx = harness.make_ctx(root, args.workload, seed, args.seconds,
                               False, devices, control=True)
        line = harness.run_cell(ctx, t)
        print(json.dumps({"seed": seed, "correct": line["correct"],
                          "attempted": line["attempted"],
                          "metrics": line["metrics"],
                          "check": line["check"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

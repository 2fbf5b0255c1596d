"""Run one cell of ``BENCHMARK.json`` once, on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints progress and the compared numbers on stderr and, as the last line
of stdout, one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and with ``--trace 1`` a ``breakdown``) and last
``check``.  Exits non-zero, printing no result, when JAX finds no TPU or
fewer chips than the cell needs.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench.core.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))

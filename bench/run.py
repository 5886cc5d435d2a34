#!/usr/bin/env python3
"""Run one benchmark cell once:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``; ``breakdown`` with ``--trace 1``; the
numbers compared for ``correct`` under ``checks``).  Without a TPU, or
with fewer chips than the cell asks for, it exits 1 and prints no result.
"""

import time

T_START = time.monotonic()          # set-up is counted from here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))

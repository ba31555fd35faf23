"""Readings for a cell's limits, on the chip: one run of the cell as
``run.py`` makes it, which also reads, at the same positions, the control
and the faults planted in the reference put in the program's place
(``harness.check_sample``), each judged on the cell's limits, under the
key ``readings`` of the result line. ``--fault <name>`` plants one of
``faults.FAULTS`` in the engine. One seed per process: the benchmark's
runs never call this.

    python3 perfbench/calibrate.py --workload <cell> --seed <n> \\
        --seconds <s> --trace 0 [--fault state_unchanged]
"""
from __future__ import annotations

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from perfbench import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(calibrate=True))

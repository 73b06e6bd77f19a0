#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 benchmarks/chip/run_cell.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  Exits non-zero, printing no result,
when JAX finds no TPU or fewer chips than the cell asks for.  The last
line of standard output is the result as one JSON object.
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())

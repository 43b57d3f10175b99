#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object; the numbers that
decided ``correct`` are also the last lines of standard error.  Exits 3,
printing no result, when JAX finds no TPU or fewer chips than the cell
asks for.  See ``bench/harness.py``.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:]))

"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the
numbers that decide ``correct`` are also the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
import time

T0 = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from harness.run import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))

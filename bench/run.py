"""Entry point of the benchmark: one run of one cell (see
``bench/harness.py``).

    python3 bench/run.py --workload road_ny.solve --seed 7 --seconds 20 --trace 0
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))

"""Run one benchmark cell once (see `yardstick/harness.py`):

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
import time

T0 = time.perf_counter()          # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from yardstick.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t0=T0))

"""python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, holds the cell's chips, refuses any platform but ``tpu``. Prints
free-form JSON lines as it goes and the contract's one result object last.
"""

import os
import sys
import time

T_PROCESS_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmark import harness

    harness.main(sys.argv[1:], T_PROCESS_START)

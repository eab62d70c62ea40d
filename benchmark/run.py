"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>: one run of one cell of BENCHMARK.json on the attached TPU.
The last line of standard output is the result; see benchmark/README.md."""

import os
import sys
import time

T_PROCESS = time.time()

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark.harness.runner import main

    sys.exit(main(t_process=T_PROCESS))

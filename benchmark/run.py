"""Run one cell of ``BENCHMARK.json`` once:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which imports JAX once and starts no child. It needs the TPU
chips the cell asks for and exits non-zero, printing no result, without them
(or without the program: a directory that holds only the benchmark). Sizes
are data: there is no size switch. The last line of standard output is the
result object; the lines before it say what was run and what was counted.
"""

import os
import sys
import time

if __name__ == "__main__":
    _started = time.perf_counter()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import harness

    sys.exit(harness.main(sys.argv[1:], _started))

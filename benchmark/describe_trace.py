"""Print what a profiler trace holds, for a person to read before writing
patterns into ``kernel_names.json``: planes, lines, and on each line the
names that took most time, with one event's stats.

    python benchmark/describe_trace.py .bench_out/trace/<workload>

Touches no device: it only reads the file.
"""

import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import trace_reduce

    profile = trace_reduce.load(trace_reduce.find_xplane(sys.argv[1]))
    print(json.dumps(trace_reduce.describe(profile), indent=1))

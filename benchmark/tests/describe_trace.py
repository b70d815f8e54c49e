#!/usr/bin/env python3
"""Print what a cell's newest trace holds — planes, lines, the heaviest
event names with their stats — to be read by hand before a pattern goes
into a metric file.

    python3 benchmark/tests/describe_trace.py <cell> [rows]
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

if __name__ == "__main__":
    from benchmark import common, trace_read

    path = trace_read.find_xplane(os.path.join(common.SCRATCH, "trace", sys.argv[1]))
    print(trace_read.describe(path, int(sys.argv[2]) if len(sys.argv) > 2 else 14))

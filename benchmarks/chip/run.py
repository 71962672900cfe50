#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in the root ``BENCHMARK.json``; see
``chipbench/harness.py`` for the flow.  The last line of standard output
is the result as one JSON object.
"""
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from chipbench.harness import run
    sys.exit(run(t_start=T_START))

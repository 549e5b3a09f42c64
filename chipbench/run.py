"""Chip benchmark of the CAFL-L federated engine: one cell, one process.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Runs from the root of a checkout on a machine with the TPU chips the
cell asks for; see ``chipbench/README.md`` for what it measures, how
``correct`` is decided, and how to add a cell, a configuration or a
metric.
"""
import os
import sys
import time

T_PROCESS = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

from harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_process=T_PROCESS))

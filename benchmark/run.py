"""One process, one cell, once:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name (``BENCHMARK.json``,
``configs/``, ``workloads/``, ``layer_metrics/``); this file and
``harness.py`` hold no cell's, metric's or model's name.
"""

import time

_T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmark import harness

    raise SystemExit(harness.main(sys.argv[1:], _T_START))

"""The benchmark's command: one run of one cell.

    python3 benchmarks/run.py --workload W --seed N --seconds S --trace 0|1

Everything it reads besides the program under test lives under
`benchmarks/` and is found by the names in `BENCHMARK.json`; see
`benchmarks/README.md`. The last line of standard output is the result.
"""
import time

T_START = time.monotonic()    # set-up is counted from here

import os      # noqa: E402
import sys     # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)      # `paddle_tpu`, the system under test
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import cli       # noqa: E402

if __name__ == "__main__":
    sys.exit(cli.main(t_start=T_START))

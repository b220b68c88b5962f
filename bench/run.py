"""Q-StaR chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the TPU chips the cell
asks for (``BENCHMARK.json``).  Without them it prints no result and
exits with code 2.  The last line of standard output is the result; the
numbers of the comparison with the plain reference come last on
standard error.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from qsbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())

"""One run of one benchmark cell on the machine it is started on:

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the
numbers compared with the reference, each beside its limit, are the last
lines of standard error.  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.time()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

if __name__ == "__main__":
    from bench import main
    sys.exit(main(t_start=T_START))

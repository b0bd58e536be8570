#!/usr/bin/env python3
"""One run of one cell of the benchmark (see benchmarks/README.md).

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One process, one cell, one run, on the TPU(s) of the machine it is started
on; any other backend ends it with no result line.  The last line of stdout
is the result object and nothing else.
"""

import time

T_START = time.perf_counter()   # set-up counts from here: imports included

import argparse    # noqa: E402
import json        # noqa: E402
import os          # noqa: E402
import sys         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the program under test is the checkout this file sits in
    sys.path[:0] = [os.path.dirname(HERE), HERE]
    from harness import loop, spec

    cell = spec.load_cell(args.workload)
    result = loop.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The last line of standard output is the
result as one JSON object; the last lines of standard error are the
numbers the correctness check compared, each beside its limit; a run
that printed its result exits 0, whether ``correct`` is true or false.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 3.  See ``bench/harness.py`` for what one run does.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime logs to a fixed path under /tmp unless told
    # otherwise; a run writes nothing outside its checkout and TMPDIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench.harness import NoChip, run_cell

    try:
        run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                 t_start=T_START)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())

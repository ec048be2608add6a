#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
                         --trace <0|1>
    python3 bench/run.py --list

The cells are the files under ``bench/workloads/``.  The run needs the
accelerator the cell asks for: with no TPU, or fewer chips than the cell
needs, it prints no result and exits non-zero.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with the reference beside its limit.
"""
import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
# import the benchmark as the package `bench` from the checkout root, not
# its modules as top-level names from its own directory
sys.path[0] = str(ROOT)


def main(argv=None) -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", help="cell name (a file under "
                    "bench/workloads/)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--list", action="store_true", help="list the cells")
    args = ap.parse_args(argv)
    if args.list:
        print("\n".join(harness.list_workloads()))
        return 0
    if not args.workload:
        ap.error("--workload is required")
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace))


if __name__ == "__main__":
    sys.exit(main())

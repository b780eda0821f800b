"""Run one benchmark cell once on the accelerator and print its result.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``; ``checks`` last: each
compared number beside its limit, which also end standard error).  With
no TPU, or fewer chips than the cell asks for, it exits non-zero and
prints no result: it never falls back to the CPU.

``--rate`` overrides the mix's rate (the knee sweep); ``--control 1``
puts the lower-precision control in the program's place in the
comparison, so that the run must read ``correct: false`` (how the
correctness limit was set).  The benchmark's own runs use neither.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench.harness import run_cell
    line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                    platform="tpu", t_start=T_START, rate=args.rate,
                    control=bool(args.control))
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()

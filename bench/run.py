#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload paper_harmonic_d4.closed2 --seed 7 \
        --seconds 30 --trace 0

Prints progress and the compared numbers on standard error and, as the
last line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``breakdown``), and ``check``, the numbers compared with their limits.
Exits non-zero, printing no result, where JAX finds no TPU, fewer chips
than the cell asks for, or kernels that would run interpreted.
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    if args.seconds <= 0:
        ap.error("--seconds must be positive")
    from bench import harness
    try:
        return harness.run(args.workload, args.seed, args.seconds,
                           bool(args.trace))
    except harness.Refused as exc:
        print(f"bench: refused: {exc}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())

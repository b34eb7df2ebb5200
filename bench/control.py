#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference put in the
system's place, in bfloat16, must come out as not correct.

    python3 bench/control.py --workload paper_harmonic_d4.closed2 \
        --seeds 101 102 103 --requests 4

For each seed it draws the first ``--requests`` requests of the cell's
window (the same draws a run makes), serves them with the plain Monte
Carlo estimator of ``reference/mc.py`` in bfloat16 (the control) and in
float32 (its twin), and compares both with the closed form exactly as a
run does (``check.compare``).  A fixed-budget request gets its budget; a
request with a stderr target gets the plain sample count that meets the
target in float32, 1.1 (sigma / target)^2 from the closed-form variance.
The last line of standard output is JSON: the numbers of each seed and
dtype.  Runs only on a TPU, as the benchmark does.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import check, discover, loadgen  # noqa: E402
from bench.harness import Cell, _merge  # noqa: E402
from bench.reference import mc  # noqa: E402


def window_params(cell: Cell, clients: int, n_requests: int) -> list[dict]:
    """The first ``n_requests`` window requests, client by client."""
    out = []
    i = 0
    while len(out) < n_requests:
        for c in range(clients):
            if len(out) < n_requests:
                out.append(cell.draw(loadgen.PHASE_WINDOW, c, i))
        i += 1
    return out


def samples_for(request: dict, reference, params: dict,
                round_samples: int) -> int:
    n = check.expected_samples(request, round_samples)
    if n is not None:
        return n
    exact = reference.exact(params)
    var = reference.second_moment(params) - exact * exact
    return math.ceil(1.1 * float(var.max()) / request["target_stderr"] ** 2)


def readings(workload: str, seeds, n_requests: int, *,
             overrides=None) -> list[dict]:
    import jax.numpy as jnp

    bench = discover.load_benchmark()
    cell_entry = discover.workload(bench, workload)
    overrides = overrides or {}
    config = _merge(discover.config(cell_entry["config"]),
                    overrides.get("config"))
    mix = _merge(discover.traffic(cell_entry["traffic"]),
                 overrides.get("traffic"))
    reference = discover.reference(config["request"]["form"])
    out = []
    for seed in seeds:
        cell = Cell(config, seed)
        req = cell.request
        params = window_params(cell, int(mix["clients"]), n_requests)
        for name in ("bfloat16", "float32"):
            answers = []
            for j, p in enumerate(params):
                n = samples_for(req, reference, p, cell.round_samples)
                means, ses, n_used = mc.plain_mc(
                    reference.integrand, p, int(req["dim"]), n,
                    getattr(jnp, name), seed * 1000 + j)
                answers.append((p, types.SimpleNamespace(
                    means=means, stderrs=ses, n_per_family=(n_used,),
                    failed=False)))
            numbers = check.compare(answers, req, reference,
                                    cell.round_samples, config["check"])
            out.append({"seed": seed, "dtype": name,
                        "correct": check.passed(numbers),
                        "numbers": {k: v["value"]
                                    for k, v in numbers.items()}})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, default=4)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    rows = readings(args.workload, args.seeds, args.requests)
    for r in rows:
        print(f"control {args.workload} seed {r['seed']} {r['dtype']}: "
              f"correct={r['correct']} {r['numbers']}", file=sys.stderr)
    print(json.dumps({"workload": args.workload, "readings": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

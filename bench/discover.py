"""Finds the benchmark's parts by the names ``BENCHMARK.json`` gives them.

A configuration is ``configs/<name>.json``, a traffic mix
``traffic/<name>.json``, an integrand form ``forms/<form>.py`` with its
closed form in ``reference/<form>.py``, and a per-layer metric the reader
``layer_metrics/<name>.py``.  A metric named ``<base>.<split>`` that has
no file of its own is read by ``layer_metrics/<base>.py``: the split
names the cells it is reported in, not a different quantity.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json", encoding="utf-8") as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def _json(path: Path) -> dict:
    if not path.is_file():
        raise KeyError(f"{path.relative_to(ROOT)} does not exist")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def config(name: str, bench_dir: Path = BENCH) -> dict:
    return _json(bench_dir / "configs" / f"{name}.json")


def traffic(name: str, bench_dir: Path = BENCH) -> dict:
    return _json(bench_dir / "traffic" / f"{name}.json")


def _module(path: Path, label: str):
    if not path.is_file():
        raise KeyError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def form(name: str, bench_dir: Path = BENCH):
    return _module(bench_dir / "forms" / f"{name}.py", f"bench_form_{name}")


def reference(name: str, bench_dir: Path = BENCH):
    return _module(bench_dir / "reference" / f"{name}.py",
                   f"bench_reference_{name}")


def metric_reader(name: str, bench_dir: Path = BENCH):
    """The module whose ``read(ctx)`` computes per-layer metric ``name``."""
    d = bench_dir / "layer_metrics"
    path = d / f"{name}.py"
    if not path.is_file():
        path = d / f"{name.split('.', 1)[0]}.py"
    return _module(path, f"bench_metric_{name.replace('.', '_')}")


def cell_metrics(bench: dict, name: str, trace: bool) -> list[dict]:
    """The metrics a run of cell ``name`` reports: its end-to-end metrics,
    or with ``trace`` its per-layer metrics."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in reported)]

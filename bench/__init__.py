"""On-chip benchmark of the integration service.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json``.  Everything a cell needs is
found by name: ``configs/<config>.json``, ``traffic/<mix>.json``,
``forms/<form>.py`` with ``reference/<form>.py``, and one reader per
per-layer metric in ``layer_metrics/``.
"""

"""The harness finds every part of a cell by the name BENCHMARK.json gives
it, and BENCHMARK.json keeps to the benchmark's contract."""

import json
import math
import re

import pytest

from bench import discover

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return discover.load_benchmark()


def test_every_cell_finds_its_config_mix_form_and_metrics(bench):
    for w in bench["workloads"]:
        cfg = discover.config(w["config"])
        assert cfg["name"] == w["config"]
        mix = discover.traffic(w["traffic"])
        assert mix["loop"] == "closed" and mix["clients"] >= 1
        form = discover.form(cfg["request"]["form"])
        assert callable(form.draw) and callable(form.family)
        ref = discover.reference(cfg["request"]["form"])
        assert callable(ref.exact) and callable(ref.second_moment)
        e2e = discover.cell_metrics(bench, w["name"], trace=False)
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        layer = discover.cell_metrics(bench, w["name"], trace=True)
        assert layer, w["name"]
        for m in layer:
            assert m["moves"] in names
            assert callable(discover.metric_reader(m["name"]).read)


def test_a_new_metric_mix_and_config_are_found_by_file_name(tmp_path):
    for sub in ("layer_metrics", "traffic", "configs"):
        (tmp_path / sub).mkdir()
    (tmp_path / "layer_metrics" / "queue_depth.py").write_text(
        "def read(ctx):\n    return 4.0\n")
    (tmp_path / "traffic" / "closed8.json").write_text(
        json.dumps({"loop": "closed", "clients": 8}))
    (tmp_path / "configs" / "new_deployment.json").write_text(
        json.dumps({"name": "new_deployment"}))
    # a split name without a file of its own is read by its base's reader
    reader = discover.metric_reader("queue_depth.serve", bench_dir=tmp_path)
    assert reader.read(None) == 4.0
    assert discover.traffic("closed8", bench_dir=tmp_path)["clients"] == 8
    assert discover.config("new_deployment",
                           bench_dir=tmp_path)["name"] == "new_deployment"
    with pytest.raises(KeyError):
        discover.metric_reader("absent_metric", bench_dir=tmp_path)


def test_benchmark_json_keeps_to_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][1] == "bench/run.py"
    assert 1 <= bench["run_seconds"] <= 51
    assert isinstance(bench["run_seconds"], int)
    n_cells = len(bench["workloads"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, n_cells // 2)
    # a full check must fit 43200 s with 24 cells
    per_run = bench["run_seconds"] + 60
    assert (2 + 14 * 24) * per_run + 24 * 180 + 1200 <= 43200
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/configs/")
        names.append(c["name"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        names.append(w["name"])
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) \
        == n_cells
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        names.append(m["name"])
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        names.append(m["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert len(json.dumps(bench)) <= 64 * 1024
    assert not math.isnan(bench["run_seconds"])

"""A later change adds a cell as data: a traffic file, a limits file and
an entry in BENCHMARK.json, with no edit to the harness."""
import json
import shutil

import pytest

import bench


@pytest.fixture
def checkout(tmp_path):
    root = tmp_path / "co"
    shutil.copytree(bench.HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(bench.ROOT / "BENCHMARK.json", root)
    return root


def add_cell(root, name, traffic, limits):
    """A copy of the benchmark's first cell under ``name``, with its own
    traffic file (``traffic``) and limits file (``limits``, if any)."""
    here = root / "benchmarks" / "chip"
    b = json.loads((root / "BENCHMARK.json").read_text())
    first = b["workloads"][0]
    (here / "traffic" / f"{name}.json").write_text(json.dumps(traffic))
    if limits is not None:
        (here / "limits" / f"{name}.json").write_text(json.dumps(limits))
    b["workloads"].append(dict(first, name=name, traffic=name,
                               why="a copy at another size"))
    for m in b["end_to_end"] + b["per_layer"]:
        if first["name"] in m.get("workloads", ()):
            m["workloads"].append(name)
    return b, here, first


def test_the_committed_benchmark_validates():
    assert bench.validate(bench.benchmark()) == []


def test_the_pending_cells_validate():
    from conftest import with_pending
    assert bench.validate(with_pending(bench.benchmark())) == []


def test_a_dropped_in_cell_is_found_by_name(checkout):
    first = bench.benchmark()["workloads"][0]
    traffic = json.loads((bench.HERE / "traffic"
                          / f"{first['traffic']}.json").read_text())
    traffic["population"] += 1
    limits = json.loads((bench.HERE / "limits"
                         / f"{first['name']}.json").read_text())
    b, here, first = add_cell(checkout, "dropped.in", traffic, limits)
    assert bench.validate(b, here) == []
    spec = bench.cell_spec(b, "dropped.in", here)
    old = bench.cell_spec(b, first["name"], here)
    assert spec["traffic"] == traffic
    assert spec["cfg"] == old["cfg"] and spec["limits"] == limits
    assert spec["end_to_end"] == old["end_to_end"]
    assert spec["per_layer"] == old["per_layer"]


def test_a_cell_missing_its_files_is_refused(checkout):
    b, here, _ = add_cell(checkout, "dropped.in", {"population": 2}, None)
    errors = bench.validate(b, here)
    assert any("dropped.in" in e for e in errors)


def test_a_metric_that_a_cell_cannot_report_is_refused(checkout):
    b = json.loads((checkout / "BENCHMARK.json").read_text())
    cell = b["workloads"][0]["name"]
    b["end_to_end"].append({"name": "other_per_s", "unit": "1/s",
                            "better": "higher", "bound": 0.01,
                            "source": "host_clock", "workloads": []})
    b["per_layer"].append({"name": "mfu.other", "unit": "%",
                           "better": "higher", "source": "host_clock",
                           "layer": "whole step", "moves": "other_per_s",
                           "workloads": [cell]})
    errors = bench.validate(b, checkout / "benchmarks" / "chip")
    assert any("mfu.other" in e for e in errors)

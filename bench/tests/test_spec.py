"""BENCHMARK.json names files that exist, in the shape the harness reads;
the entry refuses a machine without a TPU."""
import dataclasses
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from harness import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"] and BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("name", CELLS)
def test_every_file_of_a_cell_is_found_by_name(name):
    cell = spec.find_cell(name)
    assert cell.model.samples_per_call(cell.config, cell.traffic) > 0
    assert callable(cell.reference.loss)
    assert {"loss_gap", "momentum_gap", "change_gap"} <= set(cell.limits)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]).read)


def test_names_units_and_bounds():
    names = [c["name"] for c in BENCH["configs"]] + CELLS
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_state_their_cuts(entry):
    cfg = json.loads((spec.ROOT / entry["file"]).read_text())
    assert cfg["reduced"] == entry["reduced"] and cfg["source"] == entry["source"]
    for key in entry["reduced"]:
        assert key in cfg and key in cfg["published"]


def test_without_a_tpu_the_entry_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_without_the_program_the_entry_exits_nonzero(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=300,
    )
    assert out.returncode != 0 and out.stdout.strip() == ""


@pytest.mark.parametrize("K", [2, 5, 16])
def test_a_graph_by_edges_is_the_graph_by_name(K):
    from harness.program import topology
    from harness.reference import graph
    from tiny import tiny_cell

    edges = {"name": "ring-by-edges", "edges": [[k, (k + 1) % K] for k in range(K)]}
    cell = tiny_cell("resnet20-k16.every-step")
    by_name = dataclasses.replace(cell, config=dict(cell.config, num_agents=K))
    by_edges = dataclasses.replace(by_name, traffic=dict(cell.traffic, topology=edges))
    assert (topology(by_name).c_matrix() == topology(by_edges).c_matrix()).all()
    assert (graph("ring", K) == topology(by_edges).c_matrix()).all()
    assert (graph(edges, K) == graph("ring", K)).all()

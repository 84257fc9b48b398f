"""``BENCHMARK.json`` resolves, by name, to the files that run it."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
sys.path.insert(0, str(BENCH))
import run  # noqa: E402


def metrics_of(kind, cell):
    return [m["name"] for m in run.cell_metrics(MANIFEST, cell, kind)]


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    assert MANIFEST["paths"] == ["bench"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    spec, config, traffic = run.resolve(MANIFEST, cell)
    conf = next(c for c in MANIFEST["configs"] if c["name"] == spec["config"])
    assert conf["file"].startswith("bench/configs/")
    assert config["name"] == spec["config"]
    assert (BENCH / "drivers" / f"{traffic['driver']}.py").is_file()
    assert spec["chips"] in (1, 4)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    e2e = metrics_of("end_to_end", cell)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert metrics_of("per_layer", cell)


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_per_layer_metric_has_a_reader_and_moves_a_reported_metric(metric):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == metric)
    reader = run.load_module(BENCH / "metrics" / f"{metric}.py")
    assert callable(reader.read)
    assert m["moves"] in {e["name"] for e in MANIFEST["end_to_end"]}
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert m["moves"] in metrics_of("end_to_end", cell)


def test_names_and_units_use_the_allowed_characters():
    names = [c["name"] for c in MANIFEST["configs"]] + CELLS
    names += [w["traffic"] for w in MANIFEST["workloads"]]
    names += [k for c in MANIFEST["configs"] for k in c["reduced"]]
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names += [m["name"] for m in metrics]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for group in ("configs", "workloads"):
        assert len({x["name"] for x in MANIFEST[group]}) == len(
            MANIFEST[group])
    assert len({m["name"] for m in metrics}) == len(metrics)


def test_bounds_and_layers():
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    assert all("\n" not in x and 0 < len(x) <= 200 for x in layers)


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "2147483653", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_refuses_without_a_tpu():
    p = _run(ROOT, {"PYTHONPATH": str(ROOT / "src")})
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout

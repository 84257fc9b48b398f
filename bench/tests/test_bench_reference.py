"""The harness's comparison on the CPU at a tiny size: sound runs of the
program are correct, the control (the plain reference in bfloat16, put in
the program's place) is not, and a traced run reports its layers."""
import dataclasses
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402

CELLS = ["pendigits.search", "cardio.robust_k8", "cardio.serve_mixed"]


@pytest.fixture(autouse=True)
def fresh_programs():
    jax.clear_caches()


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    out = bench_tiny.execute(workload)
    assert out["correct"], out["compared"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert all(c["value"] == 0 for c in out["compared"].values())
    assert "setup_s" in out["metrics"]
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    _, cell = bench_tiny.cell(workload)
    cell.warm()
    cell.window(0.2)
    cell.release()
    cell.outputs = cell.replay(dataclasses.replace(cell.search,
                                                   precision="bfloat16"))
    assert any(value > limit for _, value, limit in cell.check())


@pytest.mark.parametrize("workload", ["cardio.robust_k8",
                                      "cardio.serve_mixed"])
def test_traced_run_reports_its_layers(workload, monkeypatch):
    import peaks

    monkeypatch.setattr(peaks, "peaks", lambda kind: bench_tiny.CPU_PEAKS)
    out = bench_tiny.execute(workload, trace=True)
    assert out["correct"]
    layer = "search" if "search" in str(out["metrics"]) else "serve"
    assert f"step_mfu.{layer}" in out["metrics"]
    assert 0 < out["metrics"][f"step_mfu.{layer}"]["value"] < 100
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}

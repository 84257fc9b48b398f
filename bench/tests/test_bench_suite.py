"""The suite cell (``paper_suite.sharded4``, driver ``suite``) on the CPU at
a tiny size: sound runs are correct on a one-device mesh and on four
virtual devices, the bfloat16 control and three breaks of the timed path
are not, a traced run reports the cell's counter, and the reference
replay of one cell equals that cell of ``run_suite``."""
import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402
import run  # noqa: E402
from drivers import suite  # noqa: E402
from test_bench_faults import (answer_altered, half_the_rows,  # noqa: E402
                               state_unchanged)

WORKLOAD = "paper_suite.sharded4"
SEED = 2**31 + 11


def tiny_cell(mesh: int = 1, seed: int = SEED, rows: int = 96,
              generations: int = 2, seeds: int = 2):
    """(BENCHMARK.json, the cell) at a tiny size: ``seeds`` searches a
    dataset, the cells split over ``mesh`` devices."""
    bench, config, traffic = bench_tiny.tiny(WORKLOAD, rows=rows,
                                             generations=generations)
    traffic.update(mesh=mesh, seeds=seeds)
    return bench, run.driver(traffic).Cell(config, traffic, seed)


def execute(trace: bool = False, mesh: int = 1) -> dict:
    t0 = time.perf_counter()
    bench, cell = tiny_cell(mesh)
    out, _ = run.execute(bench, WORKLOAD, cell, jax.devices()[:mesh], 0.5,
                         trace, t0)
    return out


@pytest.fixture(autouse=True)
def fresh_programs():
    jax.clear_caches()


def test_sound_run_is_correct():
    out = execute()
    assert out["correct"], out["compared"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert all(c["value"] == 0 for c in out["compared"].values())
    assert {"chromo_evals_per_s", "setup_s"} <= set(out["metrics"])


@pytest.mark.parametrize("seed", [SEED, 3, 2**32 + 5])
def test_replays_lie_on_every_device(seed):
    """One replayed cell per dataset, which with 8 seeds a dataset (one
    bucket of 8 cells, 2 a device on four) lie on all four devices and in
    both lanes of a device."""
    _, cell = tiny_cell(seed=seed, seeds=8)
    assert [i // 8 for i in cell.replayed] == list(range(5))
    assert {i % 8 // 2 for i in cell.replayed} == {0, 1, 2, 3}
    assert {i % 2 for i in cell.replayed} == {0, 1}


def test_control_is_not_correct():
    _, cell = tiny_cell()
    cell.warm()
    cell.window(0.2)
    cell.release()
    cell.outputs = cell.replay(dataclasses.replace(cell.search,
                                                   precision="bfloat16"))
    assert any(value > limit for _, value, limit in cell.check())


@pytest.mark.parametrize("fault", [state_unchanged, half_the_rows,
                                   answer_altered])
def test_broken_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = execute()
    jax.clear_caches()
    assert not out["correct"], out["compared"]


def test_traced_run_reports_the_padded_work(monkeypatch):
    import peaks

    monkeypatch.setattr(peaks, "peaks", lambda kind: bench_tiny.CPU_PEAKS)
    out = execute(trace=True)
    assert out["correct"]
    # all five tiny datasets have 96 rows: one bucket, one 128-row tile;
    # the share is a mean of the datasets' own, weighted by evaluations
    sizes = [(10, 3, 2), (21, 3, 3), (16, 5, 10), (11, 2, 6), (11, 4, 7)]
    shares = [100 * (1 - sum(a * b for a, b in zip(s, s[1:])) * 96
                     / (155 * 128)) for s in sizes]
    assert min(shares) < out["metrics"]["padded_work.suite"]["value"] < max(
        shares)
    assert 0 < out["metrics"]["step_mfu.search"]["value"] < 100
    assert 0 < out["metrics"]["evaluated_rows.search"]["value"] <= 100
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_suite_readers_on_four_devices():
    """``bucket_gap_ms.suite`` and ``chip_skew.suite`` on a hand-made trace
    of four chips: three bucket programs each, chip i idle for the last
    5·i ns of every program, and two ``suite.bucket`` spans that cover
    part of the idle time between programs."""
    import xplane

    devices = [f"/device:TPU:{i}" for i in range(4)]
    progs = [("jit__run_suite_cells", 0, 100),
             ("jit__run_suite_cells", 110, 200),
             ("jit__run_suite_cells", 230, 300)]
    ops = {d: [("fusion.1", s, e - 5 * i) for _, s, e in progs]
           for i, d in enumerate(devices)}
    host = [("bench.window", 0, 400), ("suite.bucket", 90, 120),
            ("suite.bucket", 215, 235)]
    trace = xplane.Trace((0, 400), ops, {d: list(progs) for d in devices},
                         host)

    def read(name):
        return run.load_module(
            bench_tiny.BENCH / "metrics" / f"{name}.py").read(
                {"trace": trace})

    # between programs [100, 110] and [200, 230], inside the spans 10 + 15
    # ns of idle on every chip, over two intervals
    assert read("bucket_gap_ms.suite") == pytest.approx(12.5e-6)
    # busy 260 − 15·i ns: (260 − 215) ÷ 260
    assert read("chip_skew.suite") == pytest.approx(100 * 45 / 260)
    one = xplane.Trace((0, 400), {devices[0]: ops[devices[0]]},
                       {devices[0]: list(progs)}, host)
    assert run.load_module(bench_tiny.BENCH / "metrics"
                           / "chip_skew.suite.py").read({"trace": one}) is None


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_numpy_intervals_match_xplane(seed):
    """``bench/intervals.py`` merges a chip's operations as ``xplane``
    does: nested, overlapping and window-crossing intervals alike."""
    import intervals
    import xplane

    rng = np.random.default_rng(seed)
    starts = rng.integers(-50, 1000, 300)
    ops = sorted((("op", int(s), int(s + d)) for s, d in
                  zip(starts, rng.integers(0, 40, 300))), key=lambda o: o[1])
    trace = xplane.Trace((0, 900), {"/device:TPU:0": ops}, {}, [])
    assert intervals.busy_ns(trace, "/device:TPU:0") == xplane.busy_ns(
        trace, "/device:TPU:0")
    windows = [(100, 180), (400, 420), (850, 900)]
    assert intervals.idle_within(trace, "/device:TPU:0", windows) == sum(
        xplane.idle_ns(trace, "/device:TPU:0", lo, hi) for lo, hi in windows)


def test_replay_cell_equals_the_suite_cell():
    """``replay_cell`` at a dataset's own topology gives the state and
    front that ``run_suite`` gives that dataset's cell in the padded
    layout."""
    from repro.api import MLPTopology, Problem, run_suite
    from inputs import doping_genomes, rng_for, training_split
    from reference import ga

    names, sizes = ("breast_cancer", "whitewine"), ((10, 3, 2), (11, 4, 7))
    searches = [ga.Search(s, pop=8) for s in sizes]
    data = [training_split(n, 5, 80 + 40 * i) for i, n in enumerate(names)]
    rng = rng_for(5, 1)
    doping = [doping_genomes(ga.layout(s), 2, rng) for s in searches]
    cfg = suite.ga_config(searches[0], 3, 0)
    problems = [Problem.from_data(MLPTopology(s), x, y, cfg, baseline_acc=0.9)
                for s, (x, y) in zip(sizes, data)]
    res = run_suite(problems, [3, 17], doping_seeds=doping)
    i = res.cells_of(1)[1]
    state, front = suite.replay_cell(searches[1], *data[1], 0.9, 17, 3,
                                     doping[1])
    got = res.state_at(i)
    for f in suite.STATE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(got, f)), state[f],
                                      err_msg=f)
    for k in ("objectives", "genomes"):
        np.testing.assert_array_equal(res.front_at(i)[k], front[k])


def test_four_device_mesh_is_correct():
    """The tiny cell with its cells split over four virtual CPU devices,
    in a process of its own."""
    code = (
        "import json, sys; sys.path.insert(0, 'bench/tests'); "
        "import test_bench_suite as t; "
        "out = t.execute(mesh=4); "
        "print(json.dumps({'correct': out['correct'], "
        "'count': out['device']['count']}))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(bench_tiny.BENCH.parent / "src"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env,
                       cwd=bench_tiny.BENCH.parent)
    assert p.returncode == 0, p.stderr[-4000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == {
        "correct": True, "count": 4}

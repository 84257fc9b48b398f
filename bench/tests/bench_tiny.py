"""Cells of the benchmark cut to a size the CPU runs in seconds, for the
tests: the harness's own code paths, the program's default CPU backends,
the plain reference."""
from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402

CPU_PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def tiny(workload: str, rows: int = 300, generations: int = 4):
    """(BENCHMARK.json, config, traffic) of ``workload`` at a tiny size."""
    bench = run.manifest()
    _, config, traffic = run.resolve(bench, workload)
    config = copy.deepcopy(config)
    config.update(pop=8, generations=generations)
    for ds in config["datasets"]:
        ds["rows"] = rows
    traffic = dict(traffic)
    if traffic["driver"] == "serve":
        traffic.update(budgets={"2": 2, "4": 1}, segment_len=2,
                       outstanding=4, check_jobs=3)
    return bench, config, traffic


def cell(workload: str, seed: int = 2**31 + 7, **kw):
    bench, config, traffic = tiny(workload, **kw)
    return bench, run.driver(traffic).Cell(config, traffic, seed)


def execute(workload: str, seconds: float = 0.5, trace: bool = False,
            seed: int = 2**31 + 7, **kw) -> dict:
    """One run of ``workload`` on the CPU, past the harness's chip check."""
    import jax

    t0 = time.perf_counter()
    bench, c = cell(workload, seed, **kw)
    out, _ = run.execute(bench, workload, c, jax.devices(), seconds, trace,
                         t0)
    return out

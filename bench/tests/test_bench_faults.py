"""A run with the timed path broken underneath comes out not correct:
the harness's chip check is skipped and the rest of a run is driven on
the CPU at a tiny size, once per fault and cell."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402
from repro.core import engine  # noqa: E402

CELLS = ["pendigits.search", "cardio.robust_k8", "cardio.serve_mixed"]


def state_unchanged(monkeypatch):
    """Every generation returns the state it was given."""
    def frozen(problem, state, active=None):
        return state, (state.obj[:, 0].min(), state.obj[:, 1].min(),
                       jnp.int32(0), jnp.int32(0))
    monkeypatch.setattr(engine, "generation", frozen)


def half_the_rows(monkeypatch):
    """Fitness counts the first half of the training rows and takes the
    accuracy over them alone."""
    counts = engine.population_counts

    def half(problem, pop, n_valid=None):
        n = problem.labels.shape[0]
        keep = jnp.arange(n) < problem.n_valid_samples // 2
        p = dataclasses.replace(problem,
                                labels=jnp.where(keep, problem.labels, -1))
        return counts(p, pop, n_valid) * 2
    monkeypatch.setattr(engine, "population_counts", half)


def answer_altered(monkeypatch):
    """The front's first area is off by one adder where it is made."""
    front_of = engine.front_of

    def altered(state):
        front = front_of(state)
        front["objectives"] = front["objectives"].copy()
        front["objectives"][0, 1] += 1
        return front
    monkeypatch.setattr(engine, "front_of", altered)


@pytest.mark.parametrize("fault", [state_unchanged, half_the_rows,
                                   answer_altered])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_path_is_not_correct(workload, fault, monkeypatch):
    jax.clear_caches()
    fault(monkeypatch)
    out = bench_tiny.execute(workload, rows=96, generations=2)
    jax.clear_caches()
    assert not out["correct"], out["compared"]

"""Property suite: the O(P log P) sweep ranking vs the dominance-matrix
oracle (and the python peel reference) on adversarial populations —
duplicate objective rows, one-axis ties, arbitrary feasible/infeasible
mixes with equal violations. Rank, crowding and survivor selection must
all be bit-identical; see test_ranking_path.py for the hypothesis-free
edge cases and whole-run equivalences."""
import numpy as np
import pytest
import jax.numpy as jnp

pytest.importorskip("hypothesis")  # property tests need hypothesis
from hypothesis import given, settings, strategies as st

from repro.core.nsga2 import dominance_matrix, nondominated_rank
from repro.kernels.pop_ranking import (population_ranking,
                                       rank_select_rerank, sweep_rank)


# allow_subnormal=False: the jax CPU backend enables FTZ globally, which
# trips hypothesis's subnormal sanity check. width=32: the draws become
# float32 arrays, so they must not be float32 subnormals either (and the
# bounds must be float32 values).
def _f(lo, hi):
    return st.floats(float(np.float32(lo)), float(np.float32(hi)),
                     allow_nan=False, allow_subnormal=False, width=32)


# continuous objectives: ties are rare, fronts are thin
smooth = st.lists(st.tuples(_f(0, 1), _f(0, 100), _f(0, 0.2)),
                  min_size=1, max_size=40)
# quantised objectives/violations: duplicate rows, axis ties and equal
# violations are the common case, exercising every tie rule at once
grid = st.lists(st.tuples(st.integers(0, 4), st.integers(0, 4),
                          st.integers(0, 3)),
                min_size=1, max_size=40)


def _matrix_rank(obj, viol):
    return np.asarray(nondominated_rank(dominance_matrix(obj, viol)))


def _check_equal(obj, viol):
    obj, viol = jnp.asarray(obj), jnp.asarray(viol)
    want = _matrix_rank(obj, viol)
    got = np.asarray(sweep_rank(obj, viol))
    np.testing.assert_array_equal(want, got)
    return obj, viol, want


@given(smooth)
@settings(max_examples=60, deadline=None)
def test_sweep_rank_matches_matrix_smooth(rows):
    arr = np.asarray(rows, np.float32)
    _check_equal(arr[:, :2], arr[:, 2] - 0.1)   # mix feasible/infeasible


@given(grid)
@settings(max_examples=60, deadline=None)
def test_sweep_rank_matches_matrix_ties(rows):
    arr = np.asarray(rows, np.float32)
    obj = arr[:, :2] / 4.0
    viol = np.maximum(arr[:, 2] - 1.0, 0.0)     # many exactly-equal layers
    _check_equal(obj, viol)


@given(grid)
@settings(max_examples=30, deadline=None)
def test_ranking_and_survivors_match(rows):
    """Downstream of equal ranks everything else must follow: crowding,
    the dispatcher's (rank, crowd) pair, and the full
    rank→select→re-rank tail of a (μ+λ) generation."""
    arr = np.asarray(rows, np.float32)
    obj = jnp.asarray(arr[:, :2] / 4.0)
    viol = jnp.asarray(np.maximum(arr[:, 2] - 1.0, 0.0))
    rank_m, crowd_m = population_ranking(obj, viol, backend="matrix")
    rank_s, crowd_s = population_ranking(obj, viol, backend="sweep")
    np.testing.assert_array_equal(np.asarray(rank_m), np.asarray(rank_s))
    np.testing.assert_array_equal(np.asarray(crowd_m), np.asarray(crowd_s))
    mu = max(1, obj.shape[0] // 2)
    tail_m = rank_select_rerank(obj, viol, mu, backend="matrix")
    tail_s = rank_select_rerank(obj, viol, mu, backend="sweep")
    for a, b, what in zip(tail_m, tail_s, ("keep", "rank", "crowd")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"survivor {what} differs")


@given(grid)
@settings(max_examples=30, deadline=None)
def test_sweep_rank_properties(rows):
    """Structural invariants, independent of the oracle: every front
    0..max is populated, feasible always outrank infeasible, and equal
    objective rows (same feasibility) share a front."""
    arr = np.asarray(rows, np.float32)
    obj = arr[:, :2] / 4.0
    viol = np.maximum(arr[:, 2] - 1.0, 0.0)
    rank = np.asarray(sweep_rank(jnp.asarray(obj), jnp.asarray(viol)))
    assert set(rank.tolist()) == set(range(rank.max() + 1))
    feas = viol <= 0
    if feas.any() and (~feas).any():
        assert rank[feas].max() < rank[~feas].min()
    for i in range(len(obj)):
        same = (obj == obj[i]).all(axis=1) & (viol == viol[i])
        assert (rank[same] == rank[i]).all()

"""NSGA-II primitives vs an O(n²) python reference (property-based)."""
import numpy as np
import pytest
import jax.numpy as jnp
pytest.importorskip("hypothesis")  # property tests need hypothesis
from hypothesis import given, settings, strategies as st

from repro.core.nsga2 import (dominance_matrix,
                              nondominated_rank,
                              crowding_distance,
                              tournament_select,
                              survivor_select)


def ref_rank(obj, viol):
    """Classic front-peeling reference."""
    P = len(obj)
    feas = viol <= 0

    def dom(i, j):
        if feas[i] and not feas[j]:
            return True
        if not feas[i] and not feas[j]:
            return viol[i] < viol[j]
        if feas[i] and feas[j]:
            return (np.all(obj[i] <= obj[j]) and np.any(obj[i] < obj[j]))
        return False

    rank = np.full(P, -1)
    r = 0
    remaining = set(range(P))
    while remaining:
        front = [i for i in remaining
                 if not any(dom(j, i) for j in remaining if j != i)]
        assert front, "cycle in dominance?"
        for i in front:
            rank[i] = r
            remaining.discard(i)
        r += 1
    return rank


# allow_subnormal=False: the jax CPU backend enables FTZ globally, which
# trips hypothesis's subnormal sanity check. width=32: the draws become
# float32 arrays, so they must not be float32 subnormals either (and the
# bounds must be float32 values).
def _f(lo, hi):
    return st.floats(float(np.float32(lo)), float(np.float32(hi)),
                     allow_nan=False, allow_subnormal=False, width=32)


objs = st.lists(st.tuples(_f(0, 1), _f(0, 100), _f(0, 0.2)),
                min_size=3, max_size=24)


@given(objs)
@settings(max_examples=40, deadline=None)
def test_rank_matches_reference(rows):
    arr = np.asarray(rows, np.float32)
    obj, viol = arr[:, :2], arr[:, 2] - 0.1   # mix feasible/infeasible
    dom = dominance_matrix(jnp.asarray(obj), jnp.asarray(viol))
    rank = np.asarray(nondominated_rank(dom))
    want = ref_rank(obj.astype(np.float64), viol.astype(np.float64))
    np.testing.assert_array_equal(rank, want)


@given(objs)
@settings(max_examples=25, deadline=None)
def test_dominance_is_strict_partial_order(rows):
    arr = np.asarray(rows, np.float32)
    dom = np.asarray(dominance_matrix(jnp.asarray(arr[:, :2]),
                                      jnp.asarray(arr[:, 2] * 0)))
    assert not np.any(np.diag(dom))
    assert not np.any(dom & dom.T), "antisymmetry violated"


def test_crowding_boundaries_infinite():
    obj = jnp.asarray([[0.0, 5.0], [0.5, 3.0], [1.0, 1.0]])
    rank = jnp.zeros(3, jnp.int32)
    d = crowding_distance(obj, rank)
    assert np.isinf(float(d[0])) and np.isinf(float(d[2]))
    assert np.isfinite(float(d[1]))


def test_survivor_prefers_lower_rank():
    rank = jnp.asarray([1, 0, 2, 0])
    crowd = jnp.asarray([9.0, 0.1, 9.0, 0.2])
    keep = np.asarray(survivor_select(rank, crowd, 2))
    assert set(keep.tolist()) == {1, 3}


def test_tournament_prefers_dominant(key):
    rank = jnp.asarray([0] + [5] * 63)
    crowd = jnp.ones(64)
    sel = np.asarray(tournament_select(key, rank, crowd, 512))
    # individual 0 must win every tournament it joins
    freq0 = (sel == 0).mean()
    assert freq0 > 1.5 / 64

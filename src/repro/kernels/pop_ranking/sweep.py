"""O(P log P) sweep-based constrained 2-objective NSGA-II front ranking.

The matrix oracle in ``repro.core.nsga2`` builds the O(P²M) constrained
dominance matrix and peels fronts with a *data-dependent* ``while_loop``
(one iteration per front). Both hurt at scale: converged pools peel
hundreds of fronts, and under ``vmap`` the peel's trip count is the max
front count over all batch lanes — one converged lane stalls every cell of
a ``run_batch``/``run_grid``/``run_suite`` dispatch. With exactly M=2
objectives the Jensen/Kung sort-and-sweep construction applies instead,
and Deb's constrained-dominance rules reduce onto the same sweep:

* **Feasible individuals** (``viol <= 0``) dominate among themselves by
  plain Pareto dominance, and are never dominated by infeasible ones, so
  their peel ranks equal the standalone 2-objective non-dominated sort of
  the feasible subset. Sort lexicographically by (obj₀ ↑, obj₁ ↑) and map
  each point to an integer ``key`` that orders by (obj₁, obj₀) with equal
  objective pairs *sharing* a key. For j before i in the sort order

      j dominates i  ⟺  key_j < key_i

  (obj₁ⱼ < obj₁ᵢ gives both sides, since obj₀ⱼ ≤ obj₀ᵢ by sort order;
  equal obj₁ falls through to obj₀ where strictness means a strictly
  better obj₀; exact duplicates share the key and dominate nothing).
  The front index of a point is the length of the longest dominance
  chain ending at it, so the pass is patience sorting on ``key``:
  maintain the staircase ``M[r]`` = minimum key already placed on front
  ``r`` (strictly increasing in ``r`` — a front-r+1 point always has a
  front-r dominator of strictly smaller key), and each point's front is
  the count of staircase cells strictly below its key — the fronts of
  its dominators are exactly 0..rank−1 because dominance is transitive
  along each dominator's own chain. The count is a vectorised
  compare-and-sum, which beats a per-step binary search on CPU; ``M`` is
  then min-updated at the front just assigned. Duplicates need no
  special case: equal keys see the same cells strictly below them.
* **Infeasible individuals** are dominated by every feasible one and by
  every infeasible one of strictly smaller violation, so they peel as
  violation layers *after* all feasible fronts: rank = (number of
  feasible fronts) + (dense rank of the violation among infeasible
  violations). Equal violations share a layer — none dominates another
  and their dominator sets coincide.

Everything is fixed-shape — one lexsort, one key sort, one length-P
``lax.scan`` whose body is an O(P) compare-and-sum plus a one-element
scatter, and a cumulative sum — so the pass vmaps and shard_maps with
*no* cross-lane trip-count coupling, and the ranks are bit-identical to
``nsga2.nondominated_rank`` (they are the same integers; the hypothesis
suite in tests/test_ranking_sweep.py pins the equivalence, and
tests/test_ranking_path.py pins it through whole runs). The scan is the
sequential core — the front index is the longest strictly-increasing
subsequence of ``key`` ending at each element, an inherently
left-to-right computation — but each step is branch-free SIMD work.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_IMAX = jnp.int32(2 ** 31 - 1)


def _sort_and_key(obj: jnp.ndarray, viol: jnp.ndarray):
    """Feasible-first (k1, k2) lexsort + the int32 dominance key.

    Infeasible rows use (viol, viol) as their sort pair so equal
    violations land adjacent (their dense layering is read off the sorted
    k1 column); their ``key`` entries are never consumed by the scan.
    """
    P = obj.shape[0]
    feas = viol <= 0.0
    v = viol.astype(jnp.float32)
    k1 = jnp.where(feas, obj[:, 0].astype(jnp.float32), v)
    k2 = jnp.where(feas, obj[:, 1].astype(jnp.float32), v)
    order = jnp.lexsort((k2, k1, ~feas))
    k1s, k2s, fs = k1[order], k2[order], feas[order]
    # Equal (k1, k2) rows are adjacent after the sort, so a boundary
    # cumsum yields a dense pair id — no second lexsort.
    newpair = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         ((k1s[1:] != k1s[:-1]) | (k2s[1:] != k2s[:-1])).astype(jnp.int32)])
    pair_id = jnp.cumsum(newpair)
    # First-occurrence index of each k2 value: a monotone, tie-preserving
    # integer image of k2. key = (k2 digit, pair id) then orders by
    # (k2, k1): within equal k2 the pair id grows with k1 (it was
    # assigned in (k1, k2) order), and equal pairs share both digits.
    # Bounded by P(P+1)+P, int32-safe for P < 46 000.
    f2 = jnp.searchsorted(jnp.sort(k2s), k2s, side="left").astype(jnp.int32)
    key = f2 * jnp.int32(P + 1) + pair_id
    return k1s, fs, key, order


def sweep_rank(obj: jnp.ndarray, viol: jnp.ndarray, *,
               kernel: bool = False, interpret: bool = False) -> jnp.ndarray:
    """Constrained non-dominated front index per individual (0 = best).

    obj: (P, 2) to-minimize objectives; viol: (P,) violation (≤ 0 means
    feasible). Returns (P,) int32 ranks equal to
    ``nsga2.nondominated_rank(nsga2.dominance_matrix(obj, viol))``.
    ``kernel`` runs the staircase pass as one Pallas call
    (``pop_ranking.kernel``; ``interpret`` for its interpret mode) instead
    of the ``lax.scan``; the ranks are the same.
    """
    P, M = obj.shape
    if M != 2:
        raise ValueError(f"sweep ranking is 2-objective only, got M={M}")
    k1s, fs, key, order = _sort_and_key(obj, viol)

    def step(staircase, x):
        k, f = x
        r = jnp.sum((staircase < k).astype(jnp.int32))
        staircase = jnp.where(f, staircase.at[r].min(k), staircase)
        return staircase, r

    if kernel:
        from .kernel import staircase_fronts

        ranks_f = staircase_fronts(jnp.where(fs, key, _IMAX),
                                   interpret=interpret)
    else:
        m0 = jnp.full((P,), _IMAX)
        _, ranks_f = jax.lax.scan(step, m0, (key, fs), unroll=16)

    # infeasible layers start after the last feasible front
    prev_k1 = jnp.concatenate([k1s[:1], k1s[:-1]])
    prev_f = jnp.concatenate([jnp.array([False]), fs[:-1]])
    n_fronts = jnp.max(jnp.where(fs, ranks_f, -1)) + 1
    first = jnp.arange(P) == 0
    new_layer = ~fs & (first | prev_f | (k1s != prev_k1))
    layer = jnp.cumsum(new_layer.astype(jnp.int32)) - 1
    rank_s = jnp.where(fs, ranks_f, n_fronts + layer)
    return jnp.zeros((P,), jnp.int32).at[order].set(rank_s)


def sweep_ranking(obj: jnp.ndarray, viol: jnp.ndarray, *,
                  kernel: bool = False, interpret: bool = False):
    """(rank, crowd) via the sweep — the fast-path twin of
    ``nsga2.evaluate_ranking`` (crowding is shared: identical ranks give
    identical distances)."""
    from ...core.nsga2 import crowding_distance

    rank = sweep_rank(obj, viol, kernel=kernel, interpret=interpret)
    return rank, crowding_distance(obj, rank)

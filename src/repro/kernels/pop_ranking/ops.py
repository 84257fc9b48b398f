"""Public op: constrained NSGA-II ranking with backend dispatch.

The ranking counterpart of ``pop_mlp.population_correct`` (fitness),
``pop_variation.population_variation`` (variation) and
``pop_generation.population_generation`` (the whole step): every rank /
crowding / survivor computation in the engine routes through here,
selected by ``GAConfig.ranking_backend``.

Backends:
  "auto"   — the O(P log P) sort-and-sweep (fixed-shape; the default
             everywhere — ranking has no TPU-vs-CPU split)
  "sweep"  — the sweep, explicitly (``pop_ranking.sweep``)
  "matrix" — the O(P²) dominance-matrix + bounded front-peel oracle of
             ``repro.core.nsga2`` (seed semantics, kept as the
             equivalence reference)

Both backends produce bit-identical results — the front index of an
individual is a well-defined integer, the sweep computes the same
integers without materialising the O(P²) matrix or running the
data-dependent peel loop, and crowding/survivor selection are shared
downstream of the ranks (tests/test_ranking_path.py,
tests/test_ranking_sweep.py). The matrix path's one structural advantage
is kept too: its (μ+λ) re-rank reuses the combined pool's dominance
matrix (``nsga2.subset_ranking``), while the sweep simply re-sweeps the
μ survivors — cheaper than one peel iteration of the matrix oracle.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...core.nsga2 import (crowding_distance, dominance_matrix,
                           evaluate_ranking, ranking_from_dom,
                           subset_ranking, survivor_select)
from .sweep import sweep_rank, sweep_ranking
from ..policy import BACKEND_CHOICES

BACKENDS = BACKEND_CHOICES["ranking"]


def _resolve(backend: str | None) -> str:
    if backend is None or backend == "auto":
        return "sweep"
    if backend not in BACKENDS:
        raise ValueError(f"unknown ranking backend {backend!r}; "
                         f"want {BACKENDS}")
    return backend


def fold_objectives(obj):
    """(N, 3) [nominal err, area, robust err] → (N, 2) exact
    lexicographic fold; (N, 2) passes through untouched.

    The device-variation MC fitness adds a robustness column
    (``engine.objectives``); both ranking backends are 2-objective
    machines, so the error pair folds into ONE float32 key:
    ``dense_rank(e_nom) * N + dense_rank(e_rob)``. Dense ranks are
    integers < N, so for N ≤ 4096 the composite is ≤ N²−1 ≤ 2²⁴−1 —
    exactly representable in float32, making the fold *exact*: composite
    order is precisely the lexicographic (e_nom, then e_rob) order, and
    composite equality is pairwise equality. Dominance on
    [composite, area] therefore treats robustness as the error
    tie-breaker next to the area trade-off. The fold is applied once at
    the entry of both public ops, so the sweep and matrix backends see
    the same (N, 2) input and stay bit-identical to each other —
    including the crowding distances, which are computed on the folded
    columns.
    """
    if obj.shape[-1] == 2:
        return obj
    if obj.shape[-1] != 3:
        raise ValueError(f"ranking expects 2 or 3 objectives, got "
                         f"M={obj.shape[-1]}")
    n = obj.shape[0]
    if n > 4096:
        raise ValueError(f"the 3-objective fold is float32-exact only for "
                         f"pools of at most 4096, got {n}")

    def dense(col):
        return jnp.searchsorted(jnp.sort(col), col,
                                side="left").astype(jnp.int32)

    comp = (dense(obj[:, 0]) * n + dense(obj[:, 2])).astype(jnp.float32)
    return jnp.stack([comp, obj[:, 1]], axis=-1)


def population_ranking(obj, viol, *, backend: str | None = None):
    """(P, 2|3) objectives + (P,) violations → ((P,) rank, (P,) crowd).

    A third objective column (robust error, device-variation MC fitness)
    is folded lexicographically first — see :func:`fold_objectives`."""
    obj = fold_objectives(obj)
    if _resolve(backend) == "sweep":
        return sweep_ranking(obj, viol)
    return evaluate_ranking(obj, viol)


def rank_select_rerank(obj, viol, mu: int, *, backend: str | None = None):
    """The whole (μ+λ) ranking tail: rank the pool, pick the top-``mu``
    survivors by (rank ↑, crowding ↓), and re-rank the survivor subset.

    Returns (keep, rank, crowd) with keep (mu,) int32 pool indices and
    rank/crowd (mu,) the *subset* ranking of the survivors (constrained
    dominance is pairwise, so re-ranking the subset directly equals
    slicing the pool matrix — ``nsga2.subset_ranking``). A 3-objective
    pool is folded ONCE at entry (:func:`fold_objectives`) and the folded
    pair is used throughout — pool rank, survivor re-rank and crowding —
    on both backends alike.
    """
    obj = fold_objectives(obj)
    if _resolve(backend) == "sweep":
        rank, crowd = sweep_ranking(obj, viol)
        keep = survivor_select(rank, crowd, mu)
        rank2 = sweep_rank(obj[keep], viol[keep])
        return keep, rank2, crowding_distance(obj[keep], rank2)
    dom = dominance_matrix(obj, viol)
    rank, crowd = ranking_from_dom(dom, obj)
    keep = survivor_select(rank, crowd, mu)
    rank2, crowd2 = subset_ranking(dom, obj, keep)
    return keep, rank2, crowd2

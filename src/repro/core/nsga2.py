"""Vectorised NSGA-II primitives (Deb et al. 2002) — paper §IV-A.

Everything operates on whole populations as arrays and is jit/vmap/shard_map
compatible:

  * constrained-dominance matrix (feasibility-first, Deb's rules),
  * non-dominated sorting by iterative front peeling (bounded while_loop),
  * crowding distance computed *globally* with a single lexsort per objective
    (neighbours within the same front; boundaries get +inf),
  * binary tournament selection on (rank ↑, crowding ↓),
  * (μ+λ) survivor truncation by (rank ↑, crowding ↓).

The 10 % accuracy-loss feasibility bound of the paper enters through the
violation vector ``viol`` (0 = feasible).

The dominance-matrix + front-peel pair here is the O(P²) *oracle* path of
the ``repro.kernels.pop_ranking`` dispatcher
(``GAConfig.ranking_backend="matrix"``); the default sweep computes
identical ranks in O(P log P) fixed-shape sorts and a staircase pass
(a ``lax.scan``, or one Pallas call on TPU).
Crowding, tournament and survivor selection are shared by both backends.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dominance_matrix(obj: jnp.ndarray, viol: jnp.ndarray) -> jnp.ndarray:
    """dom[i, j] = True iff i constrained-dominates j.

    obj: (P, M) to-minimize objectives; viol: (P,) constraint violation ≥ 0.
    """
    feas = viol <= 0.0
    le = jnp.all(obj[:, None, :] <= obj[None, :, :], axis=-1)
    lt = jnp.any(obj[:, None, :] < obj[None, :, :], axis=-1)
    obj_dom = le & lt
    fi = feas[:, None]
    fj = feas[None, :]
    vi = viol[:, None]
    vj = viol[None, :]
    dom = (fi & ~fj) | (~fi & ~fj & (vi < vj)) | (fi & fj & obj_dom)
    return dom & ~jnp.eye(obj.shape[0], dtype=bool)


def nondominated_rank(dom: jnp.ndarray) -> jnp.ndarray:
    """Front index per individual (0 = best) by peeling zero-indegree nodes.

    The peel body is a float32 vector-matrix product (BLAS gemv) instead of
    a bool mask-and-reduce: converged pools peel hundreds of fronts per
    generation, and the O(P²) body dominated the NSGA-II cost of the fitness
    hot loop. Counts stay ≤ P < 2²⁴ so float32 arithmetic is integer-exact —
    ranks are bit-identical to the bool formulation.

    The loop is bounded at P iterations (every front holds at least one
    individual, so at most P peels rank everyone; the cycle-free dominance
    relation alone guarantees termination, but the traced cond carries the
    explicit ``r < P`` bound so the loop is provably finite in the HLO
    too). This matrix path is the seed-semantics oracle of the
    ``repro.kernels.pop_ranking`` dispatcher; the default "sweep" backend
    computes the same ranks in O(P log P) fixed-shape ops — see
    ``pop_ranking.sweep``."""
    P = dom.shape[0]
    UNRANKED = P
    domf = dom.astype(jnp.float32)

    def cond(carry):
        rank, _, r = carry
        return jnp.any(rank == UNRANKED) & (r < P)

    def body(carry):
        rank, n_dominators, r = carry
        front = (n_dominators == 0.0) & (rank == UNRANKED)
        rank = jnp.where(front, r, rank)
        removed = front.astype(jnp.float32) @ domf
        n_dominators = jnp.where(front, jnp.float32(P + 1),
                                 n_dominators - removed)
        return rank, n_dominators, r + 1

    rank0 = jnp.full((P,), UNRANKED, jnp.int32)
    nd0 = jnp.sum(domf, axis=0)
    rank, _, _ = jax.lax.while_loop(cond, body, (rank0, nd0, jnp.int32(0)))
    return rank


def crowding_distance(obj: jnp.ndarray, rank: jnp.ndarray) -> jnp.ndarray:
    """Crowding distance with per-front normalisation, fully vectorised."""
    P, M = obj.shape
    dist = jnp.zeros((P,), jnp.float32)
    big = jnp.float32(jnp.inf)
    for m in range(M):
        key = obj[:, m].astype(jnp.float32)
        order = jnp.lexsort((key, rank))
        skey = key[order]
        srank = rank[order]
        same_prev = jnp.concatenate([jnp.array([False]), srank[1:] == srank[:-1]])
        same_next = jnp.concatenate([srank[1:] == srank[:-1], jnp.array([False])])
        prev_val = jnp.concatenate([skey[:1], skey[:-1]])
        next_val = jnp.concatenate([skey[1:], skey[-1:]])
        fmin = jax.ops.segment_min(key, rank, num_segments=P + 1)
        fmax = jax.ops.segment_max(key, rank, num_segments=P + 1)
        denom = jnp.maximum((fmax - fmin)[srank], 1e-12)
        contrib = jnp.where(same_prev & same_next,
                            (next_val - prev_val) / denom, big)
        dist = dist.at[order].add(contrib)
    return dist


def ranking_from_dom(dom: jnp.ndarray, obj: jnp.ndarray):
    """(rank, crowd) from a precomputed dominance matrix."""
    rank = nondominated_rank(dom)
    crowd = crowding_distance(obj, rank)
    return rank, crowd


def evaluate_ranking(obj: jnp.ndarray, viol: jnp.ndarray):
    return ranking_from_dom(dominance_matrix(obj, viol), obj)


def subset_ranking(dom: jnp.ndarray, obj: jnp.ndarray, keep: jnp.ndarray):
    """Re-rank the ``keep`` subset without recomputing dominance.

    Constrained dominance is pairwise, so ``dom[keep][:, keep]`` equals
    ``dominance_matrix(obj[keep], viol[keep])`` exactly — the (μ+λ)
    survivor re-ranking reuses the combined pool's O(P²M) matrix instead
    of rebuilding it (the second-biggest cost of a generation after
    fitness)."""
    return ranking_from_dom(dom[keep][:, keep], obj[keep])


def tournament_select(key, rank, crowd, n: int) -> jnp.ndarray:
    """Binary tournaments → (n,) parent indices."""
    P = rank.shape[0]
    idx = jax.random.randint(key, (n, 2), 0, P)
    a, b = idx[:, 0], idx[:, 1]
    a_wins = (rank[a] < rank[b]) | ((rank[a] == rank[b]) & (crowd[a] > crowd[b]))
    return jnp.where(a_wins, a, b)


def survivor_select(rank, crowd, mu: int) -> jnp.ndarray:
    """Top-μ indices by (rank ↑, crowding ↓)."""
    order = jnp.lexsort((-crowd, rank))
    return order[:mu]

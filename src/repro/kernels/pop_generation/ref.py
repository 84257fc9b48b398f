"""Fused jnp generation step — the CPU/GPU fast path of the
``population_generation`` dispatcher.

One NSGA-II (μ+λ) generation as a single traced region: variation
(through the ``population_variation`` dispatcher) → duplicate-suppressed
fitness → dominance ranking → survivor selection. ``use_cache=True`` (the
"ref" backend) routes the fitness through the cross-generation
:class:`~repro.core.dedup.EvalCache` carried in ``GAState`` — children
identical to any chromosome evaluated earlier in the run reuse its integer
correct count and the packed evaluation batch shrinks accordingly (the
``n_valid`` tile skip makes the saving real). ``use_cache=False`` (the
"phases" backend) is the per-phase oracle chain of earlier revisions:
within-generation dedup only, the cache (if any) carried through untouched.

Both paths produce bit-identical states: cached values are exact integer
counts, so *which* rows skip evaluation can never change a result, only
its cost — the float objective chain always runs on the same ints.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dedup import dedup_eval
from ..pop_ranking import rank_select_rerank
from ..pop_variation import population_variation


def _rank_and_select(state, pop, counts, c_obj, c_viol, key, cache,
                     n_eval, n_hit, backend=None):
    """Shared (μ+λ) tail: rank the pool, keep the best P, emit aux.

    The ranking itself goes through the ``pop_ranking`` dispatcher —
    the O(P log P) sweep by default, the dominance-matrix oracle on
    ``backend="matrix"`` — with bit-identical survivors either way."""
    P = state.pop.shape[0]
    with jax.named_scope("ga.objectives"):
        obj = jnp.concatenate([state.obj, c_obj], axis=0)
        viol = jnp.concatenate([state.viol, c_viol], axis=0)
    with jax.named_scope("ga.rank"):
        keep, rank2, crowd2 = rank_select_rerank(obj, viol, P,
                                                 backend=backend)
    with jax.named_scope("ga.survive"):
        new = type(state)(pop[keep], obj[keep], viol[keep], rank2, crowd2,
                          counts[keep], key, state.gen + 1, cache)
        aux = (new.obj[:, 0].min(), new.obj[:, 1].min(), n_eval, n_hit)
    return new, aux


def pop_generation_jnp(problem, state, use_cache: bool = True, active=None):
    """One generation, fused jnp — see module docstring.

    Returns (new_state, (best_err, best_area, n_eval, n_hit)).

    ``active`` (optional () bool): the serve path's retirement gate — an
    inactive lane contributes zero rows to the shared dedup evaluation
    bound and leaves its EvalCache bitwise untouched; its returned state
    is garbage the caller (``engine._budgeted_generation``) discards via
    where-select.
    """
    from ...core import engine  # lazy: engine dispatches back into us

    cfg = problem.cfg
    P = state.pop.shape[0]
    with jax.named_scope("ga.variation"):
        key, k_off = jax.random.split(state.key)
        children = population_variation(
            k_off, state.pop, state.rank, state.crowd, genes=problem.genes,
            pc=problem.crossover_rate, pm=problem.mutation_rate_gene,
            backend=cfg.backends.variation, pop_tile=cfg.pop_tile)
    with jax.named_scope("ga.objectives"):
        pop = jnp.concatenate([state.pop, children], axis=0)

    mode = engine.dedup_mode(cfg)
    cache = state.cache
    n_hit = jnp.int32(0)

    def eval_fn(rows, n):
        with jax.named_scope("ga.fitness"):
            return engine.population_counts(problem, rows, n)

    if mode != "off":
        with jax.named_scope("ga.cache"):
            if mode == "cache" and use_cache and cache is not None:
                # children duplicating a parent, each other, or ANY
                # chromosome evaluated earlier in the run reuse cached
                # integer counts
                counts, n_eval, n_hit, cache = dedup_eval(
                    eval_fn, pop, known=state.counts,
                    axis_name=cfg.batch_axis,
                    gene_mask=problem.genes.valid, cache=cache,
                    gen=state.gen + 1, ids=problem.genes.ids, active=active)
            else:
                # within-generation dedup only (the legacy/oracle path)
                counts, n_eval = dedup_eval(
                    eval_fn, pop, known=state.counts,
                    axis_name=cfg.batch_axis,
                    gene_mask=problem.genes.valid, ids=problem.genes.ids,
                    active=active)
        with jax.named_scope("ga.objectives"):
            c_obj, c_viol = engine.objectives(
                problem, children,
                engine.counts_accuracy(problem, counts[P:]))
    else:
        # dedup off: counts are unused placeholders — match the state's
        # count shape, which grows a K column under device-variation MC
        counts = jnp.zeros((2 * P,) + state.counts.shape[1:], jnp.int32)
        with jax.named_scope("ga.fitness"):
            c_obj, c_viol = engine.fitness(problem, children)
        n_eval = (jnp.int32(P) if active is None
                  else jnp.where(active, P, 0).astype(jnp.int32))
    return _rank_and_select(state, pop, counts, c_obj, c_viol, key, cache,
                            n_eval, n_hit, backend=cfg.backends.ranking)

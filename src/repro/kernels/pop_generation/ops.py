"""Public op: one full NSGA-II generation with backend dispatch.

This is the single entry point ``engine.generation`` routes through — the
whole-step counterpart of ``pop_mlp.population_correct`` (fitness) and
``pop_variation.population_variation`` (variation). See
``GAConfig.generation_backend``.

Backends:
  "auto"      — megakernel on TPU, fused jnp path elsewhere (default)
  "kernel"    — Pallas variation+fitness megakernel, compiled
  "interpret" — the megakernel in interpret mode (CPU validation)
  "ref"       — fused jnp generation with the cross-generation EvalCache
                (the CPU fast path; see ``repro.core.dedup``)
  "phases"    — the per-phase oracle chain (variation dispatcher → legacy
                within-generation dedup → ranking), cache untouched

All backends produce bit-identical GAStates: the megakernel addresses the
identical Threefry counters and accumulates the identical integer counts
as the per-phase chain, and the cache only changes *which* rows are
evaluated, never their values. The accounting aux differs by design —
the kernel path evaluates every child (n_eval = P, n_hit = 0: it wins by
fusing the phases in VMEM, not by skipping rows), the ref path reports
genuine evaluations and cache hits. The kernel path carries the cache
through untouched; cross-generation skipping is the XLA path's win
(tile-skip on packed misses), fusion is the TPU path's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.genome import _slot_keys
from ...core.nsga2 import tournament_select
from ...core.operators import variation_keys
from ..pop_variation.ops import _VARIATION_SLOTS
from .ref import pop_generation_jnp, _rank_and_select
from .kernel import pop_generation_kernel
from ..policy import BACKEND_CHOICES

BACKENDS = BACKEND_CHOICES["generation"]


def _generation_kernel(problem, state, interpret: bool, active=None):
    """Megakernel path: parent gather in XLA, variation+fitness fused in
    one pallas_call, ranking through the ``pop_ranking`` dispatcher
    (honouring ``GAConfig.ranking_backend``) — all inside the caller's
    jit."""
    from ...core import engine  # lazy: engine dispatches back into us

    cfg = problem.cfg
    t = problem.genes
    P = state.pop.shape[0]
    if P % 2:
        raise ValueError(f"variation needs an even population, got {P}")
    # named scopes group the XLA phases around the kernel in a profile;
    # the kernel stays outside them, found by its own name
    with jax.named_scope("ga.parents"):
        key, k_off = jax.random.split(state.key)
        k_sel, k_cx, k_var = variation_keys(k_off)
        var_keys = _slot_keys(k_var, _VARIATION_SLOTS)
        parents = tournament_select(k_sel, state.rank, state.crowd, P)
        pa = state.pop[parents[: P // 2]]
        pb = state.pop[parents[P // 2:]]
        do_cx = jax.random.uniform(k_cx, (P // 2,)) < problem.crossover_rate
        # child frame: row p < P/2 is pair p as (a=pa, b=pb); row P/2 + p
        # the same pair with roles flipped — see pop_variation.ops
        a_rows = jnp.concatenate([pa, pb], axis=0)
        b_rows = jnp.concatenate([pb, pa], axis=0)
        do_rows = jnp.concatenate([do_cx, do_cx])
    with jax.named_scope("ga.fitness"):
        n_samp = problem.n_valid_samples
        if cfg.batch_axis is not None:
            n_samp = jax.lax.pmax(n_samp, cfg.batch_axis)
        dev = (engine.device_deltas(problem) if engine.variation_on(cfg)
               else None)
    children, child_counts = pop_generation_kernel(
        a_rows, b_rows, do_rows, t.low, t.high, t.is_mask, t.mask_bits,
        t.ids, var_keys, problem.mutation_rate_gene, problem.x_int,
        problem.labels, spec=problem.spec, interpret=interpret,
        n_valid_samples=n_samp, out_mask=problem.out_mask, dev=dev)
    with jax.named_scope("ga.objectives"):
        pop = jnp.concatenate([state.pop, children], axis=0)
        if engine.dedup_mode(cfg) != "off":
            counts = jnp.concatenate([state.counts, child_counts])
        else:
            counts = jnp.zeros((2 * P,) + state.counts.shape[1:], jnp.int32)
        c_obj, c_viol = engine.objectives(
            problem, children, engine.counts_accuracy(problem, child_counts))
    # the megakernel evaluates every child regardless of ``active`` (its
    # win is VMEM fusion, not row skipping) — only the accounting is
    # gated, so a retired lane reports zero evaluations like the jnp path
    n_eval = (jnp.int32(P) if active is None
              else jnp.where(active, P, 0).astype(jnp.int32))
    return _rank_and_select(state, pop, counts, c_obj, c_viol, key,
                            state.cache, n_eval, jnp.int32(0),
                            backend=cfg.backends.ranking)


def population_generation(problem, state, *, backend=None, active=None):
    """(Problem, GAState) → (new GAState, aux) — ONE (μ+λ) generation.

    aux = (best_err, best_area, n_eval, n_hit). ``backend`` overrides
    ``problem.cfg.backends.generation``. ``active`` (optional () bool) is
    the serve path's per-lane retirement gate — see ``engine.generation``.
    """
    if backend is None:
        backend = problem.cfg.backends.generation
    if backend is None or backend == "auto":
        backend = "kernel" if jax.default_backend() == "tpu" else "ref"
    if backend == "ref":
        return pop_generation_jnp(problem, state, use_cache=True,
                                  active=active)
    if backend == "phases":
        return pop_generation_jnp(problem, state, use_cache=False,
                                  active=active)
    if backend in ("kernel", "interpret"):
        return _generation_kernel(problem, state,
                                  interpret=(backend == "interpret"),
                                  active=active)
    raise ValueError(f"unknown generation backend {backend!r}; "
                     f"want {BACKENDS}")

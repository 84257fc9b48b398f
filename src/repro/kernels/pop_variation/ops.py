"""Public op: fused GA variation with backend dispatch.

This is the single entry point the engine uses for the variation side of a
generation (tournament → crossover → mutation → clip) — the counterpart of
``pop_mlp.population_correct`` on the fitness side. See
``GAConfig.variation_backend``.

Backends:
  "auto"      — Pallas kernel on TPU, fused jnp path elsewhere (default)
  "kernel"    — Pallas kernel, compiled
  "interpret" — Pallas kernel, interpret mode (structural validation on CPU)
  "ref"       — fused jnp path: ONE counter-based Threefry pass for all
                gene-shaped draws + one elementwise region (the fast CPU path)
  "ops"       — the chained legacy operator calls in ``core.operators``
                (seed-semantics oracle; separate draw passes)

All backends are bit-identical: they share the key schedule
(``operators.variation_keys``) and the gene-addressed draw contract
(``genome.gene_uniform``), so fusing or splitting the passes cannot move
a bit — tests/test_variation_path.py asserts it backend against backend
and through whole ``GATrainer`` runs (the RNG contract itself is
property-tested in tests/test_variation.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.genome import (GenomeSpec, _slot_keys, SLOT_CROSS_SWAP,
                            SLOT_MUT_DO, SLOT_MUT_VAL)
from ...core.nsga2 import tournament_select
from ...core.operators import make_offspring, variation_keys
from .ref import pop_variation_ref
from .kernel import pop_variation_kernel
from ..policy import BACKEND_CHOICES

BACKENDS = BACKEND_CHOICES["variation"]

_VARIATION_SLOTS = (SLOT_CROSS_SWAP, SLOT_MUT_DO, SLOT_MUT_VAL)


def population_variation(key, pop, rank, crowd, *, genes, pc, pm,
                         backend=None, pop_tile: int = 64, interpret=None):
    """(P, G) population + ranking → (P, G) int32 children, one fused pass.

    key: the generation's offspring key (split internally via
        ``variation_keys``). pc / pm: crossover and per-gene mutation
        probabilities (traced ``Problem`` leaves or floats).
    genes: ``GeneTable`` (or a ``GenomeSpec``, whose identity table is
        used) — bounds, mask metadata and PRNG draw ids, all traced.
    pop_tile: population tile of the Pallas kernel path.
    """
    t = genes.table() if isinstance(genes, GenomeSpec) else genes
    if backend is None or backend == "auto":
        backend = "kernel" if jax.default_backend() == "tpu" else "ref"
    P = pop.shape[0]
    if P % 2:
        raise ValueError(f"variation needs an even population, got {P}")
    if backend == "ops":
        return make_offspring(key, pop, rank, crowd, t, pc, pm)
    k_sel, k_cx, k_var = variation_keys(key)
    parents = tournament_select(k_sel, rank, crowd, P)
    pa = pop[parents[: P // 2]]
    pb = pop[parents[P // 2:]]
    do_cx = jax.random.uniform(k_cx, (P // 2, 1)) < pc

    if backend == "ref":
        return pop_variation_ref(k_var, pa, pb, do_cx, t, pm)
    if backend == "kernel" or backend == "interpret":
        # child frame: row p < P/2 is pair p as (a=pa, b=pb); row P/2 + p
        # is the same pair with the roles flipped (uniform crossover's
        # complementary child) — the kernel re-addresses the swap draw by
        # p mod P/2, so both children of a pair see the same swap bits
        a_rows = jnp.concatenate([pa, pb], axis=0)
        b_rows = jnp.concatenate([pb, pa], axis=0)
        do_rows = jnp.concatenate([do_cx[:, 0], do_cx[:, 0]])
        return pop_variation_kernel(
            a_rows, b_rows, do_rows, t.low, t.high, t.is_mask, t.mask_bits,
            t.ids, _slot_keys(k_var, _VARIATION_SLOTS), pm, bp=pop_tile,
            interpret=(backend == "interpret" if interpret is None
                       else interpret))
    raise ValueError(f"unknown variation backend {backend!r}; "
                     f"want {BACKENDS}")

"""Public op: population fitness with backend dispatch.

This is the single entry point the trainers (GATrainer, islands) and the
benchmarks use for the fitness hot loop — see ``GAConfig.fitness_backend``.

Backends:
  "auto"      — Pallas kernel on TPU, tiled jnp path elsewhere (default)
  "kernel"    — Pallas kernel, compiled
  "interpret" — Pallas kernel, interpret mode (structural validation on CPU)
  "ref"       — sample/population-tiled jnp path (the fast CPU path)
  "jnp"       — untiled vmap oracle (seed semantics; no n_valid_rows skip)

``n_valid_rows`` (traced int32) enables the dedup fast path: rows past it
live in population tiles that are skipped outright ("ref", "kernel",
"interpret") and have unspecified counts. The "jnp" oracle evaluates
everything regardless.

``n_valid_samples`` (traced int32) is the sample-axis twin: tiles of
padded samples (suite batching pads every lane to the widest dataset;
padded labels are −1 and contribute zero counts) are skipped outright on
the tiled backends — bit-identical, the skipped tiles could only add
zero. The "jnp" oracle evaluates them.

``out_mask`` ((n_out,), traced) marks the valid output columns of a
padded-topology chromosome (suite batching): invalid columns are pinned to
INT32_MIN before the argmax on every backend, so a padded genome predicts
exactly like its unpadded original.
"""
from __future__ import annotations

import jax

from .kernel import pop_mlp_correct, pop_mlp_correct_mc
from .ref import (pop_mlp_correct_ref, pop_mlp_correct_tiled,
                  pop_mlp_correct_mc as pop_mlp_correct_mc_ref)
from ..policy import BACKEND_CHOICES

BACKENDS = BACKEND_CHOICES["fitness"]


def population_correct(pop, x_int, labels, *, spec, backend=None,
                       use_kernel=None, interpret=None,
                       pop_tile: int = 64, sample_tile: int = 256,
                       n_valid_rows=None, n_valid_samples=None,
                       out_mask=None, dev=None, gene_high=None):
    """(P, G) × (S, n_in) × (S,) → (P,) int32 correct counts.

    With ``dev`` ((K, G) int32 device-variation deltas,
    ``engine.device_deltas``) every chromosome is evaluated on all K
    perturbed device instances in one dispatch and the result is (P, K)
    per-instance counts instead; ``gene_high`` ((G,) exclusive upper
    bounds) bounds the perturbed exponents per gene. The "jnp" oracle has
    no instance axis and rejects ``dev``.

    ``pop_tile``/``sample_tile`` tile the "ref" path only: the Pallas
    kernels derive their tiles from the shapes (``kernel.sample_tile``).

    ``use_kernel``/``interpret`` are the legacy knobs (pre-dispatcher API)
    and take precedence over ``backend`` when given."""
    if use_kernel is not None:
        backend = "kernel" if use_kernel else "jnp"
        if use_kernel and interpret is None:
            interpret = jax.default_backend() != "tpu"
    if backend is None or backend == "auto":
        backend = "kernel" if jax.default_backend() == "tpu" else "ref"
    if dev is not None:
        if backend == "jnp":
            raise ValueError("the 'jnp' fitness oracle has no "
                             "device-instance axis; use ref/kernel/"
                             "interpret/auto for dev != None")
        if gene_high is None:
            raise ValueError("dev needs gene_high (per-gene exclusive "
                             "upper bounds, GeneTable.high)")
        if backend == "kernel" or backend == "interpret":
            return pop_mlp_correct_mc(
                pop, x_int, labels, dev, gene_high, spec=spec,
                interpret=(backend == "interpret" if interpret is None
                           else interpret),
                n_valid_rows=n_valid_rows, n_valid_samples=n_valid_samples,
                out_mask=out_mask)
        return pop_mlp_correct_mc_ref(
            pop, x_int, labels, spec=spec, dev=dev, gene_high=gene_high,
            pop_tile=pop_tile, sample_tile=sample_tile,
            n_valid_rows=n_valid_rows, n_valid_samples=n_valid_samples,
            out_mask=out_mask)
    if backend == "kernel" or backend == "interpret":
        return pop_mlp_correct(
            pop, x_int, labels, spec=spec,
            interpret=(backend == "interpret" if interpret is None
                       else interpret),
            n_valid_rows=n_valid_rows, n_valid_samples=n_valid_samples,
            out_mask=out_mask)
    if backend == "ref":
        return pop_mlp_correct_tiled(pop, x_int, labels, spec=spec,
                                     pop_tile=pop_tile,
                                     sample_tile=sample_tile,
                                     n_valid_rows=n_valid_rows,
                                     n_valid_samples=n_valid_samples,
                                     out_mask=out_mask)
    if backend == "jnp":
        return pop_mlp_correct_ref(pop, x_int, labels, spec=spec,
                                   out_mask=out_mask)
    raise ValueError(f"unknown fitness backend {backend!r}; want {BACKENDS}")

"""Pallas TPU kernel of the sweep ranking's staircase pass.

``sweep.sweep_rank`` spends its time in one length-P ``lax.scan``: for each
point of the sorted pool, count the staircase cells strictly below its key
(its front), then min-update the staircase at that front. Under XLA the
scan is a while loop of P/unroll trips, each a few dozen small device
operations, so a generation records thousands of operations and pays a
launch for each. Here the whole pass is one ``pallas_call``: the staircase
and the output fronts stay in vector registers as (R, 128) int32 tiles,
the keys are read one scalar a step from a (1, P) SMEM row (a row, so that
``vmap`` can block it), and the count is a float32 sum (at most P, exact)
broadcast back against a flat lane index. The fronts are the same integers
the scan yields.

Infeasible points take the key ``INT32_MAX``: they count every occupied
cell and update none, exactly as the scan's ``where(f, …)`` leaves the
staircase; their fronts are not read (``sweep.sweep_rank`` layers them by
violation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_IMAX = 2 ** 31 - 1
_LANES = 128


def _kernel(key_ref, o_ref, *, n: int):
    shape = o_ref.shape
    cell = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * _LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))

    def step(i, carry):
        stair, fronts = carry
        k = key_ref[0, i]
        below = jnp.where(stair < k, 1.0, 0.0)
        r = jnp.sum(below, axis=(0, 1), keepdims=True).astype(jnp.int32)
        stair = jnp.where(cell == r, jnp.minimum(stair, k), stair)
        return stair, jnp.where(cell == i, r, fronts)

    stair0 = jnp.full(shape, _IMAX, jnp.int32)
    _, fronts = jax.lax.fori_loop(0, n, step,
                                  (stair0, jnp.zeros(shape, jnp.int32)))
    o_ref[...] = fronts


@functools.partial(jax.jit, static_argnames=("interpret",))
def staircase_fronts(key: jnp.ndarray, *, interpret: bool = False):
    """(P,) int32 keys in sweep order → (P,) int32 staircase fronts.

    ``key`` is ``sweep._sort_and_key``'s key with infeasible points set to
    ``INT32_MAX``. Equal to the fronts of ``sweep.sweep_rank``'s scan at
    every feasible point."""
    n = key.shape[0]
    rows = -(-n // _LANES)
    out = pl.pallas_call(
        functools.partial(_kernel, n=n),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((rows, _LANES), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
        interpret=interpret,
    )(key.astype(jnp.int32).reshape(1, n))
    return out.reshape(-1)[:n]

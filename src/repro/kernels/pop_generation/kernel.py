"""Pallas TPU megakernel: fused GA variation + fitness for one generation.

One ``pallas_call`` produces a population tile's children AND their
correct-prediction counts without the children ever round-tripping through
HBM: at sample-grid step 0 the kernel runs the variation math of
``pop_variation.kernel`` (in-kernel counter-based Threefry: crossover →
mutation → clip) and writes the child block to its output ref; that block
then stays resident in VMEM while the sample grid axis sweeps the dataset,
each step running the integer forward pass of ``pop_mlp.kernel`` on it and
accumulating correct counts (tail samples masked, padded-topology output
columns pinned below any real logit, all-padding sample tiles skipped via
``pl.when`` — bit-exact, they could only add zero).

Grid iteration is row-major (the sample axis innermost), so for every
population tile the variation step runs before any fitness step reads the
children — the output block doubles as the VMEM scratch carrying them
between phases.

Bit-identity: the variation math addresses the identical Threefry counters
as ``pop_variation`` (swap draw by parent pair, mutation draws by child
row), and the fitness math is the accumulation of ``pop_mlp`` — so
children and counts equal the per-phase chain bit for bit
(tests/test_generation_path.py asserts it through whole runs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.genome import GenomeSpec
from ..pop_mlp.kernel import (count_correct, count_correct_mc,
                              sample_operands, sample_specs, valid_samples)
from ..pop_variation.kernel import _slot_uniform


def _kernel(a_ref, b_ref, do_ref, low_ref, high_ref, ismask_ref, bits_ref,
            ids_ref, keys_ref, pm_ref, x_ref, y_ref, samp_ref, om_ref,
            *refs, spec: GenomeSpec, bp: int, half: int,
            bs: int, n_valid: int, mc: bool):
    # trailing refs: [dev_ref] (device-variation MC only), child_ref, cnt_ref
    child_ref, cnt_ref = refs[-2], refs[-1]
    # program_id must stay outside the traced-cond bodies: the interpret-mode
    # impl only substitutes it at kernel top level (see pop_mlp.kernel)
    row_start = pl.program_id(0) * bp
    start = pl.program_id(1) * bs

    @pl.when(pl.program_id(1) == 0)
    def _variation():
        rows = (row_start
                + jax.lax.broadcasted_iota(jnp.int32, a_ref.shape, 0))
        gid = jnp.broadcast_to(ids_ref[...], a_ref.shape).astype(jnp.uint32)

        # crossover: the swap draw is addressed by the parent *pair* index
        pair = rows % half
        u_swap = _slot_uniform(keys_ref[0, 0], keys_ref[0, 1], gid, pair)
        swap = (do_ref[...] > 0) & (u_swap < 0.5)
        child = jnp.where(swap, b_ref[...], a_ref[...])

        # mutation: the do gate + ONE value draw (flipped-bit position on
        # mask genes, reset value elsewhere) at the child row
        u_do = _slot_uniform(keys_ref[1, 0], keys_ref[1, 1], gid, rows)
        u_val = _slot_uniform(keys_ref[2, 0], keys_ref[2, 1], gid, rows)
        bitpos = jnp.floor(u_val * jnp.maximum(bits_ref[...], 1)
                           ).astype(jnp.int32)
        flipped = jnp.bitwise_xor(child, jnp.left_shift(1, bitpos))
        lo = low_ref[...]
        hi = high_ref[...]
        reset = jnp.floor(lo.astype(jnp.float32)
                          + u_val * (hi - lo).astype(jnp.float32)
                          ).astype(jnp.int32)
        mutated = jnp.where(ismask_ref[...] > 0, flipped, reset)
        child = jnp.where(u_do < pm_ref[0, 0], mutated, child)
        child_ref[...] = jnp.clip(child, lo, hi - 1)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    # suite fast path: all-padding sample tiles (label −1) are skipped
    @pl.when(start < samp_ref[0, 0])
    def _fitness():
        args = (x_ref[...], y_ref[...], om_ref[...],
                valid_samples(start, n_valid, bp, bs), spec)
        if mc:
            # device-variation MC: the child block stays resident in VMEM
            # while the K perturbed instances each rerun the forward pass
            cnt_ref[...] += count_correct_mc(child_ref[...], refs[0][...],
                                             high_ref[...], *args)
        else:
            cnt_ref[...] += count_correct(child_ref[...], *args)


@functools.partial(jax.jit,
                   static_argnames=("spec", "bp", "bs", "interpret"))
def pop_generation_kernel(a_rows, b_rows, do_rows, table_low, table_high,
                          table_is_mask, table_mask_bits, table_ids,
                          slot_keys, pm_gene, x_int, labels, *,
                          spec: GenomeSpec, bp: int = 8, bs: int = 128,
                          interpret: bool = False, n_valid_samples=None,
                          out_mask=None, dev=None):
    """Pre-gathered parent frames + dataset → (children, correct counts).

    a_rows/b_rows: (P, G) int32 no-swap / swap sources per child row (the
        child-frame layout of ``pop_variation.ops``). do_rows: (P,) per-
        child do-crossover gate. table_*: the GeneTable leaves, (G,) each.
    slot_keys: (3, 2) uint32 — ``genome._slot_keys`` over the variation
        draw slots. pm_gene: () float32 (traced).
    x_int/labels: (S, n_in)/(S,) — the quantized dataset.
    n_valid_samples/out_mask: the suite-padding bounds of
        ``pop_mlp.pop_mlp_correct``.
    dev: optional (K, G) int32 device-variation deltas
        (``engine.device_deltas``) — the counts output then grows a K
        instance axis, the perturbed exponents clipped against the
        ``table_high`` bounds already on board.
    Returns ((P, G) int32 children, (P,) — or (P, K) with ``dev`` —
    int32 correct counts).
    """
    P, G = a_rows.shape
    half = P // 2
    S = x_int.shape[0]
    n_out = spec.topo.sizes[-1]
    bp = min(bp, P)
    pad_p = (bp - P % bp) % bp
    if pad_p:                     # padded rows compute garbage; sliced off
        a_rows = jnp.pad(a_rows, ((0, pad_p), (0, 0)))
        b_rows = jnp.pad(b_rows, ((0, pad_p), (0, 0)))
        do_rows = jnp.pad(do_rows.astype(jnp.int32), (0, pad_p))
    xt, y, samp, om, n_s = sample_operands(x_int, labels, bs,
                                           n_valid_samples, out_mask, n_out)
    row2d = lambda arr: jnp.asarray(arr, jnp.int32).reshape(-1, 1)
    gene2d = lambda arr, dt: jnp.asarray(arr, dt).reshape(1, G)
    nc = 1 if dev is None else dev.shape[0]
    dev_specs = ([] if dev is None
                 else [pl.BlockSpec((nc, G), lambda i, j: (0, 0))])
    dev_ops = () if dev is None else (jnp.asarray(dev, jnp.int32),)
    children, counts = pl.pallas_call(
        functools.partial(_kernel, spec=spec, bp=bp, half=half, bs=bs,
                          n_valid=S, mc=dev is not None),
        grid=((P + pad_p) // bp, n_s),
        in_specs=[
            pl.BlockSpec((bp, G), lambda i, j: (i, 0)),     # a_rows
            pl.BlockSpec((bp, G), lambda i, j: (i, 0)),     # b_rows
            pl.BlockSpec((bp, 1), lambda i, j: (i, 0)),     # do-crossover
            pl.BlockSpec((1, G), lambda i, j: (0, 0)),      # low
            pl.BlockSpec((1, G), lambda i, j: (0, 0)),      # high
            pl.BlockSpec((1, G), lambda i, j: (0, 0)),      # is_mask
            pl.BlockSpec((1, G), lambda i, j: (0, 0)),      # mask_bits
            pl.BlockSpec((1, G), lambda i, j: (0, 0)),      # draw ids
            pl.BlockSpec((3, 2), lambda i, j: (0, 0)),      # slot keys
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),      # pm_gene
            *sample_specs(x_int.shape[1], bs, n_out),
            *dev_specs,                                     # device deltas
        ],
        out_specs=[pl.BlockSpec((bp, G), lambda i, j: (i, 0)),
                   pl.BlockSpec((bp, nc), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((P + pad_p, G), jnp.int32),
                   jax.ShapeDtypeStruct((P + pad_p, nc), jnp.int32)],
        interpret=interpret,
    )(a_rows, b_rows, row2d(do_rows), gene2d(table_low, jnp.int32),
      gene2d(table_high, jnp.int32), gene2d(table_is_mask, jnp.int32),
      gene2d(table_mask_bits, jnp.int32), gene2d(table_ids, jnp.uint32),
      jnp.asarray(slot_keys, jnp.uint32),
      jnp.asarray(pm_gene, jnp.float32).reshape(1, 1),
      xt, y, samp, om, *dev_ops)
    return children[:P], (counts[:P, 0] if dev is None else counts[:P])

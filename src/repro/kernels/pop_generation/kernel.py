"""Pallas TPU megakernel: fused GA variation + fitness for one generation.

One ``pallas_call`` produces a population tile's children AND their
correct-prediction counts without the children ever round-tripping through
HBM: at sample-grid step 0 the kernel runs the variation math of
``pop_variation.kernel`` (in-kernel counter-based Threefry: crossover →
mutation → clip) and writes the child block to its output ref; that block
then stays resident in VMEM while the sample grid axis sweeps the dataset,
each step running the integer forward pass of ``pop_mlp.kernel`` on it and
accumulating correct counts (padded-topology output columns pinned below
any real logit, samples past the valid bound skipped — bit-exact, they
could only add zero).

The tile is gene-major, as in ``pop_mlp.kernel``: a (G_pad, 128) block,
one child per lane, so the variation's per-gene tables are (G_pad, 1)
columns, the child index is a lane iota, and every gene read of the
forward pass is a sublane broadcast of one row of the child block.

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
from ..pop_mlp.kernel import (LANES, build_instances, count_tile,
                              gene_column, gene_major, padded_sizes,
                              sample_operands, sample_specs, scratch_shapes,
                              write_counts)
from ..pop_variation.kernel import _slot_uniform


def _kernel(a_ref, b_ref, do_ref, low_ref, high_ref, ismask_ref, bits_ref,
            ids_ref, keys_ref, pm_ref, x_ref, y_ref, samp_ref, om_ref,
            *refs, spec: GenomeSpec, half: int, bs: int, mc: bool):
    # trailing refs: [dev_ref] (device-variation MC only), child_ref,
    # cnt_ref, then the scratch: cnt_acc, inst_ref
    dev_ref = refs[0] if mc else None
    child_ref, cnt_ref, cnt_acc, inst_ref = refs[-4:]
    # program_id must stay outside the traced-cond bodies: the interpret-mode
    # impl only substitutes it at kernel top level (see pop_mlp.kernel)
    row_start = pl.program_id(0) * LANES
    start = pl.program_id(1) * bs
    last = pl.num_programs(1) - 1

    @pl.when(pl.program_id(1) == 0)
    def _variation():
        # genes on sublanes, children on lanes: the per-gene tables are
        # (G_pad, 1) columns, the per-child gate a (1, LANES) row
        shape = a_ref.shape
        rows = row_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        gid = jnp.broadcast_to(ids_ref[...], shape)

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
        child = jnp.clip(child, lo, hi - 1)
        child_ref[...] = child
        cnt_acc[...] = jnp.zeros_like(cnt_acc)
        # the decoded children (the K perturbed instances under MC) are
        # built once per population tile and stay in VMEM for every step
        build_instances(child, spec, inst_ref, dev_ref, hi)

    # suite fast path: all-padding sample tiles (label −1) are skipped
    @pl.when(start < samp_ref[0, 0])
    def _fitness():
        count_tile(inst_ref, x_ref, y_ref, om_ref, cnt_acc, start,
                   samp_ref[0, 0], spec)

    @pl.when(pl.program_id(1) == last)
    def _emit():
        write_counts(cnt_acc, cnt_ref)


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def pop_generation_kernel(a_rows, b_rows, do_rows, table_low, table_high,
                          table_is_mask, table_mask_bits, table_ids,
                          slot_keys, pm_gene, x_int, labels, *,
                          spec: GenomeSpec, interpret: bool = False,
                          n_valid_samples=None, out_mask=None, dev=None):
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

    The kernel runs gene-major (``pop_mlp.kernel``): the parent frames go
    in as (G_pad, P_pad) blocks of one child per lane, and the children
    come back the same way; the population is padded to whole lane tiles
    (the padded children compute garbage that is sliced off).
    """
    P, G = a_rows.shape
    G_pad, P_pad = padded_sizes(P, G)
    samples, bs, n_s = sample_operands(x_int, labels, spec, n_valid_samples,
                                       out_mask)
    col = functools.partial(gene_column, n_genes=G_pad)
    nc = 1 if dev is None else dev.shape[0]
    mc = dev is not None
    block = pl.BlockSpec((G_pad, LANES), lambda i, j: (0, i))
    column = pl.BlockSpec((G_pad, 1), lambda i, j: (0, 0))
    children, counts = pl.pallas_call(
        functools.partial(_kernel, spec=spec, half=P // 2, bs=bs, mc=mc),
        grid=(P_pad // LANES, n_s),
        in_specs=[
            block,                                          # a_rows
            block,                                          # b_rows
            pl.BlockSpec((1, LANES), lambda i, j: (0, i)),  # do-crossover
            column,                                         # low
            column,                                         # high
            column,                                         # is_mask
            column,                                         # mask_bits
            column,                                         # draw ids
            pl.BlockSpec((3, 2), lambda i, j: (0, 0)),      # slot keys
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),      # pm_gene
            *sample_specs(x_int.shape[1], bs),
            *([pl.BlockSpec((G_pad, nc), lambda i, j: (0, 0))]  # deltas
              if mc else []),
        ],
        out_specs=[block, pl.BlockSpec((nc, LANES), lambda i, j: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((G_pad, P_pad), jnp.int32),
                   jax.ShapeDtypeStruct((nc, P_pad), jnp.int32)],
        scratch_shapes=scratch_shapes(nc, G_pad),
        interpret=interpret,
    )(gene_major(a_rows, G_pad, P_pad), gene_major(b_rows, G_pad, P_pad),
      jnp.pad(jnp.asarray(do_rows, jnp.int32), (0, P_pad - P)).reshape(1, -1),
      col(table_low), col(table_high, fill=1), col(table_is_mask),
      col(table_mask_bits), col(table_ids, dtype=jnp.uint32),
      jnp.asarray(slot_keys, jnp.uint32),
      jnp.asarray(pm_gene, jnp.float32).reshape(1, 1),
      *samples, *([gene_major(dev, G_pad, nc)] if mc else []))
    counts = counts[:, :P].T
    return children[:G, :P].T, (counts[:, 0] if dev is None else counts)

"""Pallas TPU kernel: population-batched approximate-MLP fitness evaluation.

The GA's fitness loop evaluates P chromosomes × S samples of the integer
network of paper Eq. (4) — ~26 M evaluations per training in the paper. The
kernel tiles (population × samples) into VMEM blocks; every op is int32 on
the VPU (bitwise-AND mask, shift, signed accumulate, clamp). Output is the
per-chromosome correct-prediction count, accumulated across sample tiles.

This is one backend behind the ``population_correct`` dispatcher (ops.py):

  * ``kernel``/``interpret`` — this Pallas kernel (compiled on TPU,
    interpret-mode elsewhere). The tiles derive from the shapes alone
    (:func:`row_group`, :func:`sample_tile`); the sample grid axis
    accumulates into a VMEM scratch block.
  * ``ref``/``jnp`` — the tiled / oracle jnp paths in ref.py.

Orientation: chromosomes on the lanes, samples on the sublanes. The
population block is gene-major, (G_pad, 128) — one chromosome per lane,
the gene axis padded to whole sublane groups — so gene k of a tile is the
row ``g[k:k+1, :]`` broadcast down the sublanes, never across lanes. The
inputs arrive as (bs, n_in) blocks: input feature i of a row group is one
column broadcast across the lanes, shared by every term of that feature.
An in-kernel ``fori_loop`` walks the tile's row groups (:func:`row_group`
samples each), so the forward pass's planes — (R, 128) int32, one per
neuron — stay in vregs. Hits accumulate elementwise in an (8, 128) block
per device instance and are reduced over the sublanes once, at the last
sample step. Nothing is reshaped into a (P, fan_in, fan_out) tensor: the
forward pass is statically unrolled over every (input, output) pair, and
the argmax is a strict ``>`` compare-and-select over the output planes
(the first maximal index wins, as in ``jnp.argmax``). Integer sums are
exact in any order, so the counts equal the jnp oracles bit for bit.

Skips. Duplicate-chromosome dedup (repro.core.dedup) packs rows needing
evaluation to the front and passes ``n_valid_rows``: population tiles
starting at or past it skip the forward pass (``pl.when``) and have
unspecified counts. ``n_valid_samples`` bounds the sample axis: tiles
starting at or past it are skipped, and inside a tile the row-group loop
stops at the first group past it. Samples past the bound, and the tail
padding of the last tile, carry label −1, which no argmax matches — so
both skips are bit-exact and no valid-sample mask is needed.

Genome layout per chromosome (repro.core.genome.GenomeSpec): masks,
signs, exps, biases, bshift, rshift per layer, concatenated. The spec's
layer slices arrive as static python ints.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.genome import GenomeSpec

_INT32_MIN = int(jnp.iinfo(jnp.int32).min)

# chromosomes per population tile: one per lane
LANES = 128
# vregs (8 × 128 int32 each) the live planes of one row group may fill:
# past it the compiled row loop spills (16-5-10 at 16 rows a group costs
# more per sample than at 8; 21-3-3 is cheapest at 32)
_PLANE_VREGS = 32
# VMEM bytes of one lane-padded (bs, ·) int32 sample-axis block
_SAMPLE_BLOCK_BYTES = 1 << 20

# scalar operands (valid-row / valid-sample bounds, output mask) in SMEM
SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def row_group(spec: GenomeSpec) -> int:
    """Samples per step of the in-kernel row loop: a multiple of 8, as
    many (up to 32) as keep the widest layer's live planes (its inputs,
    outputs and two temporaries, (R, 128) int32 each) within
    ``_PLANE_VREGS`` vregs."""
    live = max(sl.fan_out + (sl.fan_in if l else 0)
               for l, sl in enumerate(spec.layers)) + 2
    return 8 * max(1, min(4, _PLANE_VREGS // live))


def sample_tile(spec: GenomeSpec, n_samples: int) -> int:
    """Samples per step of the sample grid axis: whole row groups, at most
    ``_SAMPLE_BLOCK_BYTES`` of VMEM per block, as few steps as that allows,
    split evenly over them. The count of work actually run is in whole
    row groups (:func:`row_group`), not whole tiles."""
    r = row_group(spec)
    cap = max(r, _SAMPLE_BLOCK_BYTES // (LANES * 4) // r * r)
    steps = -(-n_samples // cap)
    return _round_up(-(-n_samples // steps), r)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def gene_major(rows, n_genes: int, n_lanes: int):
    """(P, G) int32 → (n_genes, n_lanes): one chromosome per column, the
    padding zero."""
    P, G = rows.shape
    return jnp.pad(jnp.asarray(rows, jnp.int32).T,
                   ((0, n_genes - G), (0, n_lanes - P)))


def padded_sizes(P: int, G: int) -> tuple[int, int]:
    """(G_pad, P_pad): the gene axis in whole sublane groups, the
    population in whole lane tiles."""
    return _round_up(G, 8), _round_up(P, LANES)


def gene_column(vec, n_genes: int, dtype=jnp.int32, fill=0):
    """(G,) per-gene vector → (n_genes, 1) column padded with ``fill``."""
    vec = jnp.asarray(vec, dtype)
    return jnp.pad(vec, (0, n_genes - vec.shape[0]),
                   constant_values=fill).reshape(n_genes, 1)


def _forward_rows(gene, x, spec: GenomeSpec):
    """gene(k) → (1, LANES) row of gene k of a decoded block (sign genes
    as ±1, :func:`build_instances`); x: (R, n_in) samples → n_out
    (R, LANES) logit planes.

    Layer l, output j: Σ_i s_ij·((h_i & m_ij) ≪ k_ij) + (b_j ≪ β), the
    hidden layers then QReLU'd — the same integer terms as
    ``core.mlp._layer_forward``, summed in another (exact) order."""
    shape = (x.shape[0], LANES)
    h = None
    n = spec.topo.n_layers
    for l, sl in enumerate(spec.layers):
        bshift = gene(sl.bshift.start)
        acc = [jnp.broadcast_to(
            jnp.left_shift(gene(sl.biases.start + j), bshift), shape)
            for j in range(sl.fan_out)]
        for i in range(sl.fan_in):
            hi = (jnp.broadcast_to(x[:, i:i + 1], shape) if l == 0
                  else h[i])
            for j in range(sl.fan_out):
                w = i * sl.fan_out + j
                term = jnp.left_shift(
                    jnp.bitwise_and(hi, gene(sl.masks.start + w)),
                    gene(sl.exps.start + w))
                acc[j] = acc[j] + gene(sl.signs.start + w) * term
        if l < n - 1:
            rshift = gene(sl.rshift.start)
            h = [jnp.clip(jnp.right_shift(a, rshift), 0,
                          2**spec.topo.act_bits - 1) for a in acc]
        else:
            h = acc
    return h


def _predict(logits, keep):
    """First-index argmax over the (R, LANES) logit planes → (R, LANES).

    keep(j) → scalar int32: output column j is real (padded-topology
    columns, 0, are pinned to INT32_MIN, so they can never win)."""
    best = idx = None
    for j, lg in enumerate(logits):
        lg = jnp.where(jnp.full(lg.shape, keep(j)) > 0, lg, _INT32_MIN)
        if best is None:
            best, idx = lg, jnp.zeros(lg.shape, jnp.int32)
            continue
        better = lg > best
        best = jnp.where(better, lg, best)
        idx = jnp.where(better, j, idx)
    return idx


def count_tile(inst_ref, x_ref, y_ref, om_ref, cnt_ref, start, n_valid,
               spec: GenomeSpec):
    """Add one sample tile's hits to ``cnt_ref`` ((K, 8, LANES)).

    inst_ref: the (K, G_pad, LANES) decoded device instances
    (:func:`build_instances`). The row loop stops at the first group at
    or past ``n_valid``."""
    bs = x_ref.shape[0]
    r = row_group(spec)
    n_groups = jax.lax.div(jnp.clip(n_valid - start, 0, bs) + (r - 1), r)

    def instance(k):
        g = inst_ref.at[k]

        def group(q, acc):
            r0 = pl.multiple_of(q * r, r)
            logits = _forward_rows(lambda c: g[c:c + 1, :],
                                   x_ref[pl.ds(r0, r), :], spec)
            hit = (_predict(logits, lambda j: om_ref[0, j])
                   == y_ref[pl.ds(r0, r), :]).astype(jnp.int32)
            for s in range(0, r, 8):
                acc = acc + hit[s:s + 8]
            return acc

        cnt_ref[k] += jax.lax.fori_loop(
            0, n_groups, group, jnp.zeros((8, LANES), jnp.int32))

    def each_instance(k, carry):
        instance(k)
        return carry

    if inst_ref.shape[0] == 1:
        instance(0)
    else:
        jax.lax.fori_loop(0, inst_ref.shape[0], each_instance, 0)


def build_instances(genome, spec: GenomeSpec, inst_ref, dev_ref=None,
                    hi=None):
    """Decode the (G_pad, LANES) gene-major block ``genome`` into
    ``inst_ref`` ((K, G_pad, LANES)), once per population tile: sign genes
    become ±1, so a term costs one multiply. Without ``dev_ref`` K = 1.
    With it, instance k adds column k of the (G_pad, K) delta block to the
    genes it moves, clipped to the per-gene exclusive bound ``hi``
    ((G_pad, 1)) — ``genome.apply_device_deltas``; deltas touch exponent
    genes only, so decoding first changes nothing."""
    gene = jax.lax.broadcasted_iota(jnp.int32, genome.shape, 0)
    is_sign = functools.reduce(
        jnp.logical_or, [(gene >= sl.signs.start) & (gene < sl.signs.stop)
                         for sl in spec.layers])
    genome = jnp.where(is_sign, genome * 2 - 1, genome)
    if dev_ref is None:
        inst_ref[0] = genome
        return
    for k in range(inst_ref.shape[0]):
        d = dev_ref[:, k:k + 1]
        inst_ref[k] = jnp.where(d == 0, genome,
                                jnp.clip(genome + d, 0, hi - 1))


def write_counts(cnt_ref, o_ref):
    """Reduce each instance's (8, LANES) hit block over the sublanes into
    row k of the (K, LANES) output block."""
    for k in range(cnt_ref.shape[0]):
        o_ref[k:k + 1, :] = jnp.sum(cnt_ref[k], axis=0, keepdims=True)


def scratch_shapes(n_inst: int, n_genes: int):
    """VMEM scratch of a fitness tile: the per-instance (8, LANES) hit
    blocks and the decoded instances of :func:`build_instances`."""
    return [pltpu.VMEM((n_inst, 8, LANES), jnp.int32),
            pltpu.VMEM((n_inst, n_genes, LANES), jnp.int32)]


def sample_operands(x_int, labels, spec: GenomeSpec, n_valid_samples,
                    out_mask):
    """The sample-axis operands every fitness kernel takes, padded to whole
    :func:`sample_tile` tiles: the inputs (S', n_in), the labels as (S', 1)
    (padded label −1 never matches), the (1, 1) valid-sample bound and the
    (1, n_out) output-column mask. Returns them with the tile and the
    tile count."""
    S = x_int.shape[0]
    n_out = spec.topo.sizes[-1]
    bs = sample_tile(spec, S)
    pad_s = _round_up(S, bs) - S
    x = jnp.pad(jnp.asarray(x_int, jnp.int32), ((0, pad_s), (0, 0)))
    y = jnp.pad(jnp.asarray(labels, jnp.int32), (0, pad_s),
                constant_values=-1).reshape(-1, 1)
    samp = jnp.full((1, 1), S if n_valid_samples is None else n_valid_samples,
                    jnp.int32)
    om = (jnp.ones((1, n_out), jnp.int32) if out_mask is None
          else jnp.asarray(out_mask, jnp.int32).reshape(1, n_out))
    return (x, y, samp, om), bs, (S + pad_s) // bs


def sample_specs(n_in: int, bs: int):
    """BlockSpecs of :func:`sample_operands` on a (population, sample) grid."""
    return [pl.BlockSpec((bs, n_in), lambda i, j: (j, 0)),    # inputs
            pl.BlockSpec((bs, 1), lambda i, j: (j, 0)),       # labels
            SMEM_SPEC,                                        # n_valid_samples
            SMEM_SPEC]                                        # output-col mask


def _kernel(genome_ref, x_ref, y_ref, samp_ref, om_ref, rows_ref, *refs,
            spec: GenomeSpec, bs: int, mc: bool):
    # trailing refs: [dev_ref, hi_ref] (device-variation MC only), o_ref,
    # then the scratch: cnt_ref, inst_ref
    dev_ref, hi_ref = refs[:2] if mc else (None, None)
    o_ref, cnt_ref, inst_ref = refs[-3:]
    # program_id must stay outside the traced-cond bodies: the interpret-mode
    # impl only substitutes it at kernel top level
    row_start = pl.program_id(0) * LANES
    start = pl.program_id(1) * bs
    last = pl.num_programs(1) - 1

    @pl.when(pl.program_id(1) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        build_instances(genome_ref[...], spec, inst_ref, dev_ref,
                        hi_ref[...] if mc else None)

    # dedup fast path: skip population tiles holding only duplicate rows;
    # suite fast path: skip sample tiles holding only padded samples
    @pl.when((row_start < rows_ref[0, 0]) & (start < samp_ref[0, 0]))
    def _compute():
        count_tile(inst_ref, x_ref, y_ref, om_ref, cnt_ref, start,
                   samp_ref[0, 0], spec)

    @pl.when(pl.program_id(1) == last)
    def _emit():
        write_counts(cnt_ref, o_ref)


def _pop_mlp_call(pop, x_int, labels, extra, nc, *, spec: GenomeSpec,
                  interpret: bool, n_valid_rows, n_valid_samples, out_mask):
    """Shared pallas_call of both fitness kernels → (P, nc) counts."""
    P, G = pop.shape
    G_pad, P_pad = padded_sizes(P, G)
    samples, bs, n_s = sample_operands(x_int, labels, spec, n_valid_samples,
                                       out_mask)
    rows = jnp.full((1, 1), P if n_valid_rows is None else n_valid_rows,
                    jnp.int32)
    mc = bool(extra)
    extra_specs = ([pl.BlockSpec((G_pad, nc), lambda i, j: (0, 0)),  # deltas
                    pl.BlockSpec((G_pad, 1), lambda i, j: (0, 0))]   # bounds
                   if mc else [])
    out = pl.pallas_call(
        functools.partial(_kernel, spec=spec, bs=bs, mc=mc),
        grid=(P_pad // LANES, n_s),
        in_specs=[pl.BlockSpec((G_pad, LANES), lambda i, j: (0, i)),
                  *sample_specs(x_int.shape[1], bs),
                  SMEM_SPEC,                                  # n_valid_rows
                  *extra_specs],
        out_specs=pl.BlockSpec((nc, LANES), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((nc, P_pad), jnp.int32),
        scratch_shapes=scratch_shapes(nc, G_pad),
        interpret=interpret,
    )(gene_major(pop, G_pad, P_pad), *samples, rows, *extra)
    return out[:, :P].T


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def pop_mlp_correct(pop: jnp.ndarray, x_int: jnp.ndarray, labels: jnp.ndarray,
                    *, spec: GenomeSpec, interpret: bool = False,
                    n_valid_rows=None, n_valid_samples=None,
                    out_mask=None) -> jnp.ndarray:
    """(P, G) × (S, n_in) × (S,) → (P,) int32 correct counts.

    ``n_valid_rows`` (optional, traced int32): rows at or past it live in
    skipped population tiles — see module docstring. ``n_valid_samples``
    (optional, traced int32): samples at or past it are padding and are
    skipped (bit-exact — padded labels are −1 and add zero). ``out_mask``
    ((n_out,), optional, traced): valid output columns of a
    padded-topology chromosome; omitted means every column is valid."""
    out = _pop_mlp_call(pop, x_int, labels, (), 1, spec=spec,
                        interpret=interpret, n_valid_rows=n_valid_rows,
                        n_valid_samples=n_valid_samples, out_mask=out_mask)
    return out[:, 0]


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def pop_mlp_correct_mc(pop: jnp.ndarray, x_int: jnp.ndarray,
                       labels: jnp.ndarray, dev: jnp.ndarray,
                       gene_high: jnp.ndarray, *, spec: GenomeSpec,
                       interpret: bool = False, n_valid_rows=None,
                       n_valid_samples=None, out_mask=None) -> jnp.ndarray:
    """Device-variation MC fitness: (P, G) × (K, G) deltas → (P, K) counts.

    The Pallas twin of ``ref.pop_mlp_correct_mc``: same grid and skips as
    :func:`pop_mlp_correct`. At sample step 0 each population tile's K
    perturbed instances are built once into VMEM scratch
    (:func:`build_instances`); every sample step then runs the forward
    pass on each. Column 0 is the nominal device (all-zero delta row —
    ``engine.device_deltas``).
    """
    K, G = dev.shape
    G_pad, _ = padded_sizes(pop.shape[0], G)
    extra = (gene_major(dev, G_pad, K),
             gene_column(gene_high, G_pad, fill=1))
    return _pop_mlp_call(pop, x_int, labels, extra, K, spec=spec,
                         interpret=interpret, n_valid_rows=n_valid_rows,
                         n_valid_samples=n_valid_samples, out_mask=out_mask)

"""Pallas TPU kernel: population-batched approximate-MLP fitness evaluation.

The GA's fitness loop evaluates P chromosomes × S samples of the integer
network of paper Eq. (4) — ~26 M evaluations per training in the paper. The
kernel tiles (population × samples) into VMEM blocks; every op is int32 on
the VPU (bitwise-AND mask, shift, signed accumulate, clamp). Output is the
per-chromosome correct-prediction count, accumulated across sample tiles.

This is one backend behind the ``population_correct`` dispatcher (ops.py):

  * ``kernel``/``interpret`` — this Pallas kernel (compiled on TPU,
    interpret-mode elsewhere). ``bp``/``bs`` tile the population and sample
    axes so blocks stay VMEM-sized; the sample grid axis accumulates into
    the output block, the tail sample tile is masked via ``n_valid``.
  * ``ref``/``jnp`` — the tiled / oracle jnp paths in ref.py.

The forward pass works on 2-D (bp, bs) int32 planes — chromosomes on
sublanes, samples on lanes — statically unrolled over every (input, output)
pair of every layer. Each gene is one (bp, 1) column of the genome block
(``genome[:, k:k+1]``) broadcast along the lanes, and each input feature is
one (1, bs) row of the *transposed* input block broadcast along the
sublanes. Nothing is ever reshaped into a (bp, fan_in, fan_out) tensor:
Mosaic cannot lay out that shape cast, and it has no integer argmax, so the
argmax is a strict ``>`` compare-and-select over the output planes (the
first maximal index wins, as in ``jnp.argmax``). Integer sums are exact in
any order, so the counts equal the jnp oracles bit for bit.

Duplicate-chromosome dedup (repro.core.dedup) packs rows needing evaluation
to the front and passes ``n_valid_rows``: population grid steps whose block
starts at or past it skip the forward pass entirely (``pl.when``), so
converged populations cost only their unique rows. Rows ≥ ``n_valid_rows``
have unspecified counts.

Genome layout per chromosome row (repro.core.genome.GenomeSpec): masks,
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

# scalar operands (valid-row / valid-sample bounds) live in SMEM
SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _forward_block(genome, xt, spec: GenomeSpec):
    """genome: (bp, G) int32; xt: (n_in, bs) int32 → n_out (bp, bs) logits.

    Layer l, output j: Σ_i s_ij·((h_i & m_ij) ≪ k_ij) + (b_j ≪ β), the
    hidden layers then QReLU'd — the same integer terms as
    ``core.mlp._layer_forward``, summed in another (exact) order."""
    bp, bs = genome.shape[0], xt.shape[1]
    col = lambda k: genome[:, k:k + 1]                        # (bp, 1)
    h = [xt[i:i + 1, :] for i in range(xt.shape[0])]          # (1, bs) each
    n = spec.topo.n_layers
    for l, sl in enumerate(spec.layers):
        bshift = col(sl.bshift.start)
        out = []
        for j in range(sl.fan_out):
            acc = jnp.broadcast_to(
                jnp.left_shift(col(sl.biases.start + j), bshift), (bp, bs))
            for i in range(sl.fan_in):
                w = i * sl.fan_out + j
                sign = col(sl.signs.start + w) * 2 - 1
                term = jnp.left_shift(
                    jnp.bitwise_and(h[i], col(sl.masks.start + w)),
                    col(sl.exps.start + w))
                acc = acc + sign * term
            out.append(acc)
        if l < n - 1:
            rshift = col(sl.rshift.start)
            h = [jnp.clip(jnp.right_shift(a, rshift), 0,
                          2**spec.topo.act_bits - 1) for a in out]
        else:
            h = out
    return h


def _predict(logits, om):
    """First-index argmax over the (bp, bs) logit planes → (bp, bs) int32.

    ``om``: (1, n_out) output-column mask — padded-topology columns
    (om == 0) are pinned to INT32_MIN, so they can never win."""
    best = idx = None
    for j, lg in enumerate(logits):
        lg = jnp.where(om[:, j:j + 1] > 0, lg, _INT32_MIN)
        if best is None:
            best, idx = lg, jnp.zeros(lg.shape, jnp.int32)
            continue
        better = lg > best
        best = jnp.where(better, lg, best)
        idx = jnp.where(better, j, idx)
    return idx


def count_correct(genome, xt, y, om, valid, spec: GenomeSpec):
    """(bp, 1) int32 correct counts of one (population × sample) block.

    y: (1, bs) labels; valid: (bp, bs) bool mask of real samples."""
    pred = _predict(_forward_block(genome, xt, spec), om)
    hit = (pred == y) & valid
    return jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)


def count_correct_mc(genome, dev, hi, xt, y, om, valid, spec: GenomeSpec):
    """(bp, K) int32 counts of one block on the K device instances.

    dev: (K, G) exponent deltas; hi: (1, G) exclusive gene bounds. Static
    unroll over the instances: each perturbs the genome block in registers
    (``genome.apply_device_deltas``) and reruns the forward pass, the input
    and label blocks loaded once for all K."""
    bp, n_dev = genome.shape[0], dev.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bp, n_dev), 1)
    tot = jnp.zeros((bp, n_dev), jnp.int32)
    for k in range(n_dev):
        d = dev[k:k + 1, :]                                     # (1, G)
        gk = jnp.where(d == 0, genome, jnp.clip(genome + d, 0, hi - 1))
        tot = tot + jnp.where(lane == k,
                              count_correct(gk, xt, y, om, valid, spec), 0)
    return tot


def valid_samples(start, n_valid: int, bp: int, bs: int):
    """(bp, bs) bool: which lanes of the sample tile at ``start`` are real
    samples (the tail tile is padded)."""
    return (start + jax.lax.broadcasted_iota(jnp.int32, (bp, bs), 1)
            ) < n_valid


def sample_operands(x_int, labels, bs: int, n_valid_samples, out_mask,
                    n_out: int):
    """The sample-axis operands every fitness kernel takes, padded to whole
    ``bs`` tiles: the transposed inputs (n_in, S'), the labels as (1, S')
    (padded label −1 never matches), the (1, 1) valid-sample bound and the
    (1, n_out) output-column mask. Returns them with the tile count."""
    S = x_int.shape[0]
    pad_s = (bs - S % bs) % bs
    xt = jnp.pad(x_int.T, ((0, 0), (0, pad_s)))
    y = jnp.pad(labels, (0, pad_s), constant_values=-1).reshape(1, -1)
    samp = jnp.full((1, 1), S if n_valid_samples is None else n_valid_samples,
                    jnp.int32)
    om = (jnp.ones((1, n_out), jnp.int32) if out_mask is None
          else jnp.asarray(out_mask, jnp.int32).reshape(1, n_out))
    return xt, y, samp, om, (S + pad_s) // bs


def sample_specs(n_in: int, bs: int, n_out: int):
    """BlockSpecs of :func:`sample_operands` on a (population, sample) grid."""
    return [pl.BlockSpec((n_in, bs), lambda i, j: (0, j)),    # inputs^T
            pl.BlockSpec((1, bs), lambda i, j: (0, j)),       # labels
            SMEM_SPEC,                                        # n_valid_samples
            pl.BlockSpec((1, n_out), lambda i, j: (0, 0))]    # output-col mask


def _kernel(genome_ref, x_ref, y_ref, samp_ref, om_ref, rows_ref, *refs,
            spec: GenomeSpec, n_valid: int, bs: int, bp: int,
            mc: bool):
    # trailing refs: [dev_ref, hi_ref] (device-variation MC only), o_ref
    o_ref = refs[-1]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # program_id must stay outside the traced-cond body: the interpret-mode
    # impl only substitutes it at kernel top level
    row_start = pl.program_id(0) * bp
    start = pl.program_id(1) * bs

    # dedup fast path: skip population blocks holding only duplicate rows;
    # suite fast path: skip sample blocks holding only padded samples
    # (label −1 — they could only ever add zero, so skipping is bit-exact)
    @pl.when((row_start < rows_ref[0, 0]) & (start < samp_ref[0, 0]))
    def _compute():
        args = (x_ref[...], y_ref[...], om_ref[...],
                valid_samples(start, n_valid, bp, bs), spec)
        if mc:
            o_ref[...] += count_correct_mc(genome_ref[...], refs[0][...],
                                           refs[1][...], *args)
        else:
            o_ref[...] += count_correct(genome_ref[...], *args)


def _pop_mlp_call(pop, x_int, labels, extra, extra_specs, nc, *,
                  spec: GenomeSpec, bp: int, bs: int, interpret: bool,
                  n_valid_rows, n_valid_samples, out_mask):
    """Shared pallas_call of both fitness kernels → (P, nc) counts."""
    P, G = pop.shape
    S = x_int.shape[0]
    n_out = spec.topo.sizes[-1]
    bp = min(bp, P)
    pad_p = (bp - P % bp) % bp
    if pad_p:                     # zero rows are valid genomes; counts dropped
        pop = jnp.pad(pop, ((0, pad_p), (0, 0)))
    xt, y, samp, om, n_s = sample_operands(x_int, labels, bs,
                                           n_valid_samples, out_mask, n_out)
    rows = jnp.full((1, 1), P if n_valid_rows is None else n_valid_rows,
                    jnp.int32)
    out = pl.pallas_call(
        functools.partial(_kernel, spec=spec, n_valid=S, bs=bs, bp=bp,
                          mc=bool(extra)),
        grid=((P + pad_p) // bp, n_s),
        in_specs=[pl.BlockSpec((bp, G), lambda i, j: (i, 0)),
                  *sample_specs(x_int.shape[1], bs, n_out),
                  SMEM_SPEC,                                  # n_valid_rows
                  *extra_specs],
        out_specs=pl.BlockSpec((bp, nc), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((P + pad_p, nc), jnp.int32),
        interpret=interpret,
    )(pop, xt, y, samp, om, rows, *extra)
    return out[:P]


@functools.partial(jax.jit,
                   static_argnames=("spec", "bp", "bs", "interpret"))
def pop_mlp_correct(pop: jnp.ndarray, x_int: jnp.ndarray, labels: jnp.ndarray,
                    *, spec: GenomeSpec, bp: int = 8, bs: int = 128,
                    interpret: bool = False,
                    n_valid_rows=None, n_valid_samples=None,
                    out_mask=None) -> jnp.ndarray:
    """(P, G) × (S, n_in) × (S,) → (P,) int32 correct counts.

    ``n_valid_rows`` (optional, traced int32): rows at or past it live in
    skipped population blocks — see module docstring. ``n_valid_samples``
    (optional, traced int32): sample blocks at or past it hold only padded
    samples and are skipped (bit-exact — padded labels are −1 and add
    zero). ``out_mask`` ((n_out,), optional, traced): valid output columns
    of a padded-topology chromosome; omitted means every column is
    valid."""
    out = _pop_mlp_call(pop, x_int, labels, (), [], 1, spec=spec, bp=bp,
                        bs=bs, interpret=interpret,
                        n_valid_rows=n_valid_rows,
                        n_valid_samples=n_valid_samples, out_mask=out_mask)
    return out[:, 0]


@functools.partial(jax.jit,
                   static_argnames=("spec", "bp", "bs", "interpret"))
def pop_mlp_correct_mc(pop: jnp.ndarray, x_int: jnp.ndarray,
                       labels: jnp.ndarray, dev: jnp.ndarray,
                       gene_high: jnp.ndarray, *, spec: GenomeSpec,
                       bp: int = 8, bs: int = 128, interpret: bool = False,
                       n_valid_rows=None, n_valid_samples=None,
                       out_mask=None) -> jnp.ndarray:
    """Device-variation MC fitness: (P, G) × (K, G) deltas → (P, K) counts.

    The Pallas twin of ``ref.pop_mlp_correct_mc``: same grid and tile
    skips as :func:`pop_mlp_correct`, but the delta table (one (K, G)
    block broadcast to every grid step) and the per-gene exclusive upper
    bounds ride along, the instance axis is statically unrolled inside
    the kernel (:func:`count_correct_mc`), and the output block grows to
    (bp, K). Column 0 is the nominal device (all-zero delta row —
    ``engine.device_deltas``).
    """
    K, G = dev.shape
    extra = (jnp.asarray(dev, jnp.int32),
             jnp.asarray(gene_high, jnp.int32).reshape(1, G))
    extra_specs = [pl.BlockSpec((K, G), lambda i, j: (0, 0)),   # deltas
                   pl.BlockSpec((1, G), lambda i, j: (0, 0))]   # gene bounds
    return _pop_mlp_call(pop, x_int, labels, extra, extra_specs, K,
                         spec=spec, bp=bp, bs=bs, interpret=interpret,
                         n_valid_rows=n_valid_rows,
                         n_valid_samples=n_valid_samples, out_mask=out_mask)

"""Chromosome encoding for the approximate printed MLP (paper Fig. 3).

A chromosome is a flat ``int32`` vector. Genes are grouped per weight
(mask ``m``, sign ``s``, exponent ``k``), then per neuron (bias ``b``),
then per layer (output right-shift ``r`` and bias shift — see DESIGN.md
"Assumption changes"), then by layer — exactly the grouping of paper Fig. 3.

Gene semantics (paper §III / Eq. (4)):
  mask  m_{i,j}^{(l)} ∈ [0, 2^{B_in(l)})  — bitwise-AND pruning mask on the
                                            input activation (B_in bits).
  sign  s_{i,j}^{(l)} ∈ {0, 1}            — encodes {−1, +1}.
  exp   k_{i,j}^{(l)} ∈ [0, n−1)          — pow2 weight exponent (Eq. (1)).
  bias  b_j^{(l)}     ∈ [−2^{Bb−1}, 2^{Bb−1})  — low-bitwidth quantized bias.
  bshift β^{(l)}      ∈ [0, n−1)          — shared bias scale (constant folding
                                            into the adder tree is free).
  rshift r^{(l)}      ∈ [0, 8)            — free LSB-drop on the QReLU input
                                            (wiring only; searchable rescale).

Everything is specified by :class:`GenomeSpec`, which owns per-gene integer
bounds ``low``/``high`` (inclusive / exclusive) so that mutation and random
initialisation are single vectorised ``randint`` calls.

Padded-canonical layouts (suite batching): any topology embeds into a
larger "max-shape" topology by scattering its genes at the corresponding
(weight, neuron, layer) coordinates and forcing every padding gene to a
canonical zero (bounds ``[0, 1)``). The per-gene metadata that drives the
operators — bounds, mask bits, draw ids, validity — lives in a
:class:`GeneTable` pytree whose leaves trace through jit/vmap, so five
different topologies can run as lanes of ONE vmapped program over a shared
padded :class:`GenomeSpec`. All gene-shaped randomness is *gene-addressed*
(:func:`gene_uniform` keys every gene's draw by its table id, never by
array shape), which is what makes a padded run bit-identical to the
unpadded one: valid genes share their draw ids with the unpadded layout,
padding draws exist but are forced to zero.

Counter-based gene RNG (the draw-slot scheme)
---------------------------------------------

Gene-shaped draws are generated by ONE vectorised Threefry-2x32 pass over
a ``(draw_slot, gene_id, row)`` counter grid instead of a per-gene
``fold_in`` vmap (which paid a scalar hash per gene per draw, on every
path). The addressing contract — THE invariant suite batching rests on —
is that uniform ``(i, j)`` of a draw depends only on

    (key, slot, ids[j], i)

and never on the gene-axis length or the number of rows requested. The
layout: the key is folded once per *slot* (a scalar hash), the counter
words are ``(ids[j], i >> 1)``, and the two 32-bit Threefry output words
serve rows ``2r`` and ``2r + 1`` — so a draw of ``n`` rows costs
``⌈n/2⌉ · G`` hashes, every output bit is used, and any two layouts that
give a gene the same id see the same numbers for it.

Slots separate the independent uniforms a consumer needs from one key:
:data:`SLOT_CROSS_SWAP` / :data:`SLOT_MUT_DO` / :data:`SLOT_MUT_VAL` are
the per-generation variation draws (see ``operators.py`` — they share one
key; :func:`gene_uniform_slots` batches any slot set into one pass), and
:data:`SLOT_INIT` is the population init draw (its own key, so slot 0 is
safe to reuse). Draws from different slots are disjoint streams;
``tests/test_variation.py`` property-tests the whole contract.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MLPTopology:
    """(n_in, h_1, ..., n_out) with the paper's bitwidths."""

    sizes: tuple[int, ...]
    input_bits: int = 4      # paper: 4-bit inputs
    act_bits: int = 8        # paper: 8-bit QReLU outputs
    weight_bits: int = 8     # n in Eq. (1): k ∈ [0, n-1)
    bias_bits: int = 8       # low-bitwidth quantized biases

    @property
    def n_layers(self) -> int:
        return len(self.sizes) - 1

    @property
    def n_params(self) -> int:
        """Weight + bias count (the paper's 'Parameters' column, Table I)."""
        return sum(
            self.sizes[l] * self.sizes[l + 1] + self.sizes[l + 1]
            for l in range(self.n_layers)
        )

    def layer_in_bits(self, l: int) -> int:
        return self.input_bits if l == 0 else self.act_bits

    @property
    def max_exp(self) -> int:
        return self.weight_bits - 2  # k ∈ [0, n-1)  →  {0, ..., n-2}


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GeneTable:
    """Per-gene operator metadata as traced array leaves.

    The operators (init / mutation / crossover / clip) read everything they
    need about a gene from here instead of from ``GenomeSpec`` statics, so a
    batch axis can carry a *different* table per lane (the suite's five
    topologies embedded in one padded layout) through one traced program.

    ``ids`` addresses the PRNG: gene ``j``'s uniforms are counter-based
    Threefry draws keyed by ``(key, slot, ids[j], row)`` (see
    :func:`gene_uniform`), so draws depend on (key, slot, id, row) — never
    on the gene axis length. A padded table reuses the unpadded layout's
    ids at the embedded positions, which makes padded and unpadded runs
    consume identical randomness per gene. Padding entries have bounds ``[0, 1)``,
    ``is_mask=False`` and ``valid=False``: init and mutation can only write
    zero there, and clip pins them to zero (the canonical-zero rule).
    """

    low: jnp.ndarray        # (G,) int32 inclusive lower bound
    high: jnp.ndarray       # (G,) int32 exclusive upper bound
    is_mask: jnp.ndarray    # (G,) bool — bit-flip mutation instead of reset
    mask_bits: jnp.ndarray  # (G,) int32 — bit width of mask genes (0 else)
    ids: jnp.ndarray        # (G,) int32 PRNG draw ids
    valid: jnp.ndarray      # (G,) bool — False on padding

    def tree_flatten(self):
        return (self.low, self.high, self.is_mask, self.mask_bits,
                self.ids, self.valid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


# -- counter-based gene RNG -------------------------------------------------

# Draw slots: the independent gene-shaped uniforms one consumer key serves.
# The variation slots share one key per generation (operators.py /
# kernels.pop_variation); init has its own key. A mask gene consumes
# SLOT_MUT_VAL as its bit-flip position, every other gene as its reset
# value — the two interpretations are never both read for one (i, j), so
# one slot serves both (a third of the mutation draws gone).
SLOT_CROSS_SWAP = 0   # uniform_crossover's per-gene swap draw
SLOT_MUT_DO = 1       # mutate: does gene (i, j) mutate at all?
SLOT_MUT_VAL = 2      # mutate: flipped-bit position (masks) / reset value
N_VARIATION_SLOTS = 3
SLOT_INIT = 0         # random_population (separate key)
SLOT_DEVICE = 3       # device-variation Monte-Carlo perturbation draws
                      # (engine.device_deltas; own key from
                      # GAConfig.device_seed, so disjoint from the
                      # variation slots' per-generation key by key AND,
                      # belt-and-braces, by slot index)

_THREEFRY_PARITY = np.uint32(0x1BD11BDA)
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def threefry2x32(k1, k2, x1, x2):
    """Vectorised Threefry-2x32 (20 rounds): counters in, 2 hash words out.

    All four operands broadcast elementwise (uint32); returns ``(y1, y2)``
    of the broadcast shape. Bit-identical to jax.random's Threefry (the
    property suite anchors it against ``jax.random.fold_in``), but takes
    *explicit* counters — which is what lets :func:`gene_uniform` address a
    whole (slot, gene_id, row) grid in one fused pass instead of a
    per-gene ``fold_in`` vmap. Pure jnp, so the Pallas variation kernel
    runs the identical math on-chip (``kernels.pop_variation``).
    """
    k1 = jnp.asarray(k1, jnp.uint32)
    k2 = jnp.asarray(k2, jnp.uint32)
    v0 = jnp.asarray(x1, jnp.uint32) + k1
    v1 = jnp.asarray(x2, jnp.uint32) + k2
    ks = (k1, k2, k1 ^ k2 ^ _THREEFRY_PARITY)

    def rounds(v0, v1, rots):
        for r in rots:
            v0 = v0 + v1
            v1 = (v1 << np.uint32(r)) | (v1 >> np.uint32(32 - r))
            v1 = v0 ^ v1
        return v0, v1

    for d in range(5):
        v0, v1 = rounds(v0, v1, _ROTATIONS[d % 2])
        v0 = v0 + ks[(d + 1) % 3]
        v1 = v1 + ks[(d + 2) % 3] + np.uint32(d + 1)
    return v0, v1


def _key_words(key):
    """(k1, k2) uint32 words of a legacy or typed PRNG key."""
    if jnp.issubdtype(jnp.asarray(key).dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return key[0], key[1]


def _slot_keys(key, slots):
    """Fold ``key`` once per draw slot (scalar hashes) → (len(slots), 2)."""
    k1, k2 = _key_words(key)
    s = jnp.asarray(slots, jnp.uint32)
    return jnp.stack(threefry2x32(k1, k2, s, jnp.zeros_like(s)), axis=-1)


def bits_to_open01(bits):
    """uint32 → float32 in [0, 1): the same 23-mantissa-bit conversion
    jax.random.uniform uses, so draw distributions are unchanged."""
    f = jax.lax.bitcast_convert_type(
        (bits >> np.uint32(9)) | np.uint32(0x3F800000), jnp.float32)
    return f - 1.0


def gene_uniform_slots(key, ids: jnp.ndarray, n: int, slots) -> jnp.ndarray:
    """(len(slots), n, G) float32 uniforms from ONE fused Threefry pass.

    ``slots``: an int (meaning ``range(slots)``) or a sequence of draw-slot
    indices. Element (s, i, j) is the canonical gene-addressed uniform

        draw(key, slots[s], ids[j], i)

    — bit-identical to ``gene_uniform(key, ids, n, slot=slots[s])[i, j]``
    for every slot, but all slots hash in one vectorised pass over the
    (slot, gene_id, row-pair) counter grid. Rows pair up on the two
    Threefry output words (row ``2r`` ← word 1, ``2r+1`` ← word 2 of
    counter ``(ids[j], r)``), so the pass costs ``len(slots) · ⌈n/2⌉ · G``
    hashes with no wasted bits.
    """
    slots = range(slots) if isinstance(slots, int) else slots
    ks = _slot_keys(key, list(slots))                     # (S, 2)
    half = (n + 1) // 2
    hi = ids.astype(jnp.uint32)[None, None, :]            # gene id word
    lo = jnp.arange(half, dtype=jnp.uint32)[None, :, None]  # row-pair word
    y1, y2 = threefry2x32(ks[:, 0, None, None], ks[:, 1, None, None], hi, lo)
    w = jnp.stack([y1, y2], axis=2)                       # (S, half, 2, G)
    bits = w.reshape(w.shape[0], 2 * half, -1)[:, :n]
    return bits_to_open01(bits)


def gene_uniform(key, ids: jnp.ndarray, n: int, slot: int = 0) -> jnp.ndarray:
    """(n, G) float32 uniforms addressed by (key, slot, ids[j], row).

    THE canonical gene-shaped draw: element (i, j) depends only on the
    key, the draw slot and ``(ids[j], i)`` — never on the gene-axis length
    or on ``n``. Two layouts that give a gene the same id (an unpadded
    chromosome and its padded embedding) therefore draw the same number
    for it — the invariant suite batching rests on. Generated counter-
    based (module docstring): one fused Threefry pass over the
    ``(ids[j], i >> 1)`` grid under the slot-folded key, both output words
    used. Use :func:`gene_uniform_slots` to batch several slots of one key
    into a single pass.
    """
    return gene_uniform_slots(key, ids, n, (slot,))[0]


def apply_device_deltas(pop, deltas, high):
    """Perturb exponent genes by one device instance's delta row.

    pop: (…, G) int32 genomes; deltas: (G,) int32 in {-1, 0, +1} (one row
    of ``engine.device_deltas``); high: (G,) int32 exclusive upper bounds
    (``GeneTable.high``). Genes with delta 0 pass through bit-untouched —
    no clip ever moves a non-exponent or padding gene — and perturbed
    genes clip into [0, high-1] with *per-gene* bounds, so an embedded
    (suite-padded) gene keeps its inner layout's exponent range and the
    padded evaluation stays bit-identical to the unpadded one.
    """
    pert = jnp.clip(pop + deltas, 0, high - 1)
    return jnp.where(deltas == 0, pop, pert)


def random_population(key, genes: GeneTable, n: int) -> jnp.ndarray:
    """Uniform random (n, G) int32 population within the table's bounds.

    Padding bounds are [0, 1) so padded genes come out exactly zero."""
    u = gene_uniform(key, genes.ids, n, slot=SLOT_INIT)
    lo = genes.low.astype(jnp.float32)
    hi = genes.high.astype(jnp.float32)
    return jnp.floor(lo + u * (hi - lo)).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class LayerSlices:
    """Index ranges of each gene family inside the flat chromosome."""

    masks: slice     # fan_in * fan_out genes
    signs: slice
    exps: slice
    biases: slice    # fan_out genes
    bshift: slice    # 1 gene
    rshift: slice    # 1 gene
    fan_in: int
    fan_out: int
    in_bits: int


class GenomeSpec:
    """Flat-vector layout + integer bounds for a topology's chromosome."""

    def __init__(self, topo: MLPTopology):
        self.topo = topo
        self.layers: list[LayerSlices] = []
        low: list[np.ndarray] = []
        high: list[np.ndarray] = []
        off = 0

        for l in range(topo.n_layers):
            fi, fo = topo.sizes[l], topo.sizes[l + 1]
            ib = topo.layer_in_bits(l)
            nw = fi * fo

            def seg(n: int, lo: int, hi: int):
                nonlocal off
                s = slice(off, off + n)
                low.append(np.full(n, lo, np.int32))
                high.append(np.full(n, hi, np.int32))
                off += n
                return s

            masks = seg(nw, 0, 2**ib)
            signs = seg(nw, 0, 2)
            exps = seg(nw, 0, topo.max_exp + 1)
            biases = seg(fo, -(2 ** (topo.bias_bits - 1)), 2 ** (topo.bias_bits - 1))
            bshift = seg(1, 0, topo.max_exp + 1)
            rshift = seg(1, 0, 8)
            self.layers.append(
                LayerSlices(masks, signs, exps, biases, bshift, rshift, fi, fo, ib)
            )

        self.n_genes = off
        self.low = jnp.asarray(np.concatenate(low))
        self.high = jnp.asarray(np.concatenate(high))
        # Mask genes get bit-flip mutation; others get random reset.
        # Exponent genes (the per-weight pow2 scales — the printed
        # resistors) are additionally the ones device variation perturbs
        # (engine.device_deltas / apply_device_deltas).
        is_mask = np.zeros(off, bool)
        mask_bits = np.zeros(off, np.int32)
        is_exp = np.zeros(off, bool)
        for sl in self.layers:
            is_mask[sl.masks] = True
            mask_bits[sl.masks] = sl.in_bits
            is_exp[sl.exps] = True
        self.is_mask = jnp.asarray(is_mask)
        self.mask_bits = jnp.asarray(mask_bits)
        self.is_exp = jnp.asarray(is_exp)
        self.gene_ids = jnp.arange(off, dtype=jnp.int32)
        self.gene_valid = jnp.ones(off, bool)

    # Everything above follows from the topology, so two specs of one
    # topology are interchangeable: a program keyed on a spec (a static
    # part of every Problem) is reused for a fresh spec of the same shape.
    def __eq__(self, other):
        return isinstance(other, GenomeSpec) and other.topo == self.topo

    def __hash__(self):
        return hash(self.topo)

    def table(self) -> GeneTable:
        """The spec's own GeneTable (identity layout: ids are positions,
        every gene valid)."""
        return GeneTable(self.low, self.high, self.is_mask, self.mask_bits,
                         self.gene_ids, self.gene_valid)

    # -- structured views -------------------------------------------------
    def layer_params(self, genome: jnp.ndarray, l: int):
        """Return (masks[fi,fo], signs[fi,fo], exps[fi,fo], bias[fo], bshift, rshift).

        Works on a single genome (1-D) or a population (…, n_genes): the gene
        axis is always the last one.
        """
        sl = self.layers[l]
        lead = genome.shape[:-1]

        def take(s: slice, shape):
            return genome[..., s].reshape(lead + shape)

        masks = take(sl.masks, (sl.fan_in, sl.fan_out))
        signs = take(sl.signs, (sl.fan_in, sl.fan_out)) * 2 - 1   # {0,1} → {-1,+1}
        exps = take(sl.exps, (sl.fan_in, sl.fan_out))
        bias = take(sl.biases, (sl.fan_out,))
        bshift = genome[..., sl.bshift.start]
        rshift = genome[..., sl.rshift.start]
        return masks, signs, exps, bias, bshift, rshift

    def random(self, key, n: int) -> jnp.ndarray:
        """Uniform random population of ``n`` chromosomes within bounds."""
        return random_population(key, self.table(), n)

    def clip(self, genome: jnp.ndarray) -> jnp.ndarray:
        return jnp.clip(genome, self.low, self.high - 1)

    def exact_seed(
        self,
        weights: Sequence[np.ndarray],
        biases: Sequence[np.ndarray],
    ) -> np.ndarray:
        """Encode float weights as a 'nearly non-approximate' chromosome.

        Used to dope ~10 % of the initial population (paper §IV-A): full
        masks, signs/exponents from a pow2 rounding of the float weights,
        quantized biases. Scales are chosen per layer so the median weight
        magnitude maps near the middle of the exponent range.
        """
        topo = self.topo
        g = np.zeros(self.n_genes, np.int32)
        for l, sl in enumerate(self.layers):
            w = np.asarray(weights[l], np.float64)        # (fan_in, fan_out)
            b = np.asarray(biases[l], np.float64)         # (fan_out,)
            absw = np.abs(w[w != 0])
            med = np.median(absw) if absw.size else 1.0
            # target: median |w| → exponent 2 (leaves headroom both ways)
            scale = (2.0**2) / max(med, 1e-12)
            k = np.clip(np.round(np.log2(np.maximum(np.abs(w) * scale, 1e-12))),
                        0, topo.max_exp).astype(np.int32)
            s = (w >= 0).astype(np.int32)
            m = np.full(w.shape, 2**sl.in_bits - 1, np.int32)   # keep all bits
            bq = np.clip(np.round(b * scale * (2**sl.in_bits - 1)),
                         -(2 ** (topo.bias_bits - 1)),
                         2 ** (topo.bias_bits - 1) - 1).astype(np.int32)
            g[sl.masks] = m.reshape(-1)
            g[sl.signs] = s.reshape(-1)
            g[sl.exps] = k.reshape(-1)
            g[sl.biases] = bq
            g[sl.bshift.start] = 0
            # QReLU rescale ≈ log2(scale * input_range) to undo the blow-up
            g[sl.rshift.start] = int(np.clip(np.round(np.log2(scale * 15)), 0, 7))
        return g


# ---------------------------------------------------------------------------
# Padded-canonical embedding (suite batching across topologies)
# ---------------------------------------------------------------------------

def max_topology(topos: Sequence[MLPTopology]) -> MLPTopology:
    """The elementwise-max topology every ``topos`` member embeds into."""
    first = topos[0]
    for t in topos:
        if t.n_layers != first.n_layers:
            raise ValueError("suite topologies must share the layer count")
        if (t.input_bits, t.act_bits, t.weight_bits, t.bias_bits) != (
                first.input_bits, first.act_bits, first.weight_bits,
                first.bias_bits):
            raise ValueError("suite topologies must share all bit widths")
    sizes = tuple(max(t.sizes[i] for t in topos)
                  for i in range(len(first.sizes)))
    return MLPTopology(sizes, first.input_bits, first.act_bits,
                       first.weight_bits, first.bias_bits)


def pad_positions(inner: "GenomeSpec", padded: "GenomeSpec") -> np.ndarray:
    """(inner.n_genes,) positions of each inner gene in the padded layout.

    Gene families embed coordinate-wise: weight (i, j) of layer ``l`` lands
    at the padded layer's (i, j), bias j at bias j, the per-layer shift
    genes on each other. Everything the padded layout adds beyond these
    positions is padding (canonical zero)."""
    if len(inner.layers) != len(padded.layers):
        raise ValueError("padded spec must have the same layer count")
    pos = np.empty(inner.n_genes, np.int64)
    for si, sp in zip(inner.layers, padded.layers):
        if si.fan_in > sp.fan_in or si.fan_out > sp.fan_out:
            raise ValueError("padded layer smaller than the inner layer")
        if si.in_bits != sp.in_bits:
            raise ValueError("padded layer changes the input bit width")
        t = np.arange(si.fan_in * si.fan_out)
        woff = (t // si.fan_out) * sp.fan_out + t % si.fan_out
        pos[si.masks] = sp.masks.start + woff
        pos[si.signs] = sp.signs.start + woff
        pos[si.exps] = sp.exps.start + woff
        pos[si.biases] = sp.biases.start + np.arange(si.fan_out)
        pos[si.bshift] = sp.bshift.start
        pos[si.rshift] = sp.rshift.start
    return pos


def padded_table(inner: "GenomeSpec", padded: "GenomeSpec",
                 pos: np.ndarray | None = None) -> GeneTable:
    """``inner``'s GeneTable embedded in ``padded``'s flat layout.

    Embedded genes keep their bounds/mask metadata and — crucially — their
    *inner* draw ids, so a padded run consumes the same randomness per gene
    as the unpadded one. Padding entries get bounds [0, 1), no mask
    semantics and ``valid=False`` (draw id 0; the draw is never used)."""
    pos = pad_positions(inner, padded) if pos is None else pos
    G = padded.n_genes
    low = np.zeros(G, np.int32)
    high = np.ones(G, np.int32)
    is_mask = np.zeros(G, bool)
    mask_bits = np.zeros(G, np.int32)
    ids = np.zeros(G, np.int32)
    valid = np.zeros(G, bool)
    low[pos] = np.asarray(inner.low)
    high[pos] = np.asarray(inner.high)
    is_mask[pos] = np.asarray(inner.is_mask)
    mask_bits[pos] = np.asarray(inner.mask_bits)
    ids[pos] = np.arange(inner.n_genes, dtype=np.int32)
    valid[pos] = True
    return GeneTable(jnp.asarray(low), jnp.asarray(high),
                     jnp.asarray(is_mask), jnp.asarray(mask_bits),
                     jnp.asarray(ids), jnp.asarray(valid))


def pad_genomes(genomes, pos: np.ndarray, n_genes_padded: int) -> np.ndarray:
    """Scatter (..., inner_genes) chromosomes into the padded layout with
    canonical-zero padding (host-side; used for doping seeds and tests)."""
    g = np.asarray(genomes, np.int32)
    out = np.zeros(g.shape[:-1] + (n_genes_padded,), np.int32)
    out[..., pos] = g
    return out

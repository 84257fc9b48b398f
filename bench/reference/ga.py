"""Plain reference of the hardware-approximation GA search (paper §IV).

Written from the paper's description and the system's documented
semantics, in straightforward ``jax.numpy``; it imports nothing of the
system under test. One search is a (μ+λ) NSGA-II run over flat int32
chromosomes:

* genes per layer: masks, signs and pow2 exponents per weight, biases per
  neuron, one bias shift and one output right-shift (paper Fig. 3);
* every gene-shaped uniform is a counter-based Threefry-2x32 draw
  addressed by (key, slot, gene id, row);
* fitness is the exact integer forward pass of Eq. (4) over the whole
  training split, and, with device variation on, over K perturbed device
  instances (instance 0 nominal); area is the FA count of Eq. (2);
* ranking is Deb's constrained dominance by peeling fronts off the full
  dominance matrix, with per-front crowding distances; survivors are the
  best μ of parents and children by (rank, crowding), re-ranked.

``precision="bfloat16"`` runs the forward pass in bfloat16: the control
that a comparison against this reference has to fail.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

SLOT_CROSS_SWAP, SLOT_MUT_DO, SLOT_MUT_VAL = 0, 1, 2
SLOT_INIT = 0
SLOT_DEVICE = 3
_PARITY = np.uint32(0x1BD11BDA)
_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))
_N_COLS = 32
_REDUCE_ROUNDS = 16


@dataclasses.dataclass(frozen=True)
class Search:
    """Static description of one search deployment."""
    sizes: tuple
    pop: int = 256
    pc: float = 0.7
    pm: float = 0.02
    max_acc_loss: float = 0.10
    doping_frac: float = 0.10
    variation_mode: str = "off"      # "off" | "mean" | "worst"
    n_device_samples: int = 8
    device_seed: int = 0
    variation_scale: float = 0.2
    input_bits: int = 4
    act_bits: int = 8
    weight_bits: int = 8
    bias_bits: int = 8
    precision: str = "int32"         # "int32", or "bfloat16" (the control)

    @property
    def n_layers(self):
        return len(self.sizes) - 1

    def in_bits(self, layer):
        return self.input_bits if layer == 0 else self.act_bits


def layout(s: Search):
    """Per-gene bounds and kinds, and each layer's gene offsets."""
    low, high, kind, bits, layers = [], [], [], [], []
    max_exp = s.weight_bits - 2
    off = 0
    for layer in range(s.n_layers):
        fi, fo = s.sizes[layer], s.sizes[layer + 1]
        ib = s.in_bits(layer)
        start = {}
        for name, n, lo, hi in (
                ("masks", fi * fo, 0, 2 ** ib), ("signs", fi * fo, 0, 2),
                ("exps", fi * fo, 0, max_exp + 1),
                ("biases", fo, -2 ** (s.bias_bits - 1), 2 ** (s.bias_bits - 1)),
                ("bshift", 1, 0, max_exp + 1), ("rshift", 1, 0, 8)):
            start[name] = off
            low += [lo] * n
            high += [hi] * n
            kind += [name] * n
            bits += [ib if name == "masks" else 0] * n
            off += n
        layers.append((start, fi, fo, ib))
    kind = np.asarray(kind)
    return dict(low=np.asarray(low, np.int32), high=np.asarray(high, np.int32),
                is_mask=kind == "masks", is_exp=kind == "exps",
                mask_bits=np.asarray(bits, np.int32),
                ids=np.arange(off, dtype=np.int32), layers=layers, n_genes=off)


# -- counter-based draws ----------------------------------------------------

def threefry2x32(k1, k2, x1, x2):
    k1, k2 = jnp.asarray(k1, jnp.uint32), jnp.asarray(k2, jnp.uint32)
    v0 = jnp.asarray(x1, jnp.uint32) + k1
    v1 = jnp.asarray(x2, jnp.uint32) + k2
    ks = (k1, k2, k1 ^ k2 ^ _PARITY)
    for d in range(5):
        for r in _ROT[d % 2]:
            v0 = v0 + v1
            v1 = (v1 << np.uint32(r)) | (v1 >> np.uint32(32 - r))
            v1 = v0 ^ v1
        v0 = v0 + ks[(d + 1) % 3]
        v1 = v1 + ks[(d + 2) % 3] + np.uint32(d + 1)
    return v0, v1


def gene_uniform(key, ids, n, slot):
    """(n, G) float32 uniforms; element (i, j) depends on (key, slot,
    ids[j], i) only. Rows 2r and 2r+1 take the two words of counter
    (ids[j], r)."""
    kd = jax.random.key_data(key) if jnp.issubdtype(
        jnp.asarray(key).dtype, jax.dtypes.prng_key) else key
    s = jnp.uint32(slot)
    k1, k2 = threefry2x32(kd[0], kd[1], s, jnp.uint32(0))
    half = (n + 1) // 2
    y1, y2 = threefry2x32(k1, k2, jnp.asarray(ids, jnp.uint32)[None, :],
                          jnp.arange(half, dtype=jnp.uint32)[:, None])
    words = jnp.stack([y1, y2], axis=1).reshape(2 * half, -1)[:n]
    f = jax.lax.bitcast_convert_type(
        (words >> np.uint32(9)) | np.uint32(0x3F800000), jnp.float32)
    return f - 1.0


def uniform_reset(u, low, high):
    lo, hi = low.astype(jnp.float32), high.astype(jnp.float32)
    return jnp.floor(lo + u * (hi - lo)).astype(jnp.int32)


# -- fitness ----------------------------------------------------------------

def _params(g, start, fi, fo):
    w = fi * fo
    masks = g[start["masks"]:start["masks"] + w].reshape(fi, fo)
    signs = g[start["signs"]:start["signs"] + w].reshape(fi, fo) * 2 - 1
    exps = g[start["exps"]:start["exps"] + w].reshape(fi, fo)
    bias = g[start["biases"]:start["biases"] + fo]
    return masks, signs, exps, bias, g[start["bshift"]], g[start["rshift"]]


def logits(s: Search, lay, g, x):
    """Eq. (4) for one chromosome over all rows: (S, n_in) → (S, n_out)."""
    if s.precision == "bfloat16":
        return _logits_bf16(s, lay, g, x)
    h = x
    for layer, (start, fi, fo, _) in enumerate(lay["layers"]):
        masks, signs, exps, bias, bshift, rshift = _params(g, start, fi, fo)
        terms = signs * jnp.left_shift(h[:, :, None] & masks, exps)
        acc = jnp.sum(terms, axis=1) + jnp.left_shift(bias, bshift)
        if layer < s.n_layers - 1:
            acc = jnp.clip(jnp.right_shift(acc, rshift), 0,
                           2 ** s.act_bits - 1)
        h = acc
    return h


def _logits_bf16(s: Search, lay, g, x):
    """The control: Eq. (4) with bfloat16 products and sums, as a move of
    the fitness onto the matrix unit in bfloat16 would compute it."""
    bf = jnp.bfloat16
    h = x
    for layer, (start, fi, fo, _) in enumerate(lay["layers"]):
        masks, signs, exps, bias, bshift, rshift = _params(g, start, fi, fo)
        w = (signs * jnp.left_shift(1, exps)).astype(bf)
        terms = (h[:, :, None] & masks).astype(bf) * w
        acc = (jnp.sum(terms, axis=1, dtype=bf)
               + jnp.left_shift(bias, bshift).astype(bf))
        if layer < s.n_layers - 1:
            acc = jnp.clip(jnp.floor(acc / jnp.left_shift(1, rshift).astype(
                bf)), 0, 2 ** s.act_bits - 1).astype(jnp.int32)
        h = acc
    return h.astype(jnp.float32)


def device_deltas(s: Search, lay):
    """(K, G) exponent steps of the K device instances; row 0 nominal."""
    u = gene_uniform(jax.random.PRNGKey(s.device_seed), lay["ids"],
                     s.n_device_samples, SLOT_DEVICE)
    sc = jnp.float32(s.variation_scale)
    d = (jnp.where(u >= 1.0 - 0.5 * sc, 1, 0)
         - jnp.where(u < 0.5 * sc, 1, 0)).astype(jnp.int32)
    d = jnp.where(jnp.asarray(lay["is_exp"])[None, :], d, 0)
    return d.at[0].set(0)


def correct_counts(s: Search, lay, pop, x, labels):
    """(N, G) → (N,) correct counts, or (N, K) under device variation."""
    dev = device_deltas(s, lay) if s.variation_mode != "off" else None
    high = jnp.asarray(lay["high"])

    def one(g):
        if dev is None:
            return jnp.sum(jnp.argmax(logits(s, lay, g, x), -1) == labels,
                           dtype=jnp.int32)
        pert = jnp.where(dev == 0, g[None], jnp.clip(g[None] + dev, 0,
                                                     high - 1))
        return jnp.stack([
            jnp.sum(jnp.argmax(logits(s, lay, pert[k], x), -1) == labels,
                    dtype=jnp.int32) for k in range(dev.shape[0])])

    return jax.lax.map(one, pop, batch_size=8)


def _neuron_fa(masks, exps, bias, bshift, in_bits):
    j = jnp.arange(in_bits)
    bits = (masks[:, None] >> j[None, :]) & 1
    col = jnp.clip(j[None, :] + exps[:, None], 0, _N_COLS - 1)
    cols = jnp.sum(bits[..., None] * jax.nn.one_hot(col, _N_COLS,
                                                    dtype=jnp.int32),
                   axis=(0, 1))
    c = jnp.arange(_N_COLS)
    bb = (jnp.abs(bias) >> jnp.clip(c - bshift, 0, 30)) & 1
    cols = cols + jnp.where(c >= bshift, bb, 0)
    def reduce(_, carry):                 # 3:2 counters until height ≤ 2
        cols, total = carry
        fa = cols // 3
        return (cols - 2 * fa + jnp.concatenate([jnp.zeros(1, jnp.int32),
                                                 fa[:-1]]),
                total + jnp.sum(fa))

    cols, total = jax.lax.fori_loop(0, _REDUCE_ROUNDS, reduce,
                                    (cols, jnp.int32(0)))
    return total + jnp.sum(cols >= 2)     # final carry-propagate row


def area(lay, pop):
    """(N, G) → (N,) FA counts (Eq. (2))."""
    def one(g):
        total = jnp.int32(0)
        for start, fi, fo, ib in lay["layers"]:
            masks, _, exps, bias, bshift, _ = _params(g, start, fi, fo)
            per_neuron = jax.vmap(
                lambda m, e, b, bs=bshift, ib=ib: _neuron_fa(m, e, b, bs, ib),
                in_axes=(1, 1, 0))(masks, exps, bias)
            total = total + jnp.sum(per_neuron)
        return total
    return jax.vmap(one)(pop)


def objectives(s: Search, lay, pop, counts, inv_n, baseline, max_loss):
    acc = counts.astype(jnp.float32) * inv_n
    a = area(lay, pop).astype(jnp.float32)
    if acc.ndim == 2:
        nom = acc[:, 0]
        rob = (jnp.mean(acc, axis=-1) if s.variation_mode == "mean"
               else jnp.min(acc, axis=-1))
        obj = jnp.stack([1.0 - nom, a, 1.0 - rob], axis=-1)
    else:
        rob = acc
        obj = jnp.stack([1.0 - acc, a], axis=-1)
    return obj, jnp.maximum(0.0, (baseline - rob) - max_loss)


# -- ranking ----------------------------------------------------------------

def fold(obj):
    """Three objectives → [dense(e_nom)·N + dense(e_rob), area]: the
    lexicographic error pair as one exact float32 key."""
    if obj.shape[1] == 2:
        return obj
    n = obj.shape[0]
    dense = lambda c: jnp.searchsorted(jnp.sort(c), c, side="left").astype(
        jnp.int32)
    comp = (dense(obj[:, 0]) * n + dense(obj[:, 2])).astype(jnp.float32)
    return jnp.stack([comp, obj[:, 1]], axis=-1)


def dominance(obj, viol):
    feas = viol <= 0.0
    le = jnp.all(obj[:, None] <= obj[None], axis=-1)
    lt = jnp.any(obj[:, None] < obj[None], axis=-1)
    fi, fj = feas[:, None], feas[None]
    vi, vj = viol[:, None], viol[None]
    dom = (fi & ~fj) | (~fi & ~fj & (vi < vj)) | (fi & fj & le & lt)
    return dom & ~jnp.eye(obj.shape[0], dtype=bool)


def front_rank(dom):
    """Peel zero-indegree fronts until everyone has a rank."""
    P = dom.shape[0]
    d = dom.astype(jnp.int32)

    def body(r, carry):
        rank, nd = carry
        front = (nd == 0) & (rank == P)
        rank = jnp.where(front, r, rank)
        nd = jnp.where(front, P + 1, nd - front.astype(jnp.int32) @ d)
        return rank, nd

    def more(carry):
        return jnp.any(carry[1] == P) & (carry[0] < P)

    def step(carry):
        r, rank, nd = carry
        return (r + 1, *body(r, (rank, nd)))

    _, rank, _ = jax.lax.while_loop(more, step, (
        jnp.int32(0), jnp.full((P,), P, jnp.int32), jnp.sum(d, axis=0)))
    return rank


def crowding(obj, rank):
    P, M = obj.shape
    dist = jnp.zeros((P,), jnp.float32)
    for m in range(M):
        key = obj[:, m]
        order = jnp.lexsort((key, rank))
        sk, sr = key[order], rank[order]
        same_prev = jnp.concatenate([jnp.array([False]), sr[1:] == sr[:-1]])
        same_next = jnp.concatenate([sr[1:] == sr[:-1], jnp.array([False])])
        prev = jnp.concatenate([sk[:1], sk[:-1]])
        nxt = jnp.concatenate([sk[1:], sk[-1:]])
        fmin = jax.ops.segment_min(key, rank, num_segments=P + 1)
        fmax = jax.ops.segment_max(key, rank, num_segments=P + 1)
        denom = jnp.maximum((fmax - fmin)[sr], 1e-12)
        dist = dist.at[order].add(jnp.where(same_prev & same_next,
                                            (nxt - prev) / denom, jnp.inf))
    return dist


def ranking(obj, viol):
    f = fold(obj)
    rank = front_rank(dominance(f, viol))
    return rank, crowding(f, rank)


# -- the search -------------------------------------------------------------

def init(s: Search, lay, data, seed, doping):
    """Random population, the first rows replaced by the doping genomes."""
    key, k_pop = jax.random.split(jax.random.PRNGKey(seed))
    pop = uniform_reset(gene_uniform(k_pop, lay["ids"], s.pop, SLOT_INIT),
                        jnp.asarray(lay["low"]), jnp.asarray(lay["high"]))
    if doping is not None:
        n_dope = max(1, int(s.doping_frac * s.pop))
        reps = np.resize(np.arange(doping.shape[0]), n_dope)
        pop = pop.at[:n_dope].set(jnp.asarray(doping)[reps])
    counts = correct_counts(s, lay, pop, data["x"], data["y"])
    obj, viol = objectives(s, lay, pop, counts, data["inv_n"],
                           data["baseline"], data["max_loss"])
    rank, crowd = ranking(obj, viol)
    return dict(pop=pop, obj=obj, viol=viol, rank=rank, crowd=crowd,
                key=key, gen=jnp.int32(0))


def generation(s: Search, lay, data, st):
    """One (μ+λ) generation: tournament → uniform crossover → mutation →
    clip → fitness of the children → survivors of the pooled ranking."""
    P = s.pop
    low, high = jnp.asarray(lay["low"]), jnp.asarray(lay["high"])
    ids = jnp.asarray(lay["ids"])
    key, k_off = jax.random.split(st["key"])
    k_sel, k_cx, k_var = jax.random.split(k_off, 3)
    idx = jax.random.randint(k_sel, (P, 2), 0, P)
    a, b = idx[:, 0], idx[:, 1]
    rank, crowd = st["rank"], st["crowd"]
    a_wins = (rank[a] < rank[b]) | ((rank[a] == rank[b])
                                    & (crowd[a] > crowd[b]))
    parents = jnp.where(a_wins, a, b)
    pa, pb = st["pop"][parents[:P // 2]], st["pop"][parents[P // 2:]]
    do_cx = jax.random.uniform(k_cx, (P // 2, 1)) < jnp.float32(s.pc)
    swap = do_cx & (gene_uniform(k_var, ids, P // 2, SLOT_CROSS_SWAP) < 0.5)
    kids = jnp.concatenate([jnp.where(swap, pb, pa),
                            jnp.where(swap, pa, pb)], axis=0)
    do_mut = gene_uniform(k_var, ids, P, SLOT_MUT_DO) < jnp.float32(s.pm)
    u = gene_uniform(k_var, ids, P, SLOT_MUT_VAL)
    bitpos = jnp.floor(u * jnp.maximum(jnp.asarray(lay["mask_bits"]), 1)
                       ).astype(jnp.int32)
    flipped = kids ^ jnp.left_shift(1, bitpos)
    mutated = jnp.where(jnp.asarray(lay["is_mask"]), flipped,
                        uniform_reset(u, low, high))
    kids = jnp.clip(jnp.where(do_mut, mutated, kids), low, high - 1)

    counts = correct_counts(s, lay, kids, data["x"], data["y"])
    c_obj, c_viol = objectives(s, lay, kids, counts, data["inv_n"],
                               data["baseline"], data["max_loss"])
    pool = jnp.concatenate([st["pop"], kids])
    obj = jnp.concatenate([st["obj"], c_obj])
    viol = jnp.concatenate([st["viol"], c_viol])
    # the pool is folded once; the survivors are re-ranked on its fold
    f = fold(obj)
    r = front_rank(dominance(f, viol))
    keep = jnp.lexsort((-crowding(f, r), r))[:P]
    rank = front_rank(dominance(f[keep], viol[keep]))
    new = dict(pop=pool[keep], obj=obj[keep], viol=viol[keep], rank=rank,
               crowd=crowding(f[keep], rank), key=key, gen=st["gen"] + 1)
    return new, (new["obj"][:, 0].min(), new["obj"][:, 1].min())


def quantize(x01, bits):
    hi = 2 ** bits - 1
    return jnp.clip(jnp.round(jnp.asarray(x01, jnp.float32) * hi), 0,
                    hi).astype(jnp.int32)


def front(obj, pop, viol):
    """Feasible non-dominated rows, sorted by the first objective."""
    obj, pop = np.asarray(obj), np.asarray(pop)
    feas = np.asarray(viol) <= 0
    if not feas.any():
        feas = np.ones_like(feas)
    obj, pop = obj[feas], pop[feas]
    le = np.all(obj[:, None] <= obj[None], axis=-1)
    lt = np.any(obj[:, None] < obj[None], axis=-1)
    nd = ~(le & lt & ~np.eye(len(obj), dtype=bool)).any(axis=0)
    idx = np.where(nd)[0]
    order = idx[np.argsort(obj[idx, 0])]
    return {"objectives": obj[order], "genomes": pop[order]}


class Reference:
    """Compiled reference searches over one dataset."""

    def __init__(self, s: Search, x01, labels, baseline_acc):
        self.s = s
        self.lay = layout(s)
        self.data = dict(
            x=quantize(x01, s.input_bits), y=jnp.asarray(labels, jnp.int32),
            inv_n=jnp.float32(1.0 / len(labels)),
            baseline=jnp.float32(baseline_acc),
            max_loss=jnp.float32(s.max_acc_loss))
        self._init = jax.jit(lambda d, seed, dope: init(s, self.lay, d, seed,
                                                        dope))
        self._gen = jax.jit(lambda d, st: generation(s, self.lay, d, st))

    def run(self, seed, generations, doping=None):
        """Whole search → (final state, per-generation (best_err,
        best_area), front), all as numpy."""
        st = self._init(self.data, jnp.int32(seed), doping)
        best = []
        for _ in range(generations):
            st, aux = self._gen(self.data, st)
            best.append(aux)
        st = jax.tree_util.tree_map(np.asarray, st)
        best = np.asarray(jax.device_get(best), np.float32).reshape(-1, 2)
        return st, best, front(st["obj"], st["pop"], st["viol"])

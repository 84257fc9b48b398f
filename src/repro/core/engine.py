"""Pure functional GA engine: the single NSGA-II generation step shared by
every trainer in the repo, plus whole-run batching.

The paper's headline numbers (Tables I-III, Fig. 4) are statistics over
repeated GA runs, so the engine is built as pure functions over two pytrees:

  * :class:`Problem` — the (quantized inputs, labels, baseline accuracy)
    data leaves plus the static ``GenomeSpec``/``GAConfig`` aux, and
  * :class:`GAState` — one population's evolutionary state.

Layers on top of these:

  * :func:`generation`   — ONE (μ+λ) NSGA-II generation. This is the only
    generation-step implementation in ``repro.core``; ``GATrainer`` and
    ``islands.build_island_step`` are thin adapters over it.
  * :func:`run_scanned`  — all generations as a single ``lax.scan`` dispatch.
  * :func:`run_batch`    — ``jax.vmap`` of (init → scanned run) over a
    leading seed axis: an N-seed sweep on one dataset is ONE dispatch with
    batched PRNG keys, batched doping and per-run dedup, instead of N
    sequential ``GATrainer.run`` calls (and N recompilations). The swept
    GA hyperparameters (crossover/mutation rates, the accuracy-loss
    bound) are traced ``Problem`` leaves, so ``repro.core.sweep.run_grid``
    extends the same mechanism to a full (seed × config) grid.

Everything stays bit-identical to the pre-engine trainer/island loops:
integer correct-counts are the only cached quantity (dedup), the float
objective chain is elementwise (fusion cannot reassociate it), and the
front-peel gemv in ``nsga2`` is integer-exact in float32 — so jit, scan,
vmap and shard_map all produce the same states.
"""
from __future__ import annotations

import dataclasses
import warnings

import numpy as np
import jax
import jax.numpy as jnp

from . import genome as genome_mod
from .genome import GeneTable, GenomeSpec, MLPTopology, random_population
from .quantize import quantize_inputs
from .mlp import population_accuracy
from .area import population_area
from .dedup import EvalCache, cache_init, dedup_eval
from ..kernels.pop_ranking import population_ranking
from .pareto import pareto_front
from ..kernels.pop_mlp import population_correct
from ..kernels.policy import BackendPolicy

# "unlimited" sentinel of the per-lane generation-budget leaf: with the
# budget gate on, a lane whose state.gen can never reach its budget is
# simply never retired (int32 max — state.gen < NO_BUDGET always holds
# for any realistic run length).
NO_BUDGET = np.int32(2**31 - 1)

_LEGACY_BACKEND_FIELDS = (("fitness", "fitness_backend"),
                          ("variation", "variation_backend"),
                          ("generation", "generation_backend"),
                          ("ranking", "ranking_backend"))
_legacy_backend_warned = False


def _warn_legacy_backends(fields):
    global _legacy_backend_warned
    if _legacy_backend_warned:
        return
    _legacy_backend_warned = True
    warnings.warn(
        f"GAConfig({', '.join(fields)}=...) is deprecated; pass "
        "GAConfig(backends=BackendPolicy(...)) instead "
        "(repro.kernels.BackendPolicy, fields fitness/variation/"
        "generation/ranking)", DeprecationWarning, stacklevel=3)


@dataclasses.dataclass(frozen=True)
class GAConfig:
    pop_size: int = 256
    generations: int = 150
    crossover_rate: float = 0.7      # paper §V-A ("0.7")
    mutation_rate_gene: float = 0.02  # paper's "0.2" read per-chromosome; see operators.py
    doping_frac: float = 0.10        # paper §IV-A (~10 % nearly non-approximate)
    max_acc_loss: float = 0.10       # paper §IV-A (10 % feasibility bound)
    acc_only: bool = False           # Table III "GA" column: no area objective
    seed: int = 0
    log_every: int = 10
    # -- backend selection --------------------------------------------------
    # ``backends`` is THE knob: one validated BackendPolicy naming a
    # backend per dispatch path (fitness auto|kernel|interpret|ref|jnp,
    # variation auto|kernel|interpret|ref|ops, generation
    # auto|kernel|interpret|ref|phases, ranking auto|sweep|matrix — every
    # non-oracle choice bit-identical, see repro.kernels). The four
    # ``*_backend`` fields below are DEPRECATED aliases: a non-None value
    # overrides the matching policy field (and warns once), and after
    # construction they always mirror the resolved policy, so legacy
    # readers keep working.
    fitness_backend: str | None = None
    variation_backend: str | None = None
    generation_backend: str | None = None
    ranking_backend: str | None = None
    # population tile — shared by the fitness "ref" backend and the
    # variation Pallas kernel (one knob tiles both hot paths)
    pop_tile: int = 64
    sample_tile: int = 256           # sample tile ("ref" backend)
    # duplicate-chromosome eval caching: True/"cache" carries a cross-
    # generation EvalCache in GAState (the default), "legacy" dedups
    # within one generation only, False evaluates everything
    dedup: bool | str = True
    cache_slots: int = 4096          # EvalCache capacity (rounded to 2^k)
    cache_probes: int = 4            # open-addressing probe depth
    scan: bool = True                # lax.scan over generations (one dispatch)
    # internal: name of the enclosing vmap/shard_map axis batching whole
    # runs. Set by run_batch/sweep.run_grid so the dedup tile-skip stays a
    # real lax.cond under vmap (shared n_valid via lax.pmax); never set it
    # on a problem that runs outside that axis.
    batch_axis: str | None = None
    # -- device-variation Monte-Carlo fitness (robust printed MLPs) ---------
    # "off" (default; bit-identical to the nominal single-instance path),
    # "mean" (expected accuracy over the K sampled device instances) or
    # "worst" (worst-case instance). When on, fitness evaluates every
    # chromosome on K perturbed devices (engine.device_deltas) and the
    # objectives grow a third robustness column next to [error, area].
    variation_mode: str = "off"
    n_device_samples: int = 8        # K; instance 0 is always nominal
    # static seed of the SLOT_DEVICE draws — deliberately NOT the run key,
    # so every run path / seed / lane of a batch sees the same K devices
    device_seed: int = 0
    variation_scale: float = 0.2     # default P(an exponent gene shifts ±1)
    # -- per-lane generation budgets (the serve path) -----------------------
    # ``None`` (default): no budget machinery — ``run_scanned`` runs every
    # requested generation exactly as before, zero overhead. An integer
    # turns the budget gate ON (a *static* switch): the traced
    # ``Problem.generations_budget`` leaf (defaulted from this value,
    # overridable per lane) then bounds how many generations a lane
    # actually evolves — once ``state.gen`` reaches its budget the lane
    # becomes a no-op carry passthrough (key/gen/cache untouched, zero
    # rows contributed to the shared dedup evaluation bound), which is the
    # retirement mechanism ``repro.serve`` schedules around. A run with
    # budget == generations is bit-identical to the ungated path.
    generations_budget: int | None = None
    backends: BackendPolicy | None = None

    def __post_init__(self):
        pol = self.backends if self.backends is not None else BackendPolicy()
        legacy = {path: getattr(self, field)
                  for path, field in _LEGACY_BACKEND_FIELDS}
        given = {path: v for path, v in legacy.items()
                 if v is not None and v != getattr(pol, path)}
        if given:
            _warn_legacy_backends(sorted(f"{p}_backend" for p in given))
            pol = dataclasses.replace(pol, **given)
        object.__setattr__(self, "backends", pol)
        for path, field in _LEGACY_BACKEND_FIELDS:
            object.__setattr__(self, field, getattr(pol, path))
        if self.variation_mode not in ("off", "mean", "worst"):
            raise ValueError(
                f"unknown GAConfig.variation_mode {self.variation_mode!r}: "
                "expected 'off', 'mean' or 'worst'")
        if int(self.n_device_samples) < 1:
            raise ValueError("GAConfig.n_device_samples must be >= 1, got "
                             f"{self.n_device_samples}")
        if not 0.0 <= float(self.variation_scale) <= 1.0:
            raise ValueError("GAConfig.variation_scale must lie in [0, 1], "
                             f"got {self.variation_scale}")
        if self.variation_mode != "off" and pol.fitness == "jnp":
            raise ValueError(
                "variation_mode != 'off' needs a count-based fitness "
                "backend (auto/kernel/interpret/ref): the 'jnp' oracle "
                "has no device-instance axis")

    def with_backends(self, backends) -> "GAConfig":
        """Swap the whole :class:`BackendPolicy` — the ONLY safe way.

        A bare ``dataclasses.replace(cfg, backends=...)`` re-runs
        ``__post_init__`` with the *mirrored* legacy ``*_backend`` fields
        still holding the OLD names, which silently overrides the new
        policy back to the old one. This clears the mirrors first."""
        clear = {field: None for _, field in _LEGACY_BACKEND_FIELDS}
        return dataclasses.replace(self, backends=backends, **clear)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GAState:
    pop: jnp.ndarray        # (P, n_genes) int32
    obj: jnp.ndarray        # (P, 2) [error, area]
    viol: jnp.ndarray       # (P,)
    rank: jnp.ndarray       # (P,)
    crowd: jnp.ndarray      # (P,)
    counts: jnp.ndarray     # (P,) int32 correct counts (dedup reuse; zeros
    #                         when dedup is off — obj/viol stay the source
    #                         of truth for selection)
    key: jnp.ndarray
    gen: jnp.ndarray
    cache: EvalCache | None = None   # cross-generation eval cache (or None)

    def tree_flatten(self):
        return (self.pop, self.obj, self.viol, self.rank, self.crowd,
                self.counts, self.key, self.gen, self.cache), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Problem:
    """One (dataset, topology, config) GA problem as a pytree.

    Array leaves trace through jit/vmap/shard_map; ``spec``/``cfg`` ride in
    the aux data as statics. Besides the data (``x_int``, ``labels``,
    ``baseline_acc``), the *swept* GA hyperparameters — crossover rate,
    per-gene mutation rate and the accuracy-loss constraint bound — are
    float32 scalar leaves (filled from ``cfg`` when not given), so a config
    axis can batch whole runs over them: ``sweep.run_grid`` vmaps one
    dispatch over a (seed × hyperparameter) grid. Scalar-leaf arithmetic is
    bit-identical to the weakly-typed Python-float arithmetic the statics
    produced (``float32 ∘ float`` promotes to the same float32 ops).

    Padded-canonical problems (suite batching): ``genes`` (the per-gene
    GeneTable the operators read), ``out_mask`` (valid output columns for
    the fitness argmax) and ``inv_n`` (the float32 1/n_samples factor of
    the count→accuracy conversion) are leaves too, defaulted from the spec
    for an ordinary problem. :func:`pad_problem` replaces them with a
    smaller topology's embedding into a shared max-shape spec, which is how
    ``sweep.run_suite`` batches five different datasets/topologies as lanes
    of ONE vmapped dispatch — each lane bit-identical to its unpadded
    sequential run (see ``genome.GeneTable``).
    """
    x_int: jnp.ndarray          # (S, n_in) int32 quantized inputs
    labels: jnp.ndarray         # (S,) int32; −1 marks padded samples
    baseline_acc: jnp.ndarray   # () float32
    spec: GenomeSpec
    cfg: GAConfig
    crossover_rate: jnp.ndarray = None       # () float32
    mutation_rate_gene: jnp.ndarray = None   # () float32
    max_acc_loss: jnp.ndarray = None         # () float32
    genes: GeneTable = None                  # per-gene operator metadata
    out_mask: jnp.ndarray = None             # (n_out,) int32 valid columns
    inv_n: jnp.ndarray = None                # () float32 = 1 / n_valid_samples
    n_valid_samples: jnp.ndarray = None      # () int32 true (unpadded) S
    variation_scale: jnp.ndarray = None      # () float32 device-variation
    #                                          strength (sweepable leaf)
    generations_budget: jnp.ndarray = None   # () int32 per-lane generation
    #                                          budget (INT32_MAX = unlimited;
    #                                          only read when the static
    #                                          cfg.generations_budget gate
    #                                          is on — see run_scanned)

    def __post_init__(self):
        if self.crossover_rate is None:
            self.crossover_rate = jnp.float32(self.cfg.crossover_rate)
        if self.mutation_rate_gene is None:
            self.mutation_rate_gene = jnp.float32(self.cfg.mutation_rate_gene)
        if self.max_acc_loss is None:
            self.max_acc_loss = jnp.float32(self.cfg.max_acc_loss)
        if self.genes is None:
            self.genes = self.spec.table()
        if self.out_mask is None:
            self.out_mask = jnp.ones((self.spec.topo.sizes[-1],), jnp.int32)
        if self.inv_n is None:
            self.inv_n = jnp.float32(1.0 / self.labels.shape[0])
        if self.n_valid_samples is None:
            self.n_valid_samples = jnp.int32(self.labels.shape[0])
        if self.variation_scale is None:
            self.variation_scale = jnp.float32(self.cfg.variation_scale)
        if self.generations_budget is None:
            self.generations_budget = jnp.int32(
                NO_BUDGET if self.cfg.generations_budget is None
                else self.cfg.generations_budget)

    def tree_flatten(self):
        return ((self.x_int, self.labels, self.baseline_acc,
                 self.crossover_rate, self.mutation_rate_gene,
                 self.max_acc_loss, self.genes, self.out_mask,
                 self.inv_n, self.n_valid_samples, self.variation_scale,
                 self.generations_budget),
                (self.spec, self.cfg))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children[:3], *aux, *children[3:])

    def with_hypers(self, crossover_rate=None, mutation_rate_gene=None,
                    max_acc_loss=None, baseline_acc=None,
                    variation_scale=None) -> "Problem":
        """Replace the swept hyperparameter leaves (None keeps the current
        value); traced replacements are how a sweep builds its cells.
        ``baseline_acc`` is sweepable too — it only enters the violation
        chain, so sweeping it varies the constraint pressure of the
        feasibility bound without touching the data. ``variation_scale``
        sweeps the device-variation strength the same way (it only enters
        ``device_deltas``)."""
        kw = {k: v for k, v in [("crossover_rate", crossover_rate),
                                ("mutation_rate_gene", mutation_rate_gene),
                                ("max_acc_loss", max_acc_loss),
                                ("baseline_acc", baseline_acc),
                                ("variation_scale", variation_scale)]
              if v is not None}
        return dataclasses.replace(self, **kw)

    def replace_cfg(self, **kw) -> "Problem":
        """New Problem with ``cfg`` fields replaced (statics only)."""
        return dataclasses.replace(self, cfg=dataclasses.replace(self.cfg, **kw))

    @classmethod
    def from_data(cls, topo: MLPTopology, x01, labels,
                  cfg: GAConfig | None = None,
                  baseline_acc: float | None = None,
                  spec: GenomeSpec | None = None) -> "Problem":
        """Build from float [0,1] features (chance-level baseline if None)."""
        cfg = cfg if cfg is not None else GAConfig()
        spec = spec if spec is not None else GenomeSpec(topo)
        x_int = quantize_inputs(jnp.asarray(x01, jnp.float32), topo.input_bits)
        return cls(x_int, jnp.asarray(labels, jnp.int32),
                   jnp.float32(1.0 if baseline_acc is None else baseline_acc),
                   spec, cfg)


def dedup_mode(cfg: GAConfig) -> str:
    """Resolve ``cfg.dedup`` to "off" | "legacy" | "cache".

    The "jnp" fitness oracle has no n_valid_rows tile skip — dedup buys
    nothing there, so it is forced off. ``True`` (the default) means the
    cross-generation cached path; ``"legacy"`` keeps the within-generation
    dedup of earlier revisions; ``False`` evaluates everything. Anything
    else raises (an unknown string used to fall through to "cache"
    silently).
    """
    if cfg.dedup not in (True, False, "cache", "legacy"):
        raise ValueError(f"unknown GAConfig.dedup {cfg.dedup!r}: expected "
                         "True, False, 'cache' or 'legacy'")
    if not cfg.dedup or cfg.backends.fitness == "jnp":
        return "off"
    return "legacy" if cfg.dedup == "legacy" else "cache"


def use_dedup(cfg: GAConfig) -> bool:
    """Whether any dedup (legacy or cached) is active."""
    return dedup_mode(cfg) != "off"


def pad_problem(problem: Problem, spec_pad: GenomeSpec,
                n_samples: int | None = None) -> Problem:
    """Embed ``problem`` into the padded max-shape layout of ``spec_pad``.

    Returns a Problem that runs bit-identically to the original: genes keep
    their draw ids and bounds at the embedded positions (padding is
    canonical zero — ``genome.padded_table``), extra input columns are
    zero (AND-masked activations contribute nothing), ``out_mask`` pins
    padded output columns below any real logit, and ``inv_n`` /
    ``n_valid_samples`` keep the original sample count (the latter lets
    the tiled fitness backends *skip* all-padding sample tiles — see
    :func:`population_counts`). ``n_samples`` additionally pads the sample
    axis (features 0, label −1 — never matched by an argmax) so several
    datasets can stack on a suite axis.

    The count-based fitness backends handle all of this exactly; the "jnp"
    oracle backend does not (it averages over the padded sample axis), so
    padded problems must use ``ref``/``kernel``/``interpret``/``auto``.
    """
    if problem.cfg.backends.fitness == "jnp":
        raise ValueError("padded problems need a count-based fitness "
                         "backend (ref/kernel/interpret/auto), not 'jnp'")
    inner = problem.spec
    pos = genome_mod.pad_positions(inner, spec_pad)
    genes = genome_mod.padded_table(inner, spec_pad, pos)
    x, labels = problem.x_int, problem.labels
    S = x.shape[0]
    pad_cols = spec_pad.topo.sizes[0] - x.shape[1]
    pad_rows = 0 if n_samples is None else n_samples - S
    if pad_rows < 0:
        raise ValueError(f"n_samples={n_samples} < dataset size {S}")
    if pad_cols or pad_rows:
        x = jnp.pad(x, ((0, pad_rows), (0, pad_cols)))
        labels = jnp.pad(labels, (0, pad_rows), constant_values=-1)
    out_mask = np.zeros((spec_pad.topo.sizes[-1],), np.int32)
    out_mask[: inner.topo.sizes[-1]] = 1
    return Problem(x, labels, problem.baseline_acc, spec_pad, problem.cfg,
                   problem.crossover_rate, problem.mutation_rate_gene,
                   problem.max_acc_loss, genes, jnp.asarray(out_mask),
                   problem.inv_n, problem.n_valid_samples,
                   problem.variation_scale, problem.generations_budget)


# -- fitness ----------------------------------------------------------------

def variation_on(cfg: GAConfig) -> bool:
    """Whether device-variation Monte-Carlo fitness is active."""
    return cfg.variation_mode != "off"


def device_deltas(problem: Problem):
    """(K, G) int32 exponent perturbations of the K sampled device
    instances (K = ``cfg.n_device_samples``); row 0 is the nominal device
    (all zero).

    The draws are gene-addressed — ``genome.gene_uniform`` under
    ``SLOT_DEVICE``, keyed by the *static* ``GAConfig.device_seed`` rather
    than the run key — so every run path (trainer / run_batch / run_grid /
    run_suite / islands), every seed of a batch and every padded suite
    lane sees the same K devices, and an embedded gene draws the same
    number as in its unpadded layout. A uniform u maps to −1 when
    u < scale/2 and +1 when u ≥ 1 − scale/2 (±1 exponent step ≈ the
    printed resistor leaving its pow2 bin); ``variation_scale`` is a
    traced Problem leaf, so it sweeps via ``with_hypers`` like
    ``baseline_acc``. Only valid exponent genes perturb — masks, signs,
    biases, shifts and padding lanes always get delta 0, which
    :func:`genome.apply_device_deltas` passes through bit-untouched.
    """
    cfg = problem.cfg
    t = problem.genes
    key = jax.random.PRNGKey(cfg.device_seed)
    u = genome_mod.gene_uniform(key, t.ids, cfg.n_device_samples,
                                slot=genome_mod.SLOT_DEVICE)
    s = problem.variation_scale
    delta = (jnp.where(u >= 1.0 - 0.5 * s, 1, 0)
             - jnp.where(u < 0.5 * s, 1, 0)).astype(jnp.int32)
    live = problem.spec.is_exp & t.valid
    delta = jnp.where(live[None, :], delta, 0)
    return delta.at[0].set(0)


def population_counts(problem: Problem, pop, n_valid=None):
    """(N, G) → (N,) int32 correct counts via the dispatcher — or (N, K)
    per-device-instance counts when device-variation MC fitness is on.

    Rows at or past ``n_valid`` land in skipped tiles (dedup fast path)
    and carry unspecified values — callers overwrite them. Dedup caches
    these *integer* counts, never derived floats: the float objective
    chain is then built once per generation on the actual children, so
    XLA fusion decisions can't introduce ulp drift vs the naive loop.

    Sample tiles past the ``n_valid_samples`` bound are skipped the same
    way: padded samples (label −1) contribute zero counts, so dropping
    their tiles is bit-identical. Under a whole-run batch the bound is
    the ``lax.pmax`` over the batch axis — an unbatched scalar, keeping
    the tile-skip a real ``lax.cond`` — so a suite dispatch costs each
    lane its bucket's widest dataset, not the global padded axis."""
    cfg = problem.cfg
    n_samp = problem.n_valid_samples
    if cfg.batch_axis is not None:
        n_samp = jax.lax.pmax(n_samp, cfg.batch_axis)
    dev = device_deltas(problem) if variation_on(cfg) else None
    return population_correct(
        pop, problem.x_int, problem.labels, spec=problem.spec,
        backend=cfg.backends.fitness, pop_tile=cfg.pop_tile,
        sample_tile=cfg.sample_tile, n_valid_rows=n_valid,
        n_valid_samples=n_samp, out_mask=problem.out_mask,
        dev=dev, gene_high=problem.genes.high)


def counts_accuracy(problem: Problem, counts):
    """int32 correct counts → float32 accuracy: THE conversion every
    trainer shares. ``inv_n`` is a float32 leaf computed host-side as
    1/n_valid_samples, so the product is bit-identical to the oracle's
    ``jnp.mean`` (mean lowers to sum × reciprocal(n), and the sum of 0/1
    float32 terms equals the count exactly for n < 2²⁴) while letting a
    padded problem divide by its own sample count under vmap."""
    return counts.astype(jnp.float32) * problem.inv_n


def objectives(problem: Problem, pop, acc):
    """(pop, accuracy) → ((N, 2) [error, area], (N,) violation).

    Under device-variation MC fitness ``acc`` is (N, K) per-instance
    accuracy (column 0 nominal) and the result grows a third robustness
    column: (N, 3) [nominal error, area, robust error] where robust
    accuracy is the instance mean (``variation_mode="mean"``) or minimum
    (``"worst"``). The feasibility bound then constrains the *robust*
    accuracy — a design only counts as feasible if it holds up across the
    sampled devices. ``pop_ranking`` folds the third column
    lexicographically, so both ranking backends stay exact."""
    cfg = problem.cfg
    if acc.ndim == 2:            # device-variation MC: (N, K) instances
        nom = acc[:, 0]
        rob = (jnp.mean(acc, axis=-1) if cfg.variation_mode == "mean"
               else jnp.min(acc, axis=-1))
        if cfg.acc_only:
            area = jnp.zeros_like(nom)
        else:
            area = population_area(problem.spec, pop).astype(jnp.float32)
        obj = jnp.stack([1.0 - nom, area, 1.0 - rob], axis=-1)
        viol = jnp.maximum(0.0, (problem.baseline_acc - rob)
                           - problem.max_acc_loss)
        return obj, viol
    if cfg.acc_only:             # conventional GA training (Table III)
        area = jnp.zeros_like(acc)
    else:
        area = population_area(problem.spec, pop).astype(jnp.float32)
    obj = jnp.stack([1.0 - acc, area], axis=-1)
    viol = jnp.maximum(0.0,
                       (problem.baseline_acc - acc) - problem.max_acc_loss)
    return obj, viol


def fitness(problem: Problem, pop):
    """(N, G) → ((N, 2) objectives, (N,) violation) — non-dedup path."""
    if problem.cfg.backends.fitness == "jnp":
        acc = population_accuracy(problem.spec, pop, problem.x_int,
                                  problem.labels)
    else:
        acc = counts_accuracy(problem, population_counts(problem, pop))
    return objectives(problem, pop, acc)


# -- init -------------------------------------------------------------------

def _doping_array(doping_seeds):
    if doping_seeds is None:
        return None
    if isinstance(doping_seeds, (jnp.ndarray, np.ndarray)):
        return jnp.asarray(doping_seeds)
    return jnp.asarray(np.stack([np.asarray(s) for s in doping_seeds]))


def initial_population(problem: Problem, key, doping_seeds=None,
                       pop_size: int | None = None):
    """Random population doped with ~doping_frac nearly non-approximate
    chromosomes (paper §IV-A). ``doping_seeds``: sequence of genomes or an
    (n, n_genes) array; the same seeds dope every run of a batch."""
    cfg = problem.cfg
    P = cfg.pop_size if pop_size is None else pop_size
    pop = random_population(key, problem.genes, P)
    dope = _doping_array(doping_seeds)
    if dope is not None:
        n_dope = max(1, int(cfg.doping_frac * P))
        reps = np.resize(np.arange(dope.shape[0]), n_dope)
        pop = pop.at[:n_dope].set(dope[jnp.asarray(reps)])
    return pop


def initial_counts(problem: Problem, pop, cache: EvalCache | None = None):
    """Integer correct counts (+ rows actually evaluated) for an initial
    population; doping replicates seeds, so dedup scores them once. With a
    ``cache`` (the cross-generation path) the initial unique rows are also
    inserted (stamp 0) and ``(counts, n_eval, cache)`` is returned."""
    eval_fn = lambda rows, n: population_counts(problem, rows, n)
    if cache is not None:
        counts, n_eval, _, cache = dedup_eval(
            eval_fn, pop, axis_name=problem.cfg.batch_axis,
            gene_mask=problem.genes.valid, cache=cache, gen=jnp.int32(0),
            ids=problem.genes.ids)
        return counts, n_eval, cache
    if use_dedup(problem.cfg):
        return dedup_eval(eval_fn, pop, axis_name=problem.cfg.batch_axis,
                          gene_mask=problem.genes.valid,
                          ids=problem.genes.ids)
    return population_counts(problem, pop), jnp.int32(pop.shape[0])


def init_state(problem: Problem, key, doping_seeds=None,
               pop_size: int | None = None):
    """Pure init: root PRNG key → (GAState, n_evaluated_rows).

    Traceable end to end — ``GATrainer`` jits it with the problem as an
    argument and ``run_batch``/``sweep.run_grid`` vmap it, all bit-for-bit
    equal: the counts are integers (fusion-proof) and the float objective
    chain is elementwise. In the default dedup mode the state also carries
    a fresh :class:`~repro.core.dedup.EvalCache` seeded with the initial
    population's unique rows (per lane under vmap — each batched run gets
    its own independent table slice).
    """
    cfg = problem.cfg
    key, k_pop = jax.random.split(key)
    pop = initial_population(problem, k_pop, doping_seeds, pop_size)
    cache = None
    if cfg.backends.fitness == "jnp":
        counts = jnp.zeros((pop.shape[0],), jnp.int32)
        n_eval = jnp.int32(pop.shape[0])
        obj, viol = fitness(problem, pop)
    else:
        if dedup_mode(cfg) == "cache":
            val_shape = ((cfg.n_device_samples,) if variation_on(cfg)
                         else ())
            cache = cache_init(cfg.cache_slots, problem.genes.low.shape[0],
                               cfg.cache_probes, val_shape=val_shape)
            counts, n_eval, cache = initial_counts(problem, pop, cache)
        else:
            counts, n_eval = initial_counts(problem, pop)
        obj, viol = objectives(problem, pop, counts_accuracy(problem, counts))
    rank, crowd = population_ranking(obj, viol, backend=cfg.backends.ranking)
    return GAState(pop, obj, viol, rank, crowd, counts, key,
                   jnp.int32(0), cache), n_eval


# -- the generation step ----------------------------------------------------

def generation(problem: Problem, state: GAState, active=None):
    """One (μ+λ) NSGA-II generation; returns (state, aux) where aux is
    (best_err, best_area, n_evaluated_rows, n_cache_hits).

    THE single generation-step entry point: ``GATrainer`` jits/scans it
    directly and each island runs it locally under ``shard_map`` (the
    population size is taken from the state, so islands evolve their
    ``island_pop``-sized shard with the same code). The actual step is the
    ``repro.kernels.pop_generation`` dispatcher — the fused jnp path with
    the cross-generation cache on CPU, the variation+fitness megakernel on
    TPU, the per-phase oracle chain on request — every backend
    bit-identical in the resulting states (``GAConfig.generation_backend``).

    ``active`` (optional () bool, per lane under vmap): when False, the
    lane contributes zero rows to the shared dedup evaluation bound and
    its EvalCache is left bitwise untouched; the caller is responsible for
    where-selecting the non-cache state leaves (see ``run_scanned``).
    """
    from ..kernels.pop_generation import population_generation
    return population_generation(problem, state, active=active)


def lane_active(problem: Problem, state: GAState):
    """() bool: whether this lane still has generation budget left."""
    return state.gen < problem.generations_budget


def _budgeted_generation(problem: Problem, state: GAState):
    """Budget-gated generation step: a lane whose budget is exhausted is a
    bitwise no-op carry passthrough (pop/obj/key/gen/cache untouched, aux
    reporting zero evaluated rows), so ``repro.serve`` can park retired
    lanes inside a shared vmapped scan at (almost) zero cost.

    Skipping is two-level. Per lane, ``active`` flows into the dedup pack
    so an inactive lane contributes 0 to the shared ``pmax`` evaluation
    bound (its population tiles are genuinely skipped) and its EvalCache
    sees no inserts or re-stamps; the surviving where-select then pins the
    remaining state leaves. Across the whole batch, when *every* lane is
    inactive the ``pmax``-reduced flag is an unbatched scalar, so the
    ``lax.cond`` stays a real branch and the entire generation body is
    skipped — the segment costs one cheap dead branch per generation.
    """
    active = lane_active(problem, state)
    axis = problem.cfg.batch_axis
    any_active = (active if axis is None else
                  jax.lax.pmax(active.astype(jnp.int32), axis) > 0)

    def live(st):
        new, aux = generation(problem, st, active=active)
        sel = lambda n, o: jnp.where(active, n, o)
        # cache leaves need no select: the gated dedup pack already left a
        # retired lane's table bitwise unchanged (zero inserts/re-stamps)
        new = dataclasses.replace(
            new, pop=sel(new.pop, st.pop), obj=sel(new.obj, st.obj),
            viol=sel(new.viol, st.viol), rank=sel(new.rank, st.rank),
            crowd=sel(new.crowd, st.crowd), counts=sel(new.counts, st.counts),
            key=sel(new.key, st.key), gen=sel(new.gen, st.gen))
        aux = (sel(aux[0], st.obj[:, 0].min()),
               sel(aux[1], st.obj[:, 1].min()),
               sel(aux[2], jnp.int32(0)), sel(aux[3], jnp.int32(0)))
        return new, aux

    def dead(st):
        return st, (st.obj[:, 0].min(), st.obj[:, 1].min(),
                    jnp.int32(0), jnp.int32(0))

    return jax.lax.cond(any_active, live, dead, state)


def run_scanned(problem: Problem, state: GAState, generations: int):
    """All ``generations`` as one ``lax.scan`` dispatch.

    Returns (final state, aux) with aux = (best_err, best_area, n_eval,
    n_hit), each of shape (generations,). The state carry — including the
    cross-generation EvalCache in the default dedup mode — lives inside
    the scan, so the cache is updated in place across generations.

    With the static budget gate on (``cfg.generations_budget`` not None)
    the body is :func:`_budgeted_generation`: each lane evolves only while
    ``state.gen < problem.generations_budget`` and is a bitwise no-op
    passthrough afterwards, which makes the scan *segment-resumable* —
    calling it again on the returned state continues exactly where the
    budget (not the segment length) says. The default None path compiles
    to exactly the pre-budget program."""
    step = (generation if problem.cfg.generations_budget is None
            else _budgeted_generation)

    def body(s, _):
        return step(problem, s)

    return jax.lax.scan(body, state, None, length=generations)


# -- whole-run batching over seeds ------------------------------------------

BATCH_AXIS = "ga_runs"   # the vmap axis name whole-run batching runs under


def batch_problem(problem: Problem) -> Problem:
    """Problem tagged with the whole-run batch axis: inside a
    ``vmap(..., axis_name=BATCH_AXIS)`` its dedup shares the evaluation
    bound via ``lax.pmax`` so the tile-skip stays a real ``lax.cond``
    (see ``dedup_eval``). Do not run a tagged problem outside that axis."""
    if problem.cfg.batch_axis == BATCH_AXIS:
        return problem
    return problem.replace_cfg(batch_axis=BATCH_AXIS)


def _run_batch(problem: Problem, seeds, doping, generations: int):
    def one(seed):
        state, n0 = init_state(problem, jax.random.PRNGKey(seed), doping)
        state, aux = run_scanned(problem, state, generations)
        return state, aux, n0

    return jax.vmap(one, axis_name=BATCH_AXIS)(seeds)


_run_batch_jit = jax.jit(_run_batch, static_argnames="generations")


def run_batch(problem: Problem, seeds, generations: int | None = None,
              doping_seeds=None, jit: bool = True):
    """vmap whole scanned runs over a leading seed axis — ONE dispatch.

    seeds: (N,) integer PRNG seeds, one independent GA run each.
    Returns (states, aux, init_evals): every GAState leaf and aux entry
    gains a leading (N,) axis; use ``state_at``/``front_of`` to peel runs.

    Results are bit-identical to a Python loop of per-seed
    ``init_state`` + ``run_scanned`` calls — and to per-seed
    ``GATrainer.run`` calls, which route through the same traced
    functions — dedup on or off: counts are integers, the ranking
    gemv/while_loop are integer-exact under batching, and every adapter
    passes ``problem`` as a jit *argument* (closing over it would turn
    ``baseline_acc`` into a compile-time constant and shift the violation
    chain by an ulp). Under the batch the dedup tile-skip stays a real
    ``lax.cond``: the runs share one ``lax.pmax`` evaluation bound
    (``BATCH_AXIS``), so tiles past the widest run's unique-row count are
    genuinely skipped instead of degrading to a both-branches select.

    Buffer donation: the GAState carry lives entirely *inside* this
    dispatch (init → scan in one program), so XLA aliases it across scan
    iterations automatically and there is nothing to donate at this
    boundary; the donated boundaries are the adapters that pass a state
    back in per call (``GATrainer``'s step/scan jits, the islands round).
    """
    gens = problem.cfg.generations if generations is None else generations
    problem = batch_problem(problem)
    seeds = jnp.asarray(seeds, jnp.int32)
    doping = _doping_array(doping_seeds)
    fn = _run_batch_jit if jit else _run_batch
    return fn(problem, seeds, doping, gens)


def state_at(states: GAState, i: int) -> GAState:
    """Peel run ``i`` off a batched GAState."""
    return jax.tree_util.tree_map(lambda a: a[i], states)


# -- lane health validation (the serve supervisor's boundary check) ---------

# check names, index-aligned with the validate_state result vector
VALIDATION_CHECKS = ("finite_objectives", "genome_in_bounds",
                     "counts_in_range", "cache_accounting")


def validate_state(problem: Problem, state: GAState) -> jnp.ndarray:
    """Device-side engine-invariant checks for ONE lane, reduced to a
    (len(VALIDATION_CHECKS),) bool vector (index-aligned with the names).

    The checks are chosen so a *healthy* state can never trip them — they
    are exactly the invariants every generation step preserves — while a
    poisoned lane (NaN objectives from numerically-corrupt data, an
    out-of-bounds genome from a bad doping seed or bit-flipped buffer,
    impossible correct counts, a cache whose accounting ran ahead of the
    generation clock) fails loudly:

      * ``finite_objectives`` — every objective is finite and every
        constraint violation is finite and non-negative (crowding is
        allowed its by-design +inf boundary values, so it is NOT checked
        for finiteness — only the inputs the ranking derives from are).
      * ``genome_in_bounds``  — every gene lies in its GeneTable bounds
        ``[low, high)``; padding genes have bounds ``[0, 1)`` so the same
        comparison also enforces the canonical-zero padding rule.
      * ``counts_in_range``   — cached correct counts lie in
        ``[0, n_valid_samples]`` (zeros when dedup is off, so trivially
        true there; elementwise, so the MC (P, K) shape checks too).
      * ``cache_accounting``  — live EvalCache entries (stamp ≥ 0) hold
        in-range counts and no stamp exceeds the lane's generation clock
        (inserts are stamped with the generation that produced them).
        Constant True when the state carries no cache.

    Pure and vmappable: ``repro.serve.supervisor`` jits
    ``vmap(validate_state)`` over the stacked serve lanes and pulls ONE
    (n_lanes, n_checks) bool array per segment boundary, quarantining any
    busy lane with a False entry instead of letting it poison siblings.
    """
    t = problem.genes
    finite = (jnp.isfinite(state.obj).all()
              & jnp.isfinite(state.viol).all()
              & (state.viol >= 0.0).all())
    in_bounds = ((state.pop >= t.low[None, :])
                 & (state.pop < t.high[None, :])).all()
    n = problem.n_valid_samples
    counts_ok = ((state.counts >= 0) & (state.counts <= n)).all()
    if state.cache is None:
        cache_ok = jnp.bool_(True)
    else:
        live = state.cache.stamp >= 0
        vals = state.cache.vals
        # vals is (C,) or (C, K); broadcast the live mask over trailing axes
        live_v = live.reshape(live.shape + (1,) * (vals.ndim - 1))
        vals_ok = jnp.where(live_v, (vals >= 0) & (vals <= n), True).all()
        stamp_ok = jnp.where(live, state.cache.stamp <= state.gen,
                             True).all()
        cache_ok = vals_ok & stamp_ok
    return jnp.stack([finite, in_bounds, counts_ok, cache_ok])


def validate_ok(problem: Problem, state: GAState) -> jnp.ndarray:
    """() bool — all :data:`VALIDATION_CHECKS` hold for this lane."""
    return validate_state(problem, state).all()


# -- host-side output -------------------------------------------------------

def front_of(state: GAState):
    """Feasible estimated Pareto front (paper Fig. 2 output)."""
    obj = np.asarray(state.obj)
    pops = np.asarray(state.pop)
    feas = np.asarray(state.viol) <= 0
    if not feas.any():
        feas = np.ones_like(feas)
    return pareto_front(obj[feas], extras={"genomes": pops[feas]})

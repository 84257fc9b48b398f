"""Kernel micro-benchmarks: fitness-evaluation throughput (the paper's
26M-evaluations workload) and pow2 storage savings.

On a CPU host the wall clock measures the jnp paths (what ``"auto"``
picks there), so these numbers say nothing about the TPU.
``chip_smoke.py`` runs the kernels on the chip; ``tests/test_tpu_compile.py``
compiles them for a v5e at the paper's widths.

The fitness rows track the hot-path fusion work (dispatcher + tiling + scan
+ dedup) and are written machine-readably to ``BENCH_fitness.json`` so PRs
have a perf trajectory:

  * ``fitness_eval``         — seed baseline: untiled jnp oracle, one jitted
                               call per generation-equivalent.
  * ``fitness_dispatch``     — ``population_correct`` "ref" backend
                               (sample/population-tiled jnp).
  * ``variation_fused``      — ``population_variation`` "ref" backend (ONE
                               counter-based Threefry pass for all gene
                               draws) vs the PR-4 per-gene fold_in draw
                               structure; summary ratio
                               ``variation_speedup_vs_seed``.
  * ``phase_breakdown``      — ``variation_us_per_gen`` /
                               ``fitness_us_per_gen`` /
                               ``ranking_us_per_gen``: one generation's
                               three traced regions timed as separate
                               dispatches, so future PRs can see which
                               phase dominates. ``ranking_us_per_gen``
                               stays the O(P²) dominance-matrix oracle
                               (comparable with pre-sweep baselines);
                               ``ranking_sweep_us_per_gen`` times the
                               O(P log P) sweep the generation step now
                               actually runs, and the summary ratio
                               ``ranking_speedup_vs_matrix`` gates the
                               win. Plus the fused side:
                               ``generation_fused_us_per_gen`` times ONE
                               ``engine.generation`` dispatch (variation →
                               cache-deduped fitness → ranking through the
                               ``pop_generation`` dispatcher) on a
                               converged-population state with a warm
                               cross-generation EvalCache, and
                               ``cache_hit_rate`` /
                               ``cross_gen_unique_evals`` report what the
                               cache did during the warm-up generations;
                               summary ratio ``generation_fused_speedup``.
  * ``fitness_trainer_*``    — full scanned ``GATrainer.run`` (fitness +
                               NSGA-II + operators in one dispatch), dedup
                               off/on, on the *converged-population*
                               workload (doped near-identical elites, low
                               pm/pc — the exploitation regime where most
                               children recur): the dedup-on side packs
                               the few genuine misses to the front and
                               tile-skips the rest via the EvalCache +
                               known-parent reuse; chromo_evals_per_s
                               counts the nominal children·samples
                               workload like the seed row, so the ratio
                               credits skipped rows.
  * ``mc_fitness``           — device-variation Monte-Carlo fitness: ONE
                               K-instance batched ``population_correct``
                               dispatch (``dev=`` (K, G) deltas) vs K
                               sequential 1-instance dispatches of the
                               same work; summary ratio
                               ``mc_k8_overhead_vs_k1`` (< 1.0 = batching
                               the instance axis beats re-dispatching,
                               gated as an absolute ceiling in
                               check_regression).
  * ``fitness_batched_seeds``— an N-seed sweep: N sequential ``GATrainer``
                               runs (one compile each — the pre-engine cost
                               of repeated-run statistics) vs ONE
                               ``engine.run_batch`` dispatch that vmaps the
                               whole scanned run over the seed axis.
  * ``fitness_swept_configs``— a (seed × hyperparameter) grid: sequential
                               ``GATrainer`` runs (every config is a fresh
                               static → a fresh compile) vs ONE
                               ``sweep.run_grid`` dispatch batching the
                               config axis through traced Problem leaves;
                               per-cell fronts are asserted bit-identical.
  * ``fitness_suite``        — the paper's full 5-dataset experiment grid:
                               sequential per-(dataset, seed) ``GATrainer``
                               runs (5 different topologies → a fresh
                               compile each) vs ONE padded
                               ``sweep.run_suite`` dispatch; per-cell
                               fronts are asserted bit-identical to the
                               unpadded sequential runs.
  * ``serve_stream``         — a heterogeneous 12-job stream (2 datasets,
                               budgets 64..16) through the continuous-
                               batching ``SearchServer`` vs ONE static
                               max-shape ``run_suite`` dispatch padded to
                               the longest budget vs sequential trainers;
                               per-job fronts asserted bit-identical to
                               the sequential runs; summary ratio
                               ``serve_throughput_speedup_vs_static``
                               (steady-state warm passes both sides).
  * ``serve_chaos``          — the same stream bare vs under the
                               fault-tolerant ``Supervisor`` (per-segment
                               lane validation + crc-stamped two-phase
                               auto-checkpointing armed, fault-free);
                               per-job results asserted bit-identical;
                               summary ratio
                               ``supervised_overhead_vs_bare`` (gated as
                               an absolute < 1.10 ceiling — supervision
                               must stay a <10% tax), plus a kill+recover
                               pass timed as info.

Every workload is seeded from ``common.BENCH_SEED`` (the ``--seed`` flag of
``benchmarks.run``), so two runs at the same seed score identical chromosome
streams and the CI regression gate compares like with like.
"""
from __future__ import annotations

import json
import os
import platform
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.api import BackendPolicy, GAConfig, GATrainer
# the per-phase benchmarks time *internals* on purpose — they are the one
# place allowed to reach under the repro.api facade
from repro.core import engine, nsga2, sweep
from repro.core.genome import MLPTopology, GenomeSpec
from repro.core.mlp import population_accuracy
from repro.core.operators import variation_keys
from repro.core.quantize import quantize_inputs, pow2_quantize
from repro.kernels.pop_mlp import population_correct
from repro.kernels.pop_ranking import rank_select_rerank
from repro.kernels.pop_variation import population_variation
from repro.data import load_dataset

from . import common
from .common import emit_row

_POP = 256
_RESULTS_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_fitness.json")


def _cardio_workload():
    ds = load_dataset("cardio")
    topo = MLPTopology(ds.topology)
    spec = GenomeSpec(topo)
    pop = spec.random(jax.random.PRNGKey(common.BENCH_SEED), _POP)
    xi = quantize_inputs(jnp.asarray(ds.x_train), 4)
    labels = jnp.asarray(ds.y_train)
    return ds, topo, spec, pop, xi, labels


def _converged_workload():
    """The exploitation-regime GA workload: 8 elites, each 4 genes off one
    base genome, doped over the whole population with low mutation and
    crossover rates — so most children duplicate a parent or a recently
    seen genome and the dedup/cache path has real work to skip. This is
    the converged-front phase every long NSGA-II run ends in (and where
    the paper's 26 M-evaluation budget is mostly spent)."""
    ds, topo, spec, _, xi, labels = _cardio_workload()
    rng = np.random.default_rng(common.BENCH_SEED)
    base = np.asarray(spec.random(jax.random.PRNGKey(common.BENCH_SEED), 1))[0]
    low, high = np.asarray(spec.low), np.asarray(spec.high)
    elites = []
    for _ in range(8):
        g = base.copy()
        for j in rng.choice(g.shape[0], 4, replace=False):
            g[j] = rng.integers(low[j], high[j])
        elites.append(g)
    return ds, topo, spec, xi, labels, elites


def _converged_cfg(dedup, gens: int = 20) -> GAConfig:
    return GAConfig(pop_size=_POP, generations=gens, seed=common.BENCH_SEED,
                    backends=BackendPolicy(fitness="ref"), dedup=dedup, scan=True,
                    mutation_rate_gene=0.0005, crossover_rate=0.1,
                    doping_frac=1.0)


def _time(fn, iters=5):
    """Mean-of-N timing after one warm call. The seed oracle, dispatcher
    and trainer rows all use this estimator, so their speedup ratios in
    BENCH_fitness.json compare like with like — and stay comparable with
    the ratios recorded by earlier PRs. (``fitness_batched_seeds``
    deliberately reports single-shot cold timings instead — compile time
    IS the sweep cost being measured there.)"""
    fn()                              # compile + warm cache
    t0 = time.time()
    for _ in range(iters):
        fn()
    return (time.time() - t0) / iters


def bench_fitness_throughput(results):
    """Seed baseline: the untiled jnp oracle (pre-dispatcher semantics)."""
    _, _, spec, pop, xi, labels = _cardio_workload()
    fn = jax.jit(lambda p: population_accuracy(spec, p, xi, labels))
    dt = _time(lambda: fn(pop).block_until_ready())
    evals = _POP * xi.shape[0]
    results["fitness_eval"] = {
        "us_per_call": dt * 1e6, "chromo_evals_per_s": evals / dt,
        "pop": _POP, "samples": int(xi.shape[0]), "backend": "jnp-oracle"}
    emit_row("kernel/fitness_eval", dt * 1e6,
             f"chromo_evals_per_s={evals / dt:.0f}|pop={_POP}|samples={xi.shape[0]}")


def bench_fitness_dispatch(results):
    """The dispatcher's tiled jnp path (what the trainers now run on CPU)."""
    _, _, spec, pop, xi, labels = _cardio_workload()
    fn = jax.jit(lambda p: population_correct(p, xi, labels, spec=spec,
                                              backend="ref"))
    dt = _time(lambda: fn(pop).block_until_ready())
    evals = _POP * xi.shape[0]
    results["fitness_dispatch"] = {
        "us_per_call": dt * 1e6, "chromo_evals_per_s": evals / dt,
        "pop": _POP, "samples": int(xi.shape[0]), "backend": "ref-tiled"}
    emit_row("kernel/fitness_dispatch", dt * 1e6,
             f"chromo_evals_per_s={evals / dt:.0f}|pop={_POP}|backend=ref")


def bench_mc_fitness(results, k: int = 8):
    """Device-variation MC fitness: batched K instances vs K dispatches.

    The batched side is what ``engine.population_counts`` runs under
    ``variation_mode != "off"``: one ``population_correct`` call with the
    full (K, G) delta block, amortizing the dataset sweep across all K
    perturbed instances. The sequential side re-dispatches the same MC
    evaluation K times with a single-instance delta block — the naive
    "loop over device samples" structure. Both sides are asserted
    bit-identical column for column; the gated ratio
    ``mc_k8_overhead_vs_k1`` = batched / sequential must stay < 1.0
    (the instance axis must be cheaper batched than re-dispatched)."""
    ds, topo, spec, pop, xi, labels = _cardio_workload()
    cfg = GAConfig(pop_size=_POP, variation_mode="mean", n_device_samples=k,
                   variation_scale=0.2, seed=common.BENCH_SEED,
                   backends=BackendPolicy(fitness="ref"))
    problem = engine.Problem.from_data(topo, ds.x_train, ds.y_train, cfg)
    dev = jax.jit(engine.device_deltas)(problem)
    high = problem.genes.high

    mc = jax.jit(lambda p, d: population_correct(
        p, xi, labels, spec=spec, backend="ref", dev=d, gene_high=high))

    def run_seq():
        # K single-instance dispatches (one compile — same shapes)
        return [mc(pop, jax.lax.dynamic_slice_in_dim(dev, i, 1))
                for i in range(k)]

    batched = mc(pop, dev)
    seq = jnp.concatenate(run_seq(), axis=-1)
    assert np.array_equal(np.asarray(batched), np.asarray(seq)), \
        "batched MC counts diverged from sequential per-instance counts"

    # interleaved best-of-repeats (same estimator story as bench_variation)
    b_ts, s_ts = [], []
    for _ in range(5):
        b_ts.append(_time(lambda: mc(pop, dev).block_until_ready(),
                          iters=10))
        s_ts.append(_time(
            lambda: jax.block_until_ready(run_seq()), iters=10))
    dt_b, dt_s = min(b_ts), min(s_ts)
    overhead = dt_b / dt_s
    evals = k * _POP * xi.shape[0]
    results["mc_fitness"] = {
        "mc_fitness_us_per_gen": dt_b * 1e6,
        "sequential_us_per_gen": dt_s * 1e6,
        "chromo_evals_per_s": evals / dt_b,
        "n_device_samples": k, "pop": _POP, "samples": int(xi.shape[0]),
        "counts_bit_identical": True, "backend": "ref-mc"}
    results["mc_k8_overhead_vs_k1"] = overhead
    emit_row("kernel/mc_fitness", dt_b * 1e6,
             f"chromo_evals_per_s={evals / dt_b:.0f}|k={k}|pop={_POP}"
             f"|seq_us={dt_s * 1e6:.0f}|overhead_vs_k1={overhead:.2f}x")


def bench_variation(results):
    """Fused variation pass vs the seed-style draw structure.

    The "seed" side replicates the PR-4 variation hot path: five separate
    gene-shaped draw passes per generation, each paying a per-gene
    ``fold_in`` vmap (a scalar Threefry hash per gene) before its uniform
    pass. The fused side is the shipped ``population_variation`` "ref"
    backend: ONE counter-based Threefry pass for all draw slots + one
    elementwise crossover/mutation/clip region. Same tournament, same
    rates — only the RNG/fusion structure differs (the streams do too;
    this row measures cost, the equivalence suite pins correctness)."""
    _, _, spec, pop, xi, labels = _cardio_workload()
    t = spec.table()
    rank = jnp.zeros((_POP,), jnp.int32)
    crowd = jnp.ones((_POP,), jnp.float32)
    pc, pm = jnp.float32(0.7), jnp.float32(0.02)

    def foldin_uniform(key, ids, n):
        # the PR-4 gene_uniform: fold_in per gene, then a per-gene uniform
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(ids)
        return jax.vmap(lambda k: jax.random.uniform(k, (n,)),
                        out_axes=1)(keys)

    def seed_offspring(key, pop):
        k_sel, k_cx, k_var = variation_keys(key)
        parents = nsga2.tournament_select(k_sel, rank, crowd, _POP)
        pa, pb = pop[parents[: _POP // 2]], pop[parents[_POP // 2:]]
        k1, k2 = jax.random.split(k_cx)
        do = jax.random.uniform(k1, (_POP // 2, 1)) < pc
        take_b = foldin_uniform(k2, t.ids, _POP // 2) < 0.5
        children = jnp.concatenate([jnp.where(do & take_b, pb, pa),
                                    jnp.where(do & take_b, pa, pb)])
        m1, m2, m3 = jax.random.split(k_var, 3)
        do_m = foldin_uniform(m1, t.ids, _POP) < pm
        bitpos = jnp.floor(foldin_uniform(m2, t.ids, _POP)
                           * jnp.maximum(t.mask_bits, 1)).astype(jnp.int32)
        flipped = jnp.bitwise_xor(children, jnp.left_shift(1, bitpos))
        lo, hi = t.low.astype(jnp.float32), t.high.astype(jnp.float32)
        reset = jnp.floor(lo + foldin_uniform(m3, t.ids, _POP)
                          * (hi - lo)).astype(jnp.int32)
        children = jnp.where(do_m, jnp.where(t.is_mask, flipped, reset),
                             children)
        return jnp.clip(children, t.low, t.high - 1)

    key = jax.random.PRNGKey(common.BENCH_SEED)
    seed_fn = jax.jit(seed_offspring)
    fused_fn = jax.jit(lambda k, p: population_variation(
        k, p, rank, crowd, genes=t, pc=pc, pm=pm, backend="ref"))
    # sub-ms calls on a jittery runner: alternate 50-iter means of the two
    # sides five times and take each side's min, so both sample the same
    # load windows and the ratio stays stable
    seed_ts, fused_ts = [], []
    for _ in range(5):
        seed_ts.append(_time(lambda: seed_fn(key, pop).block_until_ready(),
                             iters=50))
        fused_ts.append(_time(lambda: fused_fn(key, pop).block_until_ready(),
                              iters=50))
    dt_seed, dt_fused = min(seed_ts), min(fused_ts)
    speedup = dt_seed / dt_fused
    results["variation_fused"] = {
        "us_per_call_seed_foldin": dt_seed * 1e6,
        "us_per_call_fused": dt_fused * 1e6,
        "pop": _POP, "genes": int(spec.n_genes), "backend": "ref-fused"}
    results["variation_speedup_vs_seed"] = speedup
    emit_row("kernel/variation_fused", dt_fused * 1e6,
             f"pop={_POP}|genes={spec.n_genes}"
             f"|seed_foldin_us={dt_seed * 1e6:.0f}"
             f"|speedup_vs_seed={speedup:.2f}x")


def bench_phase_breakdown(results):
    """Per-phase wall clock of one GA generation (pop=256, cardio).

    Times the three traced regions a generation is made of — variation
    (tournament → crossover → mutation → clip), fitness (the
    ``population_correct`` "ref" dispatch over the children) and ranking
    (dominance matrix → front peel → crowding → survivor truncation on
    the (μ+λ) pool) — each as its own jitted call, so future PRs can see
    which phase dominates before picking a target. The full scanned
    trainer fuses all three; these rows are the unfused upper bound."""
    ds, topo, spec, pop, xi, labels = _cardio_workload()
    cfg = GAConfig(pop_size=_POP, backends=BackendPolicy(fitness="ref"),
                   seed=common.BENCH_SEED)
    problem = engine.Problem.from_data(topo, ds.x_train, ds.y_train, cfg)
    state, _ = jax.jit(lambda p: engine.init_state(
        p, jax.random.PRNGKey(common.BENCH_SEED), None))(problem)

    var_fn = jax.jit(lambda p, s: population_variation(
        jax.random.split(s.key)[1], s.pop, s.rank, s.crowd, genes=p.genes,
        pc=p.crossover_rate, pm=p.mutation_rate_gene, backend="ref"))
    dt_var = _time(lambda: var_fn(problem, state).block_until_ready(),
                   iters=20)
    children = var_fn(problem, state)

    fit_fn = jax.jit(lambda p, rows: engine.population_counts(p, rows))
    dt_fit = _time(lambda: fit_fn(problem, children).block_until_ready(),
                   iters=20)

    obj = jnp.concatenate([state.obj, state.obj])
    viol = jnp.concatenate([state.viol, state.viol])

    # the full (μ+λ) ranking tail — rank the 2P pool, truncate to P,
    # re-rank the survivors — through the pop_ranking dispatcher, once per
    # backend. "matrix" is the seed-history row; "sweep" is what
    # engine.generation runs now. Sub-ms calls on a jittery 1-vCPU
    # runner: alternate 20-iter means of the two sides and take each
    # side's min, so both sample the same load windows and the gated
    # ratio stays stable (same estimator as bench_variation).
    rank_m_fn = jax.jit(lambda o, v: rank_select_rerank(o, v, _POP,
                                                        backend="matrix"))
    rank_s_fn = jax.jit(lambda o, v: rank_select_rerank(o, v, _POP,
                                                        backend="sweep"))
    rank_ts, sweep_ts = [], []
    for _ in range(5):
        rank_ts.append(_time(
            lambda: rank_m_fn(obj, viol)[1].block_until_ready(), iters=20))
        sweep_ts.append(_time(
            lambda: rank_s_fn(obj, viol)[1].block_until_ready(), iters=20))
    dt_rank, dt_sweep = min(rank_ts), min(sweep_ts)
    ranking_speedup = dt_rank / dt_sweep

    # fused side: ONE engine.generation dispatch (pop_generation "ref" —
    # variation → cache-deduped packed fitness → ranking in one traced
    # region) on a converged-population state whose EvalCache was warmed
    # by 10 scanned generations. The unfused rows above evaluate every
    # child; the fused dispatch evaluates only the genuine misses and
    # tile-skips the rest — fusion + cache are the two wins being compared.
    ds_c, topo_c, _, _, _, elites = _converged_workload()
    cfg_c = _converged_cfg(dedup=True)
    prob_c = engine.Problem.from_data(topo_c, ds_c.x_train, ds_c.y_train,
                                      cfg_c)
    state_c, _ = jax.jit(lambda p, d: engine.init_state(
        p, jax.random.PRNGKey(common.BENCH_SEED), d))(
            prob_c, engine._doping_array(elites))
    state_c, warm_aux = jax.jit(engine.run_scanned,
                                static_argnames="generations")(
        prob_c, state_c, generations=10)
    warm_evals = int(np.asarray(warm_aux[2]).sum())
    warm_hits = int(np.asarray(warm_aux[3]).sum())
    hit_rate = warm_hits / max(1, warm_hits + warm_evals)
    gen_fn = jax.jit(lambda p, s: engine.generation(p, s)[0])
    dt_gen = _time(lambda: gen_fn(prob_c, state_c).pop.block_until_ready(),
                   iters=20)
    # the unfused sum uses the sweep ranking — the same path the fused
    # dispatch runs — so the fusion ratio isolates fusion, not the
    # ranking-backend change
    speedup = (dt_var + dt_fit + dt_sweep) / dt_gen

    results["phase_breakdown"] = {
        "variation_us_per_gen": dt_var * 1e6,
        "fitness_us_per_gen": dt_fit * 1e6,
        "ranking_us_per_gen": dt_rank * 1e6,
        "ranking_sweep_us_per_gen": dt_sweep * 1e6,
        "generation_fused_us_per_gen": dt_gen * 1e6,
        "cache_hit_rate": hit_rate,
        "cross_gen_unique_evals": warm_evals,
        "pop": _POP, "samples": int(xi.shape[0]),
        "backend": "ref (unfused per-phase dispatches; fused row: "
                   "pop_generation ref + warm EvalCache, converged pop)"}
    results["generation_fused_speedup"] = speedup
    results["ranking_speedup_vs_matrix"] = ranking_speedup
    total = dt_var + dt_fit + dt_sweep
    emit_row("kernel/phase_breakdown", total * 1e6,
             f"variation_us={dt_var * 1e6:.0f}|fitness_us={dt_fit * 1e6:.0f}"
             f"|ranking_matrix_us={dt_rank * 1e6:.0f}"
             f"|ranking_sweep_us={dt_sweep * 1e6:.0f}"
             f"|ranking_speedup_vs_matrix={ranking_speedup:.2f}x|pop={_POP}")
    emit_row("kernel/generation_fused", dt_gen * 1e6,
             f"unfused_sum_us={total * 1e6:.0f}|cache_hit_rate={hit_rate:.3f}"
             f"|cross_gen_unique_evals={warm_evals}"
             f"|speedup_vs_unfused={speedup:.2f}x")


def bench_fitness_trainer(results, dedup: bool, gens: int = 20):
    """Scanned GATrainer end to end on the converged-population workload.

    Both sides score the same chromosome stream; only the dedup path
    differs. Off: every child of every generation is evaluated. On (the
    default cache mode): within-generation duplicates collapse, children
    identical to a surviving parent reuse the carried counts, re-discovered
    genomes hit the cross-generation EvalCache, and the few genuine misses
    are packed to the front so the tiled fitness backend skips whole
    population tiles — ``chromo_evals_per_s`` counts the *nominal*
    workload, so skipped rows show up as throughput."""
    ds, topo, _, xi, labels, elites = _converged_workload()
    cfg = _converged_cfg(dedup, gens)
    tr = GATrainer(topo, ds.x_train, ds.y_train, cfg, doping_seeds=elites)
    dt = _time(lambda: tr.run(), iters=3)
    evals = gens * _POP * xi.shape[0]         # nominal children workload
    key = f"fitness_trainer_dedup_{'on' if dedup else 'off'}"
    results[key] = {
        "us_per_gen": dt / gens * 1e6, "chromo_evals_per_s": evals / dt,
        "pop": _POP, "generations": gens, "samples": int(xi.shape[0]),
        "unique_row_evals": tr.unique_evals,
        "cache_hits": tr.cache_hits,
        "nominal_row_evals": (gens + 1) * _POP,
        "workload": "converged (doped elites, pm=0.0005, pc=0.1)",
        "backend": "ref+scan+cache" if dedup else "ref+scan"}
    emit_row(f"kernel/{key}", dt / gens * 1e6,
             f"chromo_evals_per_s={evals / dt:.0f}|pop={_POP}|gens={gens}"
             f"|unique_rows={tr.unique_evals}|cache_hits={tr.cache_hits}")


def bench_fitness_batched(results, n_seeds: int = 8, pop: int = 64,
                          gens: int = 20):
    """N-seed sweep throughput: sequential trainers vs one vmapped run.

    Both sides include compilation — that IS the sweep cost: each fresh
    ``GATrainer`` re-jits its scan, while ``engine.run_batch`` compiles the
    batched program once. ``batched_warm_s`` additionally reports the
    steady-state redispatch cost."""
    ds, topo, _, _, xi, labels = _cardio_workload()

    def cfg(seed):
        return GAConfig(pop_size=pop, generations=gens, seed=seed,
                        backends=BackendPolicy(fitness="ref"), scan=True)

    t0 = time.time()
    for s in range(common.BENCH_SEED, common.BENCH_SEED + n_seeds):
        GATrainer(topo, ds.x_train, ds.y_train, cfg(s)).run()
    seq_s = time.time() - t0

    problem = engine.Problem.from_data(topo, ds.x_train, ds.y_train,
                                       cfg(common.BENCH_SEED))
    seeds = common.BENCH_SEED + np.arange(n_seeds)
    t0 = time.time()
    states, _, _ = engine.run_batch(problem, seeds)
    jax.block_until_ready(states.pop)
    batched_s = time.time() - t0
    t0 = time.time()
    states, _, _ = engine.run_batch(problem, seeds)
    jax.block_until_ready(states.pop)
    warm_s = time.time() - t0

    evals = n_seeds * gens * pop * xi.shape[0]
    speedup = seq_s / batched_s
    results["fitness_batched_seeds"] = {
        "sequential_s": seq_s, "batched_s": batched_s,
        "batched_warm_s": warm_s,
        "chromo_evals_per_s": evals / batched_s,
        "n_seeds": n_seeds, "pop": pop, "generations": gens,
        "samples": int(xi.shape[0]), "backend": "ref+scan+vmap"}
    results["batched_seeds_speedup_vs_sequential"] = speedup
    emit_row("kernel/fitness_batched_seeds", batched_s / n_seeds * 1e6,
             f"chromo_evals_per_s={evals / batched_s:.0f}|seeds={n_seeds}"
             f"|pop={pop}|gens={gens}|seq_s={seq_s:.1f}|batched_s={batched_s:.1f}"
             f"|speedup_vs_sequential={speedup:.2f}x")


def bench_fitness_swept(results, n_seeds: int = 2, pop: int = 64,
                        gens: int = 20,
                        mutation_rates=(0.02, 0.05)):
    """(seed × config) grid throughput: sequential trainers vs run_grid.

    Every config is a fresh ``GAConfig`` static for the sequential side —
    a fresh compile per cell, the real cost of a hyperparameter sweep
    before the config axis became traced Problem leaves. ``run_grid``
    compiles ONE batched program for all cells. Per-cell Pareto fronts are
    asserted bit-identical between the two sides (run_grid's contract)."""
    ds, topo, _, _, xi, labels = _cardio_workload()

    def cfg(seed, pm):
        return GAConfig(pop_size=pop, generations=gens, seed=seed,
                        mutation_rate_gene=pm, backends=BackendPolicy(fitness="ref"),
                        scan=True)

    seeds = [common.BENCH_SEED + i for i in range(n_seeds)]
    t0 = time.time()
    seq_fronts = []
    for s in seeds:
        for pm in mutation_rates:
            tr = GATrainer(topo, ds.x_train, ds.y_train, cfg(s, pm))
            state, _ = tr.run()
            seq_fronts.append(tr.front(state))
    seq_s = time.time() - t0

    problem = engine.Problem.from_data(
        topo, ds.x_train, ds.y_train, cfg(seeds[0], mutation_rates[0]))
    t0 = time.time()
    result = sweep.run_grid(problem, seeds, mutation_rates=mutation_rates)
    jax.block_until_ready(result.states.pop)
    swept_s = time.time() - t0
    fronts = result.fronts()

    for f_seq, f_grid in zip(seq_fronts, fronts):
        assert np.array_equal(f_seq["objectives"], f_grid["objectives"]), \
            "sweep front diverged from sequential trainer front"

    n_cells = result.n_cells
    evals = n_cells * gens * pop * xi.shape[0]
    speedup = seq_s / swept_s
    results["fitness_swept_configs"] = {
        "sequential_s": seq_s, "swept_s": swept_s,
        "chromo_evals_per_s": evals / swept_s,
        "n_cells": n_cells, "n_seeds": n_seeds,
        "mutation_rates": list(mutation_rates),
        "pop": pop, "generations": gens, "samples": int(xi.shape[0]),
        "fronts_bit_identical": True, "backend": "ref+scan+vmap-grid"}
    results["swept_configs_speedup_vs_sequential"] = speedup
    emit_row("kernel/fitness_swept_configs", swept_s / n_cells * 1e6,
             f"chromo_evals_per_s={evals / swept_s:.0f}|cells={n_cells}"
             f"|pop={pop}|gens={gens}|seq_s={seq_s:.1f}|swept_s={swept_s:.1f}"
             f"|speedup_vs_sequential={speedup:.2f}x")


def bench_fitness_suite(results, n_seeds: int = 2, pop: int = 64,
                        gens: int = 12):
    """5-dataset suite throughput: sequential per-dataset trainers vs ONE
    padded run_suite dispatch.

    The sequential side is the tables' pre-suite reality: every (dataset,
    seed) pair builds a fresh ``GATrainer`` over a *different topology*, so
    each pays its own compile on top of its run. ``run_suite`` embeds all
    five topologies in one max-shape layout and compiles/dispatches ONCE
    for the whole (dataset × seed) grid — the padded lanes cost extra
    arithmetic, which is the price being measured against. Per-cell fronts
    are asserted bit-identical to the sequential runs (run_suite's
    contract)."""
    from repro.data import DATASETS

    names = list(DATASETS)

    def cfg(seed):
        return GAConfig(pop_size=pop, generations=gens, seed=seed,
                        backends=BackendPolicy(fitness="ref"), scan=True)

    seeds = [common.BENCH_SEED + i for i in range(n_seeds)]
    seq_fronts, problems = [], []
    n_samples = 0
    t0 = time.time()
    for name in names:
        ds = load_dataset(name)
        topo = MLPTopology(ds.topology)
        n_samples += n_seeds * int(ds.x_train.shape[0])
        for s in seeds:
            tr = GATrainer(topo, ds.x_train, ds.y_train, cfg(s))
            state, _ = tr.run()
            seq_fronts.append(tr.front(state))
    seq_s = time.time() - t0

    for name in names:
        ds = load_dataset(name)
        problems.append(engine.Problem.from_data(
            MLPTopology(ds.topology), ds.x_train, ds.y_train, cfg(seeds[0])))
    t0 = time.time()
    result = sweep.run_suite(problems, seeds, names=names)
    jax.block_until_ready(result.states.pop)
    suite_s = time.time() - t0
    fronts = [result.front_at(i) for i in range(result.n_cells)]

    for f_seq, f_suite in zip(seq_fronts, fronts):
        assert np.array_equal(f_seq["objectives"], f_suite["objectives"]), \
            "suite front diverged from sequential trainer front"
        assert np.array_equal(f_seq["genomes"], f_suite["genomes"]), \
            "suite genomes diverged from sequential trainer genomes"

    n_cells = result.n_cells
    evals = gens * pop * n_samples          # nominal unpadded workload
    speedup = seq_s / suite_s
    results["fitness_suite"] = {
        "sequential_s": seq_s, "suite_s": suite_s,
        "chromo_evals_per_s": evals / suite_s,
        "n_datasets": len(names), "n_seeds": n_seeds, "n_cells": n_cells,
        "pop": pop, "generations": gens,
        "padded_topology": list(result.spec.topo.sizes),
        "fronts_bit_identical": True, "backend": "ref+scan+vmap-suite"}
    results["suite_speedup_vs_sequential"] = speedup
    emit_row("kernel/fitness_suite", suite_s / n_cells * 1e6,
             f"chromo_evals_per_s={evals / suite_s:.0f}|datasets={len(names)}"
             f"|cells={n_cells}|pop={pop}|gens={gens}|seq_s={seq_s:.1f}"
             f"|suite_s={suite_s:.1f}|speedup_vs_sequential={speedup:.2f}x")


def bench_serve(results, pop: int = 32, n_lanes: int = 4,
                segment_len: int = 16):
    """Continuous-batching serve throughput on a heterogeneous job stream.

    The workload is 12 jobs over two datasets (cardio 1488 samples /
    redwine 1120) with generation budgets 64..16 — the "search service"
    reality where requests differ in how long they run. Three ways to
    serve it:

      * sequential — one ``GATrainer`` per job, its own compile each
        (the pre-batching reality; also the bit-identity oracle: every
        serve front is asserted equal to its trainer's).
      * static     — ONE ``run_suite`` dispatch padded to the *longest*
        budget: every lane runs 64 generations because the program shape
        is fixed at trace time, so short jobs burn 4x their budget.
      * serve      — ``SearchServer`` (4 lanes, 16-gen segments, LJF
        admission): lanes retire at their budget via the per-lane gate
        and freed slots backfill from the queue, so the total work is
        the *sum of budgets*, not n_jobs x max_budget.

    The gated ratio ``serve_throughput_speedup_vs_static`` compares
    steady-state (warm, compile-cache hit) passes on both sides — the
    honest metric for an always-on service; cold times are recorded as
    info. Sequential stays cold (each job IS a fresh compile there)."""
    from repro.serve import SearchServer

    budgets = [64, 64, 32, 32, 24, 24, 16, 16, 16, 16, 16, 16]
    names = ["cardio", "redwine"]
    max_gens = max(budgets)
    n_seeds = len(budgets) // len(names)
    seeds = [common.BENCH_SEED + i for i in range(n_seeds)]

    def cfg(seed, gens):
        return GAConfig(pop_size=pop, generations=gens, seed=seed,
                        backends=BackendPolicy(fitness="ref"), scan=True)

    datasets = [load_dataset(n) for n in names]
    problems = [engine.Problem.from_data(
        MLPTopology(ds.topology), ds.x_train, ds.y_train,
        cfg(seeds[0], max_gens)) for ds in datasets]
    # job i: dataset i%2, seed BENCH_SEED + i//2 — budgets interleaved so
    # both datasets see the full 64..16 budget spread
    jobs = [(i % len(names), seeds[i // len(names)], budgets[i])
            for i in range(len(budgets))]

    srv = SearchServer.for_problems(problems, n_lanes=n_lanes,
                                    segment_len=segment_len,
                                    policy="longest")

    def serve_pass():
        ids = [srv.submit(problems[d], generations=g, seed=s)
               for d, s, g in jobs]
        return ids, {r.job_id: r for r in srv.drain()}

    t0 = time.time()
    ids, served = serve_pass()       # cold: compiles segment + init progs
    serve_cold_s = time.time() - t0
    serve_s = min(_timed(serve_pass) for _ in range(2))

    # sequential oracle: per-job trainers, fronts must match bit-for-bit
    t0 = time.time()
    for jid, (d, s, g) in zip(ids, jobs):
        ds = datasets[d]
        tr = GATrainer(MLPTopology(ds.topology), ds.x_train, ds.y_train,
                       cfg(s, g))
        state, _ = tr.run()
        front = tr.front(state)
        r = served[jid]
        assert np.array_equal(r.front["objectives"], front["objectives"]), \
            f"serve front diverged from sequential trainer (job {jid})"
        assert np.array_equal(r.front["genomes"], front["genomes"]), \
            f"serve genomes diverged from sequential trainer (job {jid})"
        assert r.unique_evals == tr.unique_evals, f"eval accounting {jid}"
    seq_s = time.time() - t0

    # static baseline: one max-shape run_suite dispatch, every cell padded
    # to the longest budget (single sample bucket = truly one program)
    def static_pass():
        result = sweep.run_suite(problems, seeds, names=names,
                                 generations=max_gens,
                                 sample_bucket_factor=None)
        jax.block_until_ready(result.states.pop)

    t0 = time.time()
    static_pass()                    # cold compile
    static_cold_s = time.time() - t0
    static_s = min(_timed(static_pass) for _ in range(2))

    speedup = static_s / serve_s
    lane_gens = sum(budgets)
    results["serve_stream"] = {
        "serve_s": serve_s, "static_s": static_s, "sequential_s": seq_s,
        "serve_cold_s": serve_cold_s, "static_cold_s": static_cold_s,
        "n_jobs": len(jobs), "budgets": budgets, "n_lanes": n_lanes,
        "segment_len": segment_len, "pop": pop, "policy": "longest",
        "datasets": names, "lane_generations": lane_gens,
        "static_lane_generations": len(jobs) * max_gens,
        "fronts_bit_identical": True, "backend": "ref+scan+vmap-serve"}
    results["serve_throughput_speedup_vs_static"] = speedup
    emit_row("kernel/serve_stream", serve_s / len(jobs) * 1e6,
             f"jobs={len(jobs)}|lanes={n_lanes}|segment={segment_len}"
             f"|pop={pop}|lane_gens={lane_gens}"
             f"|static_lane_gens={len(jobs) * max_gens}"
             f"|serve_s={serve_s:.2f}|static_s={static_s:.2f}"
             f"|seq_s={seq_s:.1f}|speedup_vs_static={speedup:.2f}x"
             f"|speedup_vs_sequential={seq_s / serve_s:.2f}x")


def bench_serve_chaos(results, pop: int = 32, n_lanes: int = 4,
                      segment_len: int = 16, checkpoint_every: int = 6):
    """Fault-tolerance tax of the supervised serve path.

    Same shape of heterogeneous job stream as ``bench_serve`` (two
    datasets, interleaved 64..16 generation budgets) run two ways:

      * bare       — ``SearchServer`` submit + drain, no supervision
        (the PR-9 fast path).
      * supervised — the same stream under ``Supervisor`` with the
        full fault-tolerance machinery armed on a fault-free run:
        per-segment lane validation (jitted vmap of
        ``engine.validate_state``) AND two-phase-commit
        auto-checkpointing every ``checkpoint_every`` segments
        (crc-stamped leaves to a temp directory).

    The gated ratio ``supervised_overhead_vs_bare`` =
    supervised_s / bare_s compares warm steady-state passes; the
    absolute ceiling in check_regression (< 1.10) is the contract that
    supervision stays a <10% tax, so there is no reason to run serve
    unsupervised. Both sides are asserted bit-identical per job, and a
    kill+recover pass (drop the server after ``kill_after`` segments,
    ``Supervisor.recover`` from the newest valid checkpoint, finish the
    stream) is timed as info — recovery correctness itself is the chaos
    test suite's job."""
    import shutil
    import tempfile

    from repro.serve import ChaosPlan, ChaosKill, FaultPolicy, \
        SearchServer, Supervisor

    budgets = [64, 64, 32, 32, 24, 24, 16, 16, 16, 16, 16, 16]
    names = ["cardio", "redwine"]
    max_gens = max(budgets)
    n_seeds = len(budgets) // len(names)
    seeds = [common.BENCH_SEED + i for i in range(n_seeds)]

    def cfg(seed, gens):
        return GAConfig(pop_size=pop, generations=gens, seed=seed,
                        backends=BackendPolicy(fitness="ref"), scan=True)

    datasets = [load_dataset(n) for n in names]
    problems = [engine.Problem.from_data(
        MLPTopology(ds.topology), ds.x_train, ds.y_train,
        cfg(seeds[0], max_gens)) for ds in datasets]
    jobs = [(i % len(names), seeds[i // len(names)], budgets[i])
            for i in range(len(budgets))]

    def submit_all(target):
        # names carry the dataset index so a recovery can resubmit any
        # dropped-pending job against the right problem
        return [target.submit(problems[d], generations=g, seed=s,
                              name=f"{names[d]}/s{s}/g{g}")
                for d, s, g in jobs]

    srv = SearchServer.for_problems(problems, n_lanes=n_lanes,
                                    segment_len=segment_len,
                                    policy="longest")

    def bare_pass():
        ids = submit_all(srv)
        return ids, {r.job_id: r for r in srv.drain()}

    ckpt_dir = tempfile.mkdtemp(prefix="bench_serve_chaos_")
    try:
        policy = FaultPolicy(checkpoint_every=checkpoint_every, keep=2)
        sup = Supervisor(SearchServer.for_problems(
            problems, n_lanes=n_lanes, segment_len=segment_len,
            policy="longest"), policy, directory=ckpt_dir)

        def supervised_pass():
            ids = submit_all(sup)
            return ids, {r.job_id: r for r in sup.drain()}

        ids_b, bare_res = bare_pass()        # warm both sides (compile-
        ids_s, sup_res = supervised_pass()   # cache hit) + oracle check
        n_checkpoints = sup.stats["checkpoints"]   # one pass's worth
        for jb, js in zip(ids_b, ids_s):
            assert np.array_equal(bare_res[jb].front["objectives"],
                                  sup_res[js].front["objectives"]), \
                "supervised front diverged from bare serve"
            assert bare_res[jb].unique_evals == sup_res[js].unique_evals
        # the two sides differ by well under the box's slow timing drift,
        # so time them INTERLEAVED and take per-side minima — a bare
        # block then a supervised block would hand whichever runs later
        # the warmer (or colder) machine and swamp the ratio
        bare_t, sup_t = [], []
        for _ in range(3):
            bare_t.append(_timed(bare_pass))
            sup_t.append(_timed(supervised_pass))
        bare_s, supervised_s = min(bare_t), min(sup_t)

        # kill + recover pass (info only): die mid-stream, restart from
        # the newest valid checkpoint, finish the remaining segments
        kill_after = 2 * checkpoint_every
        chaos = ChaosPlan(kill_after_segment=sup.server.segments_done
                          + kill_after)
        sup2 = Supervisor(sup.server, policy, directory=ckpt_dir,
                          chaos=chaos)
        t0 = time.time()
        submit_all(sup2)
        try:
            sup2.drain()
        except ChaosKill:
            pass
        rec = Supervisor.recover(ckpt_dir, sup.server.spec,
                                 problems[0].cfg, policy)
        for meta in rec.dropped_pending:
            d = names.index(meta["name"].split("/")[0])
            rec.submit(problems[d], generations=meta["generations"],
                       seed=meta["seed"], name=meta["name"])
        rec.drain()
        recover_s = time.time() - t0
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    overhead = supervised_s / bare_s
    results["serve_chaos"] = {
        "bare_s": bare_s, "supervised_s": supervised_s,
        "kill_recover_s": recover_s, "n_jobs": len(jobs),
        "n_lanes": n_lanes, "segment_len": segment_len, "pop": pop,
        "checkpoint_every": checkpoint_every,
        "checkpoints_per_pass": n_checkpoints,
        "validate_every_segment": True, "fronts_bit_identical": True,
        "recovered_step": rec.recovered_step}
    results["supervised_overhead_vs_bare"] = overhead
    emit_row("kernel/serve_chaos", supervised_s / len(jobs) * 1e6,
             f"jobs={len(jobs)}|lanes={n_lanes}|ckpt_every="
             f"{checkpoint_every}|bare_s={bare_s:.2f}"
             f"|supervised_s={supervised_s:.2f}"
             f"|kill_recover_s={recover_s:.2f}"
             f"|overhead_vs_bare={overhead:.3f}x")


def _timed(fn):
    t0 = time.time()
    fn()
    return time.time() - t0


def bench_pow2_packing():
    w = jax.random.normal(jax.random.PRNGKey(common.BENCH_SEED + 1),
                          (4096, 4096))
    t0 = time.time()
    packed = jax.jit(pow2_quantize)(w).block_until_ready()
    dt = time.time() - t0
    emit_row("kernel/pow2_pack", dt * 1e6,
             f"bytes_bf16={w.size * 2}|bytes_pow2={packed.size}|saving=2x"
             f"|vs_f32=4x")


def run():
    print("# Kernel micro-benchmarks")
    results = {}
    bench_fitness_throughput(results)
    bench_fitness_dispatch(results)
    bench_mc_fitness(results)
    bench_variation(results)
    bench_phase_breakdown(results)
    bench_fitness_trainer(results, dedup=False)
    bench_fitness_trainer(results, dedup=True)
    bench_fitness_batched(results)
    bench_fitness_swept(results)
    bench_fitness_suite(results)
    bench_serve(results)
    bench_serve_chaos(results)
    base = results["fitness_eval"]["chromo_evals_per_s"]
    speedup = results["fitness_dispatch"]["chromo_evals_per_s"] / base
    results["dispatch_speedup_vs_seed"] = speedup
    results["trainer_dedup_on_speedup_vs_seed"] = (
        results["fitness_trainer_dedup_on"]["chromo_evals_per_s"] / base)
    # recorded so check_regression can skip relative gates when a PR's
    # runner has a different core count than the committed baseline's
    # (vmapped/batched rows skew hard with vCPUs; absolute floors and
    # bit-identity assertions are unconditional) — and so a stale
    # baseline from a different platform/jax build is visible in review
    results["cpu_count"] = os.cpu_count()
    results["platform"] = platform.platform()
    results["jax_version"] = jax.__version__
    with open(_RESULTS_PATH, "w") as f:
        json.dump(results, f, indent=1, default=float)
    print(f"# fitness dispatch speedup vs seed oracle: {speedup:.2f}x, "
          f"fused variation vs per-gene fold_in: "
          f"{results['variation_speedup_vs_seed']:.2f}x, "
          f"sweep ranking vs dominance matrix: "
          f"{results['ranking_speedup_vs_matrix']:.2f}x, "
          f"fused generation vs unfused phases: "
          f"{results['generation_fused_speedup']:.2f}x, "
          f"scanned trainer w/ dedup+cache (converged pop): "
          f"{results['trainer_dedup_on_speedup_vs_seed']:.2f}x, "
          f"8-seed batched vs sequential: "
          f"{results['batched_seeds_speedup_vs_sequential']:.2f}x, "
          f"4-cell config grid vs sequential: "
          f"{results['swept_configs_speedup_vs_sequential']:.2f}x, "
          f"5-dataset suite vs sequential: "
          f"{results['suite_speedup_vs_sequential']:.2f}x, "
          f"serve stream vs static max-shape dispatch: "
          f"{results['serve_throughput_speedup_vs_static']:.2f}x, "
          f"supervised serve overhead vs bare: "
          f"{results['supervised_overhead_vs_bare']:.3f}x, "
          f"MC-fitness K=8 batched vs sequential: "
          f"{results['mc_k8_overhead_vs_k1']:.2f}x "
          f"(→ {_RESULTS_PATH})")
    bench_pow2_packing()
    return results


if __name__ == "__main__":
    run()

"""Shared benchmark plumbing: per-dataset pipeline pieces with caching so
tables reuse each other's work within one `python -m benchmarks.run`.

Multi-seed statistics (the paper's numbers are means over repeated GA runs)
come from ``ga_run_suite``: ONE ``sweep.run_suite`` dispatch runs every
suite-eligible dataset × ``N_SEEDS`` seeds as one padded vmapped program
(the tables' former per-dataset retraining loops). ``ga_run_multi`` slices a
dataset's cells out of it — or falls back to a per-dataset
``engine.run_batch`` when the dataset runs at a non-default (pop, gens),
e.g. the full-scale pendigits override."""
from __future__ import annotations

import functools
import time

import numpy as np

from repro.api import (GAConfig, GATrainer, Problem, MLPTopology,
                       GenomeSpec, HardwareCost, accuracy,
                       calibrated_seeds, exact_bespoke_baseline,
                       train_float_mlp, best_within_loss,
                       run_batch, run_suite, state_at, front_of,
                       enable_compile_cache)
from repro.data import load_dataset, DATASETS

# every JAX benchmark imports this module first: later runs reuse its
# compiled programs from the persistent cache
enable_compile_cache()

GA_POP = 64
GA_GENS = 60
N_SEEDS = 3          # seeds per dataset for mean±std rows (tables I/II, fig4)
# Datasets the tables iterate over; ``benchmarks.run --datasets a,b`` narrows
# it so CI smoke / local runs can subset the suite.
DATASETS_ACTIVE = DATASETS
# Base PRNG seed threaded into every sub-benchmark (float training uses
# BENCH_SEED..BENCH_SEED+N_SEEDS-1, GA runs use BENCH_SEED.., kernel_bench
# derives its workloads from it). ``benchmarks.run --seed N`` overrides it;
# at a fixed value the whole `--quick` run is deterministic, so the CI
# regression gate always measures the same chromosome streams.
BENCH_SEED = 0
# pendigits is the hardest topology (16→5→10, 10 classes): the paper spends
# 26 M evaluations there (Table III); the bench gives it a bigger slice.
GA_OVERRIDES = {"pendigits": dict(pop=128, gens=200)}


def _resolve(name: str, pop: int | None, gens: int | None):
    """Normalize (pop, gens) BEFORE any cache key is formed: explicit
    arguments equal to the defaults must hit the same cache entry as the
    no-argument call (ga_run("cardio") vs ga_run("cardio", 64, 60))."""
    over = GA_OVERRIDES.get(name, {})
    return (pop or over.get("pop", GA_POP), gens or over.get("gens", GA_GENS))


@functools.lru_cache(maxsize=None)
def dataset(name: str):
    return load_dataset(name)


def float_baseline(name: str, seed: int | None = None):
    return _float_baseline(name, int(BENCH_SEED if seed is None else seed))


@functools.lru_cache(maxsize=None)
def _float_baseline(name: str, seed: int):
    ds = dataset(name)
    topo = MLPTopology(ds.topology)
    t0 = time.time()
    fm = train_float_mlp(topo, ds.x_train, ds.y_train, ds.x_test, ds.y_test,
                         steps=800, seed=seed)
    return fm, time.time() - t0


def bespoke_baseline(name: str, seed: int | None = None):
    return _bespoke_baseline(name, int(BENCH_SEED if seed is None else seed))


@functools.lru_cache(maxsize=None)
def _bespoke_baseline(name: str, seed: int):
    ds = dataset(name)
    topo = MLPTopology(ds.topology)
    fm, _ = float_baseline(name, seed)
    return exact_bespoke_baseline(topo, fm, ds.x_test, ds.y_test)


def bespoke_baseline_stats(name: str, n_seeds: int | None = None):
    """(mean, std, accs) of the exact-baseline accuracy over independent
    float-training seeds (Table I mean±std)."""
    # BENCH_SEED resolves *before* the cache boundary so a later reseed
    # cannot hit a stale entry
    return _bespoke_baseline_stats(name, n_seeds or N_SEEDS, int(BENCH_SEED))


@functools.lru_cache(maxsize=None)
def _bespoke_baseline_stats(name: str, n_seeds: int, seed0: int):
    accs = [bespoke_baseline(name, seed0 + i).accuracy
            for i in range(n_seeds)]
    return float(np.mean(accs)), float(np.std(accs)), accs


def _ga_setup(name: str):
    """Shared GA-run preamble: (dataset, topology, baseline, doping seeds).
    Both the single-seed and the batched entry points MUST build their
    runs from this so they can never drift apart."""
    ds = dataset(name)
    topo = MLPTopology(ds.topology)
    fm, _ = float_baseline(name)
    bb = bespoke_baseline(name)
    seeds = calibrated_seeds(GenomeSpec(topo), fm, ds.x_train)
    return ds, topo, bb, seeds


def ga_run(name: str, pop: int | None = None, gens: int | None = None,
           seed: int | None = None):
    """Returns (trainer, state, wall_s, evaluations)."""
    pop, gens = _resolve(name, pop, gens)
    return _ga_run(name, pop, gens, int(BENCH_SEED if seed is None else seed))


@functools.lru_cache(maxsize=None)
def _ga_run(name: str, pop: int, gens: int, seed: int):
    ds, topo, bb, seeds = _ga_setup(name)
    tr = GATrainer(topo, ds.x_train, ds.y_train,
                   GAConfig(pop_size=pop, generations=gens, seed=seed),
                   baseline_acc=bb.accuracy, doping_seeds=seeds)
    t0 = time.time()
    state, _ = tr.run()
    return tr, state, time.time() - t0, tr.evaluations


def suite_names() -> tuple:
    """Active datasets that run at the default (GA_POP, GA_GENS) — the ones
    the one-dispatch suite covers. Datasets with a GA_OVERRIDES entry (the
    full-scale pendigits run) keep their own ``run_batch`` dispatch."""
    return tuple(n for n in DATASETS_ACTIVE
                 if _resolve(n, None, None) == (GA_POP, GA_GENS))


def ga_run_suite(n_seeds: int | None = None):
    """The whole (dataset × seed) experiment grid as ONE dispatch.

    Returns (SuiteResult, wall_s). Every cell is bit-identical to the
    sequential per-dataset ``GATrainer.run`` the tables used to loop over."""
    return _ga_run_suite(suite_names(), n_seeds or N_SEEDS, GA_POP, GA_GENS,
                         int(BENCH_SEED))


@functools.lru_cache(maxsize=None)
def _ga_run_suite(names: tuple, n_seeds: int, pop: int, gens: int,
                  seed0: int):
    problems, dopings = [], []
    for name in names:
        ds, topo, bb, seeds = _ga_setup(name)
        problems.append(Problem.from_data(
            topo, ds.x_train, ds.y_train,
            GAConfig(pop_size=pop, generations=gens),
            baseline_acc=bb.accuracy))
        dopings.append(seeds)
    t0 = time.time()
    result = run_suite(problems, seed0 + np.arange(n_seeds),
                       doping_seeds=dopings, names=list(names))
    import jax
    jax.block_until_ready(result.states.pop)
    return result, time.time() - t0


def ga_run_multi(name: str, n_seeds: int | None = None,
                 pop: int | None = None, gens: int | None = None):
    """N independent GA runs of one dataset in ONE vmapped dispatch.

    Suite-eligible datasets slice their cells out of the shared
    ``ga_run_suite`` dispatch (so tables II/III and figs 4/5 together
    trigger exactly one GA compile+run); override datasets fall back to a
    per-dataset ``engine.run_batch``.

    Returns (problem, per-seed GAStates, per-seed fronts, wall_s). Caveat
    on ``wall_s`` from the suite path: it is the dataset's uniform
    1/n_datasets share of the padded suite wall (compile included). Suite
    lanes are padded to the max topology/sample count, so every cell costs
    the same — the share reflects the *suite's* amortized per-dataset
    cost, not the dataset's standalone training time (table3 labels it
    accordingly)."""
    pop, gens = _resolve(name, pop, gens)
    n_seeds = n_seeds or N_SEEDS
    if name in suite_names() and (pop, gens) == (GA_POP, GA_GENS):
        result, wall = ga_run_suite(n_seeds)
        idxs = result.cells_of(name)
        per_seed = [result.state_at(i) for i in idxs]
        fronts = [result.front_at(i) for i in idxs]
        d = list(result.names).index(name)
        return (result.problems[d], per_seed, fronts,
                wall * len(idxs) / result.n_cells)
    return _ga_run_multi(name, n_seeds, pop, gens, int(BENCH_SEED))


@functools.lru_cache(maxsize=None)
def _ga_run_multi(name: str, n_seeds: int, pop: int, gens: int, seed0: int):
    ds, topo, bb, seeds = _ga_setup(name)
    problem = Problem.from_data(
        topo, ds.x_train, ds.y_train,
        GAConfig(pop_size=pop, generations=gens),
        baseline_acc=bb.accuracy)
    t0 = time.time()
    states, _, _ = run_batch(problem, seed0 + np.arange(n_seeds),
                             doping_seeds=seeds)
    import jax
    jax.block_until_ready(states.pop)
    wall = time.time() - t0
    per_seed = [state_at(states, i) for i in range(n_seeds)]
    fronts = [front_of(s) for s in per_seed]
    return problem, per_seed, fronts, wall


def _point_from_front(name: str, problem, front, max_loss: float):
    import jax.numpy as jnp

    ds = dataset(name)
    bb = bespoke_baseline(name)
    idx = best_within_loss(front["objectives"], 1 - bb.accuracy, max_loss)
    if idx is None:
        return None
    g = front["genomes"][idx]
    test_acc = float(accuracy(problem.spec, jnp.asarray(g),
                              jnp.asarray(ds.x_test), jnp.asarray(ds.y_test)))
    fa = int(front["objectives"][idx, 1])
    return test_acc, fa, HardwareCost.from_fa(fa), g


def table_ii_points(name: str, max_loss: float = 0.05,
                    n_seeds: int | None = None):
    """Per-seed ≤max_loss points: list of (test_acc, fa, HardwareCost,
    genome) or None — one entry per GA seed of the batched run."""
    problem, _, fronts, _ = ga_run_multi(name, n_seeds)
    return [_point_from_front(name, problem, f, max_loss) for f in fronts]


def table_ii_point(name: str, max_loss: float = 0.05):
    """Our ≤max_loss point for the first seed (legacy single-seed view):
    (test_acc, fa, HardwareCost, genome) or None."""
    return table_ii_points(name, max_loss)[0]


def mean_std(values):
    """(mean, std) of a sequence, or None when it is empty."""
    if not values:
        return None
    return float(np.mean(values)), float(np.std(values))


def emit_row(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")

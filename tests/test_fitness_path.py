"""The fused GA fitness hot path: dispatcher backends, sample/population
tiling, duplicate-chromosome dedup, and the scanned trainer loop — all must
be bit-exact w.r.t. the seed semantics (untiled jnp oracle + per-generation
Python loop)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import GAConfig, GATrainer, engine
from repro.core import genome as genome_mod
from repro.core.dedup import dedup_eval, unique_rows
from repro.core.genome import MLPTopology, GenomeSpec
from repro.core.nsga2 import (dominance_matrix, evaluate_ranking,
                              subset_ranking, survivor_select)
from repro.kernels.pop_generation import pop_generation_kernel
from repro.kernels.pop_mlp import (population_correct, pop_mlp_correct,
                                   pop_mlp_correct_ref, pop_mlp_correct_tiled)
from repro.kernels.pop_mlp.kernel import pop_mlp_correct_mc, sample_tile
from repro.kernels.pop_variation.ops import _VARIATION_SLOTS
from repro.kernels.pop_variation.ref import pop_variation_ref


@pytest.fixture(scope="module")
def small_problem():
    spec = GenomeSpec(MLPTopology((10, 3, 2)))
    pop = spec.random(jax.random.PRNGKey(0), 24)
    x = jax.random.randint(jax.random.PRNGKey(1), (301, 10), 0, 16)
    y = jax.random.randint(jax.random.PRNGKey(2), (301,), 0, 2)
    return spec, pop, x, y


# -- tiled ref vs oracle parity ---------------------------------------------

@pytest.mark.parametrize("S", [37, 100, 256, 301])   # odd, < tile, = tile, > tile
@pytest.mark.parametrize("pop_tile,sample_tile", [(64, 256), (7, 128), (5, 33)])
def test_tiled_matches_oracle(small_problem, S, pop_tile, sample_tile):
    spec, pop, x, y = small_problem
    ref = pop_mlp_correct_ref(pop, x[:S], y[:S], spec=spec)
    out = pop_mlp_correct_tiled(pop, x[:S], y[:S], spec=spec,
                                pop_tile=pop_tile, sample_tile=sample_tile)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_kernel_matches_tiled_under_sample_tiling(small_problem):
    spec, pop, x, y = small_problem
    S = 2 * 2048 + 37                  # past the sample tile's VMEM cap
    xs = jnp.tile(x, (14, 1))[:S]
    ys = jnp.tile(y, 14)[:S]
    assert S % sample_tile(spec, S) and S > 2 * sample_tile(spec, S)
    ref = pop_mlp_correct_ref(pop, xs, ys, spec=spec)
    out = pop_mlp_correct(pop, xs, ys, spec=spec, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_kernel_pads_nondividing_population(small_problem):
    spec, pop, x, y = small_problem
    ref = pop_mlp_correct_ref(pop[:6], x, y, spec=spec)
    out = pop_mlp_correct(pop[:6], x, y, spec=spec, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# -- gene-major kernels vs the jnp oracles -----------------------------------

# P: population (200 pads the lane axis); S: samples (4133 spans three
# sample tiles, the last one partial); pad: rows of the suite layout the
# 10-3-2 problem is embedded into (12-4-5, out_mask, n_valid_samples 301);
# K: device instances
ORIENTATION_CASES = {
    "p256": dict(P=256, S=301),
    "p200": dict(P=200, S=301),
    "tail_tile": dict(P=200, S=4133),
    "suite_padded": dict(P=200, S=301, pad=4133),
    "mc_k8": dict(P=200, S=301, K=8),
}


def _orientation_case(P, S, pad=None, K=None):
    """(problem, population, deltas) of one case; the problem is padded
    into the suite layout when ``pad`` is given."""
    topo = MLPTopology((10, 3, 2))
    rng = np.random.default_rng(P + S)
    problem = engine.Problem.from_data(
        topo, rng.random((S, 10), np.float32), rng.integers(0, 2, S),
        GAConfig(pop_size=P))
    pop = np.asarray(problem.spec.random(jax.random.PRNGKey(P), P))
    if pad is not None:
        padded = GenomeSpec(MLPTopology((12, 4, 5)))
        pos = genome_mod.pad_positions(problem.spec, padded)
        pop = genome_mod.pad_genomes(pop, pos, padded.n_genes)
        problem = engine.pad_problem(problem, padded, pad, pos)
    dev = None
    if K is not None:
        exps = np.zeros(problem.spec.n_genes, bool)
        for sl in problem.spec.layers:
            exps[sl.exps] = True
        dev = np.where(exps, rng.integers(-1, 2, (K, exps.size)), 0)
        dev[0] = 0                                   # the nominal device
    return problem, jnp.asarray(pop, jnp.int32), dev


def _oracle_counts(problem, pop, dev):
    """(P,) — or (P, K) — counts of the untiled jnp oracle, each device
    instance perturbed on its own (``genome.apply_device_deltas``)."""
    def counts(rows):
        return np.asarray(pop_mlp_correct_ref(
            rows, problem.x_int, problem.labels, spec=problem.spec,
            out_mask=problem.out_mask))
    if dev is None:
        return counts(pop)
    return np.stack([counts(genome_mod.apply_device_deltas(
        pop, jnp.asarray(d), problem.genes.high)) for d in dev], axis=1)


@pytest.mark.parametrize("kernel", ["fitness", "generation"])
@pytest.mark.parametrize("case", list(ORIENTATION_CASES))
def test_gene_major_kernels_match_oracles(case, kernel):
    """The fitness kernels and the generation megakernel, in interpret
    mode, equal the jnp oracles bit for bit: lane-padded populations,
    partial sample tiles, the suite layout's output mask and sample bound
    (tiles past it skipped), and K = 8 device instances."""
    problem, pop, dev = _orientation_case(**ORIENTATION_CASES[case])
    n_valid = problem.n_valid_samples
    if case == "suite_padded":             # the tiles past 301 are skipped
        assert 301 == int(n_valid) < sample_tile(problem.spec, 4133)
    kw = dict(spec=problem.spec, interpret=True, n_valid_samples=n_valid,
              out_mask=problem.out_mask)
    t = problem.genes
    if kernel == "fitness":
        rows = pop
        out = (pop_mlp_correct(pop, problem.x_int, problem.labels, **kw)
               if dev is None else
               pop_mlp_correct_mc(pop, problem.x_int, problem.labels,
                                  jnp.asarray(dev), t.high, **kw))
    else:
        key = jax.random.PRNGKey(11)
        P = pop.shape[0]
        pa, pb = pop[: P // 2], pop[P // 2:]
        do_cx = jax.random.uniform(jax.random.PRNGKey(12), (P // 2,)) < 0.7
        pm = jnp.float32(0.05)
        rows, out = pop_generation_kernel(
            jnp.concatenate([pa, pb]), jnp.concatenate([pb, pa]),
            jnp.concatenate([do_cx, do_cx]), t.low, t.high, t.is_mask,
            t.mask_bits, t.ids, genome_mod._slot_keys(key, _VARIATION_SLOTS),
            pm, problem.x_int, problem.labels,
            dev=None if dev is None else jnp.asarray(dev), **kw)
        children = pop_variation_ref(key, pa, pb, do_cx[:, None], t, pm)
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(children))
    np.testing.assert_array_equal(np.asarray(out),
                                  _oracle_counts(problem, rows, dev))


@pytest.mark.parametrize("backend", ["ref", "interpret", "jnp"])
def test_dispatcher_backends_agree(small_problem, backend):
    spec, pop, x, y = small_problem
    ref = pop_mlp_correct_ref(pop, x, y, spec=spec)
    out = population_correct(pop, x, y, spec=spec, backend=backend)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_n_valid_rows_skips_but_keeps_valid_rows_exact(small_problem, backend):
    spec, pop, x, y = small_problem
    ref = pop_mlp_correct_ref(pop, x, y, spec=spec)
    out = population_correct(pop, x, y, spec=spec, backend=backend,
                             pop_tile=8, n_valid_rows=jnp.int32(10))
    # rows < n_valid_rows are exact; later rows are unspecified (skipped)
    np.testing.assert_array_equal(np.asarray(out)[:10], np.asarray(ref)[:10])


# -- dedup cache -------------------------------------------------------------

def test_dedup_eval_matches_naive(small_problem):
    spec, pop, x, y = small_problem
    idx = jax.random.randint(jax.random.PRNGKey(3), (40,), 0, 8)
    rows = pop[idx]                              # heavy duplication
    naive = pop_mlp_correct_ref(rows, x, y, spec=spec)

    def eval_fn(batch, n):
        return population_correct(batch, x, y, spec=spec, backend="ref",
                                  pop_tile=8, n_valid_rows=n)

    out, n_eval = dedup_eval(eval_fn, rows)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(naive))
    assert int(n_eval) == len(np.unique(np.asarray(rows), axis=0))


def test_dedup_eval_reuses_known_values(small_problem):
    spec, pop, x, y = small_problem
    rows = jnp.concatenate([pop[:8], pop[:8], pop[8:12]])   # 8 known + dups

    def eval_fn(batch, n):
        return population_correct(batch, x, y, spec=spec, backend="ref",
                                  pop_tile=4, n_valid_rows=n)

    known = pop_mlp_correct_ref(pop[:8], x, y, spec=spec)
    out, n_eval = dedup_eval(eval_fn, rows, known=known)
    naive = pop_mlp_correct_ref(rows, x, y, spec=spec)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(naive))
    assert int(n_eval) == 4      # only the 4 genuinely new rows

def test_dedup_eval_jit_deterministic(small_problem):
    spec, pop, x, y = small_problem
    idx = jax.random.randint(jax.random.PRNGKey(4), (32,), 0, 6)
    rows = pop[idx]

    def eval_fn(batch, n):
        return population_correct(batch, x, y, spec=spec, backend="ref",
                                  pop_tile=8, n_valid_rows=n)

    eager, _ = dedup_eval(eval_fn, rows)
    jitted, _ = jax.jit(lambda r: dedup_eval(eval_fn, r))(rows)
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(jitted))


def test_unique_rows_roundtrip():
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 3, (20, 5))
    uniq, inverse = unique_rows(rows)
    np.testing.assert_array_equal(uniq[inverse], rows)


# -- ranking reuse -----------------------------------------------------------

def test_subset_ranking_equals_recompute(key):
    obj = jax.random.uniform(key, (48, 2))
    viol = jnp.maximum(0.0, jax.random.uniform(jax.random.PRNGKey(9), (48,)) - 0.7)
    dom = dominance_matrix(obj, viol)
    rank, crowd = evaluate_ranking(obj, viol)
    keep = survivor_select(rank, crowd, 24)
    r_direct, c_direct = evaluate_ranking(obj[keep], viol[keep])
    r_reuse, c_reuse = subset_ranking(dom, obj, keep)
    np.testing.assert_array_equal(np.asarray(r_direct), np.asarray(r_reuse))
    np.testing.assert_array_equal(np.asarray(c_direct), np.asarray(c_reuse))


# -- scanned trainer equivalence --------------------------------------------

@pytest.fixture(scope="module")
def bc_trainers(bc_dataset):
    ds = bc_dataset
    topo = MLPTopology(ds.topology)

    def make(**kw):
        cfg = GAConfig(pop_size=32, generations=8, seed=5, **kw)
        return GATrainer(topo, ds.x_train, ds.y_train, cfg)

    return make


def _states_equal(a, b):
    return (bool((a.pop == b.pop).all()) and bool((a.obj == b.obj).all())
            and bool((a.viol == b.viol).all())
            and bool((a.rank == b.rank).all())
            and bool((a.crowd == b.crowd).all()))


def test_scanned_run_matches_seed_loop(bc_trainers):
    """Acceptance: the scanned loop + tiled backend reproduce the seed
    trainer (python loop + jnp oracle) bit-for-bit, dedup disabled."""
    seed_tr = bc_trainers(fitness_backend="jnp", dedup=False, scan=False)
    new_tr = bc_trainers(fitness_backend="ref", dedup=False, scan=True)
    s_seed, _ = seed_tr.run()
    s_new, _ = new_tr.run()
    assert _states_equal(s_seed, s_new)
    f_seed, f_new = seed_tr.front(s_seed), new_tr.front(s_new)
    np.testing.assert_array_equal(f_seed["objectives"], f_new["objectives"])
    np.testing.assert_array_equal(f_seed["genomes"], f_new["genomes"])


def test_dedup_cache_is_bit_exact(bc_trainers):
    """Duplicated population rows produce identical objectives to the
    naive path — dedup changes cost, never results."""
    naive = bc_trainers(fitness_backend="ref", dedup=False, scan=True)
    dedup = bc_trainers(fitness_backend="ref", dedup=True, scan=True)
    s_naive, _ = naive.run()
    s_dedup, _ = dedup.run()
    assert _states_equal(s_naive, s_dedup)
    assert dedup.unique_evals is not None
    assert dedup.unique_evals <= 9 * 32     # never more than nominal


def test_scan_history_logged(bc_trainers):
    tr = bc_trainers()
    _, hist = tr.run(verbose=True)
    assert [h["gen"] for h in hist] == [0, 7]   # log_every=10, gens=8
    assert all(set(h) == {"gen", "best_err", "best_area"}
               for h in hist)


def test_stepped_history_logged(bc_trainers):
    tr = bc_trainers(log_every=3)
    _, hist = tr.run(verbose=True, scan=False)
    assert [h["gen"] for h in hist] == [0, 3, 6, 7]
    assert all(set(h) == {"gen", "best_err", "best_area"}
               for h in hist)

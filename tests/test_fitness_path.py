"""The fused GA fitness hot path: dispatcher backends, sample/population
tiling, duplicate-chromosome dedup, and the scanned trainer loop — all must
be bit-exact w.r.t. the seed semantics (untiled jnp oracle + per-generation
Python loop)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import GAConfig, GATrainer
from repro.core.dedup import dedup_eval, unique_rows
from repro.core.genome import MLPTopology, GenomeSpec
from repro.core.nsga2 import (dominance_matrix, evaluate_ranking,
                              subset_ranking, survivor_select)
from repro.kernels.pop_mlp import (population_correct, pop_mlp_correct,
                                   pop_mlp_correct_ref, pop_mlp_correct_tiled)


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
    ref = pop_mlp_correct_ref(pop, x, y, spec=spec)
    out = pop_mlp_correct(pop, x, y, spec=spec, bp=8, bs=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_kernel_pads_nondividing_population(small_problem):
    spec, pop, x, y = small_problem
    ref = pop_mlp_correct_ref(pop[:6], x, y, spec=spec)
    out = pop_mlp_correct(pop[:6], x, y, spec=spec, bp=4, bs=128,
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


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

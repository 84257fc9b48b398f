"""Suite batching: `sweep.run_suite` embeds several topologies/datasets in
one padded layout and runs (dataset × seed × config) as one dispatch. Every
cell must be bit-identical to the *unpadded* sequential ``GATrainer.run`` —
populations (gathered back to the inner layout), objectives, rankings, PRNG
keys and the dedup ``unique_row_evals`` accounting — and the canonical-zero
padding invariant must survive init, mutation and crossover."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import GAConfig, GATrainer
from repro.core import engine, sweep
from repro.core.genome import (MLPTopology, GenomeSpec, max_topology,
                               pad_positions, padded_table, pad_genomes,
                               random_population)
from repro.core.operators import make_offspring
from repro.data import load_dataset
from repro.kernels.pop_mlp import population_correct
from repro.kernels.pop_mlp.kernel import row_group


STATE_FIELDS = ("pop", "obj", "viol", "rank", "crowd", "counts", "key", "gen")
SEEDS = (0, 1)


def assert_states_equal(a, b, msg=""):
    for name in STATE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"{msg}: GAState.{name} differs")


@pytest.fixture(scope="module")
def two_datasets():
    # different feature counts, hidden widths, class counts, sample counts
    return load_dataset("breast_cancer"), load_dataset("redwine")


def _problems(datasets, cfg):
    return [engine.Problem.from_data(MLPTopology(ds.topology),
                                     ds.x_train, ds.y_train, cfg)
            for ds in datasets]


def _trainer(ds, cfg, seed, **kw):
    c = dataclasses.replace(cfg, seed=seed)
    return GATrainer(MLPTopology(ds.topology), ds.x_train, ds.y_train, c, **kw)


@pytest.mark.parametrize("dedup", [True, False])
def test_suite_matches_unpadded_trainers(two_datasets, dedup):
    """Acceptance: each suite cell == the unpadded sequential trainer run
    with that cell's (dataset, seed), bit for bit, dedup on and off —
    including unique-row-eval parity under the shared pmax bound."""
    cfg = GAConfig(pop_size=16, generations=4, dedup=dedup)
    result = sweep.run_suite(_problems(two_datasets, cfg), SEEDS,
                             names=[ds.name for ds in two_datasets])
    assert result.shape == (2, len(SEEDS), 1, 1, 1, 1)
    for i in range(result.n_cells):
        cell = result.cell(i)
        ds = next(d for d in two_datasets if d.name == cell["dataset"])
        tr = _trainer(ds, cfg, cell["seed"])
        state, _ = tr.run()
        assert_states_equal(result.state_at(i), state, msg=f"cell {cell}")
        if dedup:
            assert result.unique_evals(i) == tr.unique_evals, \
                f"cell {cell}: unique_row_evals diverged"
        f_tr, f_suite = tr.front(state), result.front_at(i)
        np.testing.assert_array_equal(f_tr["objectives"],
                                      f_suite["objectives"])
        np.testing.assert_array_equal(f_tr["genomes"], f_suite["genomes"])


def test_suite_with_doping_and_config_axis(two_datasets):
    """Doped inits and a mutation-rate axis compose with the dataset axis;
    every cell still equals the sequential doped trainer."""
    from repro.core import calibrated_seeds
    from repro.core.baselines import train_float_mlp

    cfg = GAConfig(pop_size=16, generations=3)
    rates = (0.02, 0.05)
    doping = []
    for ds in two_datasets:
        topo = MLPTopology(ds.topology)
        fm = train_float_mlp(topo, ds.x_train, ds.y_train, ds.x_test,
                             ds.y_test, steps=200)
        doping.append(calibrated_seeds(GenomeSpec(topo), fm, ds.x_train))
    result = sweep.run_suite(_problems(two_datasets, cfg), [0],
                             mutation_rates=rates, doping_seeds=doping,
                             names=[ds.name for ds in two_datasets])
    assert result.shape == (2, 1, 1, len(rates), 1, 1)
    for i in range(result.n_cells):
        cell = result.cell(i)
        d = result.dataset_of(i)
        ds = two_datasets[d]
        c = dataclasses.replace(cfg, mutation_rate_gene=cell["mutation_rate_gene"])
        tr = _trainer(ds, c, cell["seed"], doping_seeds=doping[d])
        state, _ = tr.run()
        assert_states_equal(result.state_at(i), state, msg=f"cell {cell}")


def test_operators_never_perturb_padded_genes(two_datasets):
    """Canonical-zero invariant: init, mutation and crossover write only
    zeros into padding — for every generation of a padded run."""
    bc, rw = two_datasets
    inner = GenomeSpec(MLPTopology(bc.topology))
    spec_pad = GenomeSpec(max_topology([MLPTopology(bc.topology),
                                        MLPTopology(rw.topology)]))
    table = padded_table(inner, spec_pad)
    key = jax.random.PRNGKey(0)
    pop = random_population(key, table, 32)
    invalid = ~np.asarray(table.valid)
    assert np.asarray(pop)[:, invalid].sum() == 0, "init wrote into padding"

    rank = jnp.zeros(32, jnp.int32)
    crowd = jnp.ones(32, jnp.float32)
    children = make_offspring(jax.random.PRNGKey(1), pop, rank, crowd, table,
                              jnp.float32(0.9), jnp.float32(0.5))
    assert np.asarray(children)[:, invalid].sum() == 0, \
        "mutation/crossover wrote into padding"

    # and a whole padded run keeps the invariant through every generation
    cfg = GAConfig(pop_size=16, generations=3)
    problem = engine.pad_problem(
        engine.Problem.from_data(MLPTopology(bc.topology), bc.x_train,
                                 bc.y_train, cfg), spec_pad)
    state, _ = engine.init_state(problem, jax.random.PRNGKey(0))
    state, _ = engine.run_scanned(problem, state, 3)
    assert np.asarray(state.pop)[:, invalid].sum() == 0


@pytest.mark.parametrize("backend", ["ref", "interpret", "jnp"])
def test_padded_fitness_counts_match_inner(two_datasets, backend):
    """Padded fan-in/fan-out/output-column masking on every backend: the
    padded genome + padded samples yield the inner counts exactly."""
    bc, rw = two_datasets
    inner = GenomeSpec(MLPTopology(bc.topology))
    spec_pad = GenomeSpec(max_topology([MLPTopology(bc.topology),
                                        MLPTopology(rw.topology)]))
    pos = pad_positions(inner, spec_pad)
    pop = inner.random(jax.random.PRNGKey(3), 12)
    cfg = GAConfig(pop_size=12, generations=1)
    p_in = engine.Problem.from_data(MLPTopology(bc.topology), bc.x_train,
                                    bc.y_train, cfg)
    p_pad = engine.pad_problem(p_in, spec_pad,
                               n_samples=p_in.x_int.shape[0] + 57)
    ref = population_correct(pop, p_in.x_int, p_in.labels, spec=inner,
                             backend=backend)
    pop_pad = jnp.asarray(pad_genomes(np.asarray(pop), pos,
                                      spec_pad.n_genes))
    out = population_correct(pop_pad, p_pad.x_int, p_pad.labels,
                             spec=spec_pad, backend=backend,
                             out_mask=p_pad.out_mask)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # the sample-tile skip (tiles past the true sample count hold only
    # label −1 padding) must be bit-identical on every backend
    out_skip = population_correct(pop_pad, p_pad.x_int, p_pad.labels,
                                  spec=spec_pad, backend=backend,
                                  out_mask=p_pad.out_mask,
                                  n_valid_samples=p_pad.n_valid_samples)
    np.testing.assert_array_equal(np.asarray(out_skip), np.asarray(ref))


def test_padded_area_matches_inner(two_datasets):
    """Padded weights/neurons contribute zero adder columns: FA counts of a
    padded population equal the inner population's exactly."""
    from repro.core.area import population_area

    bc, rw = two_datasets
    inner = GenomeSpec(MLPTopology(bc.topology))
    spec_pad = GenomeSpec(max_topology([MLPTopology(bc.topology),
                                        MLPTopology(rw.topology)]))
    pos = pad_positions(inner, spec_pad)
    pop = inner.random(jax.random.PRNGKey(4), 8)
    pop_pad = jnp.asarray(pad_genomes(np.asarray(pop), pos,
                                      spec_pad.n_genes))
    np.testing.assert_array_equal(
        np.asarray(population_area(spec_pad, pop_pad)),
        np.asarray(population_area(inner, pop)))


def test_suite_sharded_matches_vmap(two_datasets):
    """A mesh-sharded suite (cells split over devices) is bit-identical to
    the single-device vmap, including the repeat-last-cell padding."""
    cfg = GAConfig(pop_size=8, generations=2)
    problems = _problems(two_datasets, cfg)
    mesh = jax.make_mesh((1,), ("data",))
    r_vmap = sweep.run_suite(problems, [0, 2, 5])
    r_mesh = sweep.run_suite(problems, [0, 2, 5], mesh=mesh)
    for name in STATE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(r_vmap.states, name)),
            np.asarray(getattr(r_mesh.states, name)),
            err_msg=f"sharded GAState.{name} differs")
    np.testing.assert_array_equal(np.asarray(r_vmap.init_evals),
                                  np.asarray(r_mesh.init_evals))


def test_suite_rejects_mismatched_configs(two_datasets):
    bc, rw = two_datasets
    p1 = engine.Problem.from_data(MLPTopology(bc.topology), bc.x_train,
                                  bc.y_train, GAConfig(pop_size=8))
    p2 = engine.Problem.from_data(MLPTopology(rw.topology), rw.x_train,
                                  rw.y_train, GAConfig(pop_size=16))
    with pytest.raises(ValueError, match="share one GAConfig"):
        sweep.run_suite([p1, p2], [0])


def test_pad_problem_rejects_jnp_backend(two_datasets):
    bc, rw = two_datasets
    cfg = GAConfig(pop_size=8, fitness_backend="jnp")
    spec_pad = GenomeSpec(max_topology([MLPTopology(bc.topology),
                                        MLPTopology(rw.topology)]))
    p = engine.Problem.from_data(MLPTopology(bc.topology), bc.x_train,
                                 bc.y_train, cfg)
    with pytest.raises(ValueError, match="count-based"):
        engine.pad_problem(p, spec_pad)


def test_second_sharded_call_traces_nothing(two_datasets):
    """The mesh path builds its bucket program once per (generations,
    mesh, axis names): a second call with the same shapes traces and
    lowers no program at all, and the shared memo is hit, not grown."""
    cfg = GAConfig(pop_size=8, generations=2)
    problems = _problems(two_datasets, cfg)
    mesh = jax.make_mesh((1,), ("data",))
    sweep.run_suite(problems, [0, 2], mesh=mesh)
    built = sweep._sharded_program.cache_info().currsize
    lowered = []

    def listen(event, secs, fun_name=None, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        again = sweep.run_suite(problems, [0, 2], mesh=jax.make_mesh(
            (1,), ("data",)))
        jax.block_until_ready(again.states.pop)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert lowered == []
    assert sweep._sharded_program.cache_info().currsize == built


@pytest.mark.parametrize("factor", [1.0, None])
def test_fitness_terms_match_the_shape_count(two_datasets, factor):
    """``SuiteResult.fitness_terms``: real terms at each dataset's own rows
    and topology, run terms at the padded topology and the sample bound
    of the dispatch each dataset ran in, in whole row groups of the
    Pallas kernels' row loop — two buckets with the default factor, one
    shared bound without."""
    cfg = GAConfig(pop_size=8, generations=2)
    result = sweep.run_suite(_problems(two_datasets, cfg), [0, 1],
                             sample_bucket_factor=factor)
    rows = [ds.x_train.shape[0] for ds in two_datasets]
    assert rows[0] != rows[1]
    own = [10 * 3 + 3 * 2, 11 * 2 + 2 * 6]         # 10-3-2 and 11-2-6
    padded = 11 * 3 + 3 * 6                          # 11-3-6
    group = row_group(result.spec)
    assert group == 16              # 3 + 6 live planes + 2 temporaries
    bound = rows if factor else [max(rows)] * 2
    real = run = 0
    for d in range(2):
        evals = sum(result.unique_evals(i) for i in result.cells_of(d))
        real += evals * rows[d] * own[d]
        run += evals * -(-bound[d] // group) * group * padded
    assert result.fitness_terms() == (real, run)


def test_second_call_without_mesh_traces_nothing(two_datasets):
    """The one-device path reuses its programs on a second call too: the
    padded spec it builds anew compares equal to the first call's by its
    topology, so the programs' static keys match."""
    cfg = GAConfig(pop_size=8, generations=2)
    problems = _problems(two_datasets, cfg)
    first = sweep.run_suite(problems, [0, 2])
    assert sweep.suite_spec(problems) == first.spec
    assert hash(sweep.suite_spec(problems)) == hash(first.spec)
    assert sweep.suite_spec(problems[:1]) != first.spec
    lowered = []

    def listen(event, secs, fun_name=None, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        again = sweep.run_suite(problems, [0, 2])
        jax.block_until_ready(again.states.pop)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert lowered == []

"""The ranking dispatcher: ``repro.kernels.pop_ranking`` backends must be
invisible in the results — the O(P log P) sweep reproduces the
dominance-matrix oracle bit for bit on every edge-case population and
through whole trainer / batched / suite / island runs, dedup on and off.
(Property-based coverage lives in test_ranking_sweep.py; this module is
hypothesis-free so it always runs.)"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import GAConfig, GATrainer
from repro.core import engine, sweep
from repro.core.genome import MLPTopology
from repro.core.islands import IslandConfig, run_islands
from repro.core.nsga2 import evaluate_ranking
from repro.kernels.pop_ranking import (BACKENDS, population_ranking,
                                       rank_select_rerank, sweep_rank)
from repro.data import load_dataset


STATE_FIELDS = ("pop", "obj", "viol", "rank", "crowd", "counts", "key", "gen")


def assert_states_equal(a, b, msg=""):
    for name in STATE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"{msg}: GAState.{name} differs")


# -- dispatcher --------------------------------------------------------------

def test_backend_list_is_closed():
    assert BACKENDS == ("auto", "kernel", "interpret", "sweep", "matrix")
    obj = jnp.zeros((4, 2))
    viol = jnp.zeros((4,))
    with pytest.raises(ValueError, match="backend"):
        population_ranking(obj, viol, backend="nope")
    with pytest.raises(ValueError, match="backend"):
        rank_select_rerank(obj, viol, 2, backend="nope")


@pytest.mark.parametrize("platform,resolved", [("tpu", "kernel"),
                                                ("cpu", "sweep")])
def test_auto_is_the_kernel_on_tpu_only(monkeypatch, platform, resolved):
    from repro.kernels.pop_ranking import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: platform)
    assert ops._resolve("auto") == resolved
    assert ops._resolve(None) == resolved


def test_sweep_is_two_objective_only():
    with pytest.raises(ValueError, match="2-objective"):
        sweep_rank(jnp.zeros((4, 3)), jnp.zeros((4,)))


# -- edge-case populations ---------------------------------------------------

EDGE_CASES = {
    # exact duplicate objective rows (must share front, dominate nothing)
    "duplicates": (np.array([[0.3, 0.7]] * 4 + [[0.1, 0.9], [0.5, 0.5]],
                            np.float32),
                   np.zeros(6, np.float32)),
    # full tie on one axis — strictness decided on the other
    "tie-axis0": (np.stack([np.full(8, 0.25), np.arange(8) / 8.0],
                           axis=1).astype(np.float32),
                  np.zeros(8, np.float32)),
    "tie-axis1": (np.stack([np.arange(8) / 8.0, np.full(8, 0.25)],
                           axis=1).astype(np.float32),
                  np.zeros(8, np.float32)),
    # nobody feasible: pure violation layering, with an equal-viol pair
    "all-infeasible": (np.random.default_rng(0)
                       .random((7, 2)).astype(np.float32),
                       np.array([0.3, 0.1, 0.3, 0.7, 0.2, 0.1, 0.5],
                                np.float32)),
    # a clean single front (strictly decreasing trade-off)
    "single-front": (np.stack([np.arange(6) / 6.0, (5 - np.arange(6)) / 6.0],
                              axis=1).astype(np.float32),
                     np.zeros(6, np.float32)),
    # singletons, feasible and not
    "P1-feasible": (np.array([[0.2, 0.8]], np.float32),
                    np.zeros(1, np.float32)),
    "P1-infeasible": (np.array([[0.2, 0.8]], np.float32),
                      np.array([0.4], np.float32)),
    # mixed feasible/infeasible with equal violations among the infeasible
    "mixed": (np.random.default_rng(1).random((12, 2)).astype(np.float32),
              np.array([0.0] * 6 + [0.2, 0.2, 0.1, 0.0, 0.3, 0.1],
                       np.float32)),
}


@pytest.mark.parametrize("case", sorted(EDGE_CASES))
def test_sweep_matches_matrix_edge_cases(case):
    obj, viol = EDGE_CASES[case]
    obj, viol = jnp.asarray(obj), jnp.asarray(viol)
    rank_m, crowd_m = evaluate_ranking(obj, viol)
    rank_s, crowd_s = population_ranking(obj, viol, backend="sweep")
    np.testing.assert_array_equal(np.asarray(rank_m), np.asarray(rank_s),
                                  err_msg=f"{case}: ranks differ")
    np.testing.assert_array_equal(np.asarray(crowd_m), np.asarray(crowd_s),
                                  err_msg=f"{case}: crowding differs")


@pytest.mark.parametrize("case", sorted(EDGE_CASES))
def test_kernel_matches_matrix_edge_cases(case):
    """The staircase pass as a Pallas kernel (interpret mode) gives the
    oracle's ranks and crowding on every edge-case population."""
    obj, viol = EDGE_CASES[case]
    obj, viol = jnp.asarray(obj), jnp.asarray(viol)
    rank_m, crowd_m = evaluate_ranking(obj, viol)
    rank_k, crowd_k = population_ranking(obj, viol, backend="interpret")
    np.testing.assert_array_equal(np.asarray(rank_m), np.asarray(rank_k),
                                  err_msg=f"{case}: ranks differ")
    np.testing.assert_array_equal(np.asarray(crowd_m), np.asarray(crowd_k),
                                  err_msg=f"{case}: crowding differs")


@pytest.mark.parametrize("P", [130, 512])
def test_kernel_matches_sweep_past_one_lane_row(P):
    """Pools wider than one 128-lane row of the kernel's staircase: a
    many-front pool with duplicates and mixed feasibility ranks as the
    scan does, in the (μ+λ) tail too."""
    rng = np.random.default_rng(P)
    obj = jnp.asarray((rng.integers(0, 24, (P, 2)) / 24.0)
                      .astype(np.float32))
    viol = jnp.asarray(np.maximum(0.0, rng.random(P).astype(np.float32)
                                  - 0.8))
    for a, b in zip(rank_select_rerank(obj, viol, P // 2, backend="sweep"),
                    rank_select_rerank(obj, viol, P // 2,
                                       backend="interpret")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_rank_select_rerank_backends_agree(seed):
    """The whole (μ+λ) tail — survivors and their subset re-ranking —
    is bit-identical between the sweep and the matrix oracle, on pools
    with duplicates and mixed feasibility."""
    rng = np.random.default_rng(seed)
    P, mu = 64, 32
    obj = (rng.integers(0, 12, (P, 2)) / 12.0).astype(np.float32)
    viol = np.maximum(0.0, rng.random(P).astype(np.float32) - 0.7)
    obj, viol = jnp.asarray(obj), jnp.asarray(viol)
    keep_s, rank_s, crowd_s = rank_select_rerank(obj, viol, mu,
                                                 backend="sweep")
    keep_m, rank_m, crowd_m = rank_select_rerank(obj, viol, mu,
                                                 backend="matrix")
    np.testing.assert_array_equal(np.asarray(keep_s), np.asarray(keep_m))
    np.testing.assert_array_equal(np.asarray(rank_s), np.asarray(rank_m))
    np.testing.assert_array_equal(np.asarray(crowd_s), np.asarray(crowd_m))


def test_sweep_vmaps_without_cross_lane_coupling():
    """The sweep has no data-dependent trip count: a batch mixing a
    converged many-front lane with a single-front lane ranks each lane
    exactly as the unbatched call does."""
    rng = np.random.default_rng(3)
    many = (rng.integers(0, 4, (32, 2)) / 4.0).astype(np.float32)
    single = np.stack([np.arange(32) / 32.0,
                       (31 - np.arange(32)) / 32.0], axis=1).astype(np.float32)
    objs = jnp.asarray(np.stack([many, single]))
    viols = jnp.zeros((2, 32), jnp.float32)
    batched = jax.vmap(sweep_rank)(objs, viols)
    for i in range(2):
        np.testing.assert_array_equal(np.asarray(batched[i]),
                                      np.asarray(sweep_rank(objs[i],
                                                            viols[i])))


def test_kernel_vmaps_without_cross_lane_coupling():
    """Under ``vmap`` (the suite's and the server's lanes) the kernel ranks
    each lane as the unbatched scan does."""
    rng = np.random.default_rng(4)
    many = (rng.integers(0, 4, (32, 2)) / 4.0).astype(np.float32)
    single = np.stack([np.arange(32) / 32.0,
                       (31 - np.arange(32)) / 32.0], axis=1).astype(np.float32)
    objs = jnp.asarray(np.stack([many, single]))
    viols = jnp.asarray(np.stack([np.zeros(32, np.float32),
                                  np.where(np.arange(32) % 3 == 0, 0.5, 0.0)
                                  .astype(np.float32)]))
    batched = jax.vmap(lambda o, v: sweep_rank(o, v, kernel=True,
                                               interpret=True))(objs, viols)
    for i in range(2):
        np.testing.assert_array_equal(np.asarray(batched[i]),
                                      np.asarray(sweep_rank(objs[i],
                                                            viols[i])))


# -- whole-run equivalence ---------------------------------------------------

def _run(ds, **kw):
    cfg = GAConfig(pop_size=16, generations=4, seed=2,
                   fitness_backend="ref", **kw)
    tr = GATrainer(MLPTopology(ds.topology), ds.x_train, ds.y_train, cfg)
    state, _ = tr.run()
    return state


@pytest.mark.parametrize("dedup", [True, False])
def test_trainer_sweep_vs_matrix(bc_dataset, dedup):
    s_m = _run(bc_dataset, dedup=dedup, ranking_backend="matrix")
    s_s = _run(bc_dataset, dedup=dedup, ranking_backend="sweep")
    s_a = _run(bc_dataset, dedup=dedup, ranking_backend="auto")
    assert_states_equal(s_m, s_s, msg=f"sweep dedup={dedup}")
    assert_states_equal(s_s, s_a, msg=f"auto dedup={dedup}")


def test_trainer_kernel_vs_sweep(bc_dataset):
    s_s = _run(bc_dataset, ranking_backend="sweep")
    s_k = _run(bc_dataset, ranking_backend="interpret")
    assert_states_equal(s_s, s_k, msg="kernel")


def test_run_batch_sweep_vs_matrix(bc_dataset):
    ds = bc_dataset
    seeds = [0, 1]
    states = {}
    for backend in ("matrix", "sweep"):
        cfg = GAConfig(pop_size=16, generations=4, fitness_backend="ref",
                       ranking_backend=backend)
        problem = engine.Problem.from_data(MLPTopology(ds.topology),
                                           ds.x_train, ds.y_train, cfg)
        states[backend], _, _ = engine.run_batch(problem, seeds)
    for i, s in enumerate(seeds):
        assert_states_equal(engine.state_at(states["matrix"], i),
                            engine.state_at(states["sweep"], i),
                            msg=f"seed {s}")


def test_run_suite_sweep_vs_matrix(bc_dataset):
    """The padded multi-topology suite dispatch ranks identically under
    either backend (the sweep sees masked pad rows only through obj/viol,
    exactly like the matrix)."""
    rw = load_dataset("redwine")
    datasets = (bc_dataset, rw)
    fronts = {}
    for backend in ("matrix", "sweep"):
        cfg = GAConfig(pop_size=16, generations=3, ranking_backend=backend)
        problems = [engine.Problem.from_data(MLPTopology(d.topology),
                                             d.x_train, d.y_train, cfg)
                    for d in datasets]
        result = sweep.run_suite(problems, [0],
                                 names=[d.name for d in datasets])
        fronts[backend] = [result.state_at(i) for i in range(result.n_cells)]
    for i in range(len(fronts["matrix"])):
        assert_states_equal(fronts["matrix"][i], fronts["sweep"][i],
                            msg=f"suite cell {i}")


def test_islands_sweep_vs_matrix(bc_dataset):
    """Ring migration re-ranks through the dispatcher inside shard_map;
    the resulting fronts are backend-independent."""
    ds = bc_dataset
    mesh = jax.make_mesh((1,), ("data",))
    fronts = {}
    for backend in ("matrix", "sweep"):
        cfg = GAConfig(pop_size=16, generations=6, seed=3,
                       ranking_backend=backend)
        icfg = IslandConfig(ga=cfg, island_pop=16, migrate_every=3,
                            n_migrants=2, rounds=2)
        fronts[backend], _ = run_islands(MLPTopology(ds.topology),
                                         ds.x_train, ds.y_train, mesh,
                                         icfg, seed=3)
    np.testing.assert_array_equal(fronts["matrix"]["objectives"],
                                  fronts["sweep"]["objectives"])
    np.testing.assert_array_equal(fronts["matrix"]["genomes"],
                                  fronts["sweep"]["genomes"])

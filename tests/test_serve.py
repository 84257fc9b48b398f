"""Continuous-batching GA search service (`repro.serve`) + the engine's
per-lane generation-budget gate it schedules around.

Acceptance contract: every job a :class:`SearchServer` retires is
bit-identical to its standalone sequential ``GATrainer.run`` — states,
fronts AND the dedup ``unique_evals``/``cache_hits`` accounting — no
matter when the job was admitted, which lanes ran beside it, or how the
budgets straddle segment boundaries. The budget gate itself must be a
no-op when unused: budget == generations reproduces today's ungated path
bit-for-bit.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import GAConfig, GATrainer
from repro.core import engine
from repro.core.genome import MLPTopology
from repro.data import load_dataset
from repro.serve import LaneScheduler, SearchJob, SearchServer
from repro.serve import server as server_mod

STATE_FIELDS = ("pop", "obj", "viol", "rank", "crowd", "counts", "key", "gen")


def assert_states_equal(a, b, msg=""):
    for name in STATE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"{msg}: GAState.{name} differs")


def assert_caches_equal(a, b, msg=""):
    for name in ("rows", "vals", "stamp"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.cache, name)),
            np.asarray(getattr(b.cache, name)),
            err_msg=f"{msg}: EvalCache.{name} differs")


@pytest.fixture(scope="module")
def two_datasets():
    # different topologies AND sample counts (489 vs 1120): jobs land in
    # genuinely different sample-size regimes of the shared padded layout
    return load_dataset("breast_cancer"), load_dataset("redwine")


def _problem(ds, cfg):
    return engine.Problem.from_data(MLPTopology(ds.topology), ds.x_train,
                                    ds.y_train, cfg)


def _trainer_state(ds, cfg, seed, generations):
    tr = GATrainer(MLPTopology(ds.topology), ds.x_train, ds.y_train,
                   dataclasses.replace(cfg, seed=seed,
                                       generations=generations))
    state, _ = tr.run()
    return tr, state


# -- the engine budget gate (the mechanism the scheduler relies on) ---------

class TestBudgetGate:
    def _problem(self, two_datasets, cfg):
        return _problem(two_datasets[0], cfg)

    def _run(self, problem, gens, seed=0):
        state, n0 = jax.jit(engine.init_state)(problem,
                                               jax.random.PRNGKey(seed))
        state, aux = jax.jit(engine.run_scanned,
                             static_argnames="generations")(problem, state,
                                                            gens)
        return state, aux

    def test_budget_equals_generations_is_bit_identical(self, two_datasets):
        """Regression: gating with budget == G reproduces the ungated
        scan exactly — states, EvalCache and the per-generation aux."""
        cfg = GAConfig(pop_size=16, generations=4)
        plain = self._problem(two_datasets, cfg)
        gated = plain.replace_cfg(generations_budget=4)
        s_plain, a_plain = self._run(plain, 4)
        s_gated, a_gated = self._run(gated, 4)
        assert_states_equal(s_plain, s_gated, "budget=G")
        assert_caches_equal(s_plain, s_gated, "budget=G")
        for i in range(4):
            np.testing.assert_array_equal(np.asarray(a_plain[i]),
                                          np.asarray(a_gated[i]),
                                          err_msg=f"aux[{i}] differs")

    def test_exhausted_budget_is_noop_passthrough(self, two_datasets):
        """A lane past its budget freezes bitwise (key, gen and cache
        included) and reports zero evaluations."""
        cfg = GAConfig(pop_size=16, generations=8)
        plain = self._problem(two_datasets, cfg)
        gated = dataclasses.replace(plain.replace_cfg(generations_budget=1),
                                    generations_budget=jnp.int32(3))
        s3, _ = self._run(plain, 3)
        sg, aux = self._run(gated, 8)
        assert_states_equal(s3, sg, "budget=3 over 8 gens")
        assert_caches_equal(s3, sg, "budget=3 over 8 gens")
        n_eval = np.asarray(aux[2])
        assert n_eval[3:].sum() == 0, "retired lane still evaluating"
        assert np.isfinite(np.asarray(aux[0])).all()

    @pytest.mark.parametrize("dedup", [True, False])
    def test_per_lane_budgets_under_vmap(self, two_datasets, dedup):
        """Lanes with budgets [2, 5] inside one vmapped scan each match
        their standalone runs — the pmax-bounded cond skips correctly."""
        cfg = GAConfig(pop_size=16, generations=5, dedup=dedup)
        base = self._problem(two_datasets, cfg)
        lane = engine.batch_problem(base.replace_cfg(generations_budget=1))
        lanes = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            dataclasses.replace(lane, generations_budget=jnp.int32(2)),
            dataclasses.replace(lane, generations_budget=jnp.int32(5)))

        def one(p, seed):
            st, _ = engine.init_state(p, jax.random.PRNGKey(seed))
            return engine.run_scanned(p, st, 5)

        states, aux = jax.jit(jax.vmap(
            one, axis_name=engine.BATCH_AXIS))(lanes,
                                               jnp.array([0, 1], jnp.int32))
        for i, gens in enumerate((2, 5)):
            ref, _ = self._run(base, gens, seed=i)
            assert_states_equal(engine.state_at(states, i), ref,
                                f"lane {i} budget {gens}")
        assert np.asarray(aux[2])[0, 2:].sum() == 0


# -- the host-side scheduler ------------------------------------------------

class TestLaneScheduler:
    def test_fifo_order(self):
        s = LaneScheduler(2, "fifo")
        for j in (10, 11, 12):
            s.enqueue(j)
        assert s.admissions({10: 4, 11: 64, 12: 16}) == [(0, 10), (1, 11)]
        assert s.pending == [12]

    def test_longest_first_with_fifo_ties(self):
        s = LaneScheduler(3, "longest")
        for j in (0, 1, 2, 3):
            s.enqueue(j)
        got = s.admissions({0: 16, 1: 64, 2: 16, 3: 32})
        assert got == [(0, 1), (1, 3), (2, 0)]
        assert s.pending == [2]

    def test_shortest_first(self):
        s = LaneScheduler(1, "shortest")
        for j in (0, 1):
            s.enqueue(j)
        assert s.admissions({0: 8, 1: 2}) == [(0, 1)]

    def test_freed_lane_backfills(self):
        s = LaneScheduler(1)
        s.enqueue(0)
        s.enqueue(1)
        assert s.admissions({0: 1, 1: 1}) == [(0, 0)]
        assert s.admissions({1: 1}) == []          # lane busy
        s.free(0)
        assert s.admissions({1: 1}) == [(0, 1)]
        assert s.has_work                      # job 1 now runs on lane 0
        s.free(0)
        assert not s.has_work

    def test_double_occupy_raises(self):
        s = LaneScheduler(1)
        s.occupy(0, 7)
        with pytest.raises(ValueError, match="already runs"):
            s.occupy(0, 8)

    def test_unknown_policy_raises(self):
        with pytest.raises(ValueError, match="policy"):
            LaneScheduler(2, "random")


# -- the server -------------------------------------------------------------

@pytest.mark.parametrize("dedup", [True, False])
def test_server_matches_sequential_trainers(two_datasets, dedup):
    """Acceptance: a heterogeneous stream (mixed datasets, seeds and
    budgets straddling segment boundaries) retires every job bit-identical
    to its standalone sequential trainer — including eval accounting."""
    bc, rw = two_datasets
    cfg = GAConfig(pop_size=16, generations=4, dedup=dedup)
    pa, pb = _problem(bc, cfg), _problem(rw, cfg)
    srv = SearchServer.for_problems([pa, pb], n_lanes=2, segment_len=2,
                                    policy="longest")
    jobs = [(bc, pa, 3, 0), (rw, pb, 5, 1), (bc, pa, 2, 2), (rw, pb, 4, 0)]
    ids = [srv.submit(SearchJob(p, g, seed=s)) for _, p, g, s in jobs]
    results = {r.job_id: r for r in srv.drain()}
    assert sorted(results) == sorted(ids)
    for jid, (ds, _, gens, seed) in zip(ids, jobs):
        tr, state = _trainer_state(ds, cfg, seed, gens)
        r = results[jid]
        assert_states_equal(r.state, state, f"job {jid}")
        assert r.unique_evals == tr.unique_evals, f"job {jid}"
        assert r.cache_hits == tr.cache_hits, f"job {jid}"
        np.testing.assert_array_equal(r.front["objectives"],
                                      tr.front(state)["objectives"])
        np.testing.assert_array_equal(r.front["genomes"],
                                      tr.front(state)["genomes"])


@pytest.mark.parametrize("dedup", [True, False])
@pytest.mark.parametrize("dataset_idx", [0, 1])
def test_mid_stream_admission_matches_cold_start(two_datasets, dedup,
                                                 dataset_idx):
    """A job admitted at segment k (lanes already hot, different dataset
    running beside it) equals the same job run from segment 0 alone — for
    jobs from either sample-size regime of the shared layout."""
    cfg = GAConfig(pop_size=16, generations=4, dedup=dedup)
    problems = [_problem(ds, cfg) for ds in two_datasets]
    srv = SearchServer.for_problems(problems, n_lanes=2, segment_len=2)
    # occupy both lanes first, then stagger the probe job in
    srv.submit(problems[1 - dataset_idx], generations=6, seed=0)
    srv.submit(problems[1 - dataset_idx], generations=4, seed=1)
    results = srv.step()
    assert srv.segments_done == 1
    probe = srv.submit(problems[dataset_idx], generations=3, seed=7)
    while srv._sched.has_work:
        results.extend(srv.step())
    got = {r.job_id: r for r in results}[probe]
    assert got.admitted_segment >= 1, "probe job was not admitted late"
    tr, state = _trainer_state(two_datasets[dataset_idx], cfg, 7, 3)
    assert_states_equal(got.state, state, "mid-stream admission")
    assert got.unique_evals == tr.unique_evals
    assert got.cache_hits == tr.cache_hits


def test_retired_lanes_leave_survivors_clean(two_datasets):
    """While a short job retires early, long jobs sharing the batch keep
    finite objectives, exact trainer-parity accounting and bit-identical
    final states — the parked lane injects no NaN/garbage."""
    bc, rw = two_datasets
    cfg = GAConfig(pop_size=16, generations=6)
    pa, pb = _problem(bc, cfg), _problem(rw, cfg)
    srv = SearchServer.for_problems([pa, pb], n_lanes=2, segment_len=2)
    short = srv.submit(pa, generations=2, seed=0)
    long_ = srv.submit(pb, generations=6, seed=1)
    results = {}
    seen_after_retire = False
    while srv._sched.has_work:
        for r in srv.step():
            results[r.job_id] = r
        if short in results and srv._sched.has_work:
            seen_after_retire = True
    assert seen_after_retire, "short job should retire before the long one"
    survivor = results[long_].state
    assert np.isfinite(np.asarray(survivor.obj)).all()
    # crowding distance is +inf at front boundaries by design — only NaN
    # would indicate the parked lane leaked garbage into the ranking
    assert not np.isnan(np.asarray(survivor.crowd)).any()
    tr, state = _trainer_state(rw, cfg, 1, 6)
    assert_states_equal(survivor, state, "survivor lane")
    assert results[long_].unique_evals == tr.unique_evals


def test_lane_programs_compile_once(two_datasets):
    """Admissions into and retirements from all four lanes — two
    topologies, lanes reused, one quarantine — compile the place and peel
    programs once each: the lane is a traced index, not a static."""
    bc, rw = two_datasets
    cfg = GAConfig(pop_size=16, generations=4)
    pa, pb = _problem(bc, cfg), _problem(rw, cfg)
    srv = SearchServer.for_problems([pa, pb], n_lanes=4, segment_len=2)
    server_mod._place_lane.clear_cache()
    server_mod._peel_lane.clear_cache()
    budgets = [(pa, 2), (pb, 4), (pa, 6), (pb, 2), (pa, 2), (pb, 4)]
    ids = [srv.submit(p, generations=g, seed=i)
           for i, (p, g) in enumerate(budgets)]
    first = srv.step()
    # four jobs admitted at once: two retired, two still in their lanes
    assert sorted(r.job_id for r in first) == [ids[0], ids[3]]
    assert sorted(srv.active_jobs) == [1, 2]
    failed = srv.quarantine_lane(2, "probe")
    rest = srv.drain()
    assert not failed.ok and failed.job_id == ids[2]
    assert sorted(r.job_id for r in first + rest + [failed]) == ids
    assert server_mod._place_lane._cache_size() == 1
    assert server_mod._peel_lane._cache_size() == 1


@pytest.mark.parametrize("dedup", [True, False])
def test_lane_reused_after_retire_and_quarantine(two_datasets, dedup):
    """One lane serves a job to its end, then a job quarantined mid-budget,
    then a third job: the first and third equal their standalone
    trainers — states, fronts and eval accounting."""
    bc, rw = two_datasets
    cfg = GAConfig(pop_size=16, generations=4, dedup=dedup)
    pa, pb = _problem(bc, cfg), _problem(rw, cfg)
    srv = SearchServer.for_problems([pa, pb], n_lanes=1, segment_len=2)
    srv.submit(pb, generations=2, seed=3)
    [done] = srv.step()
    srv.submit(pa, generations=6, seed=4)
    assert srv.step() == []
    failed = srv.quarantine_lane(0, "probe")
    assert not failed.ok and failed.front is None
    assert failed.error == "probe" and failed.generations_run == 2
    srv.submit(pa, generations=4, seed=5)
    [got] = srv.drain()
    for r, ds, seed, gens in ((done, rw, 3, 2), (got, bc, 5, 4)):
        tr, state = _trainer_state(ds, cfg, seed, gens)
        assert_states_equal(r.state, state, f"job {r.job_id}")
        assert r.unique_evals == tr.unique_evals, f"job {r.job_id}"
        assert r.cache_hits == tr.cache_hits, f"job {r.job_id}"
        front = tr.front(state)
        for k in ("objectives", "genomes"):
            np.testing.assert_array_equal(r.front[k], front[k])


def test_submit_validation(two_datasets):
    bc, rw = two_datasets
    cfg = GAConfig(pop_size=16, generations=4)
    pa = _problem(bc, cfg)
    srv = SearchServer.for_problems([pa], n_lanes=2)
    with pytest.raises(ValueError, match="GAConfig does not match"):
        srv.submit(_problem(bc, dataclasses.replace(cfg, pop_size=32)),
                   generations=4)
    with pytest.raises(ValueError, match="samples"):
        srv.submit(_problem(rw, cfg), generations=4)   # 1120 > 489
    with pytest.raises(ValueError, match="generations"):
        srv.submit(pa, generations=0)

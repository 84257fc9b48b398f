"""Fault-tolerant serve supervision (`repro.serve.supervisor`) —
fault-free behavior, checkpoint cadence, convergence retirement, the
watchdog and quarantine plumbing.

The do-no-harm contract: a default-policy Supervisor over a fault-free
stream retires every job bit-identical to the bare ``SearchServer`` (and
hence to the standalone sequential ``GATrainer.run``), with
auto-checkpointing and per-lane validation adding boundary-only work.
Fault *injection* paths live in tests/test_chaos.py.
"""
import dataclasses
import time

import numpy as np
import pytest
import jax

from repro.core import GAConfig, GATrainer
from repro.core import engine
from repro.core.genome import MLPTopology

from repro.kernels import BackendPolicy
from repro.serve import (FaultPolicy, LaneValidationError, SearchServer,
                         SegmentTimeoutError, Supervisor)
from repro.serve.chaos import ChaosPlan

STATE_FIELDS = ("pop", "obj", "viol", "rank", "crowd", "counts", "key", "gen")


def assert_states_equal(a, b, msg=""):
    for name in STATE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"{msg}: GAState.{name} differs")


def _make(seed, n_samples, sizes):
    rng = np.random.default_rng(seed)
    x = rng.random((n_samples, sizes[0])).astype(np.float32)
    y = (x.sum(axis=1) > sizes[0] / 2).astype(np.int32)
    return MLPTopology(sizes), x, y


@pytest.fixture(scope="module")
def two_problems():
    cfg = GAConfig(pop_size=16, generations=8)
    a = _make(1, 64, (4, 4, 2))
    b = _make(2, 96, (5, 6, 2))
    pa = engine.Problem.from_data(*a[:1], a[1], a[2], cfg)
    pb = engine.Problem.from_data(*b[:1], b[1], b[2], cfg)
    return (a, pa), (b, pb), cfg


def _trainer(data, cfg, seed, generations):
    topo, x, y = data
    tr = GATrainer(topo, x, y, dataclasses.replace(cfg, seed=seed,
                                                   generations=generations))
    state, _ = tr.run()
    return tr, state


def test_faultfree_supervised_parity(two_problems, tmp_path):
    """Checkpointing + validation ON, no faults: every retired job is
    healthy and bit-identical to its standalone trainer; checkpoints
    fire on the configured cadence."""
    (da, pa), (db, pb), cfg = two_problems
    sup = Supervisor.for_problems(
        [pa, pb], FaultPolicy(checkpoint_every=2),
        directory=str(tmp_path), n_lanes=2, segment_len=4)
    jobs = [(da, pa, 8, 0), (db, pb, 12, 1), (da, pa, 4, 2)]
    ids = [sup.submit(p, generations=g, seed=s) for _, p, g, s in jobs]
    results = {r.job_id: r for r in sup.drain()}
    assert sorted(results) == sorted(ids)
    assert sup.stats["checkpoints"] >= 1
    assert sup.stats["quarantined"] == 0
    for jid, (data, _, gens, seed) in zip(ids, jobs):
        r = results[jid]
        assert r.ok and r.error is None and not r.converged
        assert r.generations_run == gens
        tr, state = _trainer(data, cfg, seed, gens)
        assert_states_equal(r.state, state, f"job {jid}")
        assert r.unique_evals == tr.unique_evals
        assert r.cache_hits == tr.cache_hits


def test_checkpointing_requires_directory(two_problems):
    (_, pa), _, _ = two_problems
    srv = SearchServer.for_problems([pa], n_lanes=1)
    with pytest.raises(ValueError, match="directory"):
        Supervisor(srv, FaultPolicy(checkpoint_every=2))


def test_allow_pending_save_and_resubmission(two_problems, tmp_path):
    """An auto-checkpoint taken while jobs still queue records them in
    the manifest; after restore they ride in ``dropped_pending`` and
    resubmitting finishes them bit-identical (admission-segment
    independence is the serve contract)."""
    (da, pa), (db, pb), cfg = two_problems
    srv = SearchServer.for_problems([pa, pb], n_lanes=1, segment_len=4)
    srv.submit(pa, generations=8, seed=0, name="running")
    queued = srv.submit(pb, generations=4, seed=1, name="queued")
    srv.step()
    with pytest.raises(ValueError, match="pending"):
        srv.save(str(tmp_path))
    srv.save(str(tmp_path), allow_pending=True)

    restored = SearchServer.restore(str(tmp_path), srv.spec, pa.cfg)
    assert [p["job_id"] for p in restored.dropped_pending] == [queued]
    meta = restored.dropped_pending[0]
    assert (meta["name"], meta["generations"], meta["seed"]) == \
        ("queued", 4, 1)
    restored.submit(pb, generations=meta["generations"], seed=meta["seed"],
                    name=meta["name"])
    results = {r.name: r for r in restored.drain()}
    for name, data, gens, seed in (("running", da, 8, 0),
                                   ("queued", db, 4, 1)):
        tr, state = _trainer(data, cfg, seed, gens)
        assert_states_equal(results[name].state, state, name)
        assert results[name].unique_evals == tr.unique_evals


def test_force_retire_hooks_validate_lane(two_problems):
    (_, pa), _, _ = two_problems
    srv = SearchServer.for_problems([pa], n_lanes=2)
    with pytest.raises(ValueError, match="no job"):
        srv.retire_lane(0)
    with pytest.raises(ValueError, match="no job"):
        srv.quarantine_lane(1, "nope")


class TestConvergenceRetirement:
    def _easy(self):
        # tiny, trivially-separable problem: the front stabilizes fast
        topo, x, y = _make(3, 32, (3, 3, 2))
        cfg = GAConfig(pop_size=16, generations=640)
        return (topo, x, y), engine.Problem.from_data(topo, x, y, cfg), cfg

    def test_patience_retires_early_bit_identical(self):
        data, p, cfg = self._easy()
        sup = Supervisor.for_problems([p], FaultPolicy(patience=3),
                                      n_lanes=1, segment_len=16)
        sup.submit(p, generations=640, seed=11)
        r = sup.drain()[0]
        assert r.ok and r.converged
        assert r.generations_run < 640
        assert sup.stats["converged"] == 1
        # early retirement is honest: the state IS the trainer state at
        # the generation it stopped, not an approximation of gen 640
        tr, state = _trainer(data, cfg, 11, r.generations_run)
        assert_states_equal(r.state, state, "converged lane")
        assert r.unique_evals == tr.unique_evals

    def test_disabled_by_default_runs_full_budget(self):
        data, p, cfg = self._easy()
        sup = Supervisor.for_problems([p], n_lanes=1, segment_len=16)
        sup.submit(p, generations=64, seed=11)
        r = sup.drain()[0]
        assert not r.converged and r.generations_run == 64
        tr, state = _trainer(data, cfg, 11, 64)
        assert_states_equal(r.state, state, "patience=0")


def test_watchdog_times_out_hung_segment(two_problems):
    (_, pa), _, _ = two_problems
    sup = Supervisor.for_problems(
        [pa], FaultPolicy(segment_timeout_s=0.05), n_lanes=1)
    sup.submit(pa, generations=4, seed=0)
    sup.server.step = lambda: time.sleep(10)       # hang the dispatch
    with pytest.raises(SegmentTimeoutError, match="watchdog"):
        sup.step()
    assert sup.stats["retries"] == 0, "timeouts must not be retried"


def test_quarantine_disabled_fails_loud(two_problems):
    (_, pa), _, _ = two_problems
    chaos = ChaosPlan(poison={0: 0}, poison_leaf="obj")
    sup = Supervisor.for_problems(
        [pa], FaultPolicy(quarantine=False), chaos=chaos,
        n_lanes=1, segment_len=4)
    sup.submit(pa, generations=8, seed=0)
    with pytest.raises(LaneValidationError, match="finite_objectives"):
        sup.drain()


class TestValidateState:
    def _state(self, two_problems, gens=2):
        (_, pa), _, _ = two_problems
        state, _ = jax.jit(engine.init_state)(pa, jax.random.PRNGKey(0))
        state, _ = jax.jit(engine.run_scanned,
                           static_argnames="generations")(pa, state, gens)
        return pa, state

    def test_healthy_state_passes_every_check(self, two_problems):
        p, st = self._state(two_problems)
        flags = np.asarray(engine.validate_state(p, st))
        assert flags.shape == (len(engine.VALIDATION_CHECKS),)
        assert flags.all(), dict(zip(engine.VALIDATION_CHECKS, flags))

    @pytest.mark.parametrize("leaf,check", [
        ("obj", "finite_objectives"),
        ("pop", "genome_in_bounds"),
        ("counts", "counts_in_range"),
    ])
    def test_poison_trips_exactly_its_check(self, two_problems, leaf, check):
        import jax.numpy as jnp
        p, st = self._state(two_problems)
        if leaf == "obj":
            bad = dataclasses.replace(st, obj=jnp.full_like(st.obj, jnp.nan))
        elif leaf == "pop":
            bad = dataclasses.replace(st, pop=st.pop + jnp.int32(1 << 20))
        else:
            bad = dataclasses.replace(st,
                                      counts=jnp.full_like(st.counts, -1))
        flags = dict(zip(engine.VALIDATION_CHECKS,
                         np.asarray(engine.validate_state(p, bad))))
        assert not flags[check]
        assert not engine.validate_ok(p, bad)

    def test_crowding_inf_is_not_a_fault(self, two_problems):
        """Crowding distance is +inf at front boundaries BY DESIGN — a
        healthy converged state must never quarantine for it."""
        p, st = self._state(two_problems, gens=4)
        assert np.isinf(np.asarray(st.crowd)).any(), \
            "fixture no longer exercises the +inf boundary case"
        assert bool(engine.validate_ok(p, st))


class TestBackendPolicy:
    def test_with_backends_beats_the_legacy_mirror(self, two_problems):
        """Regression: a bare dataclasses.replace(cfg, backends=...) is
        silently overridden by the mirrored legacy *_backend fields;
        GAConfig.with_backends is the safe swap."""
        (_, pa), _, _ = two_problems
        pol = BackendPolicy(fitness="interpret")
        assert pa.cfg.with_backends(pol).backends.fitness == "interpret"

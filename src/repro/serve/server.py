"""The always-on GA search server: segmented scan + runtime lane admission.

See the package docstring for the architecture. The invariants:

  * ONE compiled program: every segment of every stream runs the same
    jitted ``vmap(run_scanned)`` over the same stacked shapes (the
    module-level jit cache is shared across server instances, like
    ``sweep._run_suite_jit``).
  * Lane composition at runtime: admitting a job pads its Problem into
    the shared max-shape layout (``engine.pad_problem``) and writes it and
    its initial state into the standing stacked pytrees with ONE compiled
    program (``_place_lane``); retiring a lane peels its state and parks
    its slot with one more (``_peel_lane``). The lane index is a traced
    argument, so each program compiles once per stacked shape — no
    retrace, no recompile, and no per-leaf device programs.
  * Retired lanes are free: the budget gate (``cfg.generations_budget``)
    makes an exhausted lane a bitwise no-op passthrough contributing
    zero rows to the shared dedup evaluation bound; a retired lane's
    slot additionally gets a tiny *null problem* so it stops inflating
    the shared ``n_valid_samples`` sample-tile bound.
  * Bit-identity: each job's retired state/front/accounting equals its
    standalone sequential ``GATrainer.run`` exactly.
"""
from __future__ import annotations

import dataclasses
import functools
import json

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.profiler import TraceAnnotation

from ..core import engine, sweep
from ..core import genome as genome_mod
from ..core.engine import GAConfig, GAState, Problem
from ..checkpoint import manager as ckpt
from .jobs import JobResult, SearchJob
from .scheduler import LaneScheduler

# manifest key of the host-metadata blob: third element of the
# (states, problems, meta) checkpoint payload tuple
_META_LEAF = "2"


def _canon_cfg(cfg: GAConfig) -> GAConfig:
    """The job-facing config identity: the server owns the batch-axis tag
    and the budget gate, so submitted problems match modulo those."""
    return dataclasses.replace(cfg, batch_axis=None, generations_budget=None)


def _run_segment(problems: Problem, states: GAState, segment_len: int):
    def one(p, s):
        return engine.run_scanned(p, s, segment_len)

    return jax.vmap(one, axis_name=engine.BATCH_AXIS)(problems, states)


# donate the standing states: the carry is replaced wholesale every
# segment, so XLA reuses its buffers across segments
_run_segment_jit = jax.jit(_run_segment, static_argnames="segment_len",
                           donate_argnums=(1,))


def _init_lane(problem: Problem, key, doping):
    return engine.init_state(problem, key, doping)


_init_lane_jit = jax.jit(_init_lane)


def _take_lane(stacked, lane):
    """One lane's slice of every leaf of a stacked pytree."""
    return jax.tree_util.tree_map(
        lambda s: lax.dynamic_index_in_dim(s, lane, keepdims=False), stacked)


def _put_lane(stacked, lane, single):
    """Write one lane's pytree into every leaf of a stacked pytree."""
    return jax.tree_util.tree_map(
        lambda s, x: lax.dynamic_update_index_in_dim(s, x, lane, 0),
        stacked, single)


# ONE dispatch each per admission / retirement, compiled once per stacked
# shape; the stacked buffers they replace are donated
@functools.partial(jax.jit, donate_argnums=(0, 1))
def _place_lane(problems: Problem, states: GAState, lane, problem: Problem,
                state: GAState):
    """Admission: write a padded job Problem and its initial state into
    lane ``lane`` (a traced int32) of the stacked pytrees."""
    return _put_lane(problems, lane, problem), _put_lane(states, lane, state)


@functools.partial(jax.jit, donate_argnums=(0,))
def _peel_lane(problems: Problem, states: GAState, lane, null: Problem):
    """Retirement: lane ``lane``'s state without its EvalCache, and the
    stacked problems with that lane parked on ``null``."""
    state = _take_lane(dataclasses.replace(states, cache=None), lane)
    return state, _put_lane(problems, lane, null)


_lane_of = jax.jit(_take_lane)


@dataclasses.dataclass
class _JobRecord:
    """Host-side per-job bookkeeping (survives checkpoint round-trips,
    so it carries plain values rather than the SearchJob object)."""
    job_id: int
    name: str | None
    generations: int
    seed: int
    job: SearchJob | None = None          # None for restored in-flight jobs
    lane: int | None = None
    positions: np.ndarray | None = None   # inner→padded gene positions
    remaining: int = 0
    # from admission to the step's serve.sync read, the init's device
    # scalar; a host int after it
    unique_evals: int | jax.Array = 0
    cache_hits: int = 0
    admitted_segment: int | None = None


class SearchServer:
    """Continuous-batching GA search service.

    ``submit()`` enqueues :class:`SearchJob`\\ s, ``step()`` advances every
    busy lane by one ``segment_len``-generation segment (admitting queued
    jobs into free lanes first) and returns the jobs retired at the
    segment boundary, ``drain()`` steps until the queue and lanes are
    empty. All jobs of a server share one ``GAConfig`` (one compiled
    program) but each brings its own dataset, topology (≤ the server's
    ``spec``), PRNG seed, doping and generation budget.
    """

    def __init__(self, spec: "genome_mod.GenomeSpec", cfg: GAConfig, *,
                 max_samples: int, n_lanes: int = 4, segment_len: int = 16,
                 policy: str = "fifo"):
        if segment_len < 1:
            raise ValueError(f"segment_len must be >= 1, got {segment_len}")
        if cfg.backends.fitness == "jnp":
            raise ValueError("the serve path pads problems; use a "
                             "count-based fitness backend, not 'jnp'")
        self.spec = spec
        self.max_samples = int(max_samples)
        self.n_lanes = int(n_lanes)
        self.segment_len = int(segment_len)
        # the server-internal config: budget gate ON (default leaf 0 ⇒ a
        # lane with no job is inert), lanes tagged with the batch axis
        self._cfg = dataclasses.replace(cfg, batch_axis=engine.BATCH_AXIS,
                                        generations_budget=0)
        # admission inits run outside the vmap, so without the axis tag
        self._cfg_init = dataclasses.replace(self._cfg, batch_axis=None)
        self._sched = LaneScheduler(self.n_lanes, policy)
        self._jobs: dict[int, _JobRecord] = {}
        self._next_id = 0
        self._segments_done = 0
        # populated by restore(): queued jobs an allow_pending save
        # recorded but could not serialize; resubmit to keep them
        self.dropped_pending: list[dict] = []
        self._null = self._null_problem()
        null_state, _ = _init_lane_jit(
            dataclasses.replace(self._null, cfg=self._cfg_init),
            jax.random.PRNGKey(0), None)
        self._problems = sweep.stack_problems([self._null] * self.n_lanes)
        self._states = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * self.n_lanes), null_state)

    @classmethod
    def for_problems(cls, problems, **kw) -> "SearchServer":
        """Server sized for a known family of datasets: the shared spec is
        their max-shape embedding (``sweep.suite_spec``) and the sample
        axis fits the widest dataset. ``cfg`` is taken from the first
        problem (all jobs must match it anyway)."""
        problems = list(problems)
        spec = sweep.suite_spec(problems)
        max_samples = max(int(p.x_int.shape[0]) for p in problems)
        return cls(spec, problems[0].cfg, max_samples=max_samples, **kw)

    # -- lane composition ---------------------------------------------------

    def _null_problem(self) -> Problem:
        """The inert lane filler: budget 0 (never active) and a single
        valid sample, so a retired slot contributes the minimum possible
        to the shared ``n_valid_samples`` sample-tile bound."""
        S, n_in = self.max_samples, self.spec.topo.sizes[0]
        p = Problem(jnp.zeros((S, n_in), jnp.int32),
                    jnp.full((S,), -1, jnp.int32),   # −1: padding label
                    jnp.float32(1.0), self.spec, self._cfg)
        return dataclasses.replace(p, n_valid_samples=jnp.int32(1),
                                   generations_budget=jnp.int32(0))

    def _admit(self, lane: int, job_id: int):
        with TraceAnnotation("serve.admit", job=job_id, lane=lane):
            rec = self._jobs[job_id]
            job = rec.job
            inner = dataclasses.replace(job.problem, cfg=self._cfg_init)
            padded = engine.pad_problem(inner, self.spec, self.max_samples,
                                        pos=rec.positions)
            padded = dataclasses.replace(
                padded, generations_budget=jnp.int32(job.generations))
            doping = None
            if job.doping_seeds is not None:
                n_dope = max(1, int(self._cfg.doping_frac
                                    * self._cfg.pop_size))
                doping = jnp.asarray(sweep.doped_lane_rows(
                    job.doping_seeds, rec.positions, self.spec.n_genes,
                    n_dope))
            # the exact init a standalone GATrainer would run on this job
            state, n0 = _init_lane_jit(padded, jax.random.PRNGKey(job.seed),
                                       doping)
            self._problems, self._states = _place_lane(
                self._problems, self._states, np.int32(lane),
                dataclasses.replace(padded, cfg=self._cfg), state)
            rec.lane = lane
            rec.remaining = job.generations
            rec.unique_evals = n0      # read with the segment's accounting
            rec.cache_hits = 0
            rec.admitted_segment = self._segments_done

    def _retire(self, lane: int, job_id: int, *, converged: bool = False,
                error: str | None = None) -> JobResult:
        """Peel the lane's state to the host, gathered back to the job's
        gene layout, and park the slot on the null problem: budget 0
        keeps it a no-op passthrough and its 1-sample bound stops
        inflating the shared sample-tile pmax (the lane's stale state is
        inert garbage). With ``error`` the job is retired as failed."""
        with TraceAnnotation("serve.retire", job=job_id, lane=lane):
            rec = self._jobs[job_id]
            st, self._problems = _peel_lane(
                self._problems, self._states, np.int32(lane), self._null)
            st = jax.device_get(st)
            st = dataclasses.replace(st, pop=st.pop[:, rec.positions])
            result = JobResult(
                job_id=job_id, name=rec.name,
                front=None if error is not None else engine.front_of(st),
                state=st, generations=rec.generations,
                unique_evals=rec.unique_evals, cache_hits=rec.cache_hits,
                admitted_segment=rec.admitted_segment,
                retired_segment=self._segments_done,
                ok=error is None, error=error,
                generations_run=rec.generations - max(rec.remaining, 0),
                converged=converged)
            rec.lane = None
            self._sched.free(lane)
            return result

    # -- fault-tolerance hooks (driven by serve.supervisor) -----------------

    def retire_lane(self, lane: int, *, converged: bool = False) -> JobResult:
        """Force-retire a busy lane mid-budget (supervisor convergence
        retirement). The result is a healthy ``JobResult`` whose
        ``generations_run`` records how far the lane actually got."""
        job_id = self._sched.lane_job[lane]
        if job_id is None:
            raise ValueError(f"lane {lane} has no job to retire")
        return self._retire(lane, job_id, converged=converged)

    def quarantine_lane(self, lane: int, error: str) -> JobResult:
        """Retire a busy lane as FAILED: its state tripped validation.

        The lane's (suspect) state is still peeled into the result for
        forensics, but ``front`` is None and ``ok`` is False; the slot is
        parked on the null problem and freed so sibling lanes and future
        admissions are untouched — per-lane vmap slices and per-lane
        caches mean a poisoned lane cannot have perturbed its siblings.
        """
        job_id = self._sched.lane_job[lane]
        if job_id is None:
            raise ValueError(f"lane {lane} has no job to quarantine")
        return self._retire(lane, job_id, error=error)

    def lane_state(self, lane: int) -> GAState:
        """The full padded GAState of one lane (cache included) — the
        view ``engine.validate_state`` checks at segment boundaries."""
        return _lane_of(self._states, np.int32(lane))

    def lane_problem(self, lane: int) -> Problem:
        return _lane_of(self._problems, np.int32(lane))

    # -- the service loop ---------------------------------------------------

    def submit(self, job: SearchJob | Problem, *, generations=None,
               seed: int = 0, doping_seeds=None, name=None) -> int:
        """Enqueue a job; returns its id. Accepts a :class:`SearchJob` or
        a bare Problem plus the job fields as keywords."""
        with TraceAnnotation("serve.enqueue", job=self._next_id):
            if not isinstance(job, SearchJob):
                if generations is None:
                    generations = job.cfg.generations
                job = SearchJob(job, generations, seed=seed,
                                doping_seeds=doping_seeds, name=name)
            if job.generations < 1:
                raise ValueError(f"generations must be >= 1, got "
                                 f"{job.generations}")
            if _canon_cfg(job.problem.cfg) != _canon_cfg(self._cfg):
                raise ValueError("job problem's GAConfig does not match the "
                                 "server's (one compiled program needs one "
                                 "config; seed/generations ride on the job)")
            if int(job.problem.x_int.shape[0]) > self.max_samples:
                raise ValueError(
                    f"job has {job.problem.x_int.shape[0]} samples; the "
                    f"server was sized for max_samples={self.max_samples}")
            # raises if the job's topology does not fit the server's spec
            positions = genome_mod.pad_positions(job.problem.spec, self.spec)
            job_id = self._next_id
            self._next_id += 1
            self._jobs[job_id] = _JobRecord(
                job_id=job_id, name=job.name, generations=int(job.generations),
                seed=int(job.seed), job=job, positions=positions)
            self._sched.enqueue(job_id)
            return job_id

    def step(self) -> list[JobResult]:
        """Admit queued jobs into free lanes, run ONE segment, retire
        budget-exhausted lanes; returns their :class:`JobResult`\\ s."""
        budgets = {j: self._jobs[j].generations for j in self._sched.pending}
        for lane, job_id in self._sched.admissions(budgets):
            self._admit(lane, job_id)
        busy = self._sched.busy_lanes
        if not busy:
            return []
        with TraceAnnotation("serve.dispatch", lanes=len(busy)):
            self._states, aux = _run_segment_jit(
                self._problems, self._states, self.segment_len)
        self._segments_done += 1
        recs = [self._jobs[self._sched.lane_job[lane]] for lane in busy]
        with TraceAnnotation("serve.sync"):
            # (n_lanes, segment_len) accounting and the admissions' init
            # evals, in one read
            n_eval, n_hit, evals = jax.device_get(
                (aux[2], aux[3], [rec.unique_evals for rec in recs]))
        retired = []
        for lane, rec, ev in zip(busy, recs, evals):
            rec.unique_evals = int(ev) + int(n_eval[lane].sum())
            rec.cache_hits += int(n_hit[lane].sum())
            rec.remaining -= self.segment_len
            if rec.remaining <= 0:
                retired.append(self._retire(lane, rec.job_id))
        return retired

    def drain(self) -> list[JobResult]:
        """Step until every queued and in-flight job has retired."""
        results = []
        while self._sched.has_work:
            results.extend(self.step())
        return results

    @property
    def segments_done(self) -> int:
        return self._segments_done

    @property
    def has_work(self) -> bool:
        """True while any job is queued or in a lane."""
        return self._sched.has_work

    @property
    def pending_jobs(self) -> list[int]:
        return list(self._sched.pending)

    @property
    def active_jobs(self) -> dict[int, int]:
        """lane → job id of every busy lane."""
        return {i: j for i, j in enumerate(self._sched.lane_job)
                if j is not None}

    # -- checkpointing ------------------------------------------------------

    def save(self, directory: str, *, keep: int = 3,
             allow_pending: bool = False) -> str:
        """Checkpoint the in-flight lanes (states + problems + scheduler
        metadata) atomically; resumable with :meth:`restore` into a
        bit-identical continuation. By default the queue must be empty —
        pending jobs hold host-side Problems this store does not
        serialize — and retired results must already have been consumed
        from ``step()``/``drain()`` returns.

        ``allow_pending=True`` (the supervisor's auto-checkpoint mode)
        saves anyway, recording each queued job's (id, name, generations,
        seed) in the manifest: after :meth:`restore` those ride in
        ``dropped_pending`` for the caller to resubmit with their
        Problems. The serve contract makes this safe — a job's result is
        bit-identical whichever segment admits it."""
        if self._sched.pending and not allow_pending:
            raise ValueError("cannot save with pending jobs queued: admit "
                             "them (step()) or drain first, or pass "
                             "allow_pending=True to record them for "
                             "resubmission after restore")
        pending = []
        for job_id in self._sched.pending:
            rec = self._jobs[job_id]
            pending.append({"job_id": rec.job_id, "name": rec.name,
                            "generations": rec.generations,
                            "seed": rec.seed})
        lanes = []
        for lane in range(self.n_lanes):
            job_id = self._sched.lane_job[lane]
            if job_id is None:
                lanes.append(None)
                continue
            rec = self._jobs[job_id]
            lanes.append({"job_id": rec.job_id, "name": rec.name,
                          "generations": rec.generations, "seed": rec.seed,
                          "remaining": rec.remaining,
                          "unique_evals": rec.unique_evals,
                          "cache_hits": rec.cache_hits,
                          "admitted_segment": rec.admitted_segment,
                          "positions": np.asarray(rec.positions).tolist()})
        meta = {"n_lanes": self.n_lanes, "segment_len": self.segment_len,
                "max_samples": self.max_samples,
                "segments_done": self._segments_done,
                "next_id": self._next_id, "policy": self._sched.policy,
                "cfg": repr(_canon_cfg(self._cfg)), "lanes": lanes,
                "pending": pending}
        blob = np.frombuffer(json.dumps(meta).encode(), np.uint8).copy()
        payload = (self._states, self._problems, blob)
        return ckpt.save_checkpoint(directory, self._segments_done, payload,
                                    keep=keep, async_io=False)

    @classmethod
    def restore(cls, directory: str, spec: "genome_mod.GenomeSpec",
                cfg: GAConfig, *, step: int | None = None) -> "SearchServer":
        """Rebuild a server from :meth:`save` — in-flight jobs resume
        mid-budget and finish bit-identical to the uninterrupted run.
        ``spec``/``cfg`` must be the ones the saved server was built with
        (statics are not serialized; the config fingerprint is checked)."""
        if step is None:
            step = ckpt.latest_step(directory)
            if step is None:
                raise FileNotFoundError(f"no checkpoint under {directory}")
        meta = json.loads(bytes(ckpt.read_leaf(directory, step, _META_LEAF)))
        srv = cls(spec, cfg, max_samples=meta["max_samples"],
                  n_lanes=meta["n_lanes"], segment_len=meta["segment_len"],
                  policy=meta["policy"])
        if repr(_canon_cfg(srv._cfg)) != meta["cfg"]:
            raise ValueError("restore cfg does not match the saved "
                             f"server's: {meta['cfg']}")
        target = (srv._states, srv._problems, np.zeros(0, np.uint8))
        states, problems, _ = ckpt.restore_checkpoint(directory, step,
                                                      target)
        srv._states, srv._problems = states, problems
        srv._segments_done = int(meta["segments_done"])
        srv._next_id = int(meta["next_id"])
        for lane, lm in enumerate(meta["lanes"]):
            if lm is None:
                continue
            rec = _JobRecord(
                job_id=int(lm["job_id"]), name=lm["name"],
                generations=int(lm["generations"]), seed=int(lm["seed"]),
                lane=lane, positions=np.asarray(lm["positions"], np.int32),
                remaining=int(lm["remaining"]),
                unique_evals=int(lm["unique_evals"]),
                cache_hits=int(lm["cache_hits"]),
                admitted_segment=lm["admitted_segment"])
            srv._jobs[rec.job_id] = rec
            srv._sched.occupy(lane, rec.job_id)
        # queued jobs recorded by allow_pending saves: their Problems are
        # not serialized, so they come back as metadata for resubmission
        srv.dropped_pending = list(meta.get("pending", []))
        return srv

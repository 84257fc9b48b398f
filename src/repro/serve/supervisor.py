"""Fault-tolerant supervision of the continuous-batching serve loop.

:class:`Supervisor` wraps a :class:`~repro.serve.SearchServer` and runs
its segment loop under a :class:`FaultPolicy`:

  * **auto-checkpointing** — every ``checkpoint_every`` supervisor
    segments the whole server (states + problems + scheduler metadata +
    queued-job manifest) goes through ``checkpoint/manager``'s two-phase
    commit; a crash at ANY instant loses at most one checkpoint
    interval.
  * **crash recovery** — :meth:`recover` finds the latest checkpoint
    that passes full integrity verification (``latest_valid_step``:
    truncated/bit-flipped steps are skipped, ``.tmp`` half-writes are
    invisible), restores it, and resumes; resumed jobs finish
    bit-identical to the uninterrupted run (the serve contract).
  * **lane health validation + quarantine** — at every segment boundary
    one jitted ``vmap(engine.validate_state)`` checks every lane's
    engine invariants on device; a busy lane with a False flag is
    *quarantined*: retired with a failed :class:`JobResult` naming the
    tripped checks, slot freed, siblings untouched (per-lane vmap slices
    and per-lane caches mean the poison cannot have crossed lanes).
  * **transient-fault retry** — segment dispatch and checkpoint saves
    retry under capped exponential backoff for transient host faults
    (``OSError``/IO hiccups, injected :class:`~repro.serve.chaos.
    SegmentFault`\\ s). Retries are sound only for faults raised at the
    boundary, BEFORE the compiled segment dispatches: the segment jit
    donates its input buffers, so a mid-dispatch fault invalidates the
    carry — those crash the process and recover via checkpoint instead.
  * **watchdog** — ``segment_timeout_s`` bounds one segment's wall
    clock; a hung segment raises :class:`SegmentTimeoutError` (fatal,
    never retried in-process) instead of eating the host forever.
  * **convergence retirement** — with ``patience=N`` a lane whose
    Pareto front fingerprint is unchanged for N consecutive segments
    retires early (``converged=True``); off by default and bit-identical
    to the unsupervised run when disabled.

Every fault path is exercised deterministically by
``repro.serve.chaos`` (tests/test_chaos.py, ``bench_serve_chaos``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from typing import Optional

import numpy as np
import jax

from ..core import engine
from ..checkpoint import manager as ckpt
from .chaos import ChaosPlan, SegmentFault
from .jobs import JobResult
from .server import SearchServer


class SegmentTimeoutError(RuntimeError):
    """A segment exceeded ``FaultPolicy.segment_timeout_s``. Fatal by
    design: the hung dispatch may still hold the donated state buffers,
    so the only sound recovery is a fresh process + :meth:`Supervisor.
    recover` from the last checkpoint."""


class LaneValidationError(RuntimeError):
    """A lane failed ``engine.validate_state`` and the policy forbids
    quarantine (``quarantine=False``): fail the whole server loudly."""


@dataclasses.dataclass(frozen=True)
class FaultPolicy:
    """The supervisor's knobs. The defaults are the *do-no-harm* set:
    validation+quarantine on (cheap, one fused device reduction), no
    checkpointing (needs a directory), no convergence retirement, no
    watchdog — a default-policy Supervisor over a fault-free stream is
    bit-identical to the bare server.

    ``checkpoint_every``: auto-checkpoint cadence in supervisor segments
    (0 = off). ``keep``: checkpoints retained (GC). ``max_retries`` /
    ``backoff_base_s`` / ``backoff_cap_s``: capped exponential backoff
    for transient faults (delay ``base * 2^attempt`` capped at ``cap``).
    ``patience``: consecutive unchanged-front segments before early
    retirement (0 = off). ``segment_timeout_s``: per-segment watchdog
    (None = off).
    """
    checkpoint_every: int = 0
    keep: int = 3
    validate: bool = True
    quarantine: bool = True
    max_retries: int = 3
    backoff_base_s: float = 0.05
    backoff_cap_s: float = 2.0
    patience: int = 0
    segment_timeout_s: Optional[float] = None


def _validate_lanes(problems, states):
    return jax.vmap(engine.validate_state)(problems, states)


# ONE fused device reduction per segment boundary for ALL lanes; the jit
# cache is module-level and shared across supervisors (cf. _run_segment_jit)
_validate_lanes_jit = jax.jit(_validate_lanes)


def _front_fingerprint(state) -> str:
    """Order-stable digest of a lane's feasible Pareto front — the set
    of objective points (sorted by ``front_of``), NOT the genomes:
    neutral drift swaps equivalent genomes on a stable front and must
    not count as progress."""
    front = engine.front_of(state)
    h = hashlib.sha1()
    h.update(np.ascontiguousarray(front["objectives"]).tobytes())
    return h.hexdigest()


class Supervisor:
    """Run a :class:`SearchServer` under a :class:`FaultPolicy`.

    Same surface as the bare server — :meth:`submit`, :meth:`step`,
    :meth:`drain` — with fault handling between segments. ``chaos``
    (a :class:`~repro.serve.chaos.ChaosPlan`) injects deterministic
    faults for tests/benchmarks; ``sleep`` is injectable so backoff
    tests run instantly.
    """

    def __init__(self, server: SearchServer,
                 policy: Optional[FaultPolicy] = None, *,
                 directory: Optional[str] = None,
                 chaos: Optional[ChaosPlan] = None, sleep=time.sleep):
        policy = policy if policy is not None else FaultPolicy()
        if policy.checkpoint_every and directory is None:
            raise ValueError("checkpoint_every > 0 needs a checkpoint "
                             "directory")
        self.server = server
        self.policy = policy
        self.directory = directory
        self.chaos = chaos
        self._sleep = sleep
        # the supervisor's own monotone segment index. Seeded from the
        # server's counter so chaos schedules line up with segment
        # numbers in fresh runs AND stay stable across crash recovery
        # (a restored server resumes its counter from the checkpoint).
        self._seg_idx = server.segments_done
        self._front_sig: dict[int, tuple[str, int]] = {}  # job → (sig, stall)
        self.recovered_step: Optional[int] = None
        self.stats = {"segments": 0, "retries": 0, "checkpoints": 0,
                      "quarantined": 0, "converged": 0}

    # -- construction --------------------------------------------------------

    @classmethod
    def for_problems(cls, problems, policy: Optional[FaultPolicy] = None,
                     *, directory: Optional[str] = None,
                     chaos: Optional[ChaosPlan] = None, sleep=time.sleep,
                     scheduler_policy: Optional[str] = None,
                     **server_kw) -> "Supervisor":
        """Build server + supervisor in one go.

        ``policy`` here is the :class:`FaultPolicy`; the lane scheduler's
        admission policy (the server's ``policy`` kwarg) rides as
        ``scheduler_policy`` to avoid the name collision."""
        policy = policy if policy is not None else FaultPolicy()
        if scheduler_policy is not None:
            server_kw["policy"] = scheduler_policy
        server = SearchServer.for_problems(problems, **server_kw)
        return cls(server, policy, directory=directory, chaos=chaos,
                   sleep=sleep)

    @classmethod
    def recover(cls, directory: str, spec, cfg,
                policy: Optional[FaultPolicy] = None, *,
                chaos: Optional[ChaosPlan] = None,
                sleep=time.sleep) -> "Supervisor":
        """Crash recovery: restore from the newest checkpoint that passes
        FULL integrity verification (corrupt/truncated steps are skipped
        back over), resume supervision from there.

        ``sup.recovered_step`` is the step restored; ``sup.
        dropped_pending`` lists queued jobs the checkpoint could not
        serialize — resubmit them (bit-identity is admission-segment
        independent, so nothing is lost but queue position).
        """
        step = ckpt.latest_valid_step(directory)
        if step is None:
            raise FileNotFoundError(
                f"no valid checkpoint under {directory}: nothing to "
                "recover from")
        server = SearchServer.restore(directory, spec, cfg, step=step)
        sup = cls(server, policy, directory=directory, chaos=chaos,
                  sleep=sleep)
        sup.recovered_step = step
        return sup

    @property
    def dropped_pending(self) -> list[dict]:
        return self.server.dropped_pending

    # -- the supervised loop -------------------------------------------------

    def submit(self, job, **kw) -> int:
        return self.server.submit(job, **kw)

    def step(self) -> list[JobResult]:
        """One supervised segment: retry-guarded dispatch, lane health
        validation + quarantine, convergence retirement, periodic
        checkpoint. Returns every job retired at this boundary (healthy,
        converged and quarantined alike — check ``JobResult.ok``)."""
        idx = self._seg_idx
        results = self._attempt(lambda: self._dispatch(idx), "segment")
        self._seg_idx += 1
        self.stats["segments"] += 1
        if self.chaos is not None:
            self.chaos.poison_lane(idx, self.server)
        if self.policy.validate:
            results.extend(self._validate())
        if self.policy.patience:
            results.extend(self._retire_converged())
        self._maybe_checkpoint(idx)
        if self.chaos is not None:
            self.chaos.after_segment(idx)
        return results

    def drain(self) -> list[JobResult]:
        """Supervised :meth:`SearchServer.drain`."""
        results = []
        while self.server.has_work:
            results.extend(self.step())
        return results

    @property
    def segments_done(self) -> int:
        return self.server.segments_done

    # -- internals -----------------------------------------------------------

    def _dispatch(self, idx: int) -> list[JobResult]:
        if self.chaos is not None:
            # injected faults fire BEFORE the dispatch: past this point
            # the segment jit owns (donates) the state buffers and an
            # in-process retry would replay on invalidated inputs
            self.chaos.on_segment(idx)
        timeout = self.policy.segment_timeout_s
        if timeout is None:
            return self.server.step()
        box: dict = {}

        def work():
            try:
                box["result"] = self.server.step()
            except BaseException as e:          # noqa: BLE001 — re-raised
                box["error"] = e

        t = threading.Thread(target=work, daemon=True)
        t.start()
        t.join(timeout)
        if t.is_alive():
            raise SegmentTimeoutError(
                f"segment {idx} exceeded the {timeout}s watchdog "
                "(dispatch hung; recover from the last checkpoint)")
        if "error" in box:
            raise box["error"]
        return box["result"]

    def _attempt(self, fn, what: str):
        """Run ``fn`` with capped-exponential-backoff retry on transient
        faults (IO errors, injected segment faults). Timeouts, kills and
        validation failures are fatal and propagate immediately."""
        p = self.policy
        delay = p.backoff_base_s
        for attempt in range(p.max_retries + 1):
            try:
                return fn()
            except (OSError, SegmentFault):
                if attempt == p.max_retries:
                    raise
                self.stats["retries"] += 1
                self._sleep(min(delay, p.backoff_cap_s))
                delay *= 2

    def _validate(self) -> list[JobResult]:
        busy = self.server._sched.busy_lanes
        if not busy:
            return []
        flags = np.asarray(_validate_lanes_jit(self.server._problems,
                                               self.server._states))
        out = []
        for lane in busy:
            bad = ~flags[lane]
            if not bad.any():
                continue
            failed = [n for n, b in zip(engine.VALIDATION_CHECKS, bad) if b]
            job_id = self.server._sched.lane_job[lane]
            msg = (f"lane {lane} failed validation at segment "
                   f"{self.server.segments_done}: {', '.join(failed)}")
            if not self.policy.quarantine:
                raise LaneValidationError(msg)
            out.append(self.server.quarantine_lane(lane, msg))
            self.stats["quarantined"] += 1
            self._front_sig.pop(job_id, None)
        return out

    def _retire_converged(self) -> list[JobResult]:
        out = []
        for lane in list(self.server._sched.busy_lanes):
            job_id = self.server._sched.lane_job[lane]
            sig = _front_fingerprint(self.server.lane_state(lane))
            prev = self._front_sig.get(job_id)
            stalls = prev[1] + 1 if prev is not None and prev[0] == sig else 0
            self._front_sig[job_id] = (sig, stalls)
            if stalls >= self.policy.patience:
                out.append(self.server.retire_lane(lane, converged=True))
                self.stats["converged"] += 1
                del self._front_sig[job_id]
        return out

    def _maybe_checkpoint(self, idx: int):
        p = self.policy
        if not p.checkpoint_every or (idx + 1) % p.checkpoint_every:
            return

        def save():
            if self.chaos is not None:
                self.chaos.on_save(idx)
            return self.server.save(self.directory, keep=p.keep,
                                    allow_pending=True)

        path = self._attempt(save, "checkpoint")
        self.stats["checkpoints"] += 1
        if self.chaos is not None:
            # post-commit damage (bit rot) is NOT retried: the save
            # succeeded; recovery discovers it via latest_valid_step
            self.chaos.after_save(path, self.server.segments_done)

"""Deterministic fault-injection for the supervised serve path.

The load-bearing half of PR 10's robustness story: error handling nobody
can trigger is wishful thinking, so every failure mode the
:class:`~repro.serve.supervisor.Supervisor` claims to survive is
*injectable on a deterministic schedule* — the chaos tests and
``bench_serve_chaos`` replay the exact same fault sequence every run.

A :class:`ChaosPlan` names faults by the supervisor-segment index at
which they fire (NOT ``server.segments_done`` — recovery restarts the
server's counter mid-stream, while the supervisor's own monotone index
keeps the schedule stable across restore). Supported faults:

  * ``segment_faults``     — transient host fault raised *before* the
    segment dispatches (:class:`SegmentFault`). Retried with backoff;
    injected pre-dispatch on purpose: the compiled segment donates its
    input buffers, so a mid-dispatch fault invalidates the carry and the
    only sound recovery is a checkpoint restore, not an in-process retry.
  * ``io_errors``          — transient :class:`ChaosIOError` from the
    auto-checkpoint save (retried with backoff).
  * ``poison``             — overwrite one leaf of one lane's device
    state at a segment boundary (NaN objectives, out-of-bounds genome,
    or negative eval counts) so ``engine.validate_state`` trips and the
    lane is quarantined.
  * ``corrupt_steps``      — bit-flip or truncate a *committed*
    checkpoint's leaf file after the save returns (silent bit rot;
    deliberately NOT retried — recovery must skip back a step).
  * ``kill_after_segment`` — :class:`ChaosKill` simulating process death
    after segment N; tests catch it, then exercise crash recovery.

Fire-once semantics: each scheduled fault fires exactly once, so the
retry that follows a transient fault succeeds — the schedules describe
*fault events*, not permanently broken hosts.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional

import numpy as np
import jax.numpy as jnp


class SegmentFault(RuntimeError):
    """A transient host fault at a segment boundary (pre-dispatch).
    The supervisor retries these with capped exponential backoff."""


class ChaosIOError(OSError):
    """A transient checkpoint-IO fault (disk hiccup). Retried."""


class ChaosKill(RuntimeError):
    """Simulated process death — NOT retried; propagates out of the
    supervisor so tests (and the example) can exercise crash recovery
    with :meth:`Supervisor.recover`."""


# poison_leaf -> how a lane's state is damaged (all three trip a distinct
# engine.VALIDATION_CHECKS flag)
POISON_LEAVES = ("obj", "pop", "counts")


def corrupt_checkpoint(directory: str, step: int, *, kind: str = "bitflip",
                       leaf: Optional[str] = None, seed: int = 0) -> str:
    """Damage one leaf file of a COMMITTED checkpoint in place.

    ``kind``: ``"bitflip"`` XORs one byte mid-file; ``"truncate"`` cuts
    the file to half its length. ``leaf``: manifest leaf name (default:
    the largest leaf — most likely to matter). Deterministic under
    ``seed``. Returns the damaged file's path. Used by the chaos plan
    (``corrupt_steps``) and directly by checkpoint-integrity tests.
    """
    d = os.path.join(directory, f"step_{step:08d}")
    names = sorted(f for f in os.listdir(d) if f.endswith(".npy"))
    if not names:
        raise FileNotFoundError(f"no leaf files under {d}")
    if leaf is not None:
        fn = os.path.join(d, leaf + ".npy")
    else:
        fn = max((os.path.join(d, n) for n in names), key=os.path.getsize)
    size = os.path.getsize(fn)
    rng = np.random.default_rng(seed)
    if kind == "bitflip":
        with open(fn, "r+b") as f:
            pos = int(rng.integers(0, size))
            f.seek(pos)
            byte = f.read(1)
            f.seek(pos)
            f.write(bytes([byte[0] ^ 0xFF]))
    elif kind == "truncate":
        with open(fn, "r+b") as f:
            f.truncate(max(1, size // 2))
    else:
        raise ValueError(f"unknown corruption kind {kind!r}: "
                         "want 'bitflip' or 'truncate'")
    return fn


@dataclasses.dataclass
class ChaosPlan:
    """A deterministic fault schedule, keyed by supervisor segment index.

    ``segment_faults``: segment indices at which a transient
    :class:`SegmentFault` fires before dispatch.
    ``io_errors``: segment indices whose auto-checkpoint save raises a
    transient :class:`ChaosIOError` first.
    ``poison``: {segment index → lane} — after that segment, the lane's
    state leaf named by ``poison_leaf`` is overwritten with invalid data.
    ``poison_leaf``: ``"obj"`` (NaN objectives), ``"pop"`` (out-of-bounds
    genome) or ``"counts"`` (negative eval counts).
    ``corrupt_steps``: checkpoint step numbers whose committed files get
    damaged (``corrupt_kind``: "bitflip"|"truncate") right after the save
    that wrote them returns.
    ``kill_after_segment``: raise :class:`ChaosKill` after this segment
    completes (post-checkpoint), simulating sudden process death.
    ``seed`` drives the corruption byte positions only — the *schedule*
    is explicit and exact.
    """
    segment_faults: tuple = ()
    io_errors: tuple = ()
    poison: dict = dataclasses.field(default_factory=dict)
    poison_leaf: str = "obj"
    corrupt_steps: tuple = ()
    corrupt_kind: str = "bitflip"
    kill_after_segment: Optional[int] = None
    seed: int = 0

    def __post_init__(self):
        if self.poison_leaf not in POISON_LEAVES:
            raise ValueError(f"unknown poison_leaf {self.poison_leaf!r}: "
                             f"want one of {POISON_LEAVES}")
        self._fired: set = set()

    def _once(self, tag) -> bool:
        if tag in self._fired:
            return False
        self._fired.add(tag)
        return True

    # -- hooks the supervisor calls ----------------------------------------

    def on_segment(self, idx: int):
        """Before dispatching supervisor-segment ``idx``."""
        if idx in self.segment_faults and self._once(("seg", idx)):
            raise SegmentFault(f"injected transient fault at segment {idx}")

    def on_save(self, idx: int):
        """Before the auto-checkpoint save at segment ``idx``."""
        if idx in self.io_errors and self._once(("io", idx)):
            raise ChaosIOError(f"injected checkpoint IO error at "
                               f"segment {idx}")

    def poison_lane(self, idx: int, server) -> Optional[int]:
        """After segment ``idx``: damage one lane's device state in
        place. Returns the poisoned lane (or None)."""
        lane = self.poison.get(idx)
        if lane is None or not self._once(("poison", idx)):
            return None
        from . import server as server_mod

        st = server.lane_state(lane)
        if self.poison_leaf == "obj":
            bad = dataclasses.replace(
                st, obj=jnp.full_like(st.obj, jnp.nan))
        elif self.poison_leaf == "pop":
            bad = dataclasses.replace(
                st, pop=st.pop + jnp.int32(1 << 20))
        else:                                        # "counts"
            bad = dataclasses.replace(
                st, counts=jnp.full_like(st.counts, -1))
        server._problems, server._states = server_mod._place_lane(
            server._problems, server._states, np.int32(lane),
            server.lane_problem(lane), bad)
        return lane

    def after_save(self, path: str, step: int):
        """After a committed save: silent post-commit corruption."""
        if step in self.corrupt_steps and self._once(("corrupt", step)):
            directory = os.path.dirname(path)
            corrupt_checkpoint(directory, step, kind=self.corrupt_kind,
                               seed=self.seed + step)

    def after_segment(self, idx: int):
        """After segment ``idx`` fully completes (checkpoint included)."""
        if (self.kill_after_segment is not None
                and idx >= self.kill_after_segment
                and self._once(("kill",))):
            raise ChaosKill(f"injected process kill after segment {idx}")

"""Continuous-batching GA search service (ROADMAP "Serve-path
architecture").

``run_suite`` batches a *homogeneous* grid as one dispatch; a real
experiment queue is heterogeneous — jobs with different datasets,
generation budgets and constraint bounds arrive over time. ``repro.serve``
applies the LLM iteration-level-scheduling idiom (continuous batching,
sketched in ``repro.runtime.serve_loop``) to GA search: a
:class:`SearchServer` keeps a fixed number of *lanes* — one standing
stacked padded :class:`~repro.core.engine.Problem` + batched
:class:`~repro.core.engine.GAState` — and advances all of them together
in fixed-size *segments* of the budget-gated ``engine.run_scanned`` (ONE
compiled program, reused for every segment). Between segments a host-side
:class:`LaneScheduler` retires lanes whose per-lane generation budget is
exhausted (returning their Pareto fronts) and admits queued
:class:`SearchJob`\\ s into the freed slots by padding them into the shared
max-shape layout at *runtime* — lane composition is one compiled write
at a traced lane index into the standing pytrees, not a trace-time
constant.

Every job's result is bit-identical to its standalone sequential
``GATrainer.run`` (tests/test_serve.py): admission runs the same
``engine.init_state``, the segment body is the same generation step under
the same gene-addressed RNG, and a retired lane is a bitwise no-op
passthrough that contributes zero rows to the shared dedup evaluation
bound (``engine._budgeted_generation``).
"""
from .jobs import SearchJob, JobResult            # noqa: F401
from .scheduler import LaneScheduler              # noqa: F401
from .server import SearchServer                  # noqa: F401
from .supervisor import (Supervisor, FaultPolicy,            # noqa: F401
                         SegmentTimeoutError, LaneValidationError)
from .chaos import (ChaosPlan, SegmentFault, ChaosIOError,   # noqa: F401
                    ChaosKill, corrupt_checkpoint)

__all__ = ["SearchJob", "JobResult", "LaneScheduler", "SearchServer",
           "Supervisor", "FaultPolicy", "SegmentTimeoutError",
           "LaneValidationError", "ChaosPlan", "SegmentFault",
           "ChaosIOError", "ChaosKill", "corrupt_checkpoint"]

"""Stable public API of the repro package — import from here.

This module is the package's *stability boundary*: examples, benchmarks
and downstream users import ``repro.api`` and nothing deeper. Everything
re-exported here keeps its name and call signature across releases;
``repro.core.*`` / ``repro.kernels.*`` internals may move freely
underneath it (the kernel-backend split, the engine/sweep layout, …).

The surface covers the paper pipeline end to end:

  data → ``train_float_mlp`` → ``exact_bespoke_baseline`` →
  ``calibrated_seeds`` → ``train`` (or ``GATrainer`` / ``run_batch`` /
  ``run_grid`` / ``run_suite`` / ``run_islands`` for batched, swept,
  suite-wide and island-parallel searches) → ``front_of`` /
  ``best_within_loss`` → ``accuracy`` / ``HardwareCost`` /
  ``emit_verilog``.

Backend selection is the ``BackendPolicy`` value of
``GAConfig(backends=...)`` — one frozen dataclass naming the fitness /
variation / generation / ranking implementations, validated at config
construction (the legacy per-path ``*_backend`` kwargs still work but
warn). Device-variation Monte-Carlo fitness is the
``GAConfig(variation_mode=..., n_device_samples=..., variation_scale=...)``
trio; see ``engine.device_deltas`` and ROADMAP.md.

Heterogeneous job *streams* — different datasets, seeds and generation
budgets arriving over time — go through the continuous-batching search
service: build a ``SearchServer`` (``SearchServer.for_problems`` sizes its
shared padded layout), ``submit`` ``SearchJob``\\ s and ``step``/``drain``
for per-job ``JobResult`` Pareto fronts, each bit-identical to the
standalone sequential ``GATrainer.run`` of that job. The server advances
all lanes in fixed-size compiled segments and admits/retires jobs at
segment boundaries (see ``repro.serve`` and ``examples/serve_jobs.py``);
``SearchServer.save``/``restore`` checkpoint in-flight jobs resumably.

For long-lived or hostile environments wrap the server in a
``Supervisor`` under a ``FaultPolicy``: periodic auto-checkpointing
through the two-phase-commit store, crash recovery from the latest
*valid* checkpoint (``Supervisor.recover``), per-lane health validation
with quarantine, capped-backoff retry of transient faults and a segment
watchdog — all deterministic-fault-tested via ``repro.serve.chaos``
(ROADMAP "Serve-path architecture").

Scripts call ``enable_compile_cache()`` first, so that a later process
finds the compiled programs in JAX's persistent compilation cache.
"""
from __future__ import annotations

from .core.genome import (MLPTopology, GenomeSpec, GeneTable,  # noqa: F401
                          max_topology, random_population)
from .core.engine import (GAConfig, GAState, Problem,          # noqa: F401
                          run_batch, state_at, front_of, pad_problem)
from .core.trainer import GATrainer                            # noqa: F401
from .core.sweep import (SweepResult, SuiteResult,             # noqa: F401
                         run_grid, grid_cells, run_suite, suite_spec)
from .core.islands import IslandConfig, run_islands            # noqa: F401
from .core.area import (HardwareCost, mlp_fa_count,            # noqa: F401
                        population_area, baseline_mlp_fa,
                        EGFET_POWER_SCALE_06V)
from .core.mlp import (accuracy, population_accuracy,          # noqa: F401
                       mlp_forward, mlp_predict)
from .core.quantize import quantize_inputs                     # noqa: F401
from .core.pareto import (pareto_front, hypervolume_2d,        # noqa: F401
                          best_within_loss)
from .core.baselines import (train_float_mlp,                  # noqa: F401
                             exact_bespoke_baseline,
                             calibrated_seeds, post_training_approx,
                             FloatMLP, BespokeBaseline)
from .core.hdl import (emit_verilog, evaluate_genome_python,   # noqa: F401
                       evaluate_genome_instances)
from .core.hw_approx_search import LMApproxSearch, FORMATS     # noqa: F401
from .kernels import (BackendPolicy, resolve_backends,         # noqa: F401
                      BACKEND_CHOICES)
from .serve import (SearchServer, SearchJob, JobResult,        # noqa: F401
                    LaneScheduler, Supervisor, FaultPolicy)
from .compile_cache import enable_compile_cache                # noqa: F401

__all__ = [
    # genome / problem setup
    "MLPTopology", "GenomeSpec", "GeneTable", "max_topology",
    "random_population", "Problem", "pad_problem",
    # config + backend selection
    "GAConfig", "BackendPolicy", "resolve_backends", "BACKEND_CHOICES",
    # training entry points
    "train", "GATrainer", "GAState", "run_batch", "run_grid", "grid_cells",
    "run_suite", "suite_spec", "run_islands", "IslandConfig",
    "SweepResult", "SuiteResult",
    "state_at", "front_of",
    # baselines + analysis + hardware
    "train_float_mlp", "exact_bespoke_baseline", "calibrated_seeds",
    "post_training_approx", "FloatMLP", "BespokeBaseline",
    "pareto_front", "hypervolume_2d", "best_within_loss",
    "accuracy", "population_accuracy", "mlp_forward", "mlp_predict",
    "quantize_inputs", "HardwareCost", "mlp_fa_count", "population_area",
    "baseline_mlp_fa", "EGFET_POWER_SCALE_06V",
    "emit_verilog", "evaluate_genome_python", "evaluate_genome_instances",
    # LM-scale post-training approximation search
    "LMApproxSearch", "FORMATS",
    # continuous-batching search service + fault-tolerant supervision
    "SearchServer", "SearchJob", "JobResult", "LaneScheduler",
    "Supervisor", "FaultPolicy",
    # JAX's persistent compilation cache, at one fixed directory
    "enable_compile_cache",
]


def train(topo, x01, labels, cfg: GAConfig | None = None, *,
          baseline_acc: float | None = None, doping_seeds=None,
          generations: int | None = None, verbose: bool = False):
    """One-call GA training — the facade's convenience entry point.

    Builds a :class:`GATrainer` for ``(topo, x01, labels)`` and runs it;
    returns ``(trainer, state, history)``. Identical numerics to
    constructing the trainer yourself (it *is* ``GATrainer.run``); keep
    the trainer around for ``trainer.front(state)`` / eval accounting.
    """
    trainer = GATrainer(topo, x01, labels, cfg or GAConfig(),
                        baseline_acc=baseline_acc,
                        doping_seeds=doping_seeds)
    state, history = trainer.run(generations=generations, verbose=verbose)
    return trainer, state, history

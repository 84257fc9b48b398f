"""GA-based hardware-approximation-aware training (paper §IV, Fig. 2).

Single-host trainer. Objectives (paper Eq. (3)): [1 − Accuracy(θ, D),
Area(θ) in FAs]; constraint (paper §IV-A): accuracy ≥ baseline − max_acc_loss
(10 %); init (paper §IV-A): random population doped with ~10 % nearly
non-approximate chromosomes from a float MLP.

``GATrainer`` is a thin stateful adapter over the pure functional engine in
``repro.core.engine``: the NSGA-II generation step, the scanned whole-run
loop and the init all live there (and are shared, bit-for-bit, with the
island trainer in ``repro.core.islands``, the multi-seed batched runner
``engine.run_batch`` and the (seed × config) grid runner
``repro.core.sweep``). Every jitted entry point takes the ``Problem`` as a
traced *argument* — never a closure constant — so a trainer run is
bit-identical to its cell in a batched/swept dispatch (closing over the
problem would constant-fold ``baseline_acc`` into the violation chain and
shift it by an ulp). The fitness hot loop (the paper's ~26 M chromosome
evaluations) runs through the ``repro.kernels.pop_mlp.population_correct``
dispatcher — Pallas kernel on TPU, sample/population-tiled jnp elsewhere —
selected by ``GAConfig.fitness_backend``. Generations execute as a single
``lax.scan`` dispatch (``GAConfig.scan``), only children are ever scored
(parent objectives ride in ``GAState``), duplicate children reuse cached
integer counts — within a generation AND across them, via the
cross-generation ``EvalCache`` carried in the scan state (``GAConfig.dedup``,
default; see ``repro.core.dedup``) — and survivor re-ranking reuses the
combined pool's dominance matrix. All of these are bit-exact w.r.t. the
naive loop. After a scanned run, ``unique_evals`` counts the rows actually
evaluated and ``cache_hits`` the evaluations the cross-generation cache
saved.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np
import jax
from jax.profiler import TraceAnnotation

from .genome import MLPTopology
from . import engine
from .engine import GAConfig, GAState, Problem   # noqa: F401  (re-exported API)

class GATrainer:
    """Hardware-aware NSGA-II trainer for one (topology, dataset) pair."""

    def __init__(self, topo: MLPTopology, x01, labels,
                 cfg: GAConfig | None = None,
                 baseline_acc: float | None = None,
                 doping_seeds: Optional[Sequence[np.ndarray]] = None):
        cfg = cfg if cfg is not None else GAConfig()
        self.topo = topo
        self.cfg = cfg
        # chance-level baseline if no float model is supplied
        self.baseline_acc = float(baseline_acc) if baseline_acc is not None else 1.0
        self.problem = Problem.from_data(topo, x01, labels, cfg,
                                         baseline_acc=self.baseline_acc)
        self.spec = self.problem.spec
        self.x_int = self.problem.x_int
        self.labels = self.problem.labels
        self.doping_seeds = doping_seeds
        # Per-instance jits (compile caches die with the trainer — a long
        # sweep loop of fresh trainers can't grow a process-global cache).
        # The Problem is a traced ARGUMENT of each, never a closure
        # constant, so the numerics match engine.run_batch /
        # sweep.run_grid cells exactly (see module docstring). The GAState
        # argument of the step/scan dispatches is DONATED: the caller
        # never reads the pre-step state again, so XLA reuses its
        # population/objective buffers in place instead of copying them
        # per dispatch (donation aliases buffers, it never changes values).
        self._init_jit = jax.jit(lambda problem, doping: engine.init_state(
            problem, jax.random.PRNGKey(problem.cfg.seed), doping))
        self._step_jit = jax.jit(
            lambda problem, state: engine.generation(problem, state)[0],
            donate_argnums=(1,))
        self._scan_jit = jax.jit(engine.run_scanned,
                                 static_argnames="generations",
                                 donate_argnums=(1,))

    # -- init ---------------------------------------------------------------
    def init_state(self) -> GAState:
        with TraceAnnotation("ga.init"):
            state, n_eval = self._init_jit(
                self.problem, engine._doping_array(self.doping_seeds))
            self._init_unique_evals = int(n_eval)
        return state

    # -- public API ----------------------------------------------------------
    def run(self, generations: int | None = None, verbose: bool = False,
            scan: bool | None = None):
        """Train for ``generations``; returns (final state, history).

        ``scan`` (default ``cfg.scan``) runs all generations as one
        ``lax.scan`` dispatch; ``scan=False`` keeps the per-generation
        Python loop (seed semantics — bit-identical results).

        With ``verbose`` the history holds, every ``cfg.log_every``
        generations and at the last, ``gen``, ``best_err`` and
        ``best_area``."""
        gens = generations if generations is not None else self.cfg.generations
        scan = self.cfg.scan if scan is None else scan
        state = self.init_state()
        history = []
        if scan and gens > 0:
            with TraceAnnotation("ga.scan"):
                state, (best_err, best_area, n_eval, n_hit) = self._scan_jit(
                    self.problem, state, generations=gens)
                jax.block_until_ready(state.pop)
                self.unique_evals = (int(np.asarray(n_eval).sum())
                                     + self._init_unique_evals)
                self.cache_hits = int(np.asarray(n_hit).sum())
            if verbose:
                for g in range(gens):
                    if g % self.cfg.log_every == 0 or g == gens - 1:
                        history.append({
                            "gen": g,
                            "best_err": float(best_err[g]),
                            "best_area": float(best_area[g]),
                        })
        else:
            self.unique_evals = None
            self.cache_hits = None
            for g in range(gens):
                state = self._step_jit(self.problem, state)
                if verbose and (g % self.cfg.log_every == 0 or g == gens - 1):
                    err = np.asarray(state.obj[:, 0])
                    area = np.asarray(state.obj[:, 1])
                    history.append({
                        "gen": g,
                        "best_err": float(err.min()),
                        "best_area": float(area.min()),
                    })
        jax.block_until_ready(state.pop)
        self.evaluations = (gens + 1) * self.cfg.pop_size * int(self.labels.shape[0])
        return state, history

    def front(self, state: GAState):
        """Feasible estimated Pareto front (paper Fig. 2 output)."""
        with TraceAnnotation("ga.front"):
            return engine.front_of(state)

"""Island-parallel NSGA-II over the device mesh (DESIGN.md §3/§5).

The paper runs ~26 M chromosome evaluations on one EPYC socket; the GA is
embarrassingly parallel, so at pod scale we shard the population into one
island per device along the ``data`` (and ``pod``) mesh axes with
``shard_map``:

  * each island runs the full NSGA-II generation locally (no collectives)
    through the shared ``repro.core.engine`` — ``engine.generation`` is the
    same step ``GATrainer`` scans, applied to the island's
    ``island_pop``-sized shard,
  * every ``migrate_every`` generations the best ``n_migrants`` chromosomes
    hop to the next island on a ring (``lax.ppermute``) and replace the
    locals' worst — on a single device the ring is degenerate and migration
    is skipped outright, so a 1-island run is bit-for-bit a ``GATrainer``
    run of the same seed,
  * the final global Pareto front is an ``all_gather`` + host-side peel of
    the *feasible* chromosomes (same all-feasible fallback as
    ``GATrainer.front``).

Island ``i`` initializes exactly like ``GATrainer`` with seed ``seed + i``
(independent doped populations through ``engine.init_state``). Fitness goes
through the ``population_correct`` dispatcher (kernel on TPU, tiled jnp
elsewhere — ``GAConfig.fitness_backend``); objectives are carried across
rounds and travel with migrants over the ring, so only children are ever
scored (with duplicate-chromosome dedup, ``GAConfig.dedup``) — all bit-exact
w.r.t. re-evaluating everything.

The same code runs on 1 CPU device (degenerate ring) and on the 512-device
dry-run mesh; ``launch/dryrun.py`` lowers it for the production meshes.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from .genome import GenomeSpec, MLPTopology
from .quantize import quantize_inputs
from ..kernels.pop_ranking import population_ranking
from .pareto import pareto_front
from . import engine
from .dedup import EvalCache
from .engine import GAConfig, GAState, Problem


@dataclasses.dataclass(frozen=True)
class IslandConfig:
    ga: GAConfig = GAConfig()
    island_pop: int = 64          # per-device population
    migrate_every: int = 10
    n_migrants: int = 4
    rounds: int = 10              # migration rounds; total gens = rounds × migrate_every


def build_island_step(spec: GenomeSpec, cfg: IslandConfig, mesh: Mesh,
                      x_int, labels, baseline_acc: float,
                      axis_names: tuple[str, ...] = ("data",)):
    """Returns (init_fn, round_fn) running one migration round per call.

    The population and its objectives live as global arrays
    (n_devices × island_pop leading axis) sharded over ``axis_names``;
    ``init_fn`` scores each island's initial population once and every
    later score happens island-locally on children only.
    """
    problem = Problem(jnp.asarray(x_int), jnp.asarray(labels, jnp.int32),
                      jnp.float32(baseline_acc), spec, cfg.ga)
    n_axis = int(np.prod([mesh.shape[a] for a in axis_names]))
    # the cross-generation eval cache (default dedup mode) travels in the
    # carry as three extra sharded leaves — one independent table slice per
    # island, exactly like a run_batch lane's
    cached = engine.dedup_mode(cfg.ga) == "cache"
    n_carry = 7 + (3 if cached else 0)

    def island_round(problem, pop, obj, viol, counts, rank, crowd, key,
                     *cache_leaves):
        """Local shard view: pop (island_pop, genes), obj (island_pop, M)
        (M = 2, or 3 under device-variation MC fitness),
        viol/rank/crowd (island_pop,), counts (island_pop,) — or
        (island_pop, K) per-instance counts — key (1, 2) uint32 (the
        leading shard axis stays — strip it for jax.random), plus the
        island's EvalCache leaves (rows/vals/stamp) in the default dedup
        mode. ``problem`` is replicated (every island sees the full
        dataset) and traced — a closure constant would constant-fold
        ``baseline_acc`` and shift the violation chain by an ulp vs
        GATrainer/run_batch. The per-round state restarts ``gen`` at 0,
        so cache eviction stamps reset each round — an eviction-quality
        detail only, never a correctness one (entries are still confirmed
        by exact row compare)."""
        key = key[0]
        cache = (EvalCache(*cache_leaves, cfg.ga.cache_probes)
                 if cache_leaves else None)
        state = GAState(pop, obj, viol, rank, crowd, counts, key,
                        jnp.int32(0), cache)
        state, _ = engine.run_scanned(problem, state, cfg.migrate_every)
        pop, obj, viol, counts = state.pop, state.obj, state.viol, state.counts
        rank, crowd, key = state.rank, state.crowd, state.key

        if n_axis > 1:
            # --- ring migration: send my best n_migrants to the next island
            # (objectives are deterministic in the genome, so they travel
            # with it; a 1-island ring would only clone best over worst,
            # so the degenerate case skips migration entirely) ---
            order = jnp.lexsort((-crowd, rank))
            best = order[: cfg.n_migrants]
            payload = (pop[best], obj[best], viol[best], counts[best])
            axis = axis_names[-1]
            perm = [(i, (i + 1) % mesh.shape[axis])
                    for i in range(mesh.shape[axis])]
            payload = jax.lax.ppermute(payload, axis, perm)
            if len(axis_names) > 1:   # cross-pod ring on the slower axis too
                perm0 = [(i, (i + 1) % mesh.shape[axis_names[0]])
                         for i in range(mesh.shape[axis_names[0]])]
                payload = jax.lax.ppermute(payload, axis_names[0], perm0)
            worst = order[-cfg.n_migrants:]
            pop = pop.at[worst].set(payload[0])
            obj = obj.at[worst].set(payload[1])
            viol = viol.at[worst].set(payload[2])
            counts = counts.at[worst].set(payload[3])
            # migration invalidated the ranking — recompute for next round
            # (the degenerate ring keeps the scan's rank/crowd, which equal
            # a recompute bit-for-bit: nsga2.subset_ranking equivalence)
            rank, crowd = population_ranking(
                obj, viol, backend=cfg.ga.backends.ranking)
        out = (pop, obj, viol, counts, rank, crowd, key[None])
        if cache_leaves:    # migrants carry their counts; caches stay local
            out += (state.cache.rows, state.cache.vals, state.cache.stamp)
        return out

    pspec = P(axis_names)
    # the carry (pop/obj/viol/counts/rank/crowd/key + cache leaves) is
    # donated: round_fn callers rebind it every round, so its buffers
    # update in place instead of being copied per dispatch (aliasing only
    # — bit-identical)
    sharded_round = jax.jit(shard_map(
        island_round, mesh=mesh,
        in_specs=(P(),) + (pspec,) * n_carry,  # problem replicated, state sharded
        out_specs=(pspec,) * n_carry,
        check_vma=False,
    ), donate_argnums=tuple(range(1, n_carry + 1)))

    # island i == GATrainer(seed + i)'s initial state, all islands in one
    # vmapped dispatch (512 islands ≠ 512 sequential inits). The problem is
    # a jit argument for the same ulp reason as island_round; batched
    # elementwise ops then round exactly like a per-island loop.
    init_batched = jax.jit(lambda problem, seed, dope: jax.vmap(
        lambda s: engine.init_state(problem, jax.random.PRNGKey(s),
                                    dope, cfg.island_pop)[0]
    )(seed + jnp.arange(n_axis)))

    def init(seed: int, doping_seeds=None):
        states = init_batched(problem, seed,
                              engine._doping_array(doping_seeds))
        P_glob = n_axis * cfg.island_pop
        # shape-suffix-preserving flattens: obj keeps its M objective
        # columns and counts its optional K instance axis (device-
        # variation MC fitness), so each shard sees its local shapes
        carry = (states.pop.reshape(P_glob, -1),
                 states.obj.reshape((P_glob,) + states.obj.shape[2:]),
                 states.viol.reshape(P_glob),
                 states.counts.reshape((P_glob,) + states.counts.shape[2:]),
                 states.rank.reshape(P_glob), states.crowd.reshape(P_glob),
                 states.key)
        if cached:   # per-island cache slices stack on the sharded axis
            c = states.cache
            carry += (c.rows.reshape(n_axis * c.rows.shape[1], -1),
                      c.vals.reshape((n_axis * c.vals.shape[1],)
                                     + c.vals.shape[2:]),
                      c.stamp.reshape(-1))
        return carry

    def round_fn(*carry):
        return sharded_round(problem, *carry)

    return init, round_fn


def run_islands(topo: MLPTopology, x01, labels, mesh: Mesh,
                cfg: IslandConfig | None = None, baseline_acc: float = 1.0,
                axis_names: tuple[str, ...] = ("data",), seed: int = 0,
                doping_seeds=None):
    """Drive ``rounds`` migration rounds and return the global Pareto front."""
    cfg = cfg if cfg is not None else IslandConfig()
    spec = GenomeSpec(topo)
    x_int = quantize_inputs(jnp.asarray(x01, jnp.float32), topo.input_bits)
    labels = jnp.asarray(labels, jnp.int32)
    init, round_fn = build_island_step(spec, cfg, mesh, x_int, labels,
                                       baseline_acc, axis_names)
    carry = init(seed, doping_seeds)
    for _ in range(cfg.rounds):
        carry = round_fn(*carry)
    pop, obj, viol = carry[0], carry[1], carry[2]
    pop = np.asarray(jax.device_get(pop))

    # global Pareto peel on host — objectives were carried, not recomputed;
    # infeasible chromosomes (viol > 0) are dropped first, with the same
    # all-feasible fallback as GATrainer.front
    obj = np.asarray(jax.device_get(obj), np.float64)
    feas = np.asarray(jax.device_get(viol)) <= 0
    if not feas.any():
        feas = np.ones_like(feas)
    return pareto_front(obj[feas], extras={"genomes": pop[feas]}), spec

"""Config-axis GA sweeps: ONE dispatch over a (seed × hyperparameter) grid.

The paper's genetic training outcome is sensitive to the GA hyperparameters
(mutation/crossover rates, the accuracy-loss constraint bound), and the
approximation design space is explored by sweeping exactly these knobs.
Those knobs are traced float32 leaves of :class:`~repro.core.engine.Problem`
(``Problem.with_hypers``), so a whole sweep batches the same way a seed
sweep does: :func:`run_grid` vmaps (init → scanned run) over every
(seed, crossover_rate, mutation_rate_gene, max_acc_loss) cell of the
cartesian grid — one compilation, one dispatch — and returns per-cell
Pareto fronts. With a device ``Mesh`` it shards the cell axis via
``shard_map`` (data replicated, cells split), bit-identical to the
single-device path.

Every cell is bit-identical to the equivalent sequential ``GATrainer.run``
with the same hyperparameters in its ``GAConfig`` (tests/test_sweep.py):
all adapters trace the problem through the same engine functions. Dedup
stays a real tile-skip under the batch — the cells share one ``lax.pmax``
evaluation bound per generation (see ``dedup_eval``), so the per-cell
``unique_row_evals`` accounting matches the sequential runs exactly.

Typical use (see ``examples/hyperparam_sweep.py``)::

    problem = Problem.from_data(topo, x, y, GAConfig(...), baseline_acc=...)
    result = sweep.run_grid(problem, seeds=range(4),
                            mutation_rates=[0.01, 0.02, 0.05],
                            crossover_rates=[0.5, 0.7, 0.9])
    for i in range(result.n_cells):
        print(result.cell(i), result.front_at(i)["objectives"])

Suite batching (:func:`run_suite`) adds the last sequential axis: the
*dataset*. Each per-dataset Problem (its own topology, sample count, class
count, baseline) is embedded into one shared max-shape ``GenomeSpec`` via
``engine.pad_problem`` — per-gene bounds/ids, the output-column mask, the
1/n accuracy factor and the true sample count become traced leaves — and
the (dataset × seed × config) cells stack on a vmap axis, one dispatch per
*sample-size bucket* (all buckets share a single compiled program; tiles
of padded samples are skipped via the ``n_valid_samples`` pmax bound, so a
lane costs its own dataset's samples, not the widest one's). Every cell is
bit-identical to the *unpadded* sequential ``GATrainer.run`` on that
dataset (gene-addressed PRNG draws + canonical-zero padding;
tests/test_suite.py), so the paper's whole 5-dataset experiment table is a
handful of shared-program dispatches (``benchmarks/common.ga_run_suite``).
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P
from jax import shard_map

from . import engine
from . import genome as genome_mod
from .engine import GAState, Problem


def grid_cells(seeds, crossover_rates=None, mutation_rates=None,
               max_acc_losses=None, baseline_accs=None, cfg=None,
               problem=None):
    """Cartesian (seed × config) grid as flat per-cell arrays.

    ``None`` axes collapse to a single default value: the ``problem``'s
    hyperparameter *leaves* when given (the values a batched run of that
    problem would use — ``run_grid`` passes this), else the ``cfg``
    statics (``baseline_acc`` has no cfg static; its cfg-mode default is
    1.0, the chance-level convention of ``GATrainer``). Returns a dict
    with int32 ``seed`` and float32 ``crossover_rate``/
    ``mutation_rate_gene``/``max_acc_loss``/``baseline_acc`` arrays of
    shape (n_cells,), plus the grid ``shape`` tuple (n_seeds, n_crossover,
    n_mutation, n_max_loss, n_baseline) — cells are laid out in C order
    over that shape."""
    if problem is not None:
        pc0, pm0, mal0, ba0 = (float(problem.crossover_rate),
                               float(problem.mutation_rate_gene),
                               float(problem.max_acc_loss),
                               float(problem.baseline_acc))
    else:
        cfg = cfg if cfg is not None else engine.GAConfig()
        pc0, pm0, mal0, ba0 = (cfg.crossover_rate, cfg.mutation_rate_gene,
                               cfg.max_acc_loss, 1.0)
    axes = [np.asarray(list(seeds), np.int32),
            np.asarray([pc0] if crossover_rates is None
                       else list(crossover_rates), np.float32),
            np.asarray([pm0] if mutation_rates is None
                       else list(mutation_rates), np.float32),
            np.asarray([mal0] if max_acc_losses is None
                       else list(max_acc_losses), np.float32),
            np.asarray([ba0] if baseline_accs is None
                       else list(baseline_accs), np.float32)]
    shape = tuple(len(a) for a in axes)
    grids = np.meshgrid(*axes, indexing="ij")
    return {"seed": grids[0].reshape(-1),
            "crossover_rate": grids[1].reshape(-1),
            "mutation_rate_gene": grids[2].reshape(-1),
            "max_acc_loss": grids[3].reshape(-1),
            "baseline_acc": grids[4].reshape(-1),
            "shape": shape}


def _run_cells(problem: Problem, seeds, pcs, pms, mals, baccs, doping,
               generations: int):
    """vmap (init → scanned run) over the flat cell axis; the swept
    hyperparameters become per-cell Problem leaves inside the vmap."""
    def one(seed, pc, pm, mal, bacc):
        p = problem.with_hypers(crossover_rate=pc, mutation_rate_gene=pm,
                                max_acc_loss=mal, baseline_acc=bacc)
        state, n0 = engine.init_state(p, jax.random.PRNGKey(seed), doping)
        state, aux = engine.run_scanned(p, state, generations)
        return state, aux, n0

    return jax.vmap(one, axis_name=engine.BATCH_AXIS)(seeds, pcs, pms, mals,
                                                      baccs)


_run_cells_jit = jax.jit(_run_cells, static_argnames="generations")


def _auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with Auto axis types. ``jax.make_mesh`` gives Explicit
    axes, and a cell axis sharded over those cannot be indexed cell by
    cell (``state_at``/``front_at``) without naming an output sharding."""
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def _run_cells_sharded(problem: Problem, seeds, pcs, pms, mals, baccs,
                       doping, generations: int, mesh: Mesh,
                       axis_names: tuple[str, ...]):
    """shard_map the cell axis over ``mesh``: each device vmaps its slice
    of cells with the data replicated. Cells are padded (by repeating the
    last cell) to a multiple of the device count and the pads dropped —
    per-cell results are independent, so this is bit-identical to the
    unsharded path."""
    n = seeds.shape[0]
    n_dev = int(np.prod([mesh.shape[a] for a in axis_names]))
    pad = (-n) % n_dev
    if pad:
        def padded(a):
            return jnp.concatenate([a, jnp.repeat(a[-1:], pad, axis=0)])
        seeds, pcs, pms, mals, baccs = map(
            padded, (seeds, pcs, pms, mals, baccs))

    pspec = P(axis_names)
    fn = jax.jit(shard_map(
        lambda p, s, a, b, c, d, e: _run_cells(p, s, a, b, c, d, e,
                                               generations),
        mesh=_auto_axes(mesh),
        in_specs=(P(), pspec, pspec, pspec, pspec, pspec, P()),
        out_specs=pspec,
        check_vma=False,
    ))
    out = fn(problem, seeds, pcs, pms, mals, baccs, doping)
    if pad:
        out = jax.tree_util.tree_map(lambda x: x[:n], out)
    return out


@dataclasses.dataclass
class SweepResult:
    """Batched result of a (seed × config) sweep.

    ``states`` is a GAState whose every leaf has a leading (n_cells,)
    axis — including, in the default dedup mode, one independent
    cross-generation EvalCache slice per cell; ``aux`` is (best_err,
    best_area, n_eval, n_hit), each (n_cells, gens); ``init_evals`` is
    the per-cell unique-row count of the initial scoring.
    Cells are C-ordered over ``shape`` = (n_seeds, n_crossover,
    n_mutation, n_max_loss, n_baseline) and described by the flat
    ``cells`` arrays."""
    problem: Problem
    cells: dict
    states: GAState
    aux: tuple
    init_evals: jnp.ndarray

    @property
    def shape(self) -> tuple:
        return self.cells["shape"]

    @property
    def n_cells(self) -> int:
        return int(self.cells["seed"].shape[0])

    def cell(self, i: int) -> dict:
        """Hyperparameters of flat cell ``i``."""
        return {"seed": int(self.cells["seed"][i]),
                "crossover_rate": float(self.cells["crossover_rate"][i]),
                "mutation_rate_gene": float(self.cells["mutation_rate_gene"][i]),
                "max_acc_loss": float(self.cells["max_acc_loss"][i]),
                "baseline_acc": float(self.cells["baseline_acc"][i])}

    def state_at(self, i: int) -> GAState:
        return engine.state_at(self.states, i)

    def front_at(self, i: int):
        """Feasible estimated Pareto front of cell ``i``."""
        return engine.front_of(self.state_at(i))

    def fronts(self):
        return [self.front_at(i) for i in range(self.n_cells)]

    def unique_evals(self, i: int) -> int:
        """Unique chromosome rows actually evaluated by cell ``i`` (init +
        every generation) — comparable to ``GATrainer.unique_evals``."""
        return int(self.init_evals[i]) + int(np.asarray(self.aux[2][i]).sum())

    def cache_hits(self, i: int) -> int:
        """Evaluations cell ``i`` reused from its cross-generation cache —
        comparable to ``GATrainer.cache_hits``."""
        return int(np.asarray(self.aux[3][i]).sum())


def run_grid(problem: Problem, seeds, *, crossover_rates=None,
             mutation_rates=None, max_acc_losses=None, baseline_accs=None,
             generations: int | None = None, doping_seeds=None,
             mesh: Mesh | None = None,
             axis_names: tuple[str, ...] = ("data",),
             jit: bool = True) -> SweepResult:
    """Run the full (seed × config) grid in ONE dispatch.

    seeds: iterable of integer PRNG seeds (one independent run per cell).
    crossover_rates / mutation_rates / max_acc_losses: swept values for the
        corresponding ``GAConfig`` knob; ``None`` keeps the problem's
        single configured value for that axis.
    baseline_accs: swept values of the ``baseline_acc`` Problem leaf — a
        constraint-pressure axis: the feasibility bound is
        ``acc ≥ baseline_acc − max_acc_loss``, so a higher baseline
        tightens every cell's constraint without touching the data.
    generations: overrides ``problem.cfg.generations``.
    doping_seeds: the same doping genomes for every cell (paper §IV-A).
    mesh / axis_names: when given, the flat cell axis is sharded over the
        mesh axes via ``shard_map`` (one slice of cells per device, data
        replicated) — bit-identical to the single-device vmap.

    Every cell is bit-identical to a sequential ``GATrainer.run`` whose
    ``GAConfig`` carries that cell's hyperparameters and seed (and whose
    ``baseline_acc`` argument carries the cell's baseline).
    """
    # unswept axes keep the problem's (possibly with_hypers-replaced)
    # leaf values, matching what run_batch would run — not the cfg statics
    cells = grid_cells(seeds, crossover_rates, mutation_rates,
                       max_acc_losses, baseline_accs, problem=problem)
    gens = problem.cfg.generations if generations is None else generations
    problem = engine.batch_problem(problem)
    doping = engine._doping_array(doping_seeds)
    args = (jnp.asarray(cells["seed"]),
            jnp.asarray(cells["crossover_rate"]),
            jnp.asarray(cells["mutation_rate_gene"]),
            jnp.asarray(cells["max_acc_loss"]),
            jnp.asarray(cells["baseline_acc"]))
    if mesh is not None:
        states, aux, n0 = _run_cells_sharded(problem, *args, doping, gens,
                                             mesh, axis_names)
    else:
        fn = _run_cells_jit if jit else _run_cells
        states, aux, n0 = fn(problem, *args, doping, gens)
    return SweepResult(problem, cells, states, aux, n0)


# ---------------------------------------------------------------------------
# Suite batching: (dataset × seed × config) as one dispatch
# ---------------------------------------------------------------------------

def suite_spec(problems) -> "engine.GenomeSpec":
    """The shared max-shape GenomeSpec every suite problem embeds into."""
    topo = genome_mod.max_topology([p.spec.topo for p in problems])
    return genome_mod.GenomeSpec(topo)


# -- lane composition (shared by run_suite and repro.serve) -----------------
#
# A "lane" is one (dataset, seed, hypers) run embedded into a shared
# max-shape layout and tagged with the whole-run batch axis; a stack of
# lanes is ONE batched Problem a single compiled vmapped program runs.
# run_suite composes its lanes once per call (trace-time constants of that
# dispatch); SearchServer composes them at *runtime* — admitting a job is
# a scatter of one freshly padded lane into the standing stacked Problem.

def pad_lane(problem: Problem, spec_pad: "engine.GenomeSpec",
             n_samples: int) -> Problem:
    """Embed ``problem`` into the shared ``spec_pad``/``n_samples`` layout
    and tag it with the batch axis — one lane of a shared dispatch,
    bit-identical to its unpadded sequential run (``engine.pad_problem``)."""
    return engine.batch_problem(
        engine.pad_problem(problem, spec_pad, n_samples))


def stack_problems(problems) -> Problem:
    """Stack same-shape lane Problems leaf-wise: every array leaf gains a
    leading (n_lanes,) axis; the static aux (spec, cfg) must already agree
    (``tree_map`` raises on mismatched statics)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *problems)


def doped_lane_rows(doping_seeds, positions, n_genes: int, n_dope: int):
    """Per-lane doping rows in the padded layout: the dataset's unpadded
    doping genomes host-expanded to the ``n_dope``-row block (repeating
    seeds exactly like ``engine.initial_population``) and scattered into
    the shared gene axis."""
    dope = np.asarray(engine._doping_array(doping_seeds))
    reps = np.resize(np.arange(dope.shape[0]), n_dope)
    return genome_mod.pad_genomes(dope[reps], positions, n_genes)


def _run_suite_cells(problem: Problem, seeds, doping, generations: int):
    """vmap (init → scanned run) over the flat suite-cell axis. ``problem``
    is the stacked padded Problem (every leaf has a leading cell axis);
    ``doping`` is per-cell pre-expanded doping rows or None."""
    def one(p, seed, dope):
        state, n0 = engine.init_state(p, jax.random.PRNGKey(seed), dope)
        state, aux = engine.run_scanned(p, state, generations)
        return state, aux, n0

    ax = None if doping is None else 0
    return jax.vmap(one, in_axes=(0, 0, ax),
                    axis_name=engine.BATCH_AXIS)(problem, seeds, doping)


_run_suite_jit = jax.jit(_run_suite_cells, static_argnames="generations")


def _run_suite_sharded(problem: Problem, seeds, doping, generations: int,
                       mesh: Mesh, axis_names: tuple[str, ...]):
    """shard_map the suite-cell axis over ``mesh`` (cells split, nothing
    replicated — every leaf is per-cell). Cells are padded to a multiple of
    the device count by repeating the last cell and the pads dropped;
    per-cell results are independent, so this is bit-identical to vmap."""
    n = seeds.shape[0]
    n_dev = int(np.prod([mesh.shape[a] for a in axis_names]))
    pad = (-n) % n_dev
    if pad:
        def padded(a):
            return jnp.concatenate([a, jnp.repeat(a[-1:], pad, axis=0)])
        problem, seeds, doping = jax.tree_util.tree_map(
            padded, (problem, seeds, doping))

    pspec = P(axis_names)
    fn = jax.jit(shard_map(
        lambda p, s, d: _run_suite_cells(p, s, d, generations),
        mesh=_auto_axes(mesh), in_specs=(pspec, pspec, pspec), out_specs=pspec,
        check_vma=False,
    ))
    out = fn(problem, seeds, doping)
    if pad:
        out = jax.tree_util.tree_map(lambda x: x[:n], out)
    return out


@dataclasses.dataclass
class SuiteResult:
    """Batched result of a (dataset × seed × config) suite run.

    ``states``' leaves carry a leading (n_cells,) axis; cells are C-ordered
    over ``shape`` = (n_datasets, n_seeds, n_crossover, n_mutation,
    n_max_loss). ``state_at`` peels a cell and (by default) gathers its
    population back to the dataset's *unpadded* gene layout, so fronts and
    genomes flow into the downstream tooling (area/accuracy/Verilog)
    exactly like a sequential ``GATrainer`` run's."""
    problems: list              # the original (inner, unpadded) Problems
    spec: "engine.GenomeSpec"   # the shared padded spec
    names: list                 # per-dataset labels (strings or indices)
    positions: list             # per-dataset inner→padded gene positions
    cells: dict                 # flat per-cell arrays + the grid shape
    states: GAState
    aux: tuple                  # (best_err, best_area, n_eval, n_hit)
    init_evals: jnp.ndarray     # (n_cells,) unique rows of the init scoring

    @property
    def shape(self) -> tuple:
        return self.cells["shape"]

    @property
    def n_cells(self) -> int:
        return int(self.cells["seed"].shape[0])

    def dataset_of(self, i: int) -> int:
        return int(self.cells["dataset"][i])

    def cell(self, i: int) -> dict:
        d = self.dataset_of(i)
        return {"dataset": self.names[d], "seed": int(self.cells["seed"][i]),
                "crossover_rate": float(self.cells["crossover_rate"][i]),
                "mutation_rate_gene":
                    float(self.cells["mutation_rate_gene"][i]),
                "max_acc_loss": float(self.cells["max_acc_loss"][i]),
                "baseline_acc": float(self.cells["baseline_acc"][i])}

    def cells_of(self, name) -> list:
        """Flat indices of every cell of dataset ``name`` (label or index),
        in (seed × config) C order."""
        d = name if isinstance(name, int) else list(self.names).index(name)
        return [i for i in range(self.n_cells) if self.dataset_of(i) == d]

    def state_at(self, i: int, unpad: bool = True) -> GAState:
        state = engine.state_at(self.states, i)
        if unpad:
            pos = self.positions[self.dataset_of(i)]
            state = dataclasses.replace(state, pop=state.pop[:, pos])
        return state

    def front_at(self, i: int):
        """Feasible Pareto front of cell ``i``; genomes in the dataset's
        unpadded layout (objectives/violations are bit-identical either
        way — padding contributes zero area and zero logits)."""
        return engine.front_of(self.state_at(i))

    def unique_evals(self, i: int) -> int:
        """Unique chromosome rows cell ``i`` actually evaluated — matches
        the unpadded sequential ``GATrainer.unique_evals`` exactly (the
        cross-generation cache probes by id-addressed hashes, so padded
        lanes hit, insert and evict exactly like their unpadded runs)."""
        return int(self.init_evals[i]) + int(np.asarray(self.aux[2][i]).sum())

    def cache_hits(self, i: int) -> int:
        """Evaluations cell ``i`` reused from its cross-generation cache —
        matches the unpadded sequential ``GATrainer.cache_hits``."""
        return int(np.asarray(self.aux[3][i]).sum())


def _sample_buckets(sizes, factor):
    """Group dataset indices so no lane pads its sample axis by more than
    ``factor``. Greedy over sizes in descending order: a dataset joins the
    current bucket while ``bucket_max <= factor * its_size``; the returned
    buckets are each internally sorted by original index."""
    if factor is None:
        return [list(range(len(sizes)))]
    order = sorted(range(len(sizes)), key=lambda d: -sizes[d])
    buckets, bound = [], None
    for d in order:
        if bound is not None and bound <= factor * sizes[d]:
            buckets[-1].append(d)
        else:
            buckets.append([d])
            bound = sizes[d]
    return [sorted(b) for b in buckets]


def run_suite(problems, seeds, *, crossover_rates=None, mutation_rates=None,
              max_acc_losses=None, baseline_accs=None,
              generations: int | None = None,
              doping_seeds=None, names=None,
              spec: "engine.GenomeSpec | None" = None,
              sample_bucket_factor: float | None = 1.0,
              mesh: Mesh | None = None,
              axis_names: tuple[str, ...] = ("data",),
              jit: bool = True) -> SuiteResult:
    """Run several datasets' (seed × config) grids as one batched dispatch
    per sample-size bucket — equal-size buckets sharing one compiled
    program (every lane is padded to the same global shapes).

    problems: per-dataset Problems (different topologies/sample counts are
        fine — they embed into one max-shape layout). All must share the
        same ``GAConfig`` (one traced program ⇒ one population size, one
        generation count, one backend).
    seeds / crossover_rates / mutation_rates / max_acc_losses /
        baseline_accs: as in :func:`run_grid`; the cartesian grid repeats
        per dataset (an unswept baseline axis keeps each dataset's own
        baseline leaf).
    doping_seeds: optional list (aligned with ``problems``) of per-dataset
        doping genomes in their *unpadded* layouts (paper §IV-A); each
        dataset's seeds are host-expanded to the doped row block and
        scattered into the padded layout, so cell inits replicate the
        sequential trainer's doping bit-for-bit.
    names: per-dataset labels for ``SuiteResult.cell``/``cells_of``.
    sample_bucket_factor: every dispatch's lanes pay the sample-tile
        bound of its *widest* lane (``Problem.n_valid_samples`` pmax'd
        over the batch — tiles past it are skipped, see
        ``engine.population_counts``), so datasets are greedily grouped
        such that no lane overpays by more than this factor and each
        group dispatches separately. Every lane is still padded to the
        global suite max, so equal-dataset-count groups share a compiled
        program (with the default factor all paper-suite buckets do) —
        bucketing trades a few extra dispatches for fitness work
        proportional to the true sample counts instead of the padded
        axis (~2.7× on the paper suite). ``None`` = one dispatch for
        everything (the widest dataset's bound for all). Bucketing is
        pure batch composition: per-cell results are bit-identical
        regardless.
    mesh / axis_names: shard the flat cell axis via ``shard_map``
        (bit-identical to the single-device vmap; applied per bucket).

    Every cell is bit-identical to the sequential **unpadded**
    ``GATrainer.run`` on that dataset with the cell's seed and
    hyperparameters — including the dedup ``unique_row_evals`` accounting
    (each bucket's cells share one ``lax.pmax`` evaluation bound; rows
    between a cell's own count and the shared bound are evaluated but
    never gathered).
    """
    problems = list(problems)
    if not problems:
        raise ValueError("run_suite needs at least one problem")
    cfg0 = problems[0].cfg
    for p in problems[1:]:
        if p.cfg != cfg0:
            raise ValueError("suite problems must share one GAConfig "
                             f"(got {p.cfg} vs {cfg0})")
    names = list(names) if names is not None else list(range(len(problems)))
    gens = cfg0.generations if generations is None else generations
    spec_pad = suite_spec(problems) if spec is None else spec
    positions = [genome_mod.pad_positions(p.spec, spec_pad) for p in problems]
    sizes = [int(p.x_int.shape[0]) for p in problems]
    buckets = _sample_buckets(sizes, sample_bucket_factor)

    n_dope = max(1, int(cfg0.doping_frac * cfg0.pop_size))
    if doping_seeds is not None and len(doping_seeds) != len(problems):
        raise ValueError("doping_seeds must align with problems")

    # one dispatch per bucket: every lane is padded to the global s_max,
    # so buckets with the same dataset count have identical shapes and hit
    # the jit cache (with the default factor=1.0 on distinct-size datasets
    # — the paper suite — every bucket does; unequal bucket cardinalities
    # compile per cardinality). The per-dispatch n_valid_samples pmax
    # bound makes each bucket's lanes skip sample tiles past the bucket's
    # widest dataset. The gene axis is shared too, so per-cell outputs of
    # all buckets have identical shapes and concatenate into dataset order.
    s_max = max(sizes)
    per_dataset, meta, grid_shape = {}, {}, None
    for bucket in buckets:
        cell_problems, cell_dope, n_grid = [], [], None
        for d in bucket:
            p = pad_lane(problems[d], spec_pad, s_max)
            cells_d = grid_cells(seeds, crossover_rates, mutation_rates,
                                 max_acc_losses, baseline_accs, problem=p)
            if doping_seeds is not None:
                dope_rows = doped_lane_rows(doping_seeds[d], positions[d],
                                            spec_pad.n_genes, n_dope)
            for k in range(cells_d["seed"].shape[0]):
                cell_problems.append(p.with_hypers(
                    jnp.float32(cells_d["crossover_rate"][k]),
                    jnp.float32(cells_d["mutation_rate_gene"][k]),
                    jnp.float32(cells_d["max_acc_loss"][k]),
                    jnp.float32(cells_d["baseline_acc"][k])))
                if doping_seeds is not None:
                    cell_dope.append(dope_rows)
            meta[d] = [(d, cells_d["seed"][k],
                        cells_d["crossover_rate"][k],
                        cells_d["mutation_rate_gene"][k],
                        cells_d["max_acc_loss"][k],
                        cells_d["baseline_acc"][k])
                       for k in range(cells_d["seed"].shape[0])]
            n_grid = cells_d["seed"].shape[0]
            grid_shape = cells_d["shape"]

        stacked = stack_problems(cell_problems)
        seed_arr = jnp.asarray(np.concatenate(
            [[m[1] for m in meta[d]] for d in bucket]).astype(np.int32))
        doping = (None if doping_seeds is None
                  else jnp.asarray(np.stack(cell_dope)))
        if mesh is not None:
            out = _run_suite_sharded(stacked, seed_arr, doping, gens,
                                     mesh, axis_names)
        else:
            fn = _run_suite_jit if jit else _run_suite_cells
            out = fn(stacked, seed_arr, doping, gens)
        for j, d in enumerate(bucket):
            sl = slice(j * n_grid, (j + 1) * n_grid)
            per_dataset[d] = jax.tree_util.tree_map(lambda x: x[sl], out)

    flat = [m for d in range(len(problems)) for m in meta[d]]
    cells = {"dataset": np.asarray([m[0] for m in flat], np.int32),
             "seed": np.asarray([m[1] for m in flat], np.int32),
             "crossover_rate": np.asarray([m[2] for m in flat], np.float32),
             "mutation_rate_gene": np.asarray([m[3] for m in flat],
                                              np.float32),
             "max_acc_loss": np.asarray([m[4] for m in flat], np.float32),
             "baseline_acc": np.asarray([m[5] for m in flat], np.float32),
             "shape": (len(problems),) + grid_shape}
    states, aux, n0 = jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs),
        *[per_dataset[d] for d in range(len(problems))])
    return SuiteResult(problems, spec_pad, names, positions, cells, states,
                       aux, n0)

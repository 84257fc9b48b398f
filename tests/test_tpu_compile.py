"""The Pallas kernels of the main path compile for a TPU v5e at real sizes.

Interpret mode accepts shape casts, dtypes and VMEM budgets that Mosaic
refuses, so every kernel of the GA hot path is also compiled here for a
v5e that is described, not attached (``topologies.get_topology_desc``), at
the paper's widths: cardio (21-3-3, N = 1488) and pendigits (16-5-10,
N = 7696), population 256. Nothing runs: each case lowers the jitted
kernel from shapes alone and checks that the compiled program holds the
Mosaic kernel (``tpu_custom_call``). The topology is described inside a
module-scoped fixture — never at import — so that only the pytest worker
that runs this file loads the TPU compiler.
"""
import functools
import os
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.core.engine import GAConfig
from repro.core.genome import GenomeSpec, MLPTopology
from repro.data.tabular import TOPOLOGIES
from repro.kernels import BackendPolicy
from repro.kernels.pop_generation import pop_generation_kernel
from repro.kernels.pop_mlp.kernel import (pop_mlp_correct, pop_mlp_correct_mc,
                                          row_group)
from repro.kernels.pop_ranking import rank_select_rerank
from repro.kernels.pop_variation import pop_variation_kernel

P = 256
K = 8
# (dataset, training-split rows): the 70 % splits of data/tabular.py
WIDTHS = [("cardio", 1488), ("pendigits", 7696)]
KERNELS = ["pop_mlp_correct", "pop_mlp_correct_mc", "pop_variation_kernel",
           "pop_generation_kernel", "pop_generation_kernel_mc"]


@pytest.fixture(scope="module")
def mosaic_dump(tmp_path_factory):
    return tmp_path_factory.mktemp("mosaic")


@pytest.fixture(scope="module")
def topo(mosaic_dump):
    from jax.experimental import topologies
    # Mosaic dumps its passes for every kernel compiled here (read by
    # test_megakernel_reads_genes_without_lane_permutes); the TPU compiler
    # reads its flags once, when the topology is first described
    before = os.environ.get("LIBTPU_INIT_ARGS")
    os.environ["LIBTPU_INIT_ARGS"] = (
        f"{before or ''} --xla_mosaic_dump_to={mosaic_dump}").strip()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if before is None:
            del os.environ["LIBTPU_INIT_ARGS"]
        else:
            os.environ["LIBTPU_INIT_ARGS"] = before


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _args(sharding, *shapes):
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]


def _compile(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("dataset,n", WIDTHS)
def test_kernel_compiles_for_v5e(one_chip, dataset, n, kernel):
    sizes = TOPOLOGIES[dataset]
    spec = GenomeSpec(MLPTopology(sizes))
    G, n_in, n_out = spec.n_genes, sizes[0], sizes[-1]
    i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32
    pop = ((P, G), i32)
    data = [((n, n_in), i32), ((n,), i32)]
    bounds = [((), i32), ((), i32), ((n_out,), i32)]   # rows, samples, mask
    tables = [((G,), i32)] * 5 + [((3, 2), u32), ((), f32)]
    if kernel == "pop_mlp_correct":
        fn = lambda p, x, y, r, s, m: pop_mlp_correct(       # noqa: E731
            p, x, y, spec=spec, n_valid_rows=r, n_valid_samples=s,
            out_mask=m)
        shapes = [pop, *data, *bounds]
    elif kernel == "pop_mlp_correct_mc":
        fn = lambda p, x, y, d, h, r, s, m: pop_mlp_correct_mc(  # noqa: E731
            p, x, y, d, h, spec=spec, n_valid_rows=r, n_valid_samples=s,
            out_mask=m)
        shapes = [pop, *data, ((K, G), i32), ((G,), i32), *bounds]
    elif kernel == "pop_variation_kernel":
        fn = functools.partial(pop_variation_kernel, bp=64)
        shapes = [pop, pop, ((P,), i32), *tables]
    else:
        mc = kernel.endswith("_mc")

        def fn(a, b, do, lo, hi, im, mb, ids, keys, pm, x, y, s, m, *dev):
            return pop_generation_kernel(
                a, b, do, lo, hi, im, mb, ids, keys, pm, x, y, spec=spec,
                n_valid_samples=s, out_mask=m, dev=dev[0] if dev else None)
        shapes = [pop, pop, ((P,), i32), *tables, *data, *bounds[1:],
                  *([((K, G), i32)] if mc else [])]
    _compile(fn, _args(one_chip, *shapes))


@pytest.mark.parametrize("pool", [2 * P, P])
def test_ranking_tail_compiles_for_v5e(one_chip, pool):
    """The (μ+λ) ranking tail with its staircase passes as Pallas calls,
    at the pool (2P) and survivor (P) widths, vmapped over 2 lanes."""
    f32 = jnp.float32
    fn = jax.vmap(lambda o, v: rank_select_rerank(o, v, pool // 2,
                                                  backend="kernel"))
    _compile(fn, _args(one_chip, ((2, pool, 2), f32), ((2, pool), f32)))


def test_vmapped_generation_compiles_for_v5e(one_chip):
    """One generation of the engine, vmapped over 4 lanes as ``run_suite``
    and ``SearchServer`` run it, with every Pallas backend forced on:
    traced per-lane row and sample bounds reach the kernels."""
    sizes = TOPOLOGIES["cardio"]
    n = 1488
    cfg = GAConfig(pop_size=P, batch_axis=engine.BATCH_AXIS,
                   backends=BackendPolicy(fitness="kernel", variation="kernel",
                                          generation="kernel",
                                          ranking="kernel"))
    rng = np.random.default_rng(0)
    problem = engine.Problem.from_data(
        MLPTopology(sizes), rng.random((n, sizes[0]), np.float32),
        rng.integers(0, sizes[-1], n), cfg)
    state = jax.eval_shape(lambda p: engine.init_state(
        p, jax.random.PRNGKey(0))[0], problem.replace_cfg(batch_axis=None))

    def lanes(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct((4,) + a.shape, a.dtype,
                                           sharding=one_chip), tree)

    step = jax.vmap(lambda p, s: engine.generation(p, s)[0],
                    axis_name=engine.BATCH_AXIS)
    _compile(step, (lanes(problem), lanes(state)))



def test_sharded_suite_bucket_compiles_for_v5e_mesh(topo):
    """One bucket of the paper suite as ``run_suite(..., mesh=...)`` runs
    it on a four-chip host: 8 pendigits cells in the padded 21-5-10
    layout at 7,696 rows, split over a 2x2 v5e's four chips, with every
    Pallas backend forced on. The megakernel stays in the program under
    ``shard_map`` ∘ ``vmap``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.core import sweep

    cells, n = 8, 7696
    cfg = GAConfig(pop_size=P, generations=2,
                   backends=BackendPolicy(fitness="kernel", variation="kernel",
                                          generation="kernel",
                                          ranking="kernel"))
    rng = np.random.default_rng(0)
    problems = [engine.Problem.from_data(
        MLPTopology(TOPOLOGIES[d]), rng.random((64, TOPOLOGIES[d][0]),
                                               np.float32),
        rng.integers(0, TOPOLOGIES[d][-1], 64), cfg)
        for d in ("breast_cancer", "cardio", "pendigits", "redwine",
                  "whitewine")]
    spec = sweep.suite_spec(problems)
    assert spec.topo.sizes == (21, 5, 10)
    pendigits = engine.Problem.from_data(
        MLPTopology(TOPOLOGIES["pendigits"]),
        rng.random((n, 16), np.float32), rng.integers(0, 10, n), cfg)
    lane = sweep.pad_lane(pendigits, spec, n)
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    split = NamedSharding(mesh, PartitionSpec("data"))

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=split),
            tree)

    stacked = jax.eval_shape(lambda: sweep.stack_problems([lane] * cells))
    seeds = jax.ShapeDtypeStruct((cells,), jnp.int32)
    doping = jax.ShapeDtypeStruct((cells, 25, spec.n_genes), jnp.int32)
    program = sweep._sharded_program(sweep._run_suite_cells, 2, mesh,
                                     ("data",), (False, False, False))
    compiled = program.lower(sds(stacked), *sds((seeds, doping))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _row_loop(llo: str) -> str:
    """The text of a compiled kernel's row loop: the ``scf.for`` that
    carries the (8, 128) int32 hit block, or "" if it has none."""
    lines = llo.splitlines()
    start = next((i for i, line in enumerate(lines)
                  if "scf.for" in line and "vector<8x128xi32>" in line), None)
    if start is None:
        return ""
    depth = 0
    for end in range(start, len(lines)):
        depth += lines[end].count("{") - lines[end].count("}")
        if depth <= 0 and end > start:
            break
    return "\n".join(lines[start:end + 1])


def test_megakernel_reads_genes_without_lane_permutes(one_chip, mosaic_dump):
    """The megakernel's gene reads are sublane broadcasts: in its compiled
    row loop at pendigits' shapes (16-5-10, 130 shift-add terms), lane
    permutes (``llo.vperm``) per term per (8 × 128) vreg stay below 0.5 —
    what is left broadcasts the input features and the labels across the
    lanes. With chromosomes on sublanes every term read three genes by a
    lane permute each (~3.2 a term)."""
    spec = GenomeSpec(MLPTopology(TOPOLOGIES["pendigits"]))
    G, n, i32 = spec.n_genes, 7696, jnp.int32
    shapes = ([((P, G), i32)] * 2 + [((P,), i32)] + [((G,), i32)] * 4
              + [((G,), jnp.uint32), ((3, 2), jnp.uint32), ((), jnp.float32),
                 ((n, 16), i32), ((n,), i32)])
    seen = set(mosaic_dump.glob("*-post-finalize-llo.txt"))
    _compile(lambda *a: pop_generation_kernel(*a, spec=spec),
             _args(one_chip, *shapes))
    dumps = set(mosaic_dump.glob("*-post-finalize-llo.txt")) - seen
    loops = [_row_loop(d.read_text()) for d in dumps]
    assert any(loops), "no row loop in the compiled megakernel"
    rows = row_group(spec)
    for loop in filter(None, loops):
        n_vperm = len(re.findall(r"llo\.vperm\b", loop))
        assert n_vperm / (130 * rows / 8) < 0.5, n_vperm

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
from repro.kernels.pop_mlp.kernel import pop_mlp_correct, pop_mlp_correct_mc
from repro.kernels.pop_variation import pop_variation_kernel

P = 256
K = 8
# (dataset, training-split rows): the 70 % splits of data/tabular.py
WIDTHS = [("cardio", 1488), ("pendigits", 7696)]
KERNELS = ["pop_mlp_correct", "pop_mlp_correct_mc", "pop_variation_kernel",
           "pop_generation_kernel", "pop_generation_kernel_mc"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


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


def test_vmapped_generation_compiles_for_v5e(one_chip):
    """One generation of the engine, vmapped over 4 lanes as ``run_suite``
    and ``SearchServer`` run it, with every Pallas backend forced on:
    traced per-lane row and sample bounds reach the kernels."""
    sizes = TOPOLOGIES["cardio"]
    n = 1488
    cfg = GAConfig(pop_size=P, batch_axis=engine.BATCH_AXIS,
                   backends=BackendPolicy(fitness="kernel", variation="kernel",
                                          generation="kernel"))
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


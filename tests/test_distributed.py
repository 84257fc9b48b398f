"""Multi-device semantics, run in subprocesses with 8 placeholder CPU devices
(the in-process test session must keep its single real device)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, timeout=600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow
def test_island_ga_runs_and_dominates_random():
    out = run_sub("""
        import numpy as np, jax
        from repro.core.islands import run_islands, IslandConfig
        from repro.core.trainer import GAConfig
        from repro.core.genome import MLPTopology
        from repro.data import load_dataset
        mesh = jax.make_mesh((8,), ("data",))
        ds = load_dataset("breast_cancer")
        cfg = IslandConfig(ga=GAConfig(), island_pop=16, migrate_every=3,
                           n_migrants=2, rounds=3)
        front, spec = run_islands(MLPTopology(ds.topology), ds.x_train,
                                  ds.y_train, mesh, cfg)
        obj = front["objectives"]
        assert obj.shape[1] == 2 and len(obj) >= 1
        print("BEST_ERR", obj[:, 0].min())
    """)
    assert "BEST_ERR" in out
    assert float(out.split("BEST_ERR")[1].strip()) < 0.5


@pytest.mark.slow
def test_sharded_cells_match_one_device_cell_by_cell():
    """run_suite and run_grid over a 4-device ``jax.make_mesh`` (Explicit
    axes by default) equal the one-device runs, peeled cell by cell."""
    out = run_sub("""
        import numpy as np, jax
        from repro.core import GAConfig, engine, sweep
        from repro.core.genome import MLPTopology
        from repro.data import load_dataset
        cfg = GAConfig(pop_size=8, generations=2)
        probs = [engine.Problem.from_data(MLPTopology(d.topology),
                                          d.x_train[:120], d.y_train[:120],
                                          cfg)
                 for d in (load_dataset("breast_cancer"),
                           load_dataset("redwine"))]
        mesh = jax.make_mesh((4,), ("data",))
        for run in (lambda **kw: sweep.run_suite(probs, [0, 1, 2, 3], **kw),
                    lambda **kw: sweep.run_grid(probs[0], [0, 1, 2],
                                                **kw)):
            a, b = run(mesh=mesh), run()
            for i in range(a.n_cells):
                for k in ("objectives", "genomes"):
                    np.testing.assert_array_equal(a.front_at(i)[k],
                                                  b.front_at(i)[k])
                np.testing.assert_array_equal(
                    np.asarray(a.state_at(i).pop),
                    np.asarray(b.state_at(i).pop))
        print("CELLS_OK")
    """)
    assert "CELLS_OK" in out


@pytest.mark.slow
def test_sharded_moe_matches_local():
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs.base import ArchConfig, MoEConfig
        from repro.models.moe import moe_ffn, moe_decl
        from repro.models.params import materialize
        cfg = ArchConfig(name="t", family="moe", n_layers=1, d_model=16,
                         n_heads=2, n_kv_heads=2, d_ff=0, vocab_size=64,
                         head_dim=8,
                         moe=MoEConfig(n_experts=4, top_k=2, d_ff=32,
                                       capacity_factor=8.0))
        p = materialize(moe_decl(cfg), jax.random.PRNGKey(0))
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 16), jnp.float32)
        y_local, aux_local = moe_ffn(cfg, p, x, mesh=None)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        y_shard, aux_shard = jax.jit(
            lambda p, x: moe_ffn(cfg, p, x, mesh=mesh))(p, x)
        np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_shard),
                                   rtol=2e-3, atol=2e-3)
        print("MOE_OK", float(abs(aux_local - aux_shard)))
    """)
    assert "MOE_OK" in out


@pytest.mark.slow
def test_pod_compressed_grads_close_to_exact():
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.runtime.compression import pod_compressed_grads, Int8Compressor
        mesh = jax.make_mesh((8,), ("pod",))   # pod-axis view (see docstring)
        params = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 8))}
        batch = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
        def loss_fn(p, b):
            return jnp.mean((b @ p["w"]) ** 2), ()
        errors = Int8Compressor.init_error(params)
        g, (loss, _), new_err = pod_compressed_grads(
            loss_fn, params, batch, mesh, errors)
        exact = jax.grad(lambda p: loss_fn(p, batch)[0])(params)
        rel = (np.abs(np.asarray(g["w"]) - np.asarray(exact["w"])).max()
               / np.abs(np.asarray(exact["w"])).max())
        print("REL_ERR", rel)
        assert rel < 0.02
    """)
    assert "REL_ERR" in out


@pytest.mark.slow
def test_elastic_reshard_roundtrip(tmp_path):
    out = run_sub(f"""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import save_checkpoint
        from repro.runtime.elastic import reshard_checkpoint
        mesh1 = jax.make_mesh((4, 2), ("data", "model"))
        state = {{"w": jax.device_put(
            jnp.arange(64.0).reshape(8, 8),
            NamedSharding(mesh1, P("data", "model")))}}
        save_checkpoint(r"{tmp_path}", 3, state)
        # restore onto a DIFFERENT mesh shape (elastic scale-down)
        mesh2 = jax.make_mesh((2, 4), ("data", "model"))
        out = reshard_checkpoint(r"{tmp_path}", 3, state,
                                 mesh2, {{"w": P("data", "model")}})
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.arange(64.0).reshape(8, 8))
        print("RESHARD_OK", out["w"].sharding.mesh.shape)
    """)
    assert "RESHARD_OK" in out


@pytest.mark.slow
def test_cost_analysis_per_device_convention():
    """The roofline convention check: 4-way sharding ≈ 1/4 per-device flops."""
    out = run_sub("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = jax.make_mesh((4,), ("model",))
        x = jnp.ones((512, 512), jnp.float32)
        def f(a, b):
            return a @ b
        c1 = jax.jit(f).lower(x, x).compile().cost_analysis()
        sh = NamedSharding(mesh, P(None, "model"))
        c4 = jax.jit(f, in_shardings=(None, sh),
                     out_shardings=sh).lower(x, x).compile().cost_analysis()
        f1 = (c1[0] if isinstance(c1, (list, tuple)) else c1)["flops"]
        f4 = (c4[0] if isinstance(c4, (list, tuple)) else c4)["flops"]
        print("RATIO", f1 / f4)
        assert 3.0 < f1 / f4 < 5.0
    """)
    assert "RATIO" in out

"""Start-up plumbing, each case in a fresh interpreter: the persistent
compilation cache lands where ``repro.compile_cache`` says, and the package
imports in either order."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, **env_kw) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu",
               **env_kw)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_cache_goes_to_the_env_directory(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper sets nothing and a
    compiled program is written there."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.api import enable_compile_cache
        print("DIR", enable_compile_cache())
        print("CFG", jax.config.jax_compilation_cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
    """, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert f"DIR {tmp_path}" in out
    assert f"CFG {tmp_path}" in out
    assert any(tmp_path.iterdir()), "no cache entry was written"


def test_cache_defaults_to_a_fixed_checkout_directory():
    out = run_py("""
        import jax
        from repro.compile_cache import enable_compile_cache
        print("DIR", enable_compile_cache())
        print("CFG", jax.config.jax_compilation_cache_dir)
    """)
    want = os.path.join(ROOT, ".jax_cache")
    assert f"DIR {want}" in out
    assert f"CFG {want}" in out


@pytest.mark.parametrize("first", ["repro.kernels", "repro.core",
                                   "repro.serve", "repro.api"])
def test_package_imports_in_any_order(first):
    run_py(f"""
        import {first}
        import repro.kernels, repro.core, repro.serve, repro.api
        from repro.kernels import BackendPolicy
        assert repro.core.GAConfig().backends == BackendPolicy()
    """)

"""Pallas kernels vs pure-jnp oracles, interpret mode, shape/dtype sweeps."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.genome import MLPTopology, GenomeSpec
from repro.kernels.pow2_matmul import (pow2_matmul, pow2_matmul_ref,
                                       pack_weights, pow2_linear)
from repro.kernels.pop_mlp import pop_mlp_correct, pop_mlp_correct_ref
from repro.kernels.ssd_scan import ssd_state_scan, ssd_state_scan_ref


@pytest.mark.parametrize("M,K,N,bm,bn,bk", [
    (128, 128, 128, 128, 128, 128),
    (256, 384, 512, 128, 256, 128),
    (512, 256, 256, 128, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pow2_matmul_sweep(M, K, N, bm, bn, bk, dtype, key):
    x = jax.random.normal(key, (M, K), dtype)
    w = jax.random.normal(jax.random.PRNGKey(7), (K, N)) * 0.1
    wp = pack_weights(w)
    ref = pow2_matmul_ref(x, wp)
    out = pow2_matmul(x, wp, bm=bm, bn=bn, bk=bk, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_pow2_matmul_zero_weights(key):
    x = jax.random.normal(key, (128, 128), jnp.float32)
    w = jnp.zeros((128, 128))
    out = pow2_matmul(x, pack_weights(w), interpret=True)
    assert float(jnp.max(jnp.abs(out))) == 0.0


def test_pow2_linear_batched(key):
    x = jax.random.normal(key, (2, 4, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (128, 256)) * 0.1
    wp = pack_weights(w)
    out = pow2_linear(x, wp, use_kernel=False)
    assert out.shape == (2, 4, 256)


@pytest.mark.parametrize("sizes", [(10, 3, 2), (21, 3, 3), (16, 5, 10)])
@pytest.mark.parametrize("S", [100, 256, 300])
def test_pop_mlp_sweep(sizes, S, key):
    spec = GenomeSpec(MLPTopology(sizes))
    pop = spec.random(key, 8)
    x = jax.random.randint(jax.random.PRNGKey(1), (S, sizes[0]), 0, 16)
    y = jax.random.randint(jax.random.PRNGKey(2), (S,), 0, sizes[-1])
    ref = pop_mlp_correct_ref(pop, x, y, spec=spec)
    out = pop_mlp_correct(pop, x, y, spec=spec, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("b,nc,H,P,N,bh", [
    (1, 4, 8, 8, 16, 8),
    (2, 7, 16, 16, 32, 8),
    (3, 2, 32, 8, 8, 16),
])
def test_ssd_scan_sweep(b, nc, H, P, N, bh, key):
    sc = jax.random.normal(key, (b, nc, H, P, N), jnp.float32)
    dec = jax.random.uniform(jax.random.PRNGKey(5), (b, nc, H))
    ref = ssd_state_scan_ref(sc, dec)
    out = ssd_state_scan(sc, dec, bh=bh, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_ssd_scan_first_chunk_zero(key):
    sc = jax.random.normal(key, (1, 3, 8, 8, 8), jnp.float32)
    dec = jnp.ones((1, 3, 8))
    out = ssd_state_scan(sc, dec, interpret=True)
    assert float(jnp.max(jnp.abs(out[:, 0]))) == 0.0


@pytest.mark.parametrize("BH,S,D,Dv,bq,bk", [
    (4, 128, 32, 32, 32, 32),
    (2, 256, 64, 32, 64, 64),
    (8, 64, 16, 16, 32, 16),     # block_q > block_k (position-based skip)
    (2, 128, 32, 16, 16, 32),    # block_q < block_k + Dv ≠ D
])
def test_flash_attention_sweep(BH, S, D, Dv, bq, bk, key):
    from repro.kernels.flash_attention import (flash_attention,
                                               flash_attention_ref)

    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (BH, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (BH, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (BH, S, Dv), jnp.float32)
    ref = flash_attention_ref(q, k, v)
    out = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)

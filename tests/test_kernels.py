"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU).

Per assignment: for each kernel, sweep shapes/dtypes and assert_allclose
against the ref.py oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as FA
from repro.kernels import matmul as MM
from repro.kernels import ref as R
from repro.kernels import ssd as SSD

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("M,K,N", [(128, 128, 128), (256, 512, 128),
                                   (128, 256, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["none", "gelu", "relu2"])
def test_matmul_sweep(M, K, N, dtype, act):
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (M, K), dtype)
    w = (jax.random.normal(k2, (K, N), jnp.float32) / np.sqrt(K)).astype(dtype)
    b = jax.random.normal(k3, (N,), dtype)
    y = MM.matmul(x, w, b, act=act, block_m=128, block_n=128, block_k=128,
                  interpret=True)
    ref = R.matmul_ref(x, w, b, act=act)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_gated_matmul():
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (256, 256), jnp.float32)
    w1 = jax.random.normal(k2, (256, 128), jnp.float32) / 16
    w1b = jax.random.normal(k3, (256, 128), jnp.float32) / 16
    y = MM.gated_matmul(x, w1, w1b, act="silu", block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(R.gated_matmul_ref(x, w1, w1b)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,nh,nkv,S,dh", [(1, 4, 4, 128, 64),
                                           (2, 4, 2, 256, 64),
                                           (1, 8, 1, 256, 32)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, nh, nkv, S, dh, causal, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (B, nh, S, dh), dtype)
    k = jax.random.normal(k2, (B, nkv, S, dh), dtype)
    v = jax.random.normal(k3, (B, nkv, S, dh), dtype)
    o = FA.flash_attention(q, k, v, causal=causal, interpret=True)
    ref = R.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32),
                               **(_tol(dtype) if dtype == jnp.bfloat16
                                  else dict(rtol=2e-3, atol=2e-3)))


def _heads_major(x):
    return x.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("S", [256, 512])
@pytest.mark.parametrize("nh,nkv", [(16, 8), (4, 1)])
@pytest.mark.parametrize("part", ["fwd", "grad"])
def test_flash_matches_sdpa(nh, nkv, S, part):
    """The model's kernel path against the chunked jnp ``_sdpa`` it replaces
    on a training step: causal GQA in bf16, K/V not repeated for the kernel;
    the output, or the gradients into q, k and v."""
    from repro.models import attention as ATT
    B, dh = 2, 128
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, S, nh, dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, nkv, dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, nkv, dh), jnp.bfloat16)
    do = jax.random.normal(ks[3], (B, S, nh, dh), jnp.float32)

    def flash(q, k, v):
        return _heads_major(FA.flash_attention(
            _heads_major(q), _heads_major(k), _heads_major(v),
            interpret=True))

    def sdpa(q, k, v):
        g = nh // nkv
        return ATT._sdpa(q, jnp.repeat(k, g, 2), jnp.repeat(v, g, 2),
                         causal=True, q_offset=0, q_block=128)

    def run(att):
        if part == "fwd":
            return [att(q, k, v)]
        return jax.grad(lambda *a: jnp.sum(att(*a).astype(jnp.float32) * do),
                        argnums=(0, 1, 2))(q, k, v)

    for got, want in zip(run(flash), run(sdpa)):
        assert got.dtype == want.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **_tol(jnp.bfloat16))


@pytest.mark.parametrize("b,S,nh,dh,g,ds,chunk", [
    (1, 64, 2, 16, 1, 8, 16), (2, 128, 4, 32, 2, 16, 32),
    (1, 256, 2, 64, 1, 64, 64)])
def test_ssd_kernel_sweep(b, S, nh, dh, g, ds, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, S, nh, dh), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, nh), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,), jnp.float32) * 0.5)
    B = jax.random.normal(ks[3], (b, S, g, ds), jnp.float32)
    C = jax.random.normal(ks[4], (b, S, g, ds), jnp.float32)
    y = SSD.ssd(x, dt, A, B, C, chunk=chunk, interpret=True)
    ref = R.ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=5e-4, atol=5e-4)


def test_ssd_chunked_jnp_matches_sequential():
    """The model's chunked-scan path == sequential recurrence oracle."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(KEY, 5)
    b, S, nh, dh, g, ds = 2, 96, 4, 16, 2, 8
    x = jax.random.normal(ks[0], (b, S, nh, dh), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, nh), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,), jnp.float32) * 0.5)
    B = jax.random.normal(ks[3], (b, S, g, ds), jnp.float32)
    C = jax.random.normal(ks[4], (b, S, g, ds), jnp.float32)
    y, fin = ssd_chunked(x, dt, A, B, C, chunk=32)
    ref = R.ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=5e-4,
                               atol=5e-4)
    assert fin.shape == (b, nh, dh, ds)


def test_ssd_decode_matches_chunked():
    """Streaming decode over the same tokens == chunked forward."""
    from repro.models.ssm import ssd_chunked, ssd_decode_step
    ks = jax.random.split(KEY, 5)
    b, S, nh, dh, g, ds = 1, 16, 2, 8, 1, 4
    x = jax.random.normal(ks[0], (b, S, nh, dh), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, nh), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,), jnp.float32) * 0.5)
    B = jax.random.normal(ks[3], (b, S, g, ds), jnp.float32)
    C = jax.random.normal(ks[4], (b, S, g, ds), jnp.float32)
    y_ref, _ = ssd_chunked(x, dt, A, B, C, chunk=8)
    h = jnp.zeros((b, nh, dh, ds), jnp.float32)
    ys = []
    for t in range(S):
        y, h = ssd_decode_step(h, x[:, t], dt[:, t], A, B[:, t], C[:, t])
        ys.append(y)
    y_dec = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_ref),
                               rtol=5e-4, atol=5e-4)

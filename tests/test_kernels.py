"""Per-kernel Pallas (interpret=True) vs pure-jnp oracle, swept over
shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.iou import iou_matrix
from repro.kernels.kmeans_assign import kmeans_assign
from repro.kernels.tile_moments import tile_moments


def _key(i=0):
    return jax.random.PRNGKey(i)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,hq,hkv,d", [
    (1, 128, 1, 1, 128),
    (2, 256, 4, 2, 128),
    (1, 384, 8, 8, 128),
    (2, 128, 6, 2, 256),
])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_ref(b, s, hq, hkv, d, causal):
    q = jax.random.normal(_key(0), (b, s, hq, d), jnp.float32)
    k = jax.random.normal(_key(1), (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(_key(2), (b, s, hkv, d), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    exp = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    q = jax.random.normal(_key(0), (1, 128, 2, 128), jnp.bfloat16)
    k = jax.random.normal(_key(1), (1, 128, 2, 128), jnp.bfloat16)
    v = jax.random.normal(_key(2), (1, 128, 2, 128), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    exp = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(np.float32), exp.astype(np.float32),
                               atol=3e-2, rtol=3e-2)


def test_flash_attention_block_shapes():
    """Different BlockSpec tilings must agree."""
    q = jax.random.normal(_key(0), (1, 512, 2, 128), jnp.float32)
    k = jax.random.normal(_key(1), (1, 512, 1, 128), jnp.float32)
    v = jax.random.normal(_key(2), (1, 512, 1, 128), jnp.float32)
    a = flash_attention(q, k, v, causal=True, bq=128, bk=128, interpret=True)
    b2 = flash_attention(q, k, v, causal=True, bq=256, bk=128, interpret=True)
    c = flash_attention(q, k, v, causal=True, bq=128, bk=256, interpret=True)
    np.testing.assert_allclose(a, b2, atol=1e-5)
    np.testing.assert_allclose(a, c, atol=1e-5)


# ---------------------------------------------------------------------------
# kmeans assignment
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,k", [(64, 9, 4), (1000, 9, 16), (513, 32, 7),
                                   (256, 128, 64)])
def test_kmeans_assign(n, d, k):
    x = jax.random.normal(_key(0), (n, d), jnp.float32)
    c = jax.random.normal(_key(1), (k, d), jnp.float32)
    a1, d1 = kmeans_assign(x, c, interpret=True)
    a2, d2 = ref.kmeans_assign(x, c)
    assert bool(jnp.all(a1 == a2))
    np.testing.assert_allclose(d1, d2, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# tile moments
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,h,w,c", [(16, 32, 32, 3), (100, 16, 16, 3),
                                     (7, 64, 64, 1), (130, 8, 8, 4),
                                     (5, 416, 416, 3)])
def test_tile_moments(n, h, w, c):
    t = jax.random.uniform(_key(0), (n, h, w, c), jnp.float32)
    m1 = tile_moments(t, interpret=True)
    m2 = ref.tile_moments(t)
    np.testing.assert_allclose(m1, m2, atol=1e-4, rtol=1e-4)


def test_tile_moments_invariance():
    """Color moments are invariant to rotation/flip (the dedup feature
    contract from paper §III-C)."""
    t = jax.random.uniform(_key(0), (4, 32, 32, 3), jnp.float32)
    m = ref.tile_moments(t)
    m_rot = ref.tile_moments(jnp.rot90(t, axes=(1, 2)))
    m_flip = ref.tile_moments(t[:, ::-1])
    np.testing.assert_allclose(m, m_rot, atol=1e-5)
    np.testing.assert_allclose(m, m_flip, atol=1e-5)


# ---------------------------------------------------------------------------
# IoU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m", [(10, 10), (128, 64), (200, 300), (1, 5)])
def test_iou_matrix(n, m, rng):
    def boxes(k, seed):
        b = jax.random.uniform(_key(seed), (k, 4), jnp.float32)
        return b.at[:, 2:].set(b[:, :2] + jnp.abs(b[:, 2:]) + 0.01)
    a = boxes(n, 0)
    b = boxes(m, 1)
    i1 = iou_matrix(a, b, interpret=True)
    i2 = ref.iou_matrix(a, b)
    np.testing.assert_allclose(i1, i2, atol=1e-5)
    assert float(jnp.max(i1)) <= 1.0 + 1e-6
    assert float(jnp.min(i1)) >= 0.0


def test_iou_self_diagonal():
    b = jnp.array([[0., 0., 2., 2.], [1., 1., 4., 5.]])
    i = iou_matrix(b, b, interpret=True)
    np.testing.assert_allclose(jnp.diag(i), jnp.ones(2), atol=1e-6)


# ---------------------------------------------------------------------------
# int8 matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (100, 200, 150),
                                   (256, 512, 384), (1, 64, 1)])
def test_int8_matmul(m, k, n):
    xq = jax.random.randint(_key(0), (m, k), -127, 128, jnp.int8)
    wq = jax.random.randint(_key(1), (k, n), -127, 128, jnp.int8)
    xs = jax.random.uniform(_key(2), (m,)) + 0.1
    ws = jax.random.uniform(_key(3), (n,)) + 0.1
    r1 = int8_matmul(xq, wq, xs, ws, interpret=True)
    r2 = ref.int8_matmul(xq, wq, xs, ws)
    np.testing.assert_allclose(r1, r2, rtol=1e-6)


def test_quantize_roundtrip_accuracy():
    from repro.kernels.ops import quantize_int8
    x = jax.random.normal(_key(0), (64, 256), jnp.float32)
    w = jax.random.normal(_key(1), (256, 128), jnp.float32)
    xq, xs = quantize_int8(x, axis=1)
    wq, ws = quantize_int8(w, axis=0)
    approx = ref.int8_matmul(xq, wq, xs, ws)
    exact = x @ w
    rel = float(jnp.linalg.norm(approx - exact) / jnp.linalg.norm(exact))
    assert rel < 0.02, rel

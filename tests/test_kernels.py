"""Per-kernel correctness: interpret-mode Pallas vs pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, paged_attention, streaming_gemm
from repro.kernels import ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("m,n,k", [(64, 128, 128), (100, 200, 300),
                                   (256, 256, 512), (33, 257, 129)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gemm_matches_ref(m, n, k, dtype):
    a = jax.random.normal(KEY, (m, k), jnp.dtype(dtype))
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.dtype(dtype))
    out = streaming_gemm(a, b, bm=32, bn=128, bk=128, interpret=True)
    want = ref.gemm_ref(a, b)
    tol = 2e-2 if dtype == "bfloat16" else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_gemm_int8_exact():
    a = jax.random.randint(KEY, (64, 256), -127, 127, jnp.int8)
    b = jax.random.randint(jax.random.PRNGKey(1), (256, 128), -127, 127,
                           jnp.int8)
    out = streaming_gemm(a, b, bm=32, bn=128, bk=128, interpret=True)
    want = ref.gemm_ref(a, b, jnp.int8)
    np.testing.assert_array_equal(np.asarray(out, np.int32),
                                  np.asarray(want, np.int32))


@pytest.mark.parametrize("tq,tk,h,kh,d", [(128, 128, 4, 2, 32),
                                          (64, 256, 8, 8, 64),
                                          (96, 96, 6, 1, 16),
                                          (64, 64, 14, 2, 64),
                                          (32, 32, 4, 4, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_ref(tq, tk, h, kh, d, causal):
    if not causal and tq != tk:
        pytest.skip("non-causal requires equal block-divisible kv")
    q = jax.random.normal(KEY, (2, tq, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, tk, kh, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, tk, kh, d), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, bq=32, bk=32,
                          interpret=True)
    np.testing.assert_allclose(out, ref.gqa_flash_ref(q, k, v, causal),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("b,h,kh,d,page,mp", [(3, 8, 2, 32, 16, 4),
                                              (2, 4, 4, 64, 8, 6),
                                              (1, 16, 1, 16, 32, 2)])
def test_paged_matches_ref(b, h, kh, d, page, mp):
    P = b * mp + 4
    q = jax.random.normal(KEY, (b, h, d), jnp.float32)
    kp = jax.random.normal(jax.random.PRNGKey(1), (P, page, kh, d),
                           jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(2), (P, page, kh, d),
                           jnp.float32)
    table = jax.random.permutation(jax.random.PRNGKey(3), P)[:b * mp] \
        .reshape(b, mp).astype(jnp.int32)
    lens = jnp.asarray(
        np.random.default_rng(0).integers(1, page * mp, size=(b,)),
        jnp.int32)
    out = paged_attention(q, kp, vp, table, lens, interpret=True)
    want = ref.paged_ref(q, kp, vp, table, lens)
    np.testing.assert_allclose(out, want, rtol=3e-5, atol=3e-5)


def test_paged_matches_contiguous_decode():
    """Paged kernel == the model's contiguous decode attention."""
    from repro.models.layers import decode_attention
    b, h, kh, d, page, mp = 2, 8, 2, 32, 16, 4
    P = b * mp
    q = jax.random.normal(KEY, (b, h, d), jnp.float32)
    kp = jax.random.normal(jax.random.PRNGKey(1), (P, page, kh, d), jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(2), (P, page, kh, d), jnp.float32)
    table = jnp.arange(P, dtype=jnp.int32).reshape(b, mp)
    lens = jnp.asarray([17, 61], jnp.int32)
    paged = paged_attention(q, kp, vp, table, lens, interpret=True)
    k = kp[table].reshape(b, mp * page, kh, d)
    v = vp[table].reshape(b, mp * page, kh, d)
    contig = decode_attention(q, k, v, lens)
    np.testing.assert_allclose(paged, contig, rtol=3e-5, atol=3e-5)

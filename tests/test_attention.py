"""ops/attention.py: the Pallas decode-attention kernel must match the naive
masked softmax path bit-for-bit in f32 (kernel run in interpret mode on CPU)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import export as jax_export

from hyperscalees_t2i_tpu.ops.attention import (
    _naive_masked_attention,
    _pallas_attention,
    decode_attention,
)


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


@pytest.mark.parametrize(
    "B,nq,L,H,dh,kv_len",
    [
        (2, 4, 16, 2, 8, 7),  # decode: small query block, partial cache
        (1, 16, 16, 1, 8, 16),  # full-length prefix
        (2, 5, 12, 3, 4, 9),  # non-power-of-two everything (q padding path)
    ],
)
def test_pallas_matches_naive_prefix(B, nq, L, H, dh, kv_len):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(kq, (B, nq, H, dh))
    k = _rand(kk, (B, L, H, dh))
    v = _rand(kv, (B, L, H, dh))
    scale = 1.0 / math.sqrt(dh)

    ref = _naive_masked_attention(q, k, v, kv_len, None, scale)
    got = _pallas_attention(q, k, v, kv_len, None, scale, block_q=4, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_pallas_matches_naive_with_key_mask():
    """Cross-attention case: per-batch padded text mask."""
    B, nq, L, H, dh = 2, 3, 10, 2, 8
    kq, kk, kv, km = jax.random.split(jax.random.PRNGKey(1), 4)
    q = _rand(kq, (B, nq, H, dh))
    k = _rand(kk, (B, L, H, dh))
    v = _rand(kv, (B, L, H, dh))
    lens = jnp.asarray([4, 10])
    mask = jnp.arange(L)[None, :] < lens[:, None]
    scale = 1.0 / math.sqrt(dh)

    ref = _naive_masked_attention(q, k, v, None, mask, scale)
    got = _pallas_attention(q, k, v, L, mask, scale, block_q=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_decode_attention_dispatch_and_vmap():
    """The public entry point works under jit+vmap (the population axis)."""
    B, nq, L, H, dh = 2, 4, 8, 2, 4
    pop = 3
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(ks[0], (pop, B, nq, H, dh))
    k = _rand(ks[1], (pop, B, L, H, dh))
    v = _rand(ks[2], (pop, B, L, H, dh))

    f = jax.jit(jax.vmap(lambda q, k, v: decode_attention(q, k, v, kv_len=6)))
    out = f(q, k, v)
    assert out.shape == q.shape
    ref = jnp.stack(
        [
            _naive_masked_attention(q[i], k[i], v[i], 6, None, 1.0 / math.sqrt(dh))
            for i in range(pop)
        ]
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("block_kv", [4, 8])
def test_online_softmax_multi_kv_block(block_kv):
    """KV-blocked path: running max/sum over several kv blocks must equal the
    single-pass softmax (the ADVICE-r2 VMEM fix — kv is a grid dimension)."""
    B, nq, L, H, dh = 2, 6, 20, 2, 8
    kq, kk, kv, km = jax.random.split(jax.random.PRNGKey(4), 4)
    q = _rand(kq, (B, nq, H, dh))
    k = _rand(kk, (B, L, H, dh))
    v = _rand(kv, (B, L, H, dh))
    lens = jnp.asarray([13, 20])
    mask = jnp.arange(L)[None, :] < lens[:, None]
    scale = 1.0 / math.sqrt(dh)

    ref = _naive_masked_attention(q, k, v, 17, mask, scale)
    got = _pallas_attention(
        q, k, v, 17, mask, scale, block_q=4, block_kv=block_kv, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_flash_kernel_lowers_for_tpu_at_infinity_1m_geometry():
    """The kernel must pass Mosaic TPU lowering at the Infinity "1M" preset's
    final-scale geometry (64²=4096 queries, ~10k-position KV cache, dh=128 —
    the shape that overflowed VMEM with the pre-flash kernel, ADVICE r2).
    jax.export runs the full TPU lowering pipeline without needing a chip."""
    B, nq, L, H, dh = 1, 4096, 9984, 2, 128
    q = jax.ShapeDtypeStruct((B, nq, H, dh), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((B, L, H, dh), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((B, L, H, dh), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: decode_attention(q, k, v, kv_len=9936, use_pallas=True))
    exp = jax_export.export(f, platforms=["tpu"])(q, k, v)
    assert len(exp.mlir_module_serialized) > 0


def test_masked_kernel_lowers_for_tpu():
    """The boolean text mask of Infinity's cross-attention must pass the TPU
    lowering: as a (1, block_kv) block out of a [B, L] bool array it did not
    (a block's second-to-last dim must be a multiple of 8 or the whole
    axis) — found by the first v5e compile of the kernel, PR 21."""
    B, nq, L, H, dh = 8, 256, 16, 16, 64
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    f = jax.jit(lambda q, k, v, m: decode_attention(q, k, v, kv_mask=m, use_pallas=True))
    exp = jax_export.export(f, platforms=["tpu"])(
        sds(B, nq, H, dh), sds(B, L, H, dh), sds(B, L, H, dh),
        jax.ShapeDtypeStruct((B, L), jnp.bool_),
    )
    assert len(exp.mlir_module_serialized) > 0


def test_masked_prefix_ignores_cache_garbage():
    """Positions ≥ kv_len must not affect the output (the AR cache contract)."""
    B, nq, L, H, dh = 1, 2, 8, 1, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = _rand(ks[0], (B, nq, H, dh))
    k = _rand(ks[1], (B, L, H, dh))
    v = _rand(ks[2], (B, L, H, dh))
    garbage = 1e6 * _rand(ks[3], (B, L - 5, H, dh))
    k2 = k.at[:, 5:].set(garbage)
    v2 = v.at[:, 5:].set(garbage)

    a = decode_attention(q, k, v, kv_len=5, use_pallas=False)
    b = decode_attention(q, k2, v2, kv_len=5, use_pallas=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))

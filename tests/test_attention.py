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


# ---------------------------------------------------------------------------
# PR 36: a grid step holds all heads of a sequence, in the layout the models
# write. The grid up to PR 35 — one (sequence, head) a step — is the case
# "one head a step" of the same kernel (tools/kernel_check.one_head_a_step),
# and what a step holds changes no head's arithmetic: equal bit for bit.
# ---------------------------------------------------------------------------

from hyperscalees_t2i_tpu.ops import attention as attention_mod
from hyperscalees_t2i_tpu.tools.kernel_check import _VAR_SCALES, one_head_a_step


def _bf16(key, shape):
    return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)


def _bits(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("scale", range(len(_VAR_SCALES)))
def test_all_heads_a_step_equals_one_head_a_step_at_the_cell_s_calls(scale):
    """What the VAR cell calls at each of its ten scales — 8 CFG-doubled
    sequences x 16 heads x 64 under the member ``vmap`` of 4, the cache as
    long as its valid prefix: scale 8's last query block (169 = 128 + 41) and
    scale 9's last kv block (680 = 512 + 168) are filled up with zero rows."""
    nq, kv = _VAR_SCALES[scale]
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(scale), 3)
    q, k, v = _bf16(kq, (4, 8, nq, 16, 64)), _bf16(kk, (4, 8, kv, 16, 64)), _bf16(kv_, (4, 8, kv, 16, 64))
    attend = lambda q, k, v, m=None: _pallas_attention(q, k, v, kv, m, 1.0, interpret=True)
    folded = jax.jit(jax.vmap(attend))(q, k, v)
    per_head = jax.jit(jax.vmap(lambda *a: one_head_a_step(attend, *a)))(q, k, v)
    assert folded.shape == q.shape and folded.dtype == q.dtype
    np.testing.assert_array_equal(_bits(folded), _bits(per_head))
    ref = jax.vmap(lambda q, k, v: _naive_masked_attention(q, k, v, kv, None, 1.0))(q, k, v)
    np.testing.assert_allclose(_bits(folded), _bits(ref), atol=2.0 ** -7 * float(jnp.abs(ref).max()))


@pytest.mark.parametrize("scale", range(len(_VAR_SCALES)))
def test_all_heads_a_step_under_a_text_mask_and_a_nan_tail(scale):
    """The other callers' forms at the same ten (queries, prefix) pairs: a
    boolean ``kv_mask`` (Infinity's cross-attention), a cache longer than its
    valid prefix (``kv_len < L``: two kv blocks, the second wholly or partly
    past ``kv_len``), and NaN in every cache row past ``kv_len`` — a cache
    row nobody has written yet may hold anything. The logits' mask makes those
    rows' probability 0; only V's zeroed tail keeps 0 x NaN out of the sums."""
    nq, kv = _VAR_SCALES[scale]
    B, L, H, dh = 8, 700, 16, 64
    kq, kk, kv_, km = jax.random.split(jax.random.PRNGKey(100 + scale), 4)
    q, k, v = _bf16(kq, (B, nq, H, dh)), _bf16(kk, (B, L, H, dh)), _bf16(kv_, (B, L, H, dh))
    k, v = k.at[:, kv:].set(jnp.nan), v.at[:, kv:].set(jnp.nan)
    mask = jnp.arange(L)[None, :] < jax.random.randint(km, (B, 1), 1, kv + 1)
    attend = lambda q, k, v, m: _pallas_attention(q, k, v, kv, m, 1.0, interpret=True)
    folded = jax.jit(attend)(q, k, v, mask)
    per_head = jax.jit(lambda *a: one_head_a_step(attend, *a))(q, k, v, mask)
    assert bool(jnp.all(jnp.isfinite(folded.astype(jnp.float32))))
    np.testing.assert_array_equal(_bits(folded), _bits(per_head))
    ref = _naive_masked_attention(q, k, v, kv, mask, 1.0)
    np.testing.assert_allclose(_bits(folded), _bits(ref), atol=2.0 ** -7 * float(jnp.abs(ref).max()))


@pytest.mark.parametrize(
    "what, H, dh, block_q, block_kv, itemsize, heads",
    [
        ("VAR-d16 scale 9 / Infinity self-attention [8, 256, 16, 64]", 16, 64, 128, 512, 2, 16),
        ("VAR-d16 scale 0", 16, 64, 1, 1, 2, 16),
        ("Infinity cross-attention against 16 text tokens", 16, 64, 128, 16, 2, 16),
        ("Infinity 2b at the 1M preset: 16 heads of 128 lanes, ~10k keys", 16, 128, 128, 512, 2, 8),
        ("the 1M lowering test's 2 heads of 128 lanes", 2, 128, 128, 512, 2, 2),
        ("Infinity layer32: 20 heads of 104 lanes, no narrower group is whole tiles", 20, 104, 128, 512, 2, 20),
        ("float32 operands (the CPU tests') at 16 x 64", 16, 64, 128, 512, 4, 8),
        ("one head (the one-head-a-step fold)", 1, 64, 128, 512, 2, 1),
    ],
)
def test_heads_a_step_is_the_largest_group_that_fits(what, H, dh, block_q, block_kv, itemsize, heads):
    got = attention_mod._heads_per_block(H, dh, block_q, block_kv, itemsize)
    assert got == heads, what
    assert H % got == 0 and (got == H or got * dh % 128 == 0)
    fits = attention_mod._step_vmem_bytes(got, dh, block_q, block_kv, itemsize) <= attention_mod.VMEM_BUDGET_BYTES
    assert fits or not any(H % g == 0 and g * dh % 128 == 0 for g in range(1, H)), what


def _declared_call(q_shape, kv_shape, kv_len):
    """The keyword arguments ``_pallas_attention`` hands ``pallas_call`` at
    these bf16 shapes (traced, nothing run)."""
    from jax.experimental import pallas as pl

    seen = {}
    real = pl.pallas_call

    def spy(kernel, **kw):
        seen.update(kw)
        return real(kernel, **kw)

    q, kv = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in (q_shape, kv_shape))
    pl.pallas_call = spy
    try:
        jax.eval_shape(lambda q, k, v: _pallas_attention(q, k, v, kv_len, None, 1.0, interpret=True), q, kv, kv)
    finally:
        pl.pallas_call = real
    return seen


def test_the_step_s_vmem_is_what_the_call_declares():
    """``_step_vmem_bytes`` against the blocks and scratch the call really
    declares at VAR's last scale: q, out, K, V double-buffered and the three
    accumulators, ~8 MB of the 32 MiB the call may take."""
    seen = _declared_call((8, 256, 16, 64), (8, 680, 16, 64), 680)
    assert seen["grid"] == (8, 1, 2, 2) and seen["name"] == "decode_attention"
    assert seen["metadata"] == {"heads_per_block": "16"}
    tile = lambda shape, itemsize: itemsize * int(np.prod(shape[:-1])) * -(-shape[-1] // 128) * 128
    blocks = [s.block_shape for s in (*seen["in_specs"], seen["out_specs"])]
    assert blocks == [(1, 128, 1024), (1, 512, 1024), (1, 512, 1024), (1, 128, 1024)]
    declared = 2 * sum(tile(b, 2) for b in blocks) + sum(tile(s.shape, 4) for s in seen["scratch_shapes"])
    assert declared == attention_mod._step_vmem_bytes(16, 64, 128, 512, 2)
    assert 8e6 < declared <= attention_mod.VMEM_BUDGET_BYTES < seen["compiler_params"].vmem_limit_bytes


def test_a_head_group_axis_returns_where_the_heads_do_not_fit():
    """16 heads of 128 lanes: 8 a step, so the grid's head-group axis is 2 —
    the same kernel, equal to the XLA path and to one head a step."""
    B, nq, L, H, dh = 1, 24, 40, 16, 128
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = _bf16(kq, (B, nq, H, dh)), _bf16(kk, (B, L, H, dh)), _bf16(kv_, (B, L, H, dh))
    assert attention_mod._heads_per_block(H, dh, 128, 512, 2) == 8  # at the real block sizes
    attend = lambda q, k, v, m=None: _pallas_attention(q, k, v, 33, m, 0.25, block_q=16, block_kv=16, interpret=True)
    grouped = jax.jit(lambda q, k, v: _with_budget(attention_mod._step_vmem_bytes(8, dh, 16, 16, 2), attend, q, k, v))(q, k, v)
    np.testing.assert_array_equal(_bits(grouped), _bits(one_head_a_step(attend, q, k, v)))
    ref = _naive_masked_attention(q, k, v, 33, None, 0.25)
    np.testing.assert_allclose(_bits(grouped), _bits(ref), atol=2.0 ** -7 * float(jnp.abs(ref).max()))


def _with_budget(budget, fn, *args):
    """``fn(*args)`` traced with the heads' VMEM budget at ``budget`` bytes:
    a toy block fits any real budget, so the grouped grid needs a toy one."""
    real, attention_mod.VMEM_BUDGET_BYTES = attention_mod.VMEM_BUDGET_BYTES, budget
    try:
        return fn(*args)
    finally:
        attention_mod.VMEM_BUDGET_BYTES = real


def test_grouped_grid_has_the_head_group_axis():
    seen = _declared_call((1, 4096, 16, 128), (1, 9936, 16, 128), 9936)
    assert seen["grid"] == (1, 2, 32, 20) and seen["metadata"] == {"heads_per_block": "8"}
    assert seen["out_specs"].block_shape == (1, 128, 1024)


def test_toy_var_step_lowered_for_tpu_says_heads_per_block(monkeypatch):
    """``programs.jsonl``'s ``pallas_heads_per_block``: the toy VAR generate,
    lowered for a TPU with the kernel selected, says for every
    ``decode_attention`` site (one a scale: the layers are a scan) how many
    heads share a grid step."""
    from hyperscalees_t2i_tpu.models import var as var_mod
    from hyperscalees_t2i_tpu.obs.xla_cost import stablehlo_stats
    from tests.test_var import tiny_cfg

    monkeypatch.setattr(attention_mod, "should_use_pallas", lambda: True)
    cfg = tiny_cfg()
    params = var_mod.init_var(jax.random.PRNGKey(0), cfg)
    gen = lambda params, labels, key: var_mod.generate(params, cfg, labels, key, decode=False)
    lowered = jax.jit(gen).trace(params, jnp.asarray([1, 2], jnp.int32), jax.random.PRNGKey(1)).lower(
        lowering_platforms=("tpu",))
    stats = stablehlo_stats(lowered)
    assert stats["pallas_kernels"] == {"decode_attention": len(cfg.patch_nums)}
    assert stats["pallas_heads_per_block"] == {"decode_attention": {str(cfg.n_heads): len(cfg.patch_nums)}}
    assert stats["pallas_members_per_block"] == {}

"""Pallas TPU attention kernels for the AR-decode hot path.

The reference reaches flash-attn/xformers CUDA kernels through a fallback
chain (``/root/reference/VAR_models/basic_var.py:15-31``). The TPU-native
answer: a Pallas kernel that computes each (batch, head, query-block) tile's
logits entirely in VMEM — the naive XLA path materializes the full
``[2B, H, n, L]`` f32 logit tensor in HBM against a preallocated max-length
KV cache at every scale, which is what made the Infinity "1M" preset
(final scale 64² = 4096 queries) unaffordable in round 1.

Shapes follow the models' cache layout: queries ``[B, nq, H, dh]``, KV cache
``[B, L, H, dh]`` with only the first ``kv_len`` positions valid (static per
scale step). An optional boolean ``kv_mask [B, L]`` handles padded text for
cross-attention (Infinity models/infinity.py:182-194).

On non-TPU backends (CPU tests) the same math runs as a fused XLA path —
kernel (interpret mode) and XLA path are asserted equal in
tests/test_attention.py; on the chip ``tools/kernel_check.py`` compiles,
runs and compares the kernel at the VAR cache geometry.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _naive_masked_attention(
    q: jax.Array,  # [B, nq, H, dh]
    k: jax.Array,  # [B, L, H, dh]
    v: jax.Array,  # [B, L, H, dh]
    kv_len: Optional[int],
    kv_mask: Optional[jax.Array],
    sm_scale: float,
) -> jax.Array:
    """Reference path: same math, XLA-fused, f32 softmax."""
    L = k.shape[1]
    if kv_len is not None and kv_len < L:
        # static slice keeps the fallback's HBM footprint proportional to the
        # *valid* prefix, matching the models' previous behavior
        k = jax.lax.dynamic_slice_in_dim(k, 0, kv_len, axis=1)
        v = jax.lax.dynamic_slice_in_dim(v, 0, kv_len, axis=1)
        if kv_mask is not None:
            kv_mask = jax.lax.dynamic_slice_in_dim(kv_mask, 0, kv_len, axis=1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * sm_scale
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _flash_kernel(
    q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr,
    *, sm_scale: float, kv_len: int, block_kv: int,
):
    """One (batch, head, q-block, kv-block) tile with online softmax.

    VMEM holds only the [block_q, block_kv] logit tile plus running
    (max, sum, weighted-V) accumulators — the KV axis is a *grid* dimension,
    so the kernel's footprint is independent of the cache length (the old
    kernel streamed the full K/V and a [block_q, L] logit tile into VMEM,
    which at the Infinity 1M preset (~10k kv, dh 128) was at/over the ~16MB
    VMEM budget).
    """
    from jax.experimental import pallas as pl

    kv_i = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(kv_i == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32)  # [bq, dh]
    k = k_ref[0, 0].astype(jnp.float32)  # [bkv, dh]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # [bq, bkv]
    pos = kv_i * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = pos < kv_len
    if mask_ref is not None:
        valid = jnp.logical_and(valid, mask_ref[0] != 0)  # [1, bkv] over rows
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...][:, :1]  # [bq, 1]
    l_prev = l_scr[...][:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)  # rescale of previous blocks' sums
    p = jnp.exp(s - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kv_i == n_kv - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _pallas_attention(
    q: jax.Array,  # [B, nq, H, dh]
    k: jax.Array,  # [B, L, H, dh]
    v: jax.Array,
    kv_len: int,
    kv_mask: Optional[jax.Array],
    sm_scale: float,
    block_q: int = 128,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    from jax.experimental import pallas as pl

    B, nq, H, dh = q.shape
    L = k.shape[1]
    block_q = min(block_q, nq)
    n_qblk = -(-nq // block_q)
    nq_pad = n_qblk * block_q
    block_kv = min(block_kv, L)
    n_kvblk = -(-L // block_kv)
    L_pad = n_kvblk * block_kv
    # head-major layout so each grid instance reads one contiguous tile
    qt = jnp.moveaxis(q, 2, 1)  # [B, H, nq, dh]
    if nq_pad != nq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, nq_pad - nq), (0, 0)))
    kt = jnp.moveaxis(k, 2, 1)  # [B, H, L, dh]
    vt = jnp.moveaxis(v, 2, 1)
    if L_pad != L:
        # padded tail positions fall outside kv_len and are masked in-kernel
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, L_pad - L), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, L_pad - L), (0, 0)))

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, kv_len=kv_len, block_kv=block_kv
    )
    in_specs = [
        pl.BlockSpec((1, 1, block_q, dh), lambda b, h, qi, ki: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_kv, dh), lambda b, h, qi, ki: (b, h, ki, 0)),
        pl.BlockSpec((1, 1, block_kv, dh), lambda b, h, qi, ki: (b, h, ki, 0)),
    ]
    operands = [qt, kt, vt]
    if kv_mask is not None:
        if L_pad != kv_mask.shape[1]:
            kv_mask = jnp.pad(kv_mask, ((0, 0), (0, L_pad - kv_mask.shape[1])))
        # [B, 1, L] int32: Mosaic takes neither a one-row block out of a
        # [B, L] array (a block's second-to-last dim must be a multiple of 8
        # or the whole axis) nor a boolean memref
        in_specs.append(pl.BlockSpec((1, 1, block_kv), lambda b, h, qi, ki: (b, 0, ki)))
        operands.append(kv_mask.astype(jnp.int32)[:, None, :])
    else:
        kernel = _wrap_no_mask(kernel)

    scratch_shapes = _vmem_scratch(block_q, dh)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, H, nq_pad, dh), q.dtype),
        # kv innermost: it is the sequential reduce dimension; the output
        # block index is constant in ki so Pallas keeps revisiting the same
        # tile until the accumulators are finalized.
        grid=(B, H, n_qblk, n_kvblk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, dh), lambda b, h, qi, ki: (b, h, qi, 0)),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        name="decode_attention",
    )(*operands)
    out = out[:, :, :nq, :]
    return jnp.moveaxis(out, 1, 2)  # [B, nq, H, dh]


def _vmem_scratch(block_q: int, dh: int):
    """Running-max / running-sum / output accumulators ([bq,128] lanes for the
    scalars, [bq,dh] for the weighted-V sum)."""
    from jax.experimental.pallas import tpu as pltpu

    lanes = 128
    return [
        pltpu.VMEM((block_q, lanes), jnp.float32),
        pltpu.VMEM((block_q, lanes), jnp.float32),
        pltpu.VMEM((block_q, dh), jnp.float32),
    ]


def _wrap_no_mask(kernel):
    def no_mask_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        return kernel(q_ref, k_ref, v_ref, None, o_ref, m_scr, l_scr, acc_scr)

    return no_mask_kernel


def should_use_pallas() -> bool:
    """The kernel-vs-XLA auto-select gate, shared by every caller: ON on a
    TPU backend, ``HSES_USE_PALLAS=0`` opts out (the tri-state convention of
    ``ops/pallas_gate``, so the ``pallas_env`` provenance stamp — "flash-" =
    opted out — always describes the path that ran). A Mosaic refusal fails
    the enclosing AR step's compile."""
    from .pallas_gate import backend_is_tpu, env_requested

    return env_requested("HSES_USE_PALLAS") is not False and backend_is_tpu()


def decode_attention(
    q: jax.Array,  # [B, nq, H, dh]
    k_cache: jax.Array,  # [B, L, H, dh]
    v_cache: jax.Array,
    kv_len: Optional[int] = None,
    kv_mask: Optional[jax.Array] = None,
    sm_scale: Optional[float] = None,
    use_pallas: Optional[bool] = None,
) -> jax.Array:
    """Masked attention of a query block against a (partially filled) KV cache.

    ``kv_len`` (static Python int) marks the valid cache prefix — the AR
    models' per-scale write position. ``use_pallas=None`` auto-selects the
    Pallas kernel on TPU and the fused XLA path elsewhere.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if use_pallas is None:
        use_pallas = should_use_pallas()
    if not use_pallas:
        return _naive_masked_attention(q, k_cache, v_cache, kv_len, kv_mask, sm_scale)
    L = k_cache.shape[1]
    if kv_len is not None and kv_len < L:
        # kv_len is static: slice the cache so each tile's FLOPs and VMEM
        # footprint scale with the *valid* prefix, not the max-length cache
        # (early AR scales see tens of positions, the cache holds thousands).
        k_cache = jax.lax.dynamic_slice_in_dim(k_cache, 0, kv_len, axis=1)
        v_cache = jax.lax.dynamic_slice_in_dim(v_cache, 0, kv_len, axis=1)
        if kv_mask is not None:
            kv_mask = jax.lax.dynamic_slice_in_dim(kv_mask, 0, kv_len, axis=1)
        L = kv_len
    return _pallas_attention(q, k_cache, v_cache, L, kv_mask, sm_scale)

"""Pallas TPU attention kernels for the AR-decode hot path.

The reference reaches flash-attn/xformers CUDA kernels through a fallback
chain (``/root/reference/VAR_models/basic_var.py:15-31``). The TPU-native
answer: a Pallas kernel that computes each (sequence, query-block) tile's
logits entirely in VMEM — the naive XLA path materializes the full
``[2B, H, n, L]`` f32 logit tensor in HBM against a preallocated max-length
KV cache at every scale, which is what made the Infinity "1M" preset
(final scale 64² = 4096 queries) unaffordable in round 1.

Shapes follow the models' cache layout: queries ``[B, nq, H, dh]``, KV cache
``[B, L, H, dh]`` with only the first ``kv_len`` positions valid (static per
scale step). An optional boolean ``kv_mask [B, L]`` handles padded text for
cross-attention (Infinity models/infinity.py:182-194).

The grid is ``(sequence, head group, query block, kv block)`` and a step
holds as many of a sequence's heads as fit a stated VMEM budget
(:func:`_heads_per_block`: all 16 at VAR's and Infinity's 16 x 64, so one
step a sequence, query block and kv block), read as lane slices of the
models' own ``[B, n, H·dh]`` rows: a step costs a v5e ~0.6 us before it has
computed anything, a full ``[128, 64] x [512, 64]`` head ~0.5 us more, and one
(sequence, head) a step was sixteen times the steps VAR needs — most of them
on tiles of a few rows (PERF.md §6, PR 36). The call says the number in its metadata
(``heads_per_block``; ``obs/xla_cost.stablehlo_stats`` reads it into
``programs.jsonl``).

On non-TPU backends (CPU tests) the same math runs as a fused XLA path —
kernel (interpret mode) and XLA path are asserted equal in
tests/test_attention.py, all heads a step and one head a step bit for bit;
on the chip ``tools/kernel_check.py`` compiles, runs and compares the kernel
with both at the VAR cache geometry.
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


# What a call may take of VMEM (a v5e core has 128 MiB; Mosaic's default scoped
# limit is 16 MiB, which 16 heads' double-buffered blocks would fill), and the
# part of it that :func:`_heads_per_block` lets blocks and scratch have. The
# rest is the head loop's temporaries: a head's float32 q, k, v, logit and
# probability tiles are ~1.2 MB at [128, 64] x [512, 64], and nothing promises
# that the 16 unrolled heads share them.
VMEM_LIMIT_BYTES = 32 * 2**20
VMEM_BUDGET_BYTES = 12 * 2**20
_LANES = 128


def _step_vmem_bytes(heads: int, dh: int, block_q: int, block_kv: int, itemsize: int) -> int:
    """Bytes of VMEM one grid step's blocks and scratch take with ``heads``
    heads in it: q, out, K and V blocks double-buffered, the three float32
    accumulators; a row is whole 128-lane tiles."""
    lanes = lambda n: -(-n // _LANES) * _LANES
    blocks = 2 * itemsize * lanes(heads * dh) * (2 * block_q + 2 * block_kv)
    scratch = 4 * heads * block_q * (2 * _LANES + lanes(dh))
    return blocks + scratch


def _heads_per_block(H: int, dh: int, block_q: int, block_kv: int, itemsize: int) -> int:
    """How many of the ``H`` heads of a sequence one grid step holds.

    A step costs ~0.6 us on a v5e for being a step, however little its tile
    holds (VAR's scale 0, sixteen ``[1, 64] x [1, 64]`` heads: 0.63 us), and a
    head's work comes on top (0.53 us at ``[128, 64] x [512, 64]``; PERF.md
    §6, PR 36): the more heads a step the better, so the largest divisor of
    ``H`` whose blocks and scratch fit :data:`VMEM_BUDGET_BYTES` — all 16 at
    VAR's and Infinity's 16 x 64, 8 of 16 at 128 lanes a head. A group that is
    not the whole ``H·dh`` row has to be whole 128-lane tiles (Mosaic's rule
    for a block's last dimension)."""
    for heads in range(H, 0, -1):
        if H % heads or (heads != H and heads * dh % _LANES):
            continue
        if heads == 1 or _step_vmem_bytes(heads, dh, block_q, block_kv, itemsize) <= VMEM_BUDGET_BYTES:
            return heads
    return H  # no narrower group is whole lane tiles: the row as it is


def _flash_kernel(
    q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr,
    *, sm_scale: float, kv_len: int, block_kv: int, heads: int, dh: int, zero_v_tail: bool,
):
    """One (sequence, head group, q-block, kv-block) step with online softmax:
    ``heads`` heads of one sequence, each the lane slice ``h·dh … (h+1)·dh``
    of the ``[block_q, heads·dh]`` query and ``[block_kv, heads·dh]`` K, V
    blocks — the layout the models write, so nothing is transposed in HBM.
    The heads are a static loop; each runs the same float32 products, ``exp``
    and running (max, sum, weighted-V) accumulators on its own
    ``[block_q, dh] x [block_kv, dh]`` tile that a one-head step would.

    VMEM holds only a head's [block_q, block_kv] logit tile plus the
    accumulators — the KV axis is a *grid* dimension, so the kernel's
    footprint is independent of the cache length (the old kernel streamed the
    full K/V and a [block_q, L] logit tile into VMEM, which at the Infinity
    1M preset (~10k kv, dh 128) was at/over the ~16MB VMEM budget).

    Key rows past ``kv_len`` are masked in the logits (``pos >= kv_len``), so
    their probability is exactly 0 — but 0 times a NaN is NaN, and where the
    cache is longer than its valid prefix (``zero_v_tail``) the rows between
    hold whatever was written there: V's rows past ``kv_len`` are zeroed
    before ``p @ v``. (The rows the wrapper pads a block with are zeros.)
    """
    from jax.experimental import pallas as pl

    kv_i = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(kv_i == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    block_q = q_ref.shape[1]
    # the heads of a sequence share positions and the text mask
    pos = kv_i * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    valid = pos < kv_len
    if mask_ref is not None:
        valid = jnp.logical_and(valid, mask_ref[0] != 0)  # [1, bkv] over rows
    if zero_v_tail:
        v_row = kv_i * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_kv, dh), 0)
        v_valid = v_row < kv_len

    for h in range(heads):
        lanes = slice(h * dh, (h + 1) * dh)
        q = q_ref[0, :, lanes].astype(jnp.float32)  # [bq, dh]
        k = k_ref[0, :, lanes].astype(jnp.float32)  # [bkv, dh]
        v = v_ref[0, :, lanes].astype(jnp.float32)
        if zero_v_tail:
            v = jnp.where(v_valid, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [bq, bkv]
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[h][:, :1]  # [bq, 1]
        l_prev = l_scr[h][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)  # rescale of previous blocks' sums
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
        l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(kv_i == n_kv - 1)
    def _finalize():
        for h in range(heads):
            l = l_scr[h][:, :1]
            o_ref[0, :, h * dh:(h + 1) * dh] = (
                acc_scr[h] / jnp.maximum(l, 1e-30)
            ).astype(o_ref.dtype)


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
    """The one ``pallas_call`` of a layer's attention. Grid ``(B, H / heads,
    q blocks, kv blocks)``, kv innermost and sequential; q, K, V and the
    output are the callers' arrays viewed ``[B, n, H·dh]``, in blocks
    ``(1, block_q | block_kv, heads·dh)`` with ``heads`` from
    :func:`_heads_per_block` — the whole row, one step a (sequence, q block,
    kv block), wherever it fits. Nothing is transposed in HBM around the
    call. Rows are padded with zeros to a whole number of blocks (VAR: scale
    8's 169 queries to 256, scale 9's 680 keys to 1024, as before PR 36).
    With last blocks that overhang their arrays every call ran alone on the
    chip and the VAR step that holds them never came back — in that step XLA
    keeps scale 9's V at the very end of VMEM, where such a block addresses
    past it (PERF.md §6, PR 36)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, nq, H, dh = q.shape
    L = k.shape[1]
    block_q = min(block_q, nq)
    n_qblk = -(-nq // block_q)
    block_kv = min(block_kv, L)
    n_kvblk = -(-L // block_kv)
    heads = _heads_per_block(H, dh, block_q, block_kv, q.dtype.itemsize)

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, kv_len=kv_len, block_kv=block_kv,
        heads=heads, dh=dh, zero_v_tail=L > kv_len,
    )
    q_spec = pl.BlockSpec((1, block_q, heads * dh), lambda b, g, qi, ki: (b, qi, g))
    kv_spec = pl.BlockSpec((1, block_kv, heads * dh), lambda b, g, qi, ki: (b, ki, g))
    in_specs = [q_spec, kv_spec, kv_spec]
    # rows up to a whole number of blocks (zeros; key rows there fall outside
    # kv_len and are masked in-kernel): a block never overhangs its array
    rows = lambda t, n: jnp.pad(t, ((0, 0), (0, n - t.shape[1])) + ((0, 0),) * (t.ndim - 2))
    operands = [rows(t.reshape(*t.shape[:2], H * dh), n)
                for t, n in ((q, n_qblk * block_q), (k, n_kvblk * block_kv), (v, n_kvblk * block_kv))]
    if kv_mask is not None:
        # [B, 1, L] int32: Mosaic takes neither a one-row block out of a
        # [B, L] array (a block's second-to-last dim must be a multiple of 8
        # or the whole axis) nor a boolean memref
        in_specs.append(pl.BlockSpec((1, 1, block_kv), lambda b, g, qi, ki: (b, 0, ki)))
        operands.append(rows(kv_mask.astype(jnp.int32), n_kvblk * block_kv)[:, None, :])
    else:
        kernel = _wrap_no_mask(kernel)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, n_qblk * block_q, H * dh), q.dtype),
        # kv innermost: it is the sequential reduce dimension; the output
        # block index is constant in ki so Pallas keeps revisiting the same
        # tile until the accumulators are finalized.
        grid=(B, H // heads, n_qblk, n_kvblk),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=_vmem_scratch(heads, block_q, dh),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="decode_attention",
        # read back from the lowered step by obs/xla_cost.stablehlo_stats:
        # programs.jsonl says for every site how many heads share a grid step
        metadata={"heads_per_block": str(heads)},
    )(*operands)
    return out[:, :nq].reshape(B, nq, H, dh)


def _vmem_scratch(heads: int, block_q: int, dh: int):
    """Running-max / running-sum / output accumulators a head ([bq,128] lanes
    for the scalars, [bq,dh] for the weighted-V sum)."""
    from jax.experimental.pallas import tpu as pltpu

    return [
        pltpu.VMEM((heads, block_q, _LANES), jnp.float32),
        pltpu.VMEM((heads, block_q, _LANES), jnp.float32),
        pltpu.VMEM((heads, block_q, dh), jnp.float32),
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

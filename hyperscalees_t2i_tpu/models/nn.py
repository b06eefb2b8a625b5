"""Core functional layers: params are plain dict pytrees, apply fns are pure.

Design rules (TPU-first):
- arrays are channels-last (``NHWC``); matmuls hit the MXU in bf16 by default
  with f32 params (mixed policy is the model config's ``compute_dtype``);
- every dense accepts an optional LoRA leaf — the population axis vmaps over
  these leaves only, base kernels broadcast;
- no data-dependent Python control flow; everything jit-traceable.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..lora import FactoredDelta, factored_lora_delta, matmul_factored
from ..ops.fused_qlora import conv_kernel_q8_matmul, fused_qlora_applies, fused_qlora_dense
# kernel_shape: re-exported for call sites that read geometry off a node
from ..ops.quant import dequant_matmul, dequantize_kernel, kernel_shape  # noqa: F401

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key: jax.Array, d_in: int, d_out: int, bias: bool = True, std: Optional[float] = None) -> Params:
    std = std if std is not None else 1.0 / math.sqrt(d_in)
    p = {"kernel": jax.random.normal(key, (d_in, d_out), jnp.float32) * std}
    if bias:
        p["bias"] = jnp.zeros((d_out,), jnp.float32)
    return p


def stacked_dense_init(key: jax.Array, L: int, d_in: int, d_out: int, bias: bool = True, std: Optional[float] = None) -> Params:
    std = std if std is not None else 1.0 / math.sqrt(d_in)
    p = {"kernel": jax.random.normal(key, (L, d_in, d_out), jnp.float32) * std}
    if bias:
        p["bias"] = jnp.zeros((L, d_out), jnp.float32)
    return p


def conv_init(key: jax.Array, kh: int, kw: int, c_in: int, c_out: int, bias: bool = True, groups: int = 1) -> Params:
    fan_in = kh * kw * c_in // groups
    p = {"kernel": jax.random.normal(key, (kh, kw, c_in // groups, c_out), jnp.float32) / math.sqrt(fan_in)}
    if bias:
        p["bias"] = jnp.zeros((c_out,), jnp.float32)
    return p


def norm_init(dim: int, scale: bool = True, bias: bool = True) -> Params:
    p = {}
    if scale:
        p["scale"] = jnp.ones((dim,), jnp.float32)
    if bias:
        p["bias"] = jnp.zeros((dim,), jnp.float32)
    return p


# ---------------------------------------------------------------------------
# Apply fns
# ---------------------------------------------------------------------------

def dense(p: Params, x: jax.Array, lora: Optional[Params] = None, lora_scale: float = 1.0) -> jax.Array:
    """y = x @ W (+ b) (+ (alpha/r)(x@A)@B). Kernel may be 2D or per-layer-sliced.

    How an adapter meets its base is read off the node's keys and the leaf's
    type at trace time — this table is the whole decision (``conv2d`` follows
    its first three rows; attention's QKV/out projections are ordinary dense
    sites):

    ============== ============================== ================================
    base node      adapter leaf                   lowering
    ============== ============================== ================================
    ``kernel``     none, or raw arrays (serving,  ``x @ W`` (+ ``(x@a)@b·s``)
                   evaluation, strips)
    ``kernel``     ``lora.FactoredDelta`` (a      ``x @ W`` +
                   training member)               ``lora.factored_lora_delta``
    ``kernel_q8``  none / raw                     ``ops/quant.dequant_matmul``
                                                  (+ the raw delta)
    ``kernel_q8``  ``FactoredDelta``              ``ops/fused_qlora
                                                  .fused_qlora_dense``: the Pallas
                                                  kernel where platform and shape
                                                  admit it, else the sum of the
                                                  two rows above
    ============== ============================== ================================
    """
    if "kernel" in p:
        y = x @ p["kernel"].astype(x.dtype)
    elif lora is not None and fused_qlora_applies(lora):
        # base and delta in one resolution: the delta is consumed here
        y = fused_qlora_dense(x, p["kernel_q8"], lora, lora_scale)
        lora = None
    else:
        y = dequant_matmul(x, p["kernel_q8"])
    if lora is not None:
        if isinstance(lora["a"], FactoredDelta) or isinstance(lora["b"], FactoredDelta):
            y = y + factored_lora_delta(x, lora, lora_scale)
        else:
            a = lora["a"].astype(x.dtype)
            b = lora["b"].astype(x.dtype)
            y = y + ((x @ a) @ b) * jnp.asarray(lora_scale, x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


def slice_stacked(p: Params, i) -> Params:
    """Select layer ``i`` of a stacked node, dense or conv, float or int8."""
    out: Params = {}
    for k, v in p.items():
        if k == "kernel_q8":
            out[k] = {"q8": v["q8"][i], "scale": v["scale"][i]}
        else:
            out[k] = v[i]
    return out


def layer_norm(x: jax.Array, p: Optional[Params] = None, eps: float = 1e-6) -> jax.Array:
    """LayerNorm; affine only when ``p`` carries scale/bias (the DiT blocks use
    the affine-free variant with AdaLN modulation instead)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    if p is not None and "scale" in p:
        y = y * p["scale"]
    if p is not None and "bias" in p:
        y = y + p["bias"]
    return y.astype(dtype)


def group_norm(x: jax.Array, p: Optional[Params] = None, groups: int = 32, eps: float = 1e-6) -> jax.Array:
    """GroupNorm over NHWC (the CompVis-VAE normalizer)."""
    dtype = x.dtype
    B, H, W, C = x.shape
    g = min(groups, C)
    while C % g:
        g -= 1
    xg = x.astype(jnp.float32).reshape(B, H, W, g, C // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    y = ((xg - mu) * jax.lax.rsqrt(var + eps)).reshape(B, H, W, C)
    if p is not None and "scale" in p:
        y = y * p["scale"]
    if p is not None and "bias" in p:
        y = y + p["bias"]
    return y.astype(dtype)


def rms_norm(x: jax.Array, p: Optional[Params] = None, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    if p is not None and "scale" in p:
        y = y * p["scale"]
    return y.astype(dtype)


def conv2d(
    p: Params,
    x: jax.Array,
    stride: int = 1,
    padding: str = "SAME",
    groups: int = 1,
    lora: Optional[Params] = None,
    lora_scale: float = 1.0,
) -> jax.Array:
    """NHWC conv, kernel HWIO. Kernel may be float or int8-quantized
    (``kernel_q8``, see ops/quant.py). Matmul-equivalent int8 convs (1×1
    stride-1 projections, non-overlapping p×p stride-p patch embeds) lower as
    ``dense``'s third row does (ops/fused_qlora.conv_kernel_q8_matmul →
    ops/quant.dequant_matmul); everything else dequantizes at the use site
    and keeps the conv. Optional
    PEFT-style conv LoRA: an r-channel conv (A) followed by a 1×1
    projection (B) — the Z-Image VAE-decoder adapter path (reference
    es_backend.py:599-629)."""
    if "kernel" in p:
        y = None
        w = p["kernel"].astype(x.dtype)
    else:
        y = conv_kernel_q8_matmul(x, p["kernel_q8"], stride, padding, groups)
        if y is None:
            w = dequantize_kernel(p["kernel_q8"], x.dtype)
    if y is None:
        y = jax.lax.conv_general_dilated(
            x,
            w,
            window_strides=(stride, stride),
            padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups,
        )
    if lora is not None and groups == 1:
        # conv-4D ``a`` factors carry dense ES noise (no factored form, so a
        # training member hands them over already materialized); its 2D
        # ``b`` projection is a FactoredDelta.
        if isinstance(lora["b"], FactoredDelta):
            h = jax.lax.conv_general_dilated(
                x, lora["a"].astype(x.dtype), window_strides=(stride, stride),
                padding=padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            y = y + matmul_factored(h, lora["b"]) * jnp.asarray(lora_scale, x.dtype)
        else:
            a = lora["a"].astype(x.dtype)
            b = lora["b"].astype(x.dtype)
            h = jax.lax.conv_general_dilated(
                x, a, window_strides=(stride, stride), padding=padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            y = y + (h @ b) * jnp.asarray(lora_scale, x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


def timestep_embedding(t: jax.Array, dim: int, max_period: float = 10000.0, scale: float = 1.0) -> jax.Array:
    """Sinusoidal features [B, dim] (standard DiT/diffusers layout: cos|sin)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    args = scale * t.astype(jnp.float32)[:, None] * freqs[None, :]
    emb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    if dim % 2:
        emb = jnp.pad(emb, [(0, 0), (0, 1)])
    return emb


def mlp_embedder_init(key: jax.Array, d_in: int, d_out: int) -> Params:
    k1, k2 = jax.random.split(key)
    return {"linear_1": dense_init(k1, d_in, d_out), "linear_2": dense_init(k2, d_out, d_out)}


def mlp_embedder(p: Params, x: jax.Array) -> jax.Array:
    return dense(p["linear_2"], jax.nn.silu(dense(p["linear_1"], x)))


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate interleaved pairs: x [B, S, H, dh], cos/sin [S, dh/2].

    Shared by the Z-Image axial RoPE and the Infinity 2D pyramid RoPE."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    out = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.reshape(x.shape)


MAX_QK_SCALE_MUL = math.log(100.0)


def l2_normalize(x: jax.Array) -> jax.Array:
    """f32 unit-norm over the last axis (the single definition of the QK-l2
    epsilon/policy — self-attn, cross-attn, and precomputed-k paths must stay
    bit-identical for parity). Returns f32; callers cast."""
    f32 = jnp.float32
    x = x.astype(f32)
    return x * jax.lax.rsqrt(jnp.sum(x**2, -1, keepdims=True) + 1e-24)


def q_l2(q: jax.Array, scale_mul_h: jax.Array) -> jax.Array:
    """The q half of :func:`qk_l2` alone — for attention paths whose k side
    is pre-normalized once outside the layer loop (Infinity cross-attention,
    where the text K/V are constant through the scale pyramid)."""
    sm = jnp.exp(jnp.minimum(scale_mul_h.astype(jnp.float32), MAX_QK_SCALE_MUL))  # [H]
    return (l2_normalize(q) * sm[None, None, :, None]).astype(q.dtype)


def qk_l2(q: jax.Array, k: jax.Array, scale_mul_h: jax.Array):
    """q ← normalize(q)·exp(min(scale_mul, log 100)) per head; k ← normalize(k).

    The reference's attn_l2_norm path (VAR_models/basic_var.py:101-105) with a
    learned per-head log-scale; the softmax scale becomes 1. Note the AR
    models' caches store the *normalized* k, which this layout preserves.
    """
    return q_l2(q, scale_mul_h), l2_normalize(k).astype(k.dtype)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    is_causal: bool = False,
) -> jax.Array:
    """Standard softmax attention over [B, L, H, Dh] tensors.

    Uses ``jax.nn.dot_product_attention`` so XLA picks the fused TPU path; the
    Pallas flash kernel (ops/attention.py) slots in for the AR-decode models.
    """
    bias = None
    if mask is not None:
        # mask: [B, Lkv] key-validity → additive bias [B, 1, 1, Lkv]
        bias = jnp.where(mask[:, None, None, :], 0.0, -1e9).astype(q.dtype)
    return jax.nn.dot_product_attention(q, k, v, bias=bias, is_causal=is_causal)


def linear_attention(q: jax.Array, k: jax.Array, v: jax.Array, eps: float = 1e-6) -> jax.Array:
    """ReLU linear attention (Sana 'lite' attention; reference runs it through
    diffusers' SanaLinearAttnProcessor — SURVEY.md §2.1 "Sana Sprint wrappers").

    q, k, v: [B, L, H, D]. Cost O(L·D²·H) — no L×L matrix, which is the right
    trade on TPU for image-token lengths of 1024+.

    Numerics: on TPU the two big einsums keep their operands in the compute
    dtype (bf16 MXU rate — casting to f32 would halve throughput AND double
    the HBM traffic of the dominant ops) while accumulating in f32 via
    ``preferred_element_type``; the normalizer runs fully in f32. On the CPU
    backend only, bf16 operands are upcast first — XLA:CPU's DotThunk cannot
    execute bf16×bf16→f32 dots (observed on this build, eager AND compiled);
    accelerators keep the mixed fast path. In f32 configs (parity tests)
    both paths are bit-identical to all-f32.
    """
    dtype = q.dtype
    q = jax.nn.relu(q)
    k = jax.nn.relu(k)
    if dtype == jnp.bfloat16 and jax.default_backend() == "cpu":
        q = q.astype(jnp.float32)
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)
    work = q.dtype  # bf16 on accelerators, f32 on the CPU backend
    kv = jnp.einsum("blhd,blhe->bhde", k, v, preferred_element_type=jnp.float32)
    ksum = k.astype(jnp.float32).sum(axis=1)  # [B, H, D]
    num = jnp.einsum(
        "blhd,bhde->blhe", q, kv.astype(work), preferred_element_type=jnp.float32
    )
    den = jnp.einsum("blhd,bhd->blh", q.astype(jnp.float32), ksum)
    out = num / (den[..., None] + eps)
    return out.astype(dtype)


def glumb_conv_init(key: jax.Array, dim: int, ratio: float = 2.5) -> Params:
    """GLUMBConv (gated inverted-bottleneck mix-FFN) params — Sana's FFN."""
    hidden = int(round(dim * ratio))
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "conv_inverted": conv_init(k1, 1, 1, dim, hidden * 2),
        "conv_depth": conv_init(k2, 3, 3, hidden * 2, hidden * 2, groups=hidden * 2),
        "conv_point": conv_init(k3, 1, 1, hidden, dim, bias=False),
    }


def glumb_conv(p: Params, x: jax.Array, hw: tuple) -> jax.Array:
    """x: [B, L, d] tokens on an (H, W) grid → gated depthwise mix-FFN."""
    B, L, d = x.shape
    H, W = hw
    y = x.reshape(B, H, W, d)
    y = conv2d(p["conv_inverted"], y)
    y = jax.nn.silu(y)
    groups = kernel_shape(p["conv_depth"])[-1]
    y = conv2d(p["conv_depth"], y, groups=groups)
    y, gate = jnp.split(y, 2, axis=-1)
    y = y * jax.nn.silu(gate)
    y = conv2d(p["conv_point"], y)
    return y.reshape(B, L, d)


REMAT_MODES = ("none", "blocks", "full")


def remat_wrap(fn, mode: Optional[str], name: str):
    """Apply ``jax.checkpoint`` to a block/stage function per the ``--remat``
    policy, so activation temps stop scaling with depth×resolution whenever
    the program is differentiated or the compiler honors the rematerialization
    hint.

    - ``none`` (default): return ``fn`` unchanged — identical HLO to the
      pre-remat program.
    - ``blocks``: save only the values tagged :func:`remat_name` with ``name``
      (the block/stage *boundary* outputs); everything interior is recomputed.
    - ``full``: ``nothing_saveable`` — recompute everything.

    ``prevent_cse=False`` because every call site lives under ``lax.scan`` /
    ``lax.map``, where CSE across iterations is already impossible and the
    guard would only block intra-block fusion.
    """
    if mode in (None, "", "none"):
        return fn
    if mode == "blocks":
        policy = jax.checkpoint_policies.save_only_these_names(name)
    elif mode == "full":
        policy = jax.checkpoint_policies.nothing_saveable
    else:
        raise ValueError(f"unknown remat mode {mode!r} (have: {REMAT_MODES})")
    return jax.checkpoint(fn, policy=policy, prevent_cse=False)


def remat_name(x: jax.Array, mode: Optional[str], name: str) -> jax.Array:
    """Tag a block-boundary value for the ``blocks`` save policy. A no-op
    (identity, no extra HLO) under every other mode so the unoptimized
    program stays byte-identical."""
    if mode == "blocks":
        from jax.ad_checkpoint import checkpoint_name

        return checkpoint_name(x, name)
    return x


def stacked_scan(body, init: Any, length: int, mode: Optional[str], name: str) -> Any:
    """``lax.scan`` over stacked layers, remat-wrapped per the ``mode`` knob
    (``none`` lowers the byte-identical pre-optimization scan). One trace
    regardless of depth — the repo's stacked-layer contract.

    CPU caveat, relevant to the preflight HBM estimate: XLA:CPU cannot
    execute bf16 dots, and its float-normalization pass converts every bf16
    array carried through the scan's while loop to f32 — materializing a
    full-size f32 copy of the whole stacked parameter tree (measured: +6.4 GB
    for the flagship DiT, +2.5 GB for CLIP-H). A chip with native bf16
    matmul (every TPU kind in utils/mfu.py) never allocates those copies;
    tools/preflight.py therefore reports a chip-true estimate alongside the
    raw CPU one instead of this module contorting the program. (Unrolling
    the scan on CPU removes the copies for a top-level tower but *sums*
    every layer's temps when the tower sits inside lax.map nesting — 2×
    worse at flagship geometry — so it is deliberately not done.)

    ``body`` has scan signature ``(carry, layer_idx) -> (carry, None)``.
    """
    return jax.lax.scan(remat_wrap(body, mode, name), init, jnp.arange(length))[0]


def depth_to_space(x: jax.Array, factor: int) -> jax.Array:
    """[B,H,W,C·f²] → [B,H·f,W·f,C] (pixel shuffle, decoder upsampling)."""
    B, H, W, C = x.shape
    c = C // (factor * factor)
    x = x.reshape(B, H, W, factor, factor, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, H * factor, W * factor, c)

"""BSQ (binary spherical quantization) multi-scale pyramid — the Infinity
visual tokenizer's math (pure JAX).

Capability parity with the reference's Infinity path, which drives an external
BSQ-VAE through ``vae.encode``/bitwise ids
(``/root/reference/models/Infinity.py:29-556``; the tokenizer itself lives in
the non-vendored Infinity repo — SURVEY.md §7.3 "the rebuild must implement an
Infinity-equivalent itself"). BSQ replaces the VQ codebook lookup with a
*bitwise* code: features are projected to the unit sphere and each channel is
quantized to ``±1/√C`` — a token is its ``C``-bit sign pattern, predicted
bit-by-bit by the transformer (vocab 2 per bit instead of 2^C — the trick
that lets Infinity scale vocab to 2^32 and beyond).

The multi-scale residual pyramid (upsample-add, downsample-next) reuses the
same machinery as the VAR quantizer (msvq.py) — one shared implementation,
two quantizer laws.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from . import nn
from .msvq import _down_area, _up_bicubic

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class BSQConfig:
    bits: int = 16  # channels of the spherical code (vocab 2^bits implicit)
    patch_nums: Tuple[int, ...] = (1, 2, 3, 4, 5, 6, 8, 10, 13, 16)
    phi_partial: int = 4
    # decoder widths deepest→shallowest (Infinity's VAE decodes f16 latents)
    dec_ch: Tuple[int, ...] = (512, 256, 256, 128, 128)
    dec_blocks: int = 1
    compute_dtype: Any = jnp.bfloat16

    @property
    def num_scales(self) -> int:
        return len(self.patch_nums)

    @property
    def seq_len(self) -> int:
        return int(sum(p * p for p in self.patch_nums))

    @property
    def grid(self) -> int:
        return self.patch_nums[-1]


def init_bsq(key: jax.Array, cfg: BSQConfig) -> Params:
    """φ blend convs + conv decoder (no codebook — the code is the sign map)."""
    C = cfg.bits
    ks = jax.random.split(key, 3 + len(cfg.dec_ch) * (3 * cfg.dec_blocks + 1))
    params: Params = {
        "phi": {
            "kernel": jax.random.normal(ks[0], (cfg.phi_partial, 3, 3, C, C), jnp.float32)
            / math.sqrt(9 * C),
            "bias": jnp.zeros((cfg.phi_partial, C), jnp.float32),
        }
    }
    dec: Params = {"conv_in": nn.conv_init(ks[1], 3, 3, C, cfg.dec_ch[0])}
    ki = 2
    stages = []
    prev = cfg.dec_ch[0]
    for s, ch in enumerate(cfg.dec_ch):
        stage: Params = {"blocks": []}
        for b in range(cfg.dec_blocks):
            cin = prev if b == 0 else ch
            stage["blocks"].append(
                {
                    "conv1": nn.conv_init(ks[ki], 3, 3, cin, ch),
                    "conv2": nn.conv_init(ks[ki + 1], 3, 3, ch, ch),
                    "skip": nn.conv_init(ks[ki + 2], 1, 1, cin, ch, bias=False) if cin != ch else None,
                }
            )
            ki += 3
        if s < len(cfg.dec_ch) - 1:
            stage["up"] = nn.conv_init(ks[ki], 3, 3, ch, ch)
            ki += 1
        stages.append(stage)
        prev = ch
    dec["stages"] = stages
    dec["norm_out"] = nn.norm_init(cfg.dec_ch[-1])
    dec["conv_out"] = nn.conv_init(ks[ki], 3, 3, cfg.dec_ch[-1], 3)
    params["decoder"] = dec
    return params


def bits_to_vec(bits: jax.Array, C: int) -> jax.Array:
    """{0,1} bit tensor [..., C] → spherical code ±1/√C."""
    return (2.0 * bits.astype(jnp.float32) - 1.0) / math.sqrt(C)


def vec_to_bits(v: jax.Array) -> jax.Array:
    """Sign-quantize features to {0,1} bits (the BSQ law)."""
    return (v > 0).astype(jnp.int32)


def phi_index(cfg: BSQConfig, si: int) -> int:
    S, K = cfg.num_scales, cfg.phi_partial
    if S <= 1:
        return 0
    return int(round(si / (S - 1) * (K - 1)))


def phi_apply(params: Params, cfg: BSQConfig, h: jax.Array, si: int) -> jax.Array:
    node = params["phi"]
    # the kernel, float or int8, then the bias: the order the step programs
    # have always sliced them in (their lowered text depends on it)
    p = nn.slice_stacked(
        {name: node[name] for name in sorted(node, reverse=True)}, phi_index(cfg, si)
    )
    return 0.5 * h + 0.5 * nn.conv2d(p, h)


def accumulate_scale(
    params: Params,
    cfg: BSQConfig,
    f_hat: jax.Array,  # [B, pN, pN, C]
    bits: jax.Array,  # [B, pn*pn, C] sampled bits for scale si
    si: int,
) -> Tuple[jax.Array, jax.Array]:
    """Generation-side pyramid step; returns (f̂', next scale's input)."""
    B = f_hat.shape[0]
    pn = cfg.patch_nums[si]
    h = bits_to_vec(bits, cfg.bits).reshape(B, pn, pn, cfg.bits)
    h = _up_bicubic(h, cfg.grid)
    f_hat = f_hat + phi_apply(params, cfg, h.astype(f_hat.dtype), si)
    if si + 1 < cfg.num_scales:
        nxt = _down_area(f_hat, cfg.patch_nums[si + 1])
    else:
        nxt = f_hat
    return f_hat, nxt


def encode_to_scales(
    params: Params, cfg: BSQConfig, f: jax.Array
) -> Tuple[List[jax.Array], jax.Array]:
    """Greedy residual bitwise encoding → (per-scale bits [B, pn², C], f̂)."""
    B = f.shape[0]
    f_hat = jnp.zeros_like(f)
    out: List[jax.Array] = []
    for si, pn in enumerate(cfg.patch_nums):
        rest = f - f_hat
        z = _down_area(rest, pn)
        bits = vec_to_bits(z).reshape(B, pn * pn, cfg.bits)
        out.append(bits)
        f_hat, _ = accumulate_scale(params, cfg, f_hat, bits, si)
    return out, f_hat


def decode_img(params: Params, cfg: BSQConfig, f_hat: jax.Array) -> jax.Array:
    """f̂ [B, pN, pN, C] → images [B, H, W, 3] in [0, 1].

    Two decoder layouts: the native norm-free one built by :func:`init_bsq`,
    or — when the subtree carries a ``mid`` stack — an ingested CompVis-style
    tokenizer decoder (weights/infinity.py ``convert_bsq_vae``), run through
    the shared msvq decoder path."""
    dec = params["decoder"]
    dt = cfg.compute_dtype
    if "mid" in dec:
        from .msvq import run_decoder

        return run_decoder(dec, f_hat, dt)
    x = nn.conv2d(dec["conv_in"], f_hat.astype(dt))
    for stage in dec["stages"]:
        for blk in stage["blocks"]:
            h = nn.conv2d(blk["conv1"], jax.nn.silu(x))
            h = nn.conv2d(blk["conv2"], jax.nn.silu(h))
            skip = x if blk.get("skip") is None else nn.conv2d(blk["skip"], x)
            x = skip + h
        if "up" in stage:
            B, hh, ww, c = x.shape
            x = jax.image.resize(x, (B, hh * 2, ww * 2, c), method="nearest")
            x = nn.conv2d(stage["up"], x)
    x = nn.layer_norm(x, dec["norm_out"])
    x = nn.conv2d(dec["conv_out"], jax.nn.silu(x))
    return (jnp.clip(x.astype(jnp.float32), -1.0, 1.0) + 1.0) / 2.0

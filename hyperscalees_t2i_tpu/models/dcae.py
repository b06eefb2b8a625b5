"""DC-AE-style deep-compression latent decoder (and encoder) in pure JAX.

Role parity with the reference's diffusers ``AutoencoderDC`` usage
(``models/SanaSprint.py:45-58,157-163``): decode 32-channel f32 latents to RGB
inside the compiled generation step. The architecture follows the DC-AE
recipe — conv stem, per-stage residual conv blocks with ReLU-linear-attention
(LiteMLA/EfficientViT) blocks in the deepest stages, pixel-shuffle upsampling
with channel-duplicating shortcuts — sized by config so tests run a tiny
instance and the flagship matches DC-AE f32's stage widths.

TPU notes: channels-last NHWC throughout; upsampling is depth-to-space (pure
reshape/transpose — no gather); all blocks are residual so XLA fuses the
elementwise tails into the convs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import nn

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class DCAEConfig:
    latent_channels: int = 32
    # decoder stage widths, deepest→shallowest; len-1 upsamples of 2× each.
    channels: Tuple[int, ...] = (1024, 1024, 512, 512, 256, 128)
    blocks_per_stage: Tuple[int, ...] = (2, 2, 2, 2, 2, 2)
    attn_stages: Tuple[int, ...] = (0, 1)  # LiteMLA in the deepest stages
    attn_heads: int = 16
    scaling_factor: float = 0.41407
    compute_dtype: Any = jnp.bfloat16
    # activation rematerialization per decoder stage (models/nn.py
    # remat_wrap): "none" | "blocks" | "full". Decoded pixels are
    # bit-identical across modes (tests/test_memopt.py).
    remat: str = "none"

    @property
    def spatial_factor(self) -> int:
        return 2 ** (len(self.channels) - 1)


def _res_block_init(key: jax.Array, ch: int) -> Params:
    k1, k2 = jax.random.split(key)
    return {"conv1": nn.conv_init(k1, 3, 3, ch, ch), "conv2": nn.conv_init(k2, 3, 3, ch, ch)}


def _res_block(p: Params, x: jax.Array) -> jax.Array:
    y = nn.conv2d(p["conv1"], x)
    y = nn.conv2d(p["conv2"], jax.nn.silu(y))
    return x + y


def _lite_mla_init(key: jax.Array, ch: int) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "norm": nn.norm_init(ch, bias=False),
        "qkv": nn.dense_init(k1, ch, 3 * ch, bias=False),
        "proj": nn.dense_init(k2, ch, ch),
        "ffn": nn.glumb_conv_init(k3, ch, ratio=2.0),
        "ffn_norm": nn.norm_init(ch, bias=False),
    }


def _lite_mla(p: Params, x: jax.Array, heads: int) -> jax.Array:
    B, H, W, C = x.shape
    t = nn.rms_norm(x, p["norm"]).reshape(B, H * W, C)
    qkv = nn.dense(p["qkv"], t)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    heads = min(heads, C)
    sh = lambda a: a.reshape(B, H * W, heads, C // heads)
    a = nn.linear_attention(sh(q), sh(k), sh(v)).reshape(B, H * W, C)
    x = x + nn.dense(p["proj"], a).reshape(B, H, W, C)
    t = nn.rms_norm(x, p["ffn_norm"]).reshape(B, H * W, C)
    x = x + nn.glumb_conv(p["ffn"], t, (H, W)).reshape(B, H, W, C)
    return x


def init_decoder(key: jax.Array, cfg: DCAEConfig) -> Params:
    chs = cfg.channels
    keys = jax.random.split(key, 3 + len(chs) * (1 + max(cfg.blocks_per_stage)))
    ki = iter(keys)
    params: Params = {"conv_in": nn.conv_init(next(ki), 3, 3, cfg.latent_channels, chs[0])}
    stages = []
    for si, ch in enumerate(chs):
        stage: Params = {}
        if si > 0:
            stage["up"] = nn.conv_init(next(ki), 3, 3, chs[si - 1], ch * 4)
        blocks = []
        for _ in range(cfg.blocks_per_stage[si]):
            if si in cfg.attn_stages:
                blocks.append({"mla": _lite_mla_init(next(ki), ch)})
            else:
                blocks.append({"res": _res_block_init(next(ki), ch)})
        stage["blocks"] = blocks
        stages.append(stage)
    params["stages"] = stages
    params["norm_out"] = nn.norm_init(chs[-1], bias=False)
    params["conv_out"] = nn.conv_init(next(ki), 3, 3, chs[-1], 3)
    return params


def _decode_stage(stage: Params, x: jax.Array, cfg: DCAEConfig, si: int) -> jax.Array:
    """One decoder stage: optional 2× pixel-shuffle upsample then its blocks.
    Factored out of :func:`decode` so each stage can be a remat boundary —
    the stage interiors at 512/1024px are the deepest activation temps of
    the whole generate→reward program."""
    with jax.named_scope(f"stage{si}"):  # device-time scope (obs/xla_cost.INNER_SCOPES)
        if si > 0:
            up = nn.conv2d(stage["up"], x)
            # channel-duplicating shortcut: repeat input to 4× channels, shuffle up.
            rep = up.shape[-1] // x.shape[-1]
            shortcut = jnp.repeat(x, rep, axis=-1) if rep > 0 else up
            x = nn.depth_to_space(up + shortcut, 2)
        for block in stage["blocks"]:
            if "mla" in block:
                x = _lite_mla(block["mla"], x, cfg.attn_heads)
            else:
                x = _res_block(block["res"], x)
    return nn.remat_name(x, cfg.remat, "dcae_stage")


def decode(params: Params, cfg: DCAEConfig, latents: jax.Array) -> jax.Array:
    """[B, h, w, C_lat] (already divided by scaling_factor) → RGB in [0, 1].

    Matches the reference decode step ``vae.decode(x0/scaling) → postprocess``
    (``models/SanaSprint.py:157-163``) but stays an array op end-to-end — the
    per-image GPU→PIL round trip the reference pays (SURVEY.md §7.3) never
    happens; rewards consume the array directly.
    """
    dt = cfg.compute_dtype
    x = nn.conv2d(params["conv_in"], latents.astype(dt))
    for si, stage in enumerate(params["stages"]):
        stage_fn = nn.remat_wrap(
            lambda p, h, _si=si: _decode_stage(p, h, cfg, _si), cfg.remat, "dcae_stage"
        )
        x = stage_fn(stage, x)
    x = nn.rms_norm(x, params["norm_out"])
    x = nn.conv2d(params["conv_out"], jax.nn.silu(x))
    img = (x.astype(jnp.float32) * 0.5 + 0.5).clip(0.0, 1.0)
    return img


def init_encoder(key: jax.Array, cfg: DCAEConfig) -> Params:
    """Mirror-image encoder (RGB → latents). Not on the ES hot path (the
    reference never encodes images during training) but completes the
    autoencoder capability for tooling/round-trip tests."""
    chs = tuple(reversed(cfg.channels))
    keys = jax.random.split(key, 3 + len(chs) * (1 + max(cfg.blocks_per_stage)))
    ki = iter(keys)
    params: Params = {"conv_in": nn.conv_init(next(ki), 3, 3, 3, chs[0])}
    stages = []
    for si, ch in enumerate(chs):
        stage: Params = {}
        if si > 0:
            stage["down"] = nn.conv_init(next(ki), 3, 3, chs[si - 1], ch)
        stage["blocks"] = [
            {"res": _res_block_init(next(ki), ch)} for _ in range(cfg.blocks_per_stage[si])
        ]
        stages.append(stage)
    params["stages"] = stages
    params["conv_out"] = nn.conv_init(next(ki), 3, 3, chs[-1], cfg.latent_channels)
    return params


def encode(params: Params, cfg: DCAEConfig, images: jax.Array) -> jax.Array:
    """RGB in [0,1] → latents (multiply by scaling_factor to get model scale)."""
    dt = cfg.compute_dtype
    x = (images.astype(dt) - 0.5) * 2.0
    x = nn.conv2d(params["conv_in"], x)
    for si, stage in enumerate(params["stages"]):
        if si > 0:
            x = nn.conv2d(stage["down"], x, stride=2)
        for block in stage["blocks"]:
            x = _res_block(block["res"], x)
    return nn.conv2d(params["conv_out"], x).astype(jnp.float32)

"""Multi-scale residual VQ (next-scale prediction) + conv VQVAE decoder.

Capability parity with the reference's vendored VQVAE stack
(``/root/reference/VAR_models/quant.py`` — ``VectorQuantizer2``, φ
(quant_resi) conv blending, ``get_next_autoregressive_input``;
``VAR_models/vqvae.py`` + ``basic_vae.py`` — CompVis-style decoder,
``fhat_to_img``). Re-designed functional:

- the token pyramid is driven by static ``patch_nums`` (1..16 → L=Σpn²=680
  at 256px, ``VAR_models/var.py:39-46``), so every per-scale op has static
  shapes and the whole generate path lives in one jit;
- φ is the reference's *partially-shared* variant: K small 3×3 convs, scale
  ``si`` statically selects conv ``round(si/(S-1)·(K-1))`` (quant.py:199-243);
- resize semantics follow the reference: bicubic up to the full grid,
  area down to the next scale (quant.py:187-196) — both are static-shape
  ``jax.image.resize`` / average-pool ops that XLA fuses.

The accumulation loop (embed sampled ids → upsample → φ-conv → add to f̂ →
downsample to next scale) is the *generation-side* half; ``encode_to_scales``
implements the encode-side greedy residual quantization for tests/eval.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from . import nn

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MSVQConfig:
    """CompVis-parameterized so real ``vae_ch160v4096z32.pth`` weights map 1:1
    (``VAR_models/vqvae.py:17-43``: ch=160, ch_mult (1,1,2,2,4), 2 res blocks,
    mid + deepest-level self-attention, 3×3 post-quant conv)."""

    vocab_size: int = 4096
    c_vae: int = 32
    patch_nums: Tuple[int, ...] = (1, 2, 3, 4, 5, 6, 8, 10, 13, 16)
    phi_partial: int = 4  # number of partially-shared φ convs (share_quant_resi)
    ch: int = 160
    ch_mult: Tuple[int, ...] = (1, 1, 2, 2, 4)
    num_res_blocks: int = 2
    using_sa: bool = True  # self-attn blocks at the deepest up level
    using_mid_sa: bool = True  # self-attn in the mid stack
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


def _res_block_init(key: jax.Array, cin: int, cout: int) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p: Params = {
        "norm1": nn.norm_init(cin),
        "conv1": nn.conv_init(k1, 3, 3, cin, cout),
        "norm2": nn.norm_init(cout),
        "conv2": nn.conv_init(k2, 3, 3, cout, cout),
    }
    if cin != cout:
        p["nin"] = nn.conv_init(k3, 1, 1, cin, cout)
    return p


def _attn_block_init(key: jax.Array, c: int) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "norm": nn.norm_init(c),
        "qkv": nn.conv_init(k1, 1, 1, c, 3 * c),
        "proj": nn.conv_init(k2, 1, 1, c, c),
    }


def init_msvq(key: jax.Array, cfg: MSVQConfig) -> Params:
    C = cfg.c_vae
    n_levels = len(cfg.ch_mult)
    ks = jax.random.split(key, 16 + n_levels * (cfg.num_res_blocks + 1) * 4)
    ki = iter(range(len(ks)))
    params: Params = {
        # normalized codebook (the reference l2-normalizes embeddings when
        # using cosine lookup; we keep plain euclidean + unit-ball init)
        "codebook": jax.random.normal(ks[next(ki)], (cfg.vocab_size, C), jnp.float32)
        / math.sqrt(C),
        "phi": {
            "kernel": jax.random.normal(ks[next(ki)], (cfg.phi_partial, 3, 3, C, C), jnp.float32)
            / math.sqrt(9 * C),
            "bias": jnp.zeros((cfg.phi_partial, C), jnp.float32),
        },
    }
    block_in = cfg.ch * cfg.ch_mult[-1]
    dec: Params = {
        "post_quant_conv": nn.conv_init(ks[next(ki)], 3, 3, C, C),
        "conv_in": nn.conv_init(ks[next(ki)], 3, 3, C, block_in),
        "mid": {
            "block_1": _res_block_init(ks[next(ki)], block_in, block_in),
            "attn_1": _attn_block_init(ks[next(ki)], block_in) if cfg.using_mid_sa else None,
            "block_2": _res_block_init(ks[next(ki)], block_in, block_in),
        },
    }
    # up[i_level] for i_level 0..n-1 (shallowest..deepest); decode visits
    # them deepest-first (reference Decoder.forward, basic_vae.py:210-218).
    up: list = [None] * n_levels
    cin = block_in
    for i_level in reversed(range(n_levels)):
        cout = cfg.ch * cfg.ch_mult[i_level]
        level: Params = {"block": [], "attn": []}
        for _ in range(cfg.num_res_blocks + 1):
            level["block"].append(_res_block_init(ks[next(ki)], cin, cout))
            cin = cout
            if i_level == n_levels - 1 and cfg.using_sa:
                level["attn"].append(_attn_block_init(ks[next(ki)], cout))
        if i_level != 0:
            level["upsample"] = nn.conv_init(ks[next(ki)], 3, 3, cout, cout)
        up[i_level] = level
    dec["up"] = up
    dec["norm_out"] = nn.norm_init(cin)
    dec["conv_out"] = nn.conv_init(ks[next(ki)], 3, 3, cin, 3)
    params["decoder"] = dec
    return params


# ---------------------------------------------------------------------------
# resize primitives (static shapes)
# ---------------------------------------------------------------------------

def _up_bicubic(x: jax.Array, size: int) -> jax.Array:
    """[B,h,w,C] → [B,size,size,C]; bicubic like quant.py's F.interpolate."""
    B, h, w, C = x.shape
    if h == size:
        return x
    return jax.image.resize(x, (B, size, size, C), method="cubic")


def _down_area(x: jax.Array, size: int) -> jax.Array:
    """Area (average) downsample to [B,size,size,C] (quant.py:195 'area')."""
    B, h, w, C = x.shape
    if h == size:
        return x
    if h % size == 0:
        f = h // size
        return jax.lax.reduce_window(
            x, 0.0, jax.lax.add, (1, f, f, 1), (1, f, f, 1), "VALID"
        ) / float(f * f)
    # non-integer ratio (e.g. 16→13, 16→10): linear resize with antialiasing
    # matches F.interpolate(mode="area") closely for these small grids.
    return jax.image.resize(x, (B, size, size, C), method="linear", antialias=True)


def phi_index(cfg: MSVQConfig, si: int) -> int:
    """Static φ-conv selection for scale si — the reference's nearest-tick
    rule (``PhiPartiallyShared.__getitem__``, quant.py:218-227): ticks are
    ``linspace(1/3K, 1-1/3K, K)`` for K=4 (else 1/2K), queried at si/(S-1).
    A plain ``round(si/(S-1)·(K-1))`` differs (e.g. si=7 → 2 vs the
    reference's 3) for the canonical (K=4, S=10) geometry, so the tick
    arithmetic is reproduced exactly, float ties and all."""
    import numpy as np

    S, K = cfg.num_scales, cfg.phi_partial
    if S <= 1 or K <= 1:
        return 0
    lo = 1 / 3 / K if K == 4 else 1 / 2 / K
    ticks = np.linspace(lo, 1 - lo, K)
    return int(np.argmin(np.abs(ticks - si / (S - 1))))


def phi_apply(params: Params, cfg: MSVQConfig, h: jax.Array, si: int) -> jax.Array:
    """Residual-blend conv: x + conv(x) with a 0.5/0.5 mix (quant.py Phi)."""
    node = params["phi"]
    # the kernel, float or int8, then the bias: the order the step programs
    # have always sliced them in (their lowered text depends on it)
    p = nn.slice_stacked(
        {name: node[name] for name in sorted(node, reverse=True)}, phi_index(cfg, si)
    )
    return 0.5 * h + 0.5 * nn.conv2d(p, h)


def embed_ids(params: Params, ids: jax.Array) -> jax.Array:
    """Token ids [...,] → codebook vectors [..., C]."""
    return params["codebook"][ids]


def accumulate_scale(
    params: Params,
    cfg: MSVQConfig,
    f_hat: jax.Array,  # [B, pN, pN, C] running reconstruction
    ids: jax.Array,  # [B, pn*pn] sampled token ids for scale si
    si: int,
) -> Tuple[jax.Array, jax.Array]:
    """One generation-side pyramid step (quant.py:187-196).

    Returns ``(f_hat', next_input)`` where ``next_input`` is f̂' downsampled
    to scale si+1's grid ([B, pn₊₁, pn₊₁, C]); for the last scale it is f̂'.
    """
    B = f_hat.shape[0]
    pn = cfg.patch_nums[si]
    h = embed_ids(params, ids).reshape(B, pn, pn, cfg.c_vae)
    h = _up_bicubic(h, cfg.grid)
    f_hat = f_hat + phi_apply(params, cfg, h.astype(f_hat.dtype), si)
    if si + 1 < cfg.num_scales:
        nxt = _down_area(f_hat, cfg.patch_nums[si + 1])
    else:
        nxt = f_hat
    return f_hat, nxt


def encode_to_scales(
    params: Params, cfg: MSVQConfig, f: jax.Array
) -> Tuple[List[jax.Array], jax.Array]:
    """Encode-side greedy residual quantization (quant.py:135-166): latent
    ``f [B, pN, pN, C]`` → (per-scale token ids [B, pn²], reconstruction f̂).
    By construction the returned f̂ must equal replaying the ids through
    :func:`accumulate_scale` — the generate-side path (tested)."""
    B = f.shape[0]
    f_hat = jnp.zeros_like(f)
    ids_list: List[jax.Array] = []
    cb = params["codebook"]  # [V, C]
    for si, pn in enumerate(cfg.patch_nums):
        rest = f - f_hat
        z = _down_area(rest, pn).reshape(B * pn * pn, cfg.c_vae)
        d = (
            jnp.sum(z**2, -1, keepdims=True)
            - 2.0 * z @ cb.T
            + jnp.sum(cb**2, -1)[None, :]
        )
        idx = jnp.argmin(d, axis=-1).reshape(B, pn * pn)
        ids_list.append(idx)
        h = embed_ids(params, idx).reshape(B, pn, pn, cfg.c_vae)
        f_hat = f_hat + phi_apply(params, cfg, _up_bicubic(h, cfg.grid), si)
    return ids_list, f_hat


# ---------------------------------------------------------------------------
# decoder (CompVis f16 structure — weight-compatible with the reference
# checkpoints; basic_vae.py:163-226)
# ---------------------------------------------------------------------------

def _res_block(p: Params, x: jax.Array) -> jax.Array:
    """GroupNorm → SiLU → conv, twice; 1×1 shortcut on channel change."""
    h = nn.conv2d(p["conv1"], jax.nn.silu(nn.group_norm(x, p["norm1"])))
    h = nn.conv2d(p["conv2"], jax.nn.silu(nn.group_norm(h, p["norm2"])))
    skip = x if p.get("nin") is None else nn.conv2d(p["nin"], x)
    return skip + h


def _attn_block(p: Params, x: jax.Array) -> jax.Array:
    """Single-head spatial self-attention over HW (basic_vae.py:63-93)."""
    B, H, W, C = x.shape
    qkv = nn.conv2d(p["qkv"], nn.group_norm(x, p["norm"]))
    q, k, v = jnp.split(qkv.reshape(B, H * W, 3 * C), 3, axis=-1)
    w = jnp.einsum("bic,bjc->bij", q.astype(jnp.float32), k.astype(jnp.float32))
    w = jax.nn.softmax(w * (C ** -0.5), axis=-1)
    h = jnp.einsum("bij,bjc->bic", w, v.astype(jnp.float32)).astype(x.dtype)
    return x + nn.conv2d(p["proj"], h.reshape(B, H, W, C))


def run_decoder(dec: Params, f_hat: jax.Array, dt) -> jax.Array:
    """CompVis decoder subtree → images [B, H, W, 3] in [0, 1].

    Level count comes from the subtree itself (``len(dec["up"])``) and
    ``post_quant_conv`` is optional, so the same code decodes both the VAR
    VQVAE and an ingested Infinity BSQ tokenizer (models/bsq.py).
    """
    n_levels = len(dec["up"])
    x = f_hat.astype(dt)
    if dec.get("post_quant_conv") is not None:
        x = nn.conv2d(dec["post_quant_conv"], x)
    x = nn.conv2d(dec["conv_in"], x)
    mid = dec["mid"]
    x = _res_block(mid["block_1"], x)
    if mid.get("attn_1") is not None:
        x = _attn_block(mid["attn_1"], x)
    x = _res_block(mid["block_2"], x)
    for i_level in reversed(range(n_levels)):
        level = dec["up"][i_level]
        for bi, blk in enumerate(level["block"]):
            x = _res_block(blk, x)
            if level["attn"]:
                x = _attn_block(level["attn"][bi], x)
        if i_level != 0:
            B, h, w, c = x.shape
            x = jax.image.resize(x, (B, h * 2, w * 2, c), method="nearest")
            x = nn.conv2d(level["upsample"], x)
    x = jax.nn.silu(nn.group_norm(x, dec["norm_out"]))
    x = nn.conv2d(dec["conv_out"], x)
    return ((jnp.clip(x.astype(jnp.float32), -1.0, 1.0) + 1.0) / 2.0)


def decode_img(params: Params, cfg: MSVQConfig, f_hat: jax.Array) -> jax.Array:
    """f̂ [B, pN, pN, C] → images [B, H, W, 3] in [0, 1].

    The reference decodes then maps (clamp(-1,1)+1)/2 (``vqvae.py:62-63``,
    ``models/baseEGG.py:196-211``); here the [0,1] map stays in-graph so
    rewards consume the tensor directly. Includes the 3×3 ``post_quant_conv``
    (``vqvae.py:49,63``) ahead of the decoder proper.
    """
    return run_decoder(params["decoder"], f_hat, cfg.compute_dtype)

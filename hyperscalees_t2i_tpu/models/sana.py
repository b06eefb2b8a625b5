"""Sana-Sprint-style text-conditional DiT + TrigFlow/SCM samplers (pure JAX).

Capability parity with the reference's Sana family (``models/SanaSprint.py``,
which wraps diffusers' ``SanaTransformer2DModel``): linear-attention DiT over
DC-AE latents with AdaLN-single time conditioning, guidance embedding, cross
attention to cached text embeddings, gated mix-FFN (GLUMBConv) — plus the
hand-rolled one-step TrigFlow/SCM sampler math from
``models/SanaSprint.py:82-164`` and a principled multi-step TrigFlow sampler
(the reference's ``SanaPipelineES`` role, ``models/SanaSprint.py:280-503``).

TPU-first structure (NOT a port):
- params are one pytree; transformer blocks are *stacked* ``[L, ...]`` arrays
  consumed by ``lax.scan`` — one trace regardless of depth;
- LoRA deltas ride a separate flat adapter tree (see ``lora.py``) so the ES
  population vmaps over adapters only;
- channels-last NHWC latents, bf16 compute / f32 params & norms.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..lora import LoRASpec, lookup, slice_layer
from . import nn

Params = Dict[str, Any]

# Reference default target list (unifed_es.py:391).
SANA_LORA_TARGETS: Tuple[str, ...] = (
    "to_q", "to_k", "to_v", "to_out", "linear_1", "linear_2", "proj_out", r"time_embed/linear",
)


@dataclasses.dataclass(frozen=True)
class SanaConfig:
    """Architecture + sampler constants.

    Defaults mirror the Sana Sprint 1.6B 1024px geometry (32-ch DC-AE f32
    latents, 32×32 latent grid, patch 1); tests shrink everything.
    """

    in_channels: int = 32
    out_channels: int = 32
    patch_size: int = 1
    d_model: int = 2240
    n_layers: int = 20
    n_heads: int = 70
    cross_n_heads: int = 20
    caption_dim: int = 2304
    ff_ratio: float = 2.5
    guidance_embeds: bool = True
    guidance_embeds_scale: float = 0.1
    sigma_data: float = 0.5
    time_freq_dim: int = 256
    compute_dtype: Any = jnp.bfloat16
    # activation rematerialization over the scan-over-depth blocks
    # (models/nn.py remat_wrap): "none" | "blocks" | "full". θ-trajectory is
    # bit-identical across modes (tests/test_memopt.py).
    remat: str = "none"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def lora_spec(self, rank: int = 8, alpha: float = 16.0) -> LoRASpec:
        return LoRASpec(rank=rank, alpha=alpha, targets=SANA_LORA_TARGETS)


def init_sana(key: jax.Array, cfg: SanaConfig) -> Params:
    d, L = cfg.d_model, cfg.n_layers
    ks = jax.random.split(key, 20)
    hidden2 = int(round(d * cfg.ff_ratio)) * 2
    params: Params = {
        "patch_embed": nn.conv_init(ks[0], cfg.patch_size, cfg.patch_size, cfg.in_channels, d),
        "caption_norm": nn.norm_init(cfg.caption_dim, bias=False),
        "caption_proj": {
            "linear_1": nn.dense_init(ks[1], cfg.caption_dim, d),
            "linear_2": nn.dense_init(ks[2], d, d),
        },
        "time_embed": {
            "timestep": nn.mlp_embedder_init(ks[3], cfg.time_freq_dim, d),
            "linear": nn.dense_init(ks[4], d, 6 * d),
        },
        "blocks": {
            "scale_shift_table": jax.random.normal(ks[5], (L, 6, d), jnp.float32) / d**0.5,
            "attn1": {
                "to_q": nn.stacked_dense_init(ks[6], L, d, d, bias=False),
                "to_k": nn.stacked_dense_init(ks[7], L, d, d, bias=False),
                "to_v": nn.stacked_dense_init(ks[8], L, d, d, bias=False),
                "to_out": nn.stacked_dense_init(ks[9], L, d, d),
            },
            "attn2": {
                "to_q": nn.stacked_dense_init(ks[10], L, d, d, bias=False),
                "to_k": nn.stacked_dense_init(ks[11], L, d, d, bias=False),
                "to_v": nn.stacked_dense_init(ks[12], L, d, d, bias=False),
                "to_out": nn.stacked_dense_init(ks[13], L, d, d),
            },
            "ff": {
                "conv_inverted": {
                    "kernel": jax.random.normal(ks[14], (L, 1, 1, d, hidden2), jnp.float32) / d**0.5,
                    "bias": jnp.zeros((L, hidden2), jnp.float32),
                },
                "conv_depth": {
                    "kernel": jax.random.normal(ks[15], (L, 3, 3, 1, hidden2), jnp.float32) / 3.0,
                    "bias": jnp.zeros((L, hidden2), jnp.float32),
                },
                "conv_point": {
                    "kernel": jax.random.normal(ks[16], (L, 1, 1, hidden2 // 2, d), jnp.float32)
                    / (hidden2 // 2) ** 0.5,
                },
            },
        },
        "scale_shift_table": jax.random.normal(ks[17], (2, d), jnp.float32) / d**0.5,
        "proj_out": nn.dense_init(
            ks[18], d, cfg.patch_size * cfg.patch_size * cfg.out_channels
        ),
    }
    if cfg.guidance_embeds:
        params["time_embed"]["guidance"] = nn.mlp_embedder_init(ks[19], cfg.time_freq_dim, d)
    return params


def _split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    B, Lx, D = x.shape
    return x.reshape(B, Lx, n_heads, D // n_heads)


def _merge_heads(x: jax.Array) -> jax.Array:
    B, Lx, H, Dh = x.shape
    return x.reshape(B, Lx, H * Dh)


def sana_forward(
    params: Params,
    cfg: SanaConfig,
    latents: jax.Array,  # [B, H, W, C_in]
    timestep: jax.Array,  # [B] — SCM timestep in (0, 1)
    caption: jax.Array,  # [B, Ltxt, caption_dim]
    caption_mask: Optional[jax.Array] = None,  # [B, Ltxt] bool/int
    guidance: Optional[jax.Array] = None,  # [B] — pre-scaled guidance value
    lora: Optional[Params] = None,
    lora_scale: float = 1.0,
) -> jax.Array:
    """ε-prediction forward pass. Returns [B, H, W, C_out] in float32."""
    B, H, W, _ = latents.shape
    d, p = cfg.d_model, cfg.patch_size
    hw = (H // p, W // p)
    dt = cfg.compute_dtype

    # device-time scopes (obs/xla_cost.INNER_SCOPES): names only
    with jax.named_scope("dit_embed_out"):
        x = nn.conv2d(params["patch_embed"], latents.astype(dt), stride=p)
        x = x.reshape(B, hw[0] * hw[1], d)

        # --- AdaLN-single conditioning (timestep ⊕ guidance) ---------------
        t_emb = nn.mlp_embedder(
            params["time_embed"]["timestep"], nn.timestep_embedding(timestep, cfg.time_freq_dim)
        )
        if cfg.guidance_embeds:
            g = guidance if guidance is not None else jnp.zeros((B,), jnp.float32)
            t_emb = t_emb + nn.mlp_embedder(
                params["time_embed"]["guidance"], nn.timestep_embedding(g, cfg.time_freq_dim)
            )
        shared6 = nn.dense(
            params["time_embed"]["linear"],
            jax.nn.silu(t_emb),
            lookup(lora, "time_embed/linear"),
            lora_scale,
        ).reshape(B, 6, d)

        # --- caption projection ---------------------------------------------
        c = nn.rms_norm(caption.astype(dt), params["caption_norm"])
        c = nn.dense(params["caption_proj"]["linear_1"], c, lookup(lora, "caption_proj/linear_1"), lora_scale)
        c = nn.dense(params["caption_proj"]["linear_2"], jax.nn.silu(c), lookup(lora, "caption_proj/linear_2"), lora_scale)

    # --- blocks: lax.scan over stacked layers -------------------------------
    block_params = params["blocks"]
    block_lora = {
        name: lookup(lora, f"blocks/{name}")
        for name in (
            "attn1/to_q", "attn1/to_k", "attn1/to_v", "attn1/to_out",
            "attn2/to_q", "attn2/to_k", "attn2/to_v", "attn2/to_out",
        )
    }
    block_lora = {k: v for k, v in block_lora.items() if v is not None}

    def body(carry, layer_idx):
        xc = carry
        bp = jax.tree_util.tree_map(lambda a: a[layer_idx], block_params)
        bl = {k: slice_layer(v, layer_idx) for k, v in block_lora.items()}

        table = bp["scale_shift_table"].astype(jnp.float32)  # [6, d]
        mods = table[None] + shared6  # [B, 6, d]
        shift_msa, scale_msa, gate_msa, shift_mlp, scale_mlp, gate_mlp = [
            m.astype(dt)[:, None, :] for m in jnp.moveaxis(mods, 1, 0)
        ]

        # self attention: ReLU linear attention (no L×L matrix)
        with jax.named_scope("dit_self_attn"):
            h = nn.layer_norm(xc) * (1 + scale_msa) + shift_msa
            q = _split_heads(nn.dense(bp["attn1"]["to_q"], h, bl.get("attn1/to_q"), lora_scale), cfg.n_heads)
            k_ = _split_heads(nn.dense(bp["attn1"]["to_k"], h, bl.get("attn1/to_k"), lora_scale), cfg.n_heads)
            v_ = _split_heads(nn.dense(bp["attn1"]["to_v"], h, bl.get("attn1/to_v"), lora_scale), cfg.n_heads)
            a = _merge_heads(nn.linear_attention(q, k_, v_))
            a = nn.dense(bp["attn1"]["to_out"], a, bl.get("attn1/to_out"), lora_scale)
            xc = xc + gate_msa * a

        # cross attention to caption (vanilla softmax, un-normed query — Sana layout)
        with jax.named_scope("dit_cross_attn"):
            q = _split_heads(nn.dense(bp["attn2"]["to_q"], xc, bl.get("attn2/to_q"), lora_scale), cfg.cross_n_heads)
            k2 = _split_heads(nn.dense(bp["attn2"]["to_k"], c, bl.get("attn2/to_k"), lora_scale), cfg.cross_n_heads)
            v2 = _split_heads(nn.dense(bp["attn2"]["to_v"], c, bl.get("attn2/to_v"), lora_scale), cfg.cross_n_heads)
            a2 = _merge_heads(nn.attention(q, k2, v2, mask=caption_mask))
            xc = xc + nn.dense(bp["attn2"]["to_out"], a2, bl.get("attn2/to_out"), lora_scale)

        # gated mix-FFN
        with jax.named_scope("dit_ffn"):
            h = nn.layer_norm(xc) * (1 + scale_mlp) + shift_mlp
            ff = bp["ff"]
            y = nn.conv2d(ff["conv_inverted"], h.reshape(B, hw[0], hw[1], d))
            y = jax.nn.silu(y)
            y = nn.conv2d(ff["conv_depth"], y, groups=y.shape[-1])
            y, gate = jnp.split(y, 2, axis=-1)
            y = (y * jax.nn.silu(gate))
            y = nn.conv2d(ff["conv_point"], y).reshape(B, hw[0] * hw[1], d)
            xc = xc + gate_mlp * y
        # block boundary: the only value the "blocks" remat policy saves —
        # attention/FFN interiors recompute instead of persisting per layer
        xc = nn.remat_name(xc, cfg.remat, "sana_block")
        return xc, None

    x = nn.stacked_scan(body, x, cfg.n_layers, cfg.remat, "sana_block")

    # --- output head --------------------------------------------------------
    with jax.named_scope("dit_embed_out"):
        table = params["scale_shift_table"].astype(jnp.float32)[None] + t_emb[:, None, :]  # [B,2,d]
        shift, scale = table[:, 0, None, :].astype(dt), table[:, 1, None, :].astype(dt)
        x = nn.layer_norm(x) * (1 + scale) + shift
        x = nn.dense(params["proj_out"], x, lookup(lora, "proj_out"), lora_scale)

        # unpatchify → NHWC
        x = x.reshape(B, hw[0], hw[1], p, p, cfg.out_channels)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(B, H, W, cfg.out_channels)
    return x.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Samplers
# ---------------------------------------------------------------------------

def _per_image_normal(
    key: jax.Array,
    item_index: Optional[jax.Array],
    B: int,
    shape: Tuple[int, ...],
) -> jax.Array:
    """[B, *shape] standard normals with one folded key per global position."""
    idx = jnp.arange(B) if item_index is None else item_index
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)
    return jax.vmap(lambda k: jax.random.normal(k, shape, jnp.float32))(keys)


def one_step_generate(
    params: Params,
    cfg: SanaConfig,
    prompt_embeds: jax.Array,  # [B, Ltxt, caption_dim]
    prompt_mask: Optional[jax.Array],
    key: jax.Array,
    guidance_scale: float = 1.0,
    latent_hw: Tuple[int, int] = (32, 32),
    lora: Optional[Params] = None,
    lora_scale: float = 1.0,
    alpha_t: float = 0.267,
    sigma_t: float = 0.964,
    item_index: Optional[jax.Array] = None,
) -> jax.Array:
    """One-step TrigFlow/SCM generation → decoder-scale latents.

    Exact math of the reference's hand-rolled sampler
    (``models/SanaSprint.py:82-164``): latents ~ N(0, σ_d²); model evaluated at
    t≈π/2 with SCM timestep sin t/(cos t+sin t); ε-pred combined via the SCM
    formula; "scheduler one step" uses the hardcoded α_t=0.267, σ_t=0.964
    (SanaSprint.py:149-153); includes the NaN containment guard
    (SanaSprint.py:132-135) so exploded ES candidates can't poison the decode.

    Per-image noise keys are ``fold_in(key, item_index[i])`` (default
    ``arange(B)``) — the same value no matter how the batch is chunked or
    sharded, the reference's chunk-invariance contract
    (``models/zImageTurbo.py:368-371``) generalized to every generator.

    Returns latents already divided by σ_d — feed to the DC-AE decoder after
    dividing by the VAE scaling factor (the backend does that).
    """
    B = prompt_embeds.shape[0]
    h, w = latent_hw
    sd = cfg.sigma_data

    latents = _per_image_normal(key, item_index, B, (h, w, cfg.in_channels)) * sd
    latent_in = latents / sd

    t = jnp.full((B,), 1.571, jnp.float32)
    scm_t = jnp.sin(t) / (jnp.cos(t) + jnp.sin(t))  # [B]
    s = scm_t[:, None, None, None]

    guidance = jnp.full((B,), guidance_scale * cfg.guidance_embeds_scale, jnp.float32)

    eps_pred = sana_forward(
        params, cfg, latent_in, scm_t, prompt_embeds, prompt_mask, guidance, lora, lora_scale
    )
    eps_pred = jnp.nan_to_num(eps_pred, nan=0.0, posinf=0.0, neginf=0.0)

    noise_pred = ((1 - 2 * s) * latent_in + (1 - 2 * s + 2 * s**2) * eps_pred) / jnp.sqrt(
        s**2 + (1 - s) ** 2
    )
    noise_pred = noise_pred * sd

    pred_x0 = alpha_t * latents - sigma_t * noise_pred
    return pred_x0 / sd


def multistep_generate(
    params: Params,
    cfg: SanaConfig,
    prompt_embeds: jax.Array,
    prompt_mask: Optional[jax.Array],
    key: jax.Array,
    guidance_scale: float = 4.5,
    num_steps: int = 2,
    max_timestep: float = 1.57080,
    latent_hw: Tuple[int, int] = (32, 32),
    lora: Optional[Params] = None,
    lora_scale: float = 1.0,
    item_index: Optional[jax.Array] = None,
) -> jax.Array:
    """Multi-step TrigFlow consistency sampling (the reference's pipeline mode,
    ``models/SanaSprint.py:280-503`` / diffusers ``SanaSprintPipeline`` +
    SCM scheduler): at each t, convert the ε-pred to the TrigFlow prediction
    F, denoise x0 = cos(t)·x − sin(t)·F, then re-noise to the next timestep
    with fresh noise. Timesteps run linearly from ``max_timestep`` to 0.
    Per-image noise keys fold in the global item index (chunk/shard-invariant).
    """
    B = prompt_embeds.shape[0]
    h, w = latent_hw
    sd = cfg.sigma_data
    key, nkey = jax.random.split(key)
    x = _per_image_normal(nkey, item_index, B, (h, w, cfg.in_channels)) * sd
    guidance = jnp.full((B,), guidance_scale * cfg.guidance_embeds_scale, jnp.float32)

    timesteps = jnp.linspace(max_timestep, 0.0, num_steps + 1)
    for i in range(num_steps):  # tiny static loop — unrolled under jit
        t = jnp.full((B,), timesteps[i], jnp.float32)
        scm_t = jnp.sin(t) / (jnp.cos(t) + jnp.sin(t))
        s = scm_t[:, None, None, None]
        eps_pred = sana_forward(
            params, cfg, x / sd, scm_t, prompt_embeds, prompt_mask, guidance, lora, lora_scale
        )
        eps_pred = jnp.nan_to_num(eps_pred, nan=0.0, posinf=0.0, neginf=0.0)
        F = ((1 - 2 * s) * (x / sd) + (1 - 2 * s + 2 * s**2) * eps_pred) / jnp.sqrt(
            s**2 + (1 - s) ** 2
        )
        F = F * sd
        tb = timesteps[i]
        x0 = jnp.cos(tb) * x - jnp.sin(tb) * F
        t_next = timesteps[i + 1]
        key, nkey = jax.random.split(key)
        noise = _per_image_normal(nkey, item_index, B, x.shape[1:]) * sd
        x = jnp.cos(t_next) * x0 + jnp.sin(t_next) * noise
    return x / sd

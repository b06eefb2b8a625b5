"""Class-conditional next-scale autoregressive transformer (VAR-style).

Capability parity with the reference's vendored VAR
(``/root/reference/VAR_models/var.py`` — class-sos, AdaLN self-attention
blocks, per-scale CFG ramp, KV-cached ``autoregressive_infer_cfg``;
``VAR_models/basic_var.py`` — AdaLN 6-way modulation blocks).

TPU-first redesign (NOT a port):

- the scale loop is a *Python* loop over the static ``patch_nums`` pyramid, so
  every scale step has static shapes and the whole 10-scale generation + VQ
  accumulation + decode compiles into ONE XLA program (the reference runs 10
  eager transformer passes with growing tensor shapes, var.py:160-187);
- block params are stacked ``[depth, ...]`` and consumed by ``lax.scan`` —
  one trace for any depth; the KV cache is the row blocks written so far,
  ``[depth, B, n_s, H, dh]`` a scale, and grows by a block at a time with
  static shapes (torch's growing ``torch.cat`` cache, basic_var.py:85-109,
  without the copy; a preallocated ``[.., L, ..]`` buffer carried through
  the scan cost a fill and a copy of the whole of it every scale);
- CFG runs as a fused ``2B`` batch (cond rows then uncond rows) with the
  per-scale ramp ``t = cfg·si/(S-1)`` applied to the logit pair
  (var.py:172-173);
- LoRA deltas apply inside every targeted dense (ES populations vmap over
  the adapter tree only).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..lora import LoRASpec, lookup, slice_layer
from ..obs import note_program_geometry, scope as obs_scope
from ..ops.attention import decode_attention
from ..ops.quant import resolve_kernel
from ..ops.sampling import sample_top_k_top_p
from . import msvq, nn

Params = Dict[str, Any]

# Reference ES targets the attention/MLP projections of the VAR transformer
# (unifed_es.py:406 preset, applied through PEFT name matching).
# Anchored under blocks/ so the VQVAE decoder's attention convs (which also
# contain a "qkv" path segment) are never LoRA-targeted — the reference only
# adapts the AR transformer (es_backend.py:319-368).
VAR_LORA_TARGETS: Tuple[str, ...] = (
    "blocks/qkv", "blocks/attn_proj", "blocks/fc1", "blocks/fc2",
)


@dataclasses.dataclass(frozen=True)
class VARConfig:
    num_classes: int = 1000
    depth: int = 16
    d_model: int = 1024  # reference: depth*64 (var_d16 → 1024)
    n_heads: int = 16
    ff_ratio: float = 4.0
    patch_nums: Tuple[int, ...] = (1, 2, 3, 4, 5, 6, 8, 10, 13, 16)
    vq: msvq.MSVQConfig = dataclasses.field(default_factory=msvq.MSVQConfig)
    # sampler defaults (reference generate defaults: cfg 1.5/4.0 era, top_k
    # 900, top_p 0.96 — models/VAR.py generate signature)
    cfg_scale: float = 4.0
    top_k: int = 900
    top_p: float = 0.96
    temperature: float = 1.0
    # QK-l2-normalized attention with a learned per-head log-scale, softmax
    # scale 1 (basic_var.py:66-70,101-105). True in every released VAR build
    # (build_vae_var default, VAR_models/__init__.py:15) — required for the
    # var_d{16,20,24,30}.pth weight converters.
    attn_l2_norm: bool = True
    compute_dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def seq_len(self) -> int:
        return int(sum(p * p for p in self.patch_nums))

    @property
    def uncond_label(self) -> int:
        return self.num_classes  # extra row in the class table (CFG null)

    def lora_spec(self, rank: int = 8, alpha: float = 16.0) -> LoRASpec:
        return LoRASpec(rank=rank, alpha=alpha, targets=VAR_LORA_TARGETS)


def init_var(key: jax.Array, cfg: VARConfig) -> Params:
    d, D, H = cfg.d_model, cfg.depth, cfg.n_heads
    hid = int(d * cfg.ff_ratio)
    S, L = len(cfg.patch_nums), cfg.seq_len
    ks = jax.random.split(key, 16)
    params: Params = {
        "class_emb": jax.random.normal(ks[0], (cfg.num_classes + 1, d), jnp.float32) * 0.02,
        "pos_start": jax.random.normal(ks[1], (1, 1, d), jnp.float32) * 0.02,
        "lvl_emb": jax.random.normal(ks[2], (S, d), jnp.float32) * 0.02,
        "pos_emb": jax.random.normal(ks[3], (L, d), jnp.float32) * 0.02,
        "word_embed": nn.dense_init(ks[4], cfg.vq.c_vae, d),
        "blocks": {
            "ada_lin": nn.stacked_dense_init(ks[5], D, d, 6 * d, std=0.02),
            "qkv": nn.stacked_dense_init(ks[6], D, d, 3 * d),
            "attn_proj": nn.stacked_dense_init(ks[7], D, d, d, std=0.02 / math.sqrt(2 * D)),
            "fc1": nn.stacked_dense_init(ks[8], D, d, hid),
            "fc2": nn.stacked_dense_init(ks[9], D, hid, d, std=0.02 / math.sqrt(2 * D)),
        },
        "head_ada": nn.dense_init(ks[10], d, 2 * d, std=0.02),
        # (scale_mul added below when attn_l2_norm)
        "head": nn.dense_init(ks[11], d, cfg.vq.vocab_size, std=0.02),
        "vq": msvq.init_msvq(ks[12], cfg.vq),
    }
    if cfg.attn_l2_norm:
        # learned per-head log attention scale, init log(4) (basic_var.py:69)
        params["blocks"]["scale_mul"] = jnp.full((D, H), math.log(4.0), jnp.float32)
    return params


# QK-l2 attention (basic_var.py:101-105) — shared helper in nn.py
_qk_l2 = nn.qk_l2


def _scale_slices(cfg: VARConfig):
    """Static (start, n) offsets of each scale in the flat L-sequence."""
    out, pos = [], 0
    for pn in cfg.patch_nums:
        out.append((pos, pn * pn))
        pos += pn * pn
    return out


def _blocks_step(
    params: Params,
    cfg: VARConfig,
    x: jax.Array,  # [B2, n, d] current scale's token activations
    cond6_all: jax.Array,  # [depth, B2, 6, d] precomputed AdaLN modulation
    caches: Tuple[Any, Any],  # K, V: the row blocks written so far, [depth, B2, n_s, H·dh] each
    pos: int,  # static prefix length
    lora: Optional[Params],
    lora_scale: float,
) -> Tuple[jax.Array, Tuple[Tuple[jax.Array, ...], Tuple[jax.Array, ...]]]:
    """Run all transformer blocks on one scale's tokens, growing the cache.

    ``pos`` is static (Python int) per scale, so cache reads lower to
    static-slice ops. Layers run under ``lax.scan`` with stacked params. The
    cache is append-only and kept as it was written: each of K and V is the
    tuple of the earlier scales' row blocks (a single array stands for one
    block, of which the first ``pos`` rows count). The scan reads them,
    returns this scale's ``n`` rows a layer, and that block is appended — no
    row is copied again after the scale that wrote it, except into the
    operand a layer's attention reads. A row is kept as the ``qkv`` product
    wrote it, its ``H`` heads side by side (``[.., H·dh]``, 1024 lanes): that is
    the block ``decode_attention`` reads, and the TPU's tiled layout of a
    ``[.., H, dh]`` array is another (64 lanes padded to 128), so a cache kept
    by head is re-laid block by block, layer by layer, on its way into the
    call (PERF.md §6, PR 36). A cache preallocated at the whole
    sequence's length and passed through the scan is filled and copied whole
    once a scale, and each layer's whole cache once a layer: a scan's outputs
    are a fresh zero-filled buffer and its inputs are not the loop's to
    overwrite.
    """
    d, H, dh = cfg.d_model, cfg.n_heads, cfg.head_dim
    B2, n, _ = x.shape
    dt = cfg.compute_dtype
    blk = params["blocks"]

    def layer(carry, inp):
        x, = carry
        li, kP, vP, cond6 = inp  # kP/vP: this layer's row blocks so far, [B2, n_s, H·dh] each
        g1, s1, b1, g2, s2, b2 = (cond6[:, i][:, None, :] for i in range(6))

        h = nn.layer_norm(x) * (1.0 + s1.astype(dt)) + b1.astype(dt)
        qkv_p = nn.slice_stacked(blk["qkv"], li)
        qkv = nn.dense(qkv_p, h, slice_layer(lookup(lora, "blocks/qkv"), li), lora_scale)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B2, n, H, dh)
        k = k.reshape(B2, n, H, dh)
        v = v.reshape(B2, n, H, dh)
        if cfg.attn_l2_norm:
            q, k = _qk_l2(q, k, blk["scale_mul"][li])
            sm_scale = 1.0
        else:
            # reference uses 0.25/sqrt(dh) in the non-l2 branch
            # (VAR_models/basic_var.py:72), not the usual 1/sqrt(dh)
            sm_scale = 0.25 / math.sqrt(dh)
        k, v = k.astype(dt).reshape(B2, n, d), v.astype(dt).reshape(B2, n, d)
        # visible context: all written positions [0, pos+n) (static kv_len).
        # Pallas flash path on TPU keeps the logit tile in VMEM instead of a
        # [B2, H, n, L] f32 HBM tensor per scale (ops/attention.py); the view
        # by head is a view: the kernel reads the rows as they lie.
        by_head = lambda blocks: jnp.concatenate(blocks, axis=1).reshape(B2, pos + n, H, dh)
        out = (
            decode_attention(q, by_head([*kP, k]), by_head([*vP, v]), kv_len=pos + n, sm_scale=sm_scale)
            .astype(dt)
            .reshape(B2, n, d)
        )
        proj_p = nn.slice_stacked(blk["attn_proj"], li)
        out = nn.dense(proj_p, out, slice_layer(lookup(lora, "blocks/attn_proj"), li), lora_scale)
        x = x + g1.astype(dt) * out

        h2 = nn.layer_norm(x) * (1.0 + s2.astype(dt)) + b2.astype(dt)
        fc1_p = nn.slice_stacked(blk["fc1"], li)
        h2 = nn.dense(fc1_p, h2, slice_layer(lookup(lora, "blocks/fc1"), li), lora_scale)
        h2 = jax.nn.gelu(h2, approximate=True)
        fc2_p = nn.slice_stacked(blk["fc2"], li)
        h2 = nn.dense(fc2_p, h2, slice_layer(lookup(lora, "blocks/fc2"), li), lora_scale)
        x = x + g2.astype(dt) * h2.astype(dt)

        return (x,), (k, v)

    kPrev, vPrev = (c if isinstance(c, tuple) else (c[:, :, :pos].reshape(cfg.depth, B2, pos, d),)
                    for c in caches)
    (x,), (kNew, vNew) = jax.lax.scan(
        layer,
        (x.astype(dt),),
        (jnp.arange(cfg.depth), kPrev, vPrev, cond6_all),
    )
    return x, (kPrev + (kNew,), vPrev + (vNew,))


def generate(
    params: Params,
    cfg: VARConfig,
    labels: jax.Array,  # [B] int class ids
    key: jax.Array,
    cfg_scale: Optional[float] = None,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    lora: Optional[Params] = None,
    lora_scale: float = 1.0,
    decode: bool = True,
    item_index: Optional[jax.Array] = None,
) -> jax.Array:
    """KV-cached multi-scale AR generation (var.py:127-190 semantics).

    Returns images [B, H, W, 3] in [0,1] (or f̂ latents when ``decode=False``).
    One jitted program: 10 static-shape scale steps + VQ pyramid + decoder.
    Token sampling keys fold in each image's *global* batch position
    (``item_index``, default ``arange(B)``), so outputs are invariant to how
    the batch is chunked or sharded over the ``data`` mesh axis.
    """
    cfgs = cfg.cfg_scale if cfg_scale is None else cfg_scale
    tk = cfg.top_k if top_k is None else top_k
    tp = cfg.top_p if top_p is None else top_p
    B = labels.shape[0]
    item_idx = jnp.arange(B) if item_index is None else item_index
    d, H, dh, S = cfg.d_model, cfg.n_heads, cfg.head_dim, len(cfg.patch_nums)
    L = cfg.seq_len
    dt = cfg.compute_dtype
    vq_cfg = cfg.vq

    # device-time scopes (obs/xla_cost.TOP_SCOPES / INNER_SCOPES): names only
    with obs_scope("generate"):
        # CFG super-batch: cond rows then uncond rows (var.py:151).
        lbl2 = jnp.concatenate([labels, jnp.full_like(labels, cfg.uncond_label)])
        cond = params["class_emb"][lbl2]  # [2B, d]
        # AdaLN modulation per layer precomputed once (class cond is constant
        # through generation): [depth, 2B, 6, d].
        ada = params["blocks"]["ada_lin"]
        c = jax.nn.silu(cond.astype(jnp.float32))
        cond6_all = (
            jnp.einsum("bd,lde->lbe", c, resolve_kernel(ada, jnp.float32)) + ada["bias"][:, None, :]
        ).reshape(cfg.depth, 2 * B, 6, d)

        # head AdaLN (scale, shift) from the same cond (AdaLNBeforeHead).
        hs, hb = jnp.split(nn.dense(params["head_ada"], jax.nn.silu(cond)), 2, axis=-1)

        # the KV cache starts empty and grows a block of rows a scale (_blocks_step)
        kC, vC = (), ()
        # for the program record of the enclosing compile: the cache's shape
        # when full, whose fills and copies obs/xla_cost.kv_cache_whole_ops counts
        note_program_geometry(kv_cache_shape=(cfg.depth, 2 * B, L, H * dh))
        f_hat = jnp.zeros((B, vq_cfg.grid, vq_cfg.grid, vq_cfg.c_vae), jnp.float32)

        # first scale input: sos from class embedding + start/level/pos tables
        x = (
            cond[:, None, :]
            + params["pos_start"]
            + params["lvl_emb"][0][None, None, :]
            + params["pos_emb"][None, :1, :]
        ).astype(dt)

        slices = _scale_slices(cfg)
        for si, (pos, n) in enumerate(slices):
            with jax.named_scope(f"scale{si}"):
                with jax.named_scope("blocks"):
                    h, (kC, vC) = _blocks_step(params, cfg, x, cond6_all, (kC, vC), pos, lora, lora_scale)
                with jax.named_scope("head"):
                    h = nn.layer_norm(h) * (1.0 + hs[:, None, :].astype(dt)) + hb[:, None, :].astype(dt)
                    logits = nn.dense(params["head"], h).astype(jnp.float32)  # [2B, n, V]
                    t = cfgs * si / max(S - 1, 1)  # per-scale CFG ramp (var.py:172)
                    lg = (1.0 + t) * logits[:B] - t * logits[B:]
                with jax.named_scope("sample"):
                    k_si = jax.random.fold_in(key, si)
                    img_keys = jax.vmap(lambda i: jax.random.fold_in(k_si, i))(item_idx)
                    ids = jax.vmap(
                        lambda kk, row: sample_top_k_top_p(
                            kk, row, top_k=tk, top_p=tp, temperature=cfg.temperature
                        )
                    )(img_keys, lg)  # [B, n]
                with jax.named_scope("msvq_accumulate"):
                    f_hat, nxt = msvq.accumulate_scale(params["vq"], vq_cfg, f_hat, ids, si)
                if si + 1 < S:
                    pn1 = cfg.patch_nums[si + 1]
                    n1 = pn1 * pn1
                    tok = nxt.reshape(B, n1, vq_cfg.c_vae)
                    emb = nn.dense(params["word_embed"], tok.astype(jnp.float32))
                    nxt_x = (
                        emb
                        + params["lvl_emb"][si + 1][None, None, :]
                        + params["pos_emb"][None, pos + n : pos + n + n1, :]
                    )
                    x = jnp.concatenate([nxt_x, nxt_x]).astype(dt)  # cond+uncond share input

    if not decode:
        return f_hat
    with obs_scope("decode"):
        return msvq.decode_img(params["vq"], vq_cfg, f_hat)


def forward_teacher(
    params: Params,
    cfg: VARConfig,
    labels: jax.Array,  # [B]
    scale_inputs: jax.Array,  # [B, L, c_vae] ground-truth next-scale inputs
    lora: Optional[Params] = None,
    lora_scale: float = 1.0,
) -> jax.Array:
    """Teacher-forced full-sequence forward → logits [B, L, V].

    The reference's training-path ``VAR.forward`` (var.py:192-234): block-wise
    causal attention (tokens see all *completed* scales plus their own scale).
    Used here for tests (must match the KV-cached path) and for future
    likelihood work — ES training itself never needs gradients.
    """
    B, L = scale_inputs.shape[0], cfg.seq_len
    d, H, dh, S = cfg.d_model, cfg.n_heads, cfg.head_dim, len(cfg.patch_nums)
    dt = cfg.compute_dtype

    cond = params["class_emb"][labels]
    ada = params["blocks"]["ada_lin"]
    c = jax.nn.silu(cond.astype(jnp.float32))
    cond6_all = (
        jnp.einsum("bd,lde->lbe", c, resolve_kernel(ada, jnp.float32)) + ada["bias"][:, None, :]
    ).reshape(cfg.depth, B, 6, d)

    # token embeddings: first scale = sos, later scales = word_embed(inputs)
    emb = nn.dense(params["word_embed"], scale_inputs.astype(jnp.float32))  # [B, L, d]
    sos = cond[:, None, :] + params["pos_start"]
    emb = jnp.concatenate([sos + emb[:, :1] * 0.0, emb[:, 1:]], axis=1)
    lvl = jnp.concatenate(
        [jnp.full((pn * pn,), i, jnp.int32) for i, pn in enumerate(cfg.patch_nums)]
    )
    x = (emb + params["lvl_emb"][lvl][None] + params["pos_emb"][None]).astype(dt)

    # block-causal mask: query scale i sees key scale j iff j <= i
    mask = (lvl[:, None] >= lvl[None, :])  # [L, L]

    blk = params["blocks"]

    def layer(carry, inp):
        x, = carry
        li, cond6 = inp
        g1, s1, b1, g2, s2, b2 = (cond6[:, i][:, None, :] for i in range(6))
        h = nn.layer_norm(x) * (1.0 + s1.astype(dt)) + b1.astype(dt)
        qkv_p = nn.slice_stacked(blk["qkv"], li)
        qkv = nn.dense(qkv_p, h, slice_layer(lookup(lora, "blocks/qkv"), li), lora_scale)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, L, H, dh)
        k = k.reshape(B, L, H, dh)
        v = v.reshape(B, L, H, dh)
        if cfg.attn_l2_norm:
            q, k = _qk_l2(q, k, blk["scale_mul"][li])
            sm_scale = 1.0
        else:
            # reference uses 0.25/sqrt(dh) in the non-l2 branch
            # (VAR_models/basic_var.py:72), not the usual 1/sqrt(dh)
            sm_scale = 0.25 / math.sqrt(dh)
        attn = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
        attn = jnp.where(mask[None, None], attn * sm_scale, -1e30)
        attn = jax.nn.softmax(attn, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", attn.astype(dt), v.astype(dt)).reshape(B, L, d)
        proj_p = nn.slice_stacked(blk["attn_proj"], li)
        out = nn.dense(proj_p, out, slice_layer(lookup(lora, "blocks/attn_proj"), li), lora_scale)
        x = x + g1.astype(dt) * out
        h2 = nn.layer_norm(x) * (1.0 + s2.astype(dt)) + b2.astype(dt)
        fc1_p = nn.slice_stacked(blk["fc1"], li)
        h2 = nn.dense(fc1_p, h2, slice_layer(lookup(lora, "blocks/fc1"), li), lora_scale)
        h2 = jax.nn.gelu(h2, approximate=True)
        fc2_p = nn.slice_stacked(blk["fc2"], li)
        h2 = nn.dense(fc2_p, h2, slice_layer(lookup(lora, "blocks/fc2"), li), lora_scale)
        x = x + g2.astype(dt) * h2.astype(dt)
        return (x,), None

    (x,), _ = jax.lax.scan(layer, (x,), (jnp.arange(cfg.depth), cond6_all))
    hs, hb = jnp.split(nn.dense(params["head_ada"], jax.nn.silu(cond)), 2, axis=-1)
    x = nn.layer_norm(x) * (1.0 + hs[:, None, :].astype(dt)) + hb[:, None, :].astype(dt)
    return nn.dense(params["head"], x).astype(jnp.float32)

"""Decoder language models as autoregressive image-token generators.

Three families stand behind one :func:`generate`, chosen by the ``model_type``
of a ``config.json``-shaped file (:func:`config_from_json`):

- the one this file writes down: multi-head latent attention (MLA) over a
  latent cache, a sigmoid top-k router over routed experts of which this chip
  holds a share (or all), one ungated shared expert, a multi-token-prediction
  (MTP) module. ``generate`` carries one latent cache a layer. Its
  ``model_type``s and the residual path each takes (one :func:`block`; what a
  file runs follows from its keys, there is no knob):

  - none, ``deepseek_v3``, ``pangu_ultra_moe``: **sandwich norm** around one
    residual vector a token, plain RoPE, top-k by the score;
  - ``xing4_0``: **pre-norm inside manifold-constrained hyper-connections**
    (``hc_mult`` residual streams a token, mixed by a Sinkhorn-projected
    matrix), yarn-scaled RoPE (``rope_scaling``), top-k by the score plus a
    selection bias (``topk_method: noaux_tc``);
- ``qwen3_next`` (``models/lm_hybrid.py``): Gated DeltaNet layers beside gated
  softmax attention, a softmax router with a sigmoid-gated shared expert.
  ``generate`` carries a recurrent state and conv window for some layers and a
  KV cache for the others, side by side;
- ``granitemoehybrid`` with no routed experts (``models/lm_ssm.py``): Mamba-2
  (SSD) mixers beside grouped-query attention without position embedding, a
  dense SwiGLU in every layer, published multipliers on the embedding, the
  residual branches and the logits, a head tied to the embedding. No router:
  ``generate`` returns no routing rows for it. Its layers run as a
  ``lax.scan`` over the periods of its layer pattern.

What they share lives here: the use the system makes of any of them
(:class:`GeneratorUse`: the share of a stated deployment this chip holds —
``experts_held``, ``expert_offset``, ``vocab_rows_held`` — and
``image_tokens``), ``routed_experts`` over ``ops/grouped.py`` with
``expert_factors``, and :func:`generate` itself: prefill, the ``lax.scan`` of
sampled positions, sampling, the image-id range, the VQ decode. A family is the
functions of :class:`Family`. No preset table: a user with a checkpoint
directory states sizes the same way.

The first family's layer equations (the plain float32 form is
``reference/lm_reference.py``, written from the same description and sharing
no code with this file):

- block, sandwich norm: ``h = x + N2(Attn(N1(x)))``, ``y = h + N4(FFN(N3(h)))``;
  pre-norm (``sandwich_norm: false``): the same without ``N2``, ``N4``;
- block, hyper-connections (``hc_mult`` = n > 0; *mHC*, arXiv:2512.24880): the
  residual is a stream ``X [n, C]`` a token, ``X₀`` = n copies of the
  embedding, the model's output ``Σ_rows X_L``. Each sub-layer ``F`` (attention
  or FFN behind its norm) has its own ``φ [nC, n + n + n²]``, ``b``, ``α``:
  ``x̃ = RMSNorm(vec(X))`` over nC (no weight, ``hc_eps``);
  ``H_pre = σ(α_pre · x̃φ_pre + b_pre)``, ``H_post = 2σ(α_post · x̃φ_post + b_post)``,
  ``H_res = SK(clip(α_res · mat(x̃φ_res) + b_res, clamp_min, clamp_max))`` with
  ``SK``: ``M = exp(·)``, then ``hc_sinkhorn_iters`` times every column divided
  by its sum + ``hc_eps``, then every row by its sum + ``hc_eps``;
  ``u = H_pre X``, ``y = F(u)``, ``X ← H_res X + H_postᵀ y``. The coefficient
  path (norm, product at ``highest``, Sinkhorn) is float32 (:data:`HC_DTYPE`),
  the stream and ``F`` are in the configuration's dtype;
- MLA: ``cq = Nq(u Wdq)``, ``q = cq Wuq`` → heads × (nope | rope);
  ``[ckv | kr] = u Wdkv``, ``ckv ← Nkv(ckv)``, ``[k_nope | v] = ckv Wukv``;
  RoPE on ``q_rope`` and on the one ``kr`` all heads share (rotate-half; with
  ``rope_scaling`` of type yarn the frequencies are blended between
  ``θ^(-2i/d)`` and that ÷ ``factor`` by the linear ramp between the dimensions
  that turn ``beta_fast`` and ``beta_slow`` times over the original context,
  and the softmax scale is × ``(0.1 · mscale_all_dim · ln factor + 1)²``); the **cache holds
  ``[ckv | rope(kr)]``** — ``kv_lora_rank + qk_rope_head_dim`` numbers a token
  a layer, not per-head K/V. Prefill expands K/V; a decode step uses the
  absorbed form (``Wukv``'s K half folded into the query, its V half applied
  after the weighted sum over ``ckv``), LoRA delta included;
- router: ``s = sigmoid(f32(u) Wrᵀ)``, top-k by ``s`` (``noaux_tc``: by
  ``s + e_score_correction_bias``, the weights still from ``s``; no groups),
  ``w = s_top / (Σ s_top + 1e-20) · routed_scaling_factor``;
  ``MoE(u) = Shared(u) + Σ_{e ∈ top-k, e held} w_e E_e(u)`` — the router keeps
  every output, the chip computes its own experts' part for the tokens routed
  to them, normalized over all k chosen; what absent experts would add is
  left out and no code stands in for them or their exchange;
- MTP: ``h' = Block([Nh(h_i) ; Ne(Emb(t_{i+1}))] Wp)`` (with hyper-connections
  the block runs over n copies of the projected input), shared final norm and
  head. In the model and the reference; not run by :func:`generate` (at plain
  sampling the family discards it).

Generation, any family: prefill the (padded, masked) prompt ids into what
the family carries (here the latent cache), then ``image_tokens.count`` steps
of ``lax.scan`` — embed the last id (the begin-of-image id first), one pass of
the blocks over the carried state, final norm, head over the rows held, the
image-id range of the logits, top-k/top-p sampling under a key shared by the
members — and the VQ decoder of ``models/msvq.py`` over the sampled grid.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
from pathlib import Path
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..lora import LoRASpec, effective_factor, lookup
from ..obs import note_program_geometry, scope as obs_scope
from ..ops import grouped
from ..ops.quant import kernel_shape, maybe_quantize_tree, resolve_kernel
from ..ops.sampling import sample_top_k_top_p
from . import msvq, nn

Params = Dict[str, Any]

# every MLA projection, the dense FFN, the shared expert and each held routed
# expert's three matrices; router, norms, embedding, head and MTP stay frozen
LM_LORA_TARGETS: Tuple[str, ...] = (
    r"^layers/\d+/mla/(wdq|wuq|wdkv|wukv|wo)$",
    r"^layers/\d+/ffn/(gate|up|down)$",
    r"^layers/\d+/moe/shared/(gate|up|down)$",
    r"^layers/\d+/moe/experts/(gate|up|down)$",
)

PUBLISHED_KEYS = (
    "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta", "rms_norm_eps",
    "intermediate_size", "moe_intermediate_size", "n_routed_experts", "num_experts_per_tok",
    "n_shared_experts", "routed_scaling_factor", "norm_topk_prob", "num_hidden_layers",
    "first_k_dense_replace", "num_nextn_predict_layers", "vocab_size",
)


# the ``model_type``s of the family this file writes down; the last takes its
# residual path, router and RoPE from further keys of its file
XING = "xing4_0"
MLA_MODEL_TYPES = (None, "deepseek_v3", "pangu_ultra_moe", XING)
XING_KEYS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")
YARN_KEYS = ("factor", "beta_fast", "beta_slow", "mscale", "mscale_all_dim", "original_max_position_embeddings")

# dtype of a hyper-connection sub-layer's coefficient path (the norm over the
# stream, the product with φ, the Sinkhorn iterations): the configuration
# states float32. A module constant so that the benchmark's control can run
# the path in bfloat16 and be caught (``lm/hc_row_err``); nothing else
# sets it.
HC_DTYPE = jnp.float32
HC_POST_GAIN = 2.0  # H_post = 2σ(·): at b = 0 a sub-layer's output is written at weight 1


def head_columns(params: Params, lo: int, hi: int):
    """An untied head's cut to the ids ``[lo, hi)``: every leaf of the
    ``params["head"]`` node (an int8 base and its scale, or a float kernel; a
    bias) on its output axis. Returns (the params :func:`generate`'s scan is
    handed, the whole head's shape)."""
    return ({**params, "head": jax.tree_util.tree_map(lambda w: w[..., lo:hi], params["head"])},
            kernel_shape(params["head"]))


class Family(NamedTuple):
    """What :func:`generate` and the backend ask of a model family."""
    init: Callable            # (key, cfg, base_quant) -> params
    prefill_state: Callable   # (params, cfg, ids, lens, lora, scale, factors) -> (carried state, MoE stats, bytes a sequence by kind)
    decode_layers: Callable   # (params, cfg, x, state, i, prompt_len, lora, scale, factors) -> (x, state, MoE stats)
    head: Callable            # (params, cfg, hidden) -> float32 logits over the head cut_head handed it: the rows
                              # held, or the image-id range's where generate cut the head to them
    state_rows: Optional[Callable] = None  # (cfg, carried state after the scan) -> further per-image rows {name: [B]}
    cut_head: Callable = head_columns      # (params, lo, hi) -> (params with the head cut to ids [lo, hi), whole shape)


@dataclasses.dataclass(frozen=True)
class GeneratorUse:
    """What a configuration of either family states besides the model's own
    keys: this chip's share of the deployment and the system's use of the
    model as an image-token generator."""
    # --- this chip's share of the deployment (model-configs §4)
    experts_held: int = 256
    expert_offset: int = 0
    vocab_rows_held: int = 153600
    # --- the system's use of it: image ids and how they are sampled
    image_vocab: int = 4096
    image_id_offset: int = 0
    boi_id: int = 1
    grid: int = 16
    max_prompt_len: int = 64
    top_k: int = 900
    top_p: float = 0.96
    decode_batch: int = 0  # images a member decodes at a time (0: all of its batch)
    vq: msvq.MSVQConfig = dataclasses.field(default_factory=msvq.MSVQConfig)
    compute_dtype: Any = jnp.bfloat16

    def check_use(self, n_experts: int) -> None:
        if not 0 <= self.expert_offset <= n_experts - self.experts_held:
            raise ValueError(f"experts [{self.expert_offset}, +{self.experts_held}) of {n_experts}")
        if self.image_id_offset + self.image_vocab > self.vocab_rows_held:
            raise ValueError("the image-id range lies outside the vocabulary rows held")
        if self.vq.vocab_size != self.image_vocab or self.vq.patch_nums[-1] != self.grid:
            raise ValueError("the VQ codebook and grid must match image_vocab and grid")

    @property
    def image_tokens(self) -> int:
        return self.grid * self.grid

    @property
    def cache_len(self) -> int:
        return self.max_prompt_len + self.image_tokens

    def lora_spec(self, rank: int = 8, alpha: float = 16.0) -> LoRASpec:
        return LoRASpec(rank=rank, alpha=alpha, targets=self.lora_targets)


def published_from_raw(raw: Dict[str, Any], keys: Tuple[str, ...], family: str) -> Dict[str, Any]:
    """The model's own keys of a ``config.json``-shaped dict; a missing one is named."""
    missing = [k for k in keys if k not in raw]
    if missing:
        raise ValueError(f"{family} config.json needs the keys {missing}")
    return {k: raw[k] for k in keys}


def use_from_raw(raw: Dict[str, Any], n_experts: int, vocab: int) -> Dict[str, Any]:
    """:class:`GeneratorUse`'s fields from a ``config.json``-shaped dict: the
    share keys, the ``image_tokens`` group, ``vq`` (``MSVQConfig`` keys) and
    ``torch_dtype``."""
    kw: Dict[str, Any] = {"experts_held": raw.get("experts_held", n_experts),
                          "expert_offset": raw.get("expert_offset", 0),
                          "vocab_rows_held": raw.get("vocab_rows_held", vocab)}
    img = raw.get("image_tokens", {})
    for k in ("image_vocab", "image_id_offset", "boi_id", "grid", "max_prompt_len", "top_k", "top_p",
              "decode_batch"):
        if k in img:
            kw[k] = img[k]
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[raw.get("torch_dtype", "bfloat16")]
    vq = {k: tuple(v) if isinstance(v, list) else v for k, v in raw.get("vq", {}).items()}
    vq.setdefault("vocab_size", kw.get("image_vocab", GeneratorUse.image_vocab))
    vq["patch_nums"] = (kw.get("grid", GeneratorUse.grid),)  # one scale: the ids are the grid
    return dict(kw, vq=msvq.MSVQConfig(compute_dtype=dt, **vq), compute_dtype=dt)


@dataclasses.dataclass(frozen=True)
class LMConfig(GeneratorUse):
    # --- the model's own config.json keys (openPangu-Ultra-MoE-718B's as defaults)
    hidden_size: int = 7680
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 25_600_000.0
    rms_norm_eps: float = 1e-5
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_nextn_predict_layers: int = 1
    vocab_size: int = 153600
    # --- what a ``xing4_0`` file states besides; the defaults are the other model_types' block
    model_type: Optional[str] = None
    sandwich_norm: bool = True
    hc_mult: int = 0                        # residual streams a token; 0: one residual vector
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    topk_method: str = "greedy"             # "noaux_tc": chosen by score + e_score_correction_bias
    # ``rope_scaling`` (type yarn) key by key, ``rope_scaling_<key>``; factor 1: plain RoPE
    rope_scaling_factor: float = 1.0
    rope_scaling_beta_fast: float = 32.0
    rope_scaling_beta_slow: float = 1.0
    rope_scaling_mscale: float = 1.0
    rope_scaling_mscale_all_dim: float = 0.0
    rope_scaling_original_max_position_embeddings: int = 4096

    def __post_init__(self) -> None:
        wrote = ("the MLA family of models/lm.py (model_type deepseek_v3 / pangu_ultra_moe / none: sandwich norm "
                 "around one residual vector; xing4_0: pre-norm inside hyper-connection streams) writes down ")
        if self.n_shared_experts != 1:
            raise ValueError(f"n_shared_experts {self.n_shared_experts}: {wrote}one ungated shared expert")
        if self.topk_method not in ("greedy", "noaux_tc"):
            raise ValueError(f"topk_method {self.topk_method!r}: {wrote}top-k by the sigmoid score (greedy) or by "
                             "the score plus e_score_correction_bias (noaux_tc), without expert groups")
        if self.hc_mult < 0 or (self.hc_mult and self.hc_sinkhorn_iters < 1):
            raise ValueError(f"hc_mult {self.hc_mult}, hc_sinkhorn_iters {self.hc_sinkhorn_iters}: {wrote}"
                             "hc_mult >= 0 streams (0: none) and at least one Sinkhorn iteration")
        self.check_use(self.n_routed_experts)

    @classmethod
    def from_raw(cls, raw: Dict[str, Any]) -> "LMConfig":
        kw = published_from_raw(raw, PUBLISHED_KEYS, "an MLA-family")
        kw["model_type"] = raw.get("model_type")
        if kw["model_type"] == XING:
            kw.update(published_from_raw(raw, XING_KEYS, f"a {XING}"), sandwich_norm=False)
        for k in ("sandwich_norm", "topk_method"):  # any file of the family may state them
            if k in raw:
                kw[k] = raw[k]
        if raw.get("scoring_func", "sigmoid") != "sigmoid" or raw.get("n_group", 1) != 1 \
                or raw.get("topk_group", 1) != 1:
            raise ValueError("an MLA-family config.json: this model code writes down sigmoid scoring over one "
                             f"expert group (scoring_func {raw.get('scoring_func')!r}, n_group {raw.get('n_group')}, "
                             f"topk_group {raw.get('topk_group')})")
        scaling = raw.get("rope_scaling")
        if scaling:
            if scaling.get("type") != "yarn":
                raise ValueError(f"rope_scaling type {scaling.get('type')!r}: only yarn is written down")
            kw.update({f"rope_scaling_{k}": v for k, v in published_from_raw(scaling, YARN_KEYS,
                                                                             "a yarn rope_scaling group of").items()})
        return cls(**kw, **use_from_raw(raw, kw["n_routed_experts"], kw["vocab_size"]))

    @classmethod
    def from_json(cls, path: str) -> "LMConfig":
        """A ``config.json``-shaped file of this family (:func:`config_from_json`
        reads either family's)."""
        return cls.from_raw(json.loads(Path(path).read_text()))

    @property
    def cache_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def score_divisor(self) -> float:
        """What attention's scores are divided by: ``sqrt(qk head dim)``, over
        yarn's ``(0.1 · mscale_all_dim · ln factor + 1)²`` where RoPE is scaled."""
        m = self.yarn_gain(self.rope_scaling_mscale_all_dim)
        return math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim) / (m * m)

    def yarn_gain(self, mscale: float) -> float:
        """yarn's ``0.1 · mscale · ln factor + 1`` (1 where RoPE is not scaled)."""
        return 0.1 * mscale * math.log(self.rope_scaling_factor) + 1.0 if self.rope_scaling_factor > 1 else 1.0

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def lora_targets(self) -> Tuple[str, ...]:
        return LM_LORA_TARGETS

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    def family(self) -> Family:
        return MLA_FAMILY


def config_from_json(path: str):
    """A ``config.json``-shaped file → the configuration of the family its
    ``model_type`` names: the model's published keys, the share keys
    (``experts_held``, ``expert_offset``, ``vocab_rows_held``), an
    ``image_tokens`` group, ``vq`` and ``torch_dtype``. A file without
    ``model_type`` is read as the MLA family, as every file was before the key
    was looked at."""
    from . import lm_hybrid, lm_ssm  # the other families build on this module

    raw = json.loads(Path(path).read_text())
    model_type = raw.get("model_type")
    if model_type in MLA_MODEL_TYPES:
        return LMConfig.from_raw(raw)
    if model_type == lm_hybrid.MODEL_TYPE:
        return lm_hybrid.HybridLMConfig.from_raw(raw)
    if model_type == lm_ssm.MODEL_TYPE:
        return lm_ssm.SSMLMConfig.from_raw(raw)
    known = [t for t in MLA_MODEL_TYPES if t] + [lm_hybrid.MODEL_TYPE, lm_ssm.MODEL_TYPE]
    raise ValueError(f"{path}: model_type {model_type!r} is not a family this model code writes down ({known})")


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _kernel(key, shape, dt, std=None) -> Params:
    std = std if std is not None else 1.0 / math.sqrt(shape[-2])
    return {"kernel": (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)}


def _norm(dim: int) -> Params:
    return {"scale": jnp.ones((dim,), jnp.float32)}


def _swiglu_init(key, d: int, f: int, dt, experts: int = 0) -> Params:
    kg, ku, kd = jax.random.split(key, 3)
    lead = (experts,) if experts else ()
    return {"gate": _kernel(kg, lead + (d, f), dt), "up": _kernel(ku, lead + (d, f), dt),
            "down": _kernel(kd, lead + (f, d), dt)}


def _block_init(key, cfg: LMConfig, moe: bool) -> Params:
    d, H, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.compute_dtype
    ks = jax.random.split(key, 8)
    p: Params = {k: _norm(d) for k in (("n1", "n2", "n3", "n4") if cfg.sandwich_norm else ("n1", "n3"))}
    p.update({
        "mla": {
            "wdq": _kernel(ks[0], (d, cfg.q_lora_rank), dt),
            "q_norm": _norm(cfg.q_lora_rank),
            "wuq": _kernel(ks[1], (cfg.q_lora_rank, H * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)), dt),
            "wdkv": _kernel(ks[2], (d, cfg.cache_width), dt),
            "kv_norm": _norm(cfg.kv_lora_rank),
            "wukv": _kernel(ks[3], (cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), dt),
            "wo": _kernel(ks[4], (H * cfg.v_head_dim, d), dt),
        },
    })
    if cfg.hc_mult:
        # float32 and never quantized. Seeded at φ ~ N(0, 1/nC), α = 1, b = 0 (assumed, not the paper's
        # near-identity start): every entry of x̃φ is O(1), so H_res is another doubly-stochastic matrix at
        # every token and a program that dropped the dynamic term would fail every comparison
        n, nC = cfg.hc_mult, cfg.hc_mult * d
        for j, name in enumerate(("hc_attn", "hc_ffn")):
            p[name] = {"phi": jax.random.normal(jax.random.fold_in(key, 100 + j), (nC, n * (n + 2)), jnp.float32)
                       / math.sqrt(nC),
                       "b": jnp.zeros((n * (n + 2),), jnp.float32), "alpha": jnp.ones((3,), jnp.float32)}
    if moe:
        p["moe"] = {
            # float32 and never quantized: the model's code routes in float32
            "router": {"weight": jax.random.normal(ks[5], (cfg.n_routed_experts, d), jnp.float32) / math.sqrt(d)},
            "experts": _swiglu_init(ks[6], d, cfg.moe_intermediate_size, dt, experts=cfg.experts_held),
            "shared": _swiglu_init(ks[7], d, cfg.moe_intermediate_size, dt),
        }
        if cfg.topk_method == "noaux_tc":
            # seeded at N(0, 0.1²) (assumed): a choice by the score alone then differs from the model's
            p["moe"]["router"]["e_score_correction_bias"] = 0.1 * jax.random.normal(
                jax.random.fold_in(key, 102), (cfg.n_routed_experts,), jnp.float32)
    else:
        p["ffn"] = _swiglu_init(ks[6], d, cfg.intermediate_size, dt)
    return p


def init_lm(key: jax.Array, cfg: GeneratorUse, base_quant: str = "off") -> Params:
    """Seeded parameters of ``cfg``'s family."""
    return cfg.family().init(key, cfg, base_quant)


def _init_mla(key: jax.Array, cfg: LMConfig, base_quant: str = "off") -> Params:
    """Seeded parameters. ``base_quant="int8"`` quantizes each kernel inside
    the same program (``ops/quant.maybe_quantize_tree``, what ``train.cli``'s
    later ``quantize_frozen`` pass would do and then finds done): at the
    published widths the float tree (10 GB in bf16) and its int8 copy do not
    fit one chip together, so a kernel's float form lives only until its
    int8 form exists."""
    d, dt = cfg.hidden_size, cfg.compute_dtype
    L = cfg.num_hidden_layers
    ks = jax.random.split(key, L + 5)
    q = lambda tree: maybe_quantize_tree(tree, base_quant)
    params: Params = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_rows_held, d), jnp.float32) * 0.02).astype(dt),
        "layers": [q(_block_init(ks[1 + i], cfg, cfg.is_moe(i))) for i in range(L)],
        "final_norm": _norm(d),
        "head": q(_kernel(ks[L + 1], (d, cfg.vocab_rows_held), dt)),
        "vq": q(msvq.init_msvq(ks[L + 2], cfg.vq)),
    }
    if cfg.num_nextn_predict_layers:
        km = jax.random.split(ks[L + 3], 2 * cfg.num_nextn_predict_layers)
        params["mtp"] = [q({
            "nh": _norm(d), "ne": _norm(d),
            "proj": _kernel(km[2 * i], (2 * d, d), dt),
            "block": _block_init(km[2 * i + 1], cfg, moe=True),
        }) for i in range(cfg.num_nextn_predict_layers)]
    return params


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def _rms(x: jax.Array, p: Params, cfg: LMConfig) -> jax.Array:
    return nn.rms_norm(x, p, eps=cfg.rms_norm_eps)


def _yarn_blend(cfg: LMConfig, half: int):
    """Share of the *unscaled* frequency in each of the ``half`` rotary pairs
    (yarn): 1 below the pair that turns ``beta_fast`` times over the original
    context, 0 above the one that turns ``beta_slow`` times, linear between
    (the two bounds rounded outward, as DeepSeek-V3's modelling code does)."""
    dim, span = 2 * half, cfg.rope_scaling_original_max_position_embeddings

    def pair_of(turns: float) -> float:
        return dim * math.log(span / (turns * 2 * math.pi)) / (2 * math.log(cfg.rope_theta))

    lo = max(math.floor(pair_of(cfg.rope_scaling_beta_fast)), 0)
    hi = min(math.ceil(pair_of(cfg.rope_scaling_beta_slow)), dim - 1)
    ramp = (jnp.arange(half, dtype=jnp.float32) - lo) / max(hi - lo, 1e-3)
    return 1.0 - jnp.clip(ramp, 0.0, 1.0)


def _rope(x: jax.Array, pos: jax.Array, theta: float, yarn: Optional[LMConfig] = None) -> jax.Array:
    """RoPE, rotate-half convention (assumed): ``x [..., dr]`` at positions
    ``pos`` broadcastable to ``x[..., 0]``. Plain, or — ``yarn``: a
    configuration whose ``rope_scaling_factor`` is over 1 — with blended
    frequencies (:func:`_yarn_blend`) and cos / sin × ``mscale``'s ratio (1 as
    the ``xing4_0`` file states it)."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    amp = 1.0
    if yarn is not None and yarn.rope_scaling_factor > 1:
        keep = _yarn_blend(yarn, half)
        freqs = freqs * keep + freqs / yarn.rope_scaling_factor * (1.0 - keep)
        amp = yarn.yarn_gain(yarn.rope_scaling_mscale) / yarn.yarn_gain(yarn.rope_scaling_mscale_all_dim)
    ang = pos.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if amp != 1.0:
        cos, sin = cos * amp, sin * amp
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _swiglu(p: Params, u: jax.Array, lora: Optional[Params], path: str, scale: float) -> jax.Array:
    g = nn.dense(p["gate"], u, lookup(lora, f"{path}/gate"), scale)
    v = nn.dense(p["up"], u, lookup(lora, f"{path}/up"), scale)
    return nn.dense(p["down"], jax.nn.silu(g) * v, lookup(lora, f"{path}/down"), scale)


def _mla_project(p: Params, cfg: LMConfig, u: jax.Array, pos: jax.Array,
                 lora: Optional[Params], path: str, scale: float, entry_only: bool = False):
    """``u [..., d]`` at positions ``pos [...]`` → ``q_nope [..., H, dn]``,
    roped ``q_rope [..., H, dr]`` and the cache entry ``[ckv | rope(kr)]``
    (``entry_only``: the entry alone, the query path not run)."""
    H, dn, dr, c = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    kvr = nn.dense(p["wdkv"], u, lookup(lora, f"{path}/wdkv"), scale)
    entry = jnp.concatenate([_rms(kvr[..., :c], p["kv_norm"], cfg),
                             _rope(kvr[..., c:], pos, cfg.rope_theta, cfg)], axis=-1)
    if entry_only:
        return entry
    cq = _rms(nn.dense(p["wdq"], u, lookup(lora, f"{path}/wdq"), scale), p["q_norm"], cfg)
    q = nn.dense(p["wuq"], cq, lookup(lora, f"{path}/wuq"), scale).reshape(*u.shape[:-1], H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos[..., None], cfg.rope_theta, cfg)
    return q_nope, q_rope, entry


def mla_prefill(p: Params, cfg: LMConfig, u: jax.Array, pos: jax.Array, valid: jax.Array,
                lora: Optional[Params], path: str, scale: float) -> Tuple[jax.Array, jax.Array]:
    """Whole-sequence causal MLA with expanded K/V: ``u [S, T, d]``, positions
    ``pos [S, T]``, key validity ``valid [S, T]`` → (out ``[S, T, d]``, cache
    entries ``[S, T, c + dr]``)."""
    S, T, _ = u.shape
    H, dn, dv, c = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    q_nope, q_rope, entry = _mla_project(p, cfg, u, pos, lora, path, scale)
    kv = nn.dense(p["wukv"], entry[..., :c], lookup(lora, f"{path}/wukv"), scale).reshape(S, T, H, dn + dv)
    with jax.named_scope("attend"):
        f32 = jnp.float32
        sc = (jnp.einsum("sqhj,skhj->shqk", q_nope, kv[..., :dn], preferred_element_type=f32)
              + jnp.einsum("sqhr,skr->shqk", q_rope, entry[..., c:], preferred_element_type=f32))
        sc = sc / cfg.score_divisor
        see = jnp.tril(jnp.ones((T, T), bool))[None, None] & valid[:, None, None, :]
        pr = jax.nn.softmax(jnp.where(see, sc, -1e30), axis=-1)
        o = jnp.einsum("shqk,skhv->sqhv", pr.astype(u.dtype), kv[..., dn:]).reshape(S, T, H * dv)
    return nn.dense(p["wo"], o, lookup(lora, f"{path}/wo"), scale), entry


def mla_decode(p: Params, cfg: LMConfig, u: jax.Array, pos: jax.Array, cache: jax.Array, slot: jax.Array,
               valid: jax.Array, lora: Optional[Params], path: str, scale: float) -> Tuple[jax.Array, jax.Array]:
    """One position a sequence over the latent cache, absorbed form:
    ``u [S, d]`` at positions ``pos [S]``; ``cache [S, Tmax, c + dr]`` gets the
    new entry at ``slot``; ``valid [S, Tmax]`` names the slots a query sees.
    ``Wukv`` (and its LoRA delta, kept in factors) never expands the cache:
    its K half is folded into the query, its V half applied to the weighted
    sum over ``ckv``."""
    S = u.shape[0]
    H, dn, dv, c = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    dt = u.dtype
    q_nope, q_rope, entry = _mla_project(p, cfg, u, pos, lora, path, scale)
    cache = jax.lax.dynamic_update_slice(cache, entry[:, None, :].astype(cache.dtype), (0, slot, 0))
    with jax.named_scope("attend"):
        f32 = jnp.float32
        w = resolve_kernel(p["wukv"], dt).reshape(c, H, dn + dv)
        leaf = lookup(lora, f"{path}/wukv")
        q_lat = jnp.einsum("shj,chj->shc", q_nope, w[..., :dn])
        if leaf is not None:
            a = effective_factor(leaf["a"], dt)                                # [c, r]
            b = effective_factor(leaf["b"], dt).reshape(-1, H, dn + dv)        # [r, H, dn + dv]
            s = jnp.asarray(scale, dt)
            q_lat = q_lat + s * jnp.einsum("shr,cr->shc", jnp.einsum("shj,rhj->shr", q_nope, b[..., :dn]), a)
        ckv, kr = cache[..., :c], cache[..., c:]
        sc = (jnp.einsum("shc,stc->sht", q_lat, ckv, preferred_element_type=f32)
              + jnp.einsum("shr,str->sht", q_rope, kr, preferred_element_type=f32))
        sc = sc / cfg.score_divisor
        pr = jax.nn.softmax(jnp.where(valid[:, None, :], sc, -1e30), axis=-1)
        o_lat = jnp.einsum("sht,stc->shc", pr.astype(dt), ckv)
        o = jnp.einsum("shc,chv->shv", o_lat, w[..., dn:])
        if leaf is not None:
            o = o + s * jnp.einsum("shr,rhv->shv", jnp.einsum("shc,cr->shr", o_lat, a), b[..., dn:])
    return nn.dense(p["wo"], o.reshape(S, H * dv), lookup(lora, f"{path}/wo"), scale), cache


def route(p: Params, cfg: LMConfig, u: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``u [R, d]`` → (expert ids ``[R, k]`` of all ``n_routed_experts``,
    weights ``[R, k]`` normalized over the k chosen). No groups; ``noaux_tc``
    chooses by score + bias and weighs by the score."""
    s = jax.nn.sigmoid(u.astype(jnp.float32) @ p["router"]["weight"].T)
    if cfg.topk_method == "noaux_tc":
        _, top_i = jax.lax.top_k(s + p["router"]["e_score_correction_bias"], cfg.num_experts_per_tok)
        top_s = jnp.take_along_axis(s, top_i, axis=-1)
    else:
        top_s, top_i = jax.lax.top_k(s, cfg.num_experts_per_tok)
    w = top_s / (top_s.sum(-1, keepdims=True) + 1e-20) if cfg.norm_topk_prob else top_s
    return top_i.astype(jnp.int32), w * cfg.routed_scaling_factor


# Rows a member at or under which every held expert multiplies every row
# (:func:`_routed_dense`). Any row count up to the MXU's height costs the MXU
# the same, and on a v5e a product whose FLOPs take less time than its int8
# base takes to read (rows < 197e12 / (2 x 819e9) = 120) is bound by that
# read either way: there the dense form reads each expert once with the
# dequantization fused into the dot's operand, where the compiler's grouped
# kernel measured 1.3 ms a [16, 7680, 2048] s8 kernel against a 0.31 ms read
# (my chip run, PR 27). Re-decided at 128 held experts of width 512 (the
# ``qwen3_next`` cell: 8 members x 8 rows, 640 pairs, 5 rows an expert) from two traced steps: ``experts``
# takes 2.340 s a step dense (0.759 ms a call) and 2.815 s grouped (0.913 ms; ``DENSE_ROWS = 0`` set by a scratch
# script) against a 1.523 s floor (my chip runs, PR 31) - it holds there too, by a fifth. (Host-timed alone, which
# is no device metric, one call read 0.753 / 0.954 ms against a 0.492 ms read of the three s8 kernels.) The member
# axis is not visible under ``vmap``, so the choice is by a member's rows: a chunk of 8 members is 128 rows.
DENSE_ROWS = 16


def _expert_einsum(spec: str, x: jax.Array, node: Params) -> jax.Array:
    """``einsum(spec, x, kernel)`` over an ``[E, din, dout]`` node whose output
    is ``[..., E, dout]``; an int8 kernel enters the dot as it is (the convert
    fuses into the operand read) and the per-(expert, channel) scale is
    applied to the result."""
    if "kernel" in node:
        return jnp.einsum(spec, x, node["kernel"].astype(x.dtype))
    qk = node["kernel_q8"]
    y = jnp.einsum(spec, x, qk["q8"].astype(x.dtype), preferred_element_type=jnp.float32)
    return (y * qk["scale"][:, 0, :]).astype(x.dtype)


def _routed_dense(p: Params, cfg, u: jax.Array, wt: jax.Array,
                  factors: Optional[Dict[str, Any]], scale: float) -> jax.Array:
    """Few rows: all ``E`` held experts over all rows ``u [R, d]``, weighted by
    ``wt [R, E]`` (0 where a row did not choose the expert)."""
    E = cfg.experts_held
    g = _expert_einsum("td,edf->tef", u, p["gate"])
    v = _expert_einsum("td,edf->tef", u, p["up"])
    s = jnp.asarray(scale, u.dtype)
    if factors is not None:
        (ag, bg), (au, bu), (ad, bd) = factors["gate"], factors["up"], factors["down"]
        R = u.shape[0]
        g = g + s * jnp.einsum("ter,erf->tef", (u @ ag).reshape(R, E, -1), bg.reshape(E, -1, bg.shape[-1]))
        v = v + s * jnp.einsum("ter,erf->tef", (u @ au).reshape(R, E, -1), bu.reshape(E, -1, bu.shape[-1]))
    h = jax.nn.silu(g) * v
    y = _expert_einsum("tef,efd->ted", h, p["down"])
    if factors is not None:
        z = jnp.einsum("tef,fer->ter", h, ad.reshape(ad.shape[0], E, -1))
        y = y + s * jnp.einsum("ter,erd->ted", z, bd.reshape(E, -1, bd.shape[-1]))
    return jnp.einsum("te,ted->td", wt.astype(u.dtype), y)


def routed_experts(p: Params, cfg, u: jax.Array, top_i: jax.Array, top_w: jax.Array,
                   row_valid: jax.Array, factors: Optional[Dict[str, Any]], scale: float):
    """``Σ_{e ∈ top-k, e held} w_e E_e(u)`` for ``u [R, d]``: every pair whose
    expert is held here is computed, none dropped. ``factors``: this member's
    :func:`expert_factors` of the layer. Returns (``[R, d]``, local expert of
    each pair ``[R, k]`` with ``experts_held`` for "not here"). Many rows go
    through the grouped products of ``ops/grouped.py``, a few through
    :func:`_routed_dense` (:data:`DENSE_ROWS`); both give the same sums."""
    R, d = u.shape
    K, E = cfg.num_experts_per_tok, cfg.experts_held
    local = top_i - cfg.expert_offset
    held = (local >= 0) & (local < E) & row_valid[:, None]
    e = jnp.where(held, local, E)
    w = jnp.where(held, top_w, 0.0)
    if R <= DENSE_ROWS:
        wt = (jax.nn.one_hot(e, E + 1, dtype=jnp.float32)[..., :E] * w[..., None]).sum(1)
        return _routed_dense(p, cfg, u, wt, factors, scale), e
    e = e.reshape(R * K)
    xp = jnp.repeat(u, K, axis=0)
    g = grouped.grouped_matmul(xp, e, p["gate"])
    v = grouped.grouped_matmul(xp, e, p["up"])
    s = jnp.asarray(scale, u.dtype)
    if factors is not None:
        (ag, bg), (au, bu), (ad, bd) = factors["gate"], factors["up"], factors["down"]
        g = g + s * grouped.expert_lora_rows(jnp.repeat(u @ ag, K, axis=0), e, bg, E)
        v = v + s * grouped.expert_lora_rows(jnp.repeat(u @ au, K, axis=0), e, bu, E)
    h = jax.nn.silu(g) * v
    y = grouped.grouped_matmul(h, e, p["down"])
    if factors is not None:
        y = y + s * grouped.expert_lora_rows(h @ ad, e, bd, E)
    return jnp.einsum("rk,rkd->rd", w.astype(u.dtype), y.reshape(R, K, d)), e.reshape(R, K)


def expert_factors(lora: Optional[Params], cfg, dtype) -> Optional[List[Optional[Dict[str, Any]]]]:
    """Per layer, one member's routed-expert LoRA factors laid side by side
    (``ops/grouped.expert_lora_factors``): built once a generation, outside
    the decode loop, from the adapter tree (raw or ``FactoredDelta`` leaves)."""
    if lora is None:
        return None
    out: List[Optional[Dict[str, Any]]] = []
    for li in range(cfg.num_hidden_layers):
        leaves = {m: lookup(lora, f"layers/{li}/moe/experts/{m}") for m in ("gate", "up", "down")}
        out.append(None if any(v is None for v in leaves.values()) else
                   {m: grouped.expert_lora_factors(v, dtype) for m, v in leaves.items()})
    return out


def routed_with_stats(p: Params, cfg, u: jax.Array, top_i: jax.Array, top_w: jax.Array, row_valid: jax.Array,
                      factors: Optional[Dict[str, Any]], scale: float):
    """:func:`routed_experts` and the counters of this call (either family)."""
    routed, e = routed_experts(p, cfg, u, top_i, top_w, row_valid, factors, scale)
    return routed, {
        "assign": (e < cfg.experts_held).sum(-1).astype(jnp.int32),           # [R] pairs computed here
        "load": grouped.expert_load_ratio(e.reshape(-1), cfg.experts_held),   # scalar, this call
        "topk": jnp.sort(top_i, axis=-1),                                     # [R, k] as a set
    }


def moe(p: Params, cfg: LMConfig, u: jax.Array, row_valid: jax.Array, lora: Optional[Params],
        factors: Optional[Dict[str, Any]], path: str, scale: float):
    """``u [R, d]`` → (``[R, d]``, counters of this call). ``row_valid`` marks
    rows that are tokens (padding is routed nowhere)."""
    with jax.named_scope("router"):
        top_i, top_w = route(p, cfg, u)
    with jax.named_scope("shared"):
        shared = _swiglu(p["shared"], u, lora, f"{path}/shared", scale)
    with jax.named_scope("experts"):
        routed, stats = routed_with_stats(p["experts"], cfg, u, top_i, top_w, row_valid, factors, scale)
    return shared + routed, stats


# ---------------------------------------------------------------------------
# the residual path: one vector a token, or hyper-connection streams
# ---------------------------------------------------------------------------

def hc_coefficients(hc: Params, cfg: LMConfig, x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One sub-layer's hyper-connection coefficients for streams ``x [..., n,
    C]``: (``H_pre [..., n]``, ``H_post [..., n]``, ``H_res [..., n, n]``,
    doubly stochastic up to the Sinkhorn iterations' convergence), computed in
    :data:`HC_DTYPE` whatever the stream's dtype."""
    n, ft = cfg.hc_mult, HC_DTYPE
    eps = jnp.asarray(cfg.hc_eps, ft)
    with jax.named_scope("hc_coeff"):
        v = x.reshape(*x.shape[:-2], -1).astype(ft)
        xt = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
        z = jnp.dot(xt, hc["phi"].astype(ft), precision=jax.lax.Precision.HIGHEST)
        a, b = hc["alpha"].astype(ft), hc["b"].astype(ft)
        pre = jax.nn.sigmoid(a[0] * z[..., :n] + b[:n])
        post = HC_POST_GAIN * jax.nn.sigmoid(a[1] * z[..., n: 2 * n] + b[n: 2 * n])
        logits = jnp.clip(a[2] * z[..., 2 * n:] + b[2 * n:], cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max)
    with jax.named_scope("hc_sinkhorn"):
        # 80 small launches a sub-layer on a TPU (a ``sum`` over 4 is a reduce, which ends a fusion; PERF.md §6,
        # PR 33 says what was tried in ``jax.numpy`` instead and why one kernel is the next step)
        m = jnp.exp(logits.reshape(*logits.shape[:-1], n, n))
        for _ in range(cfg.hc_sinkhorn_iters):
            m = m / (m.sum(-2, keepdims=True) + eps)   # every column by its sum
            m = m / (m.sum(-1, keepdims=True) + eps)   # then every row by its sum
    return pre, post, m


def _hc_read(pre: jax.Array, x: jax.Array) -> jax.Array:
    """``u = H_pre X``: the sub-layer's input ``[..., C]``. Four terms a
    number, multiplied and summed in float32 on the vector unit (a dot at the
    default precision would round the coefficients to bfloat16)."""
    with jax.named_scope("hc_mix"):
        return (pre.astype(jnp.float32)[..., None] * x.astype(jnp.float32)).sum(-2).astype(x.dtype)


def _hc_write(post: jax.Array, res: jax.Array, x: jax.Array, y: jax.Array) -> jax.Array:
    """``X ← H_res X + H_postᵀ y``, in float32, rounded once to the stream's dtype."""
    f32 = jnp.float32
    with jax.named_scope("hc_mix"):
        mixed = (res.astype(f32)[..., None] * x.astype(f32)[..., None, :, :]).sum(-2)
        return (mixed + post.astype(f32)[..., None] * y.astype(f32)[..., None, :]).astype(x.dtype)


def _sublayer(hc: Optional[Params], cfg: LMConfig, x: jax.Array, f, scope: Optional[str],
              hc_seen: Optional[List[jax.Array]]):
    """One sub-layer ``f(u) -> (y, extra)`` (its norms inside) on the residual
    path the configuration states: ``x + f(x)`` on one vector, or read /
    write of the streams by this sub-layer's coefficients. ``f`` and the
    plain add run under ``scope``; the streams' work under ``lm_hc``."""
    ctx = jax.named_scope(scope) if scope else contextlib.nullcontext()
    if not cfg.hc_mult:
        with ctx:
            y, extra = f(x)
            return x + y, extra
    with jax.named_scope("lm_hc"):
        pre, post, res = hc_coefficients(hc, cfg, x)
        u = _hc_read(pre, x)
    with ctx:
        y, extra = f(u)
    with jax.named_scope("lm_hc"):
        x = _hc_write(post, res, x, y)
    if hc_seen is not None:
        hc_seen.append(res)
    return x, extra


def block(p: Params, cfg: LMConfig, li: int, x: jax.Array, attn, row_valid: jax.Array,
          lora: Optional[Params], factors, scale: float, prefix: str = "layers",
          hc_seen: Optional[List[jax.Array]] = None):
    """One layer on ``x [..., d]`` — with hyper-connections on the streams
    ``x [..., n, d]`` — sandwich norm or pre-norm as the configuration says;
    ``attn(u) -> (out, extra)`` is the MLA form the caller is in (prefill or
    decode). Returns (y, extra, MoE stats or None); the two sub-layers'
    ``H_res`` are appended to ``hc_seen`` where a list is given."""
    path = f"{prefix}/{li}"
    after = (lambda t, n: _rms(t, p[n], cfg)) if cfg.sandwich_norm else (lambda t, n: t)

    def attention(u):
        a, extra = attn(_rms(u, p["n1"], cfg))
        return after(a, "n2"), extra

    def ffn(h):
        u = _rms(h, p["n3"], cfg)
        if "moe" in p:
            with jax.named_scope("lm_moe"):
                flat = u.reshape(-1, u.shape[-1])
                f, stats = moe(p["moe"], cfg, flat, row_valid.reshape(-1), lora,
                               factors, f"{path}/moe", scale)
                f = f.reshape(u.shape)
        else:
            with jax.named_scope("lm_dense_ffn"):
                f, stats = _swiglu(p["ffn"], u, lora, f"{path}/ffn", scale), None
        return after(f, "n4"), stats

    h, extra = _sublayer(p.get("hc_attn"), cfg, x, attention, "lm_mla", hc_seen)
    y, stats = _sublayer(p.get("hc_ffn"), cfg, h, ffn, None, hc_seen)
    return y, extra, stats


def _stream_in(cfg: LMConfig, x: jax.Array) -> jax.Array:
    """``X₀``: ``hc_mult`` copies of a token's vector (the vector itself without hyper-connections)."""
    return jnp.repeat(x[..., None, :], cfg.hc_mult, axis=-2) if cfg.hc_mult else x


def _stream_out(cfg: LMConfig, x: jax.Array) -> jax.Array:
    """The model's output: the streams summed (assumed: the hyper-connections convention)."""
    return x.astype(jnp.float32).sum(-2).astype(x.dtype) if cfg.hc_mult else x


def _hc_gauges(seen: List[jax.Array], valid: jax.Array) -> Dict[str, jax.Array]:
    """The ``H_res`` of some sub-layers (each ``[S, ..., n, n]``, ``valid [S,
    ...]`` marking tokens) → per sequence ``[S]``: ``err``, the largest |row or
    column sum − 1| (the columns' part is what the Sinkhorn iterations have
    not converged away); ``row``, the largest |row sum − 1| alone (rows are
    normalized last: float32 leaves ``hc_eps`` and a rounding, a narrower
    coefficient path its own spacing); ``off``, the off-diagonal mass summed
    over its tokens and sub-layers (1 − trace ÷ n each); ``n``, how many."""
    r = jnp.stack(seen).astype(jnp.float32)                                       # [k, S, ..., n, n]
    row = jnp.abs(r.sum(-1) - 1.0).max(-1)
    err = jnp.maximum(row, jnp.abs(r.sum(-2) - 1.0).max(-1))
    off = 1.0 - jnp.trace(r, axis1=-2, axis2=-1) / r.shape[-1]
    axes = (0,) + tuple(range(2, err.ndim))
    return {"err": jnp.where(valid, err, 0.0).max(axes), "row": jnp.where(valid, row, 0.0).max(axes),
            "off": jnp.where(valid, off, 0.0).sum(axes),
            "n": (valid.astype(jnp.float32) * len(seen)).sum(tuple(range(1, valid.ndim)))}


def _hc_gauges_joined(was: Dict[str, jax.Array], now: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return {k: jnp.maximum(was[k], now[k]) if k in ("err", "row") else was[k] + now[k] for k in was}


def _embed(params: Params, cfg, ids: jax.Array) -> jax.Array:
    return params["embed"][ids].astype(cfg.compute_dtype)


def _head(params: Params, cfg: LMConfig, h: jax.Array) -> jax.Array:
    return nn.dense(params["head"], _rms(h, params["final_norm"], cfg)).astype(jnp.float32)


def prefill(params: Params, cfg: LMConfig, ids: jax.Array, lens: jax.Array,
            lora: Optional[Params] = None, lora_scale: float = 1.0, factors=None, cache_only: bool = False,
            hc_seen: Optional[List[jax.Array]] = None):
    """``ids [S, T]`` (right-padded, ``lens [S]`` real) through every block.
    Returns (hidden ``[S, T, d]`` before the final norm — the streams summed,
    where the model has them —, per-layer cache entries ``[S, T, c + dr]``,
    per-MoE-layer stats with rows ``[S, T]``); ``hc_seen``: see :func:`block`.
    ``cache_only`` (generation: the begin-of-image position, not the prompt's
    last, yields the first logits): the last layer stops at its cache entry —
    nothing reads what its attention and FFN would add, so they are neither
    run nor counted, and the hidden state returned is None."""
    S, T = ids.shape
    pos = jnp.broadcast_to(jnp.arange(T), (S, T))
    valid = pos < lens[:, None]
    factors = factors if factors is not None else expert_factors(lora, cfg, cfg.compute_dtype)
    x = _stream_in(cfg, _embed(params, cfg, ids))
    entries, stats = [], []
    for li, p in enumerate(params["layers"]):
        if cache_only and li == len(params["layers"]) - 1:
            if cfg.hc_mult:
                with jax.named_scope("lm_hc"):
                    x = _hc_read(hc_coefficients(p["hc_attn"], cfg, x)[0], x)
            with jax.named_scope("lm_mla"):
                entries.append(_mla_project(p["mla"], cfg, _rms(x, p["n1"], cfg), pos, lora,
                                            f"layers/{li}/mla", lora_scale, entry_only=True))
            return None, entries, stats
        attn = lambda u, p=p, li=li: mla_prefill(p["mla"], cfg, u, pos, valid, lora, f"layers/{li}/mla", lora_scale)
        x, entry, st = block(p, cfg, li, x, attn, valid, lora, factors[li] if factors else None, lora_scale,
                             hc_seen=hc_seen)
        entries.append(entry)
        if st is not None:
            stats.append({"assign": st["assign"].reshape(S, T), "load": st["load"],
                          "topk": st["topk"].reshape(S, T, -1)})
    return _stream_out(cfg, x), entries, stats


def forward_logits(params: Params, cfg: LMConfig, ids: jax.Array, lens: jax.Array,
                   lora: Optional[Params] = None, lora_scale: float = 1.0) -> jax.Array:
    """Teacher-forced logits ``[S, T, vocab_rows_held]`` (tests)."""
    return _head(params, cfg, prefill(params, cfg, ids, lens, lora, lora_scale)[0])


def mtp_logits(params: Params, cfg: LMConfig, hidden: jax.Array, next_ids: jax.Array, lens: jax.Array,
               module: int = 0) -> jax.Array:
    """The MTP module over a whole sequence: ``hidden [S, T, d]`` (the main
    model's, before its final norm) and ``next_ids [S, T]`` (``t_{i+1}``) →
    logits for ``t_{i+2}``. Embedding, final norm and head are the main
    model's; the block is the module's own MoE block. Not adapted by LoRA."""
    p = params["mtp"][module]
    S, T = next_ids.shape
    pos = jnp.broadcast_to(jnp.arange(T), (S, T))
    valid = pos < lens[:, None]
    x = jnp.concatenate([_rms(hidden, p["nh"], cfg), _rms(_embed(params, cfg, next_ids), p["ne"], cfg)], axis=-1)
    x = _stream_in(cfg, nn.dense(p["proj"], x))
    attn = lambda u: mla_prefill(p["block"]["mla"], cfg, u, pos, valid, None, "mtp", 1.0)
    y, _, _ = block(p["block"], cfg, 0, x, attn, valid, None, None, 1.0, prefix="mtp")
    return _head(params, cfg, _stream_out(cfg, y))


def _mla_prefill_state(params: Params, cfg: LMConfig, ids: jax.Array, lens: jax.Array,
                       lora, lora_scale: float, factors):
    """:class:`Family` hook: the prompt into one latent cache a layer (and,
    with hyper-connections, the gauges of the ``H_res`` seen so far)."""
    B, P = ids.shape
    dt = cfg.compute_dtype
    seen: List[jax.Array] = []
    _, entries, stats = prefill(params, cfg, ids, lens, lora, lora_scale, factors, cache_only=True, hc_seen=seen)
    state = {"caches": tuple(jnp.zeros((B, cfg.cache_len, cfg.cache_width), dt).at[:, :P].set(e.astype(dt))
                             for e in entries)}
    if cfg.hc_mult:
        state["hc"] = _hc_gauges(seen, jnp.arange(P) < lens[:, None]) if seen else \
            {k: jnp.zeros((B,), jnp.float32) for k in ("err", "row", "off", "n")}
    return state, stats, {}


def decode_slot(cfg: GeneratorUse, i: jax.Array, prompt_len: jax.Array):
    """Sampled position ``i`` of right-padded prompts ``prompt_len [B]`` in a
    cache of ``cache_len`` slots: (the slot it is written to, each sequence's
    true position ``[B]``, the slots a query sees ``[B, cache_len]``: its own
    prompt's, then the sampled ones up to this)."""
    P = cfg.max_prompt_len
    slots = jnp.arange(cfg.cache_len)
    slot = P + i
    pos = prompt_len + i
    valid = (slots[None, :] < prompt_len[:, None]) | ((slots[None, :] >= P) & (slots[None, :] <= slot))
    return slot, pos, valid


def _mla_decode_layers(params: Params, cfg: LMConfig, x: jax.Array, state, i: jax.Array, prompt_len: jax.Array,
                       lora, lora_scale: float, factors):
    """:class:`Family` hook: sampled position ``i`` of every sequence, ``x [B,
    d]``, through the blocks over the latent caches."""
    B = x.shape[0]
    slot, pos, valid = decode_slot(cfg, i, prompt_len)
    caches, new_caches, stats, seen = state["caches"], [], [], []
    x = _stream_in(cfg, x)
    for li, p in enumerate(params["layers"]):
        attn = lambda u, p=p, li=li: mla_decode(
            p["mla"], cfg, u, pos, caches[li], slot, valid, lora, f"layers/{li}/mla", lora_scale)
        x, cache, st = block(p, cfg, li, x, attn, jnp.ones((B,), bool), lora,
                             factors[li] if factors else None, lora_scale, hc_seen=seen)
        new_caches.append(cache)
        if st is not None:
            stats.append(st)
    state = dict(state, caches=tuple(new_caches))
    if cfg.hc_mult:
        with jax.named_scope("lm_hc"):
            state["hc"] = _hc_gauges_joined(state["hc"], _hc_gauges(seen, jnp.ones((B,), bool)))
    return _stream_out(cfg, x), state, stats


def _mla_state_rows(cfg: LMConfig, state) -> Dict[str, jax.Array]:
    """:class:`Family` hook: the hyper-connection gauges a sequence gathered
    (``backends/lm_backend.step_metrics`` reduces them to ``lm/hc_*``)."""
    return {f"hc_{k}": v for k, v in state.get("hc", {}).items()}


MLA_FAMILY = Family(init=_init_mla, prefill_state=_mla_prefill_state, decode_layers=_mla_decode_layers, head=_head,
                    state_rows=_mla_state_rows)

PROBE_EVERY = 16  # logits are kept at every 16th sampled position


def generate(
    params: Params,
    cfg: GeneratorUse,
    prompt_ids: jax.Array,   # [B, max_prompt_len] right-padded
    prompt_len: jax.Array,   # [B]
    key: jax.Array,
    lora: Optional[Params] = None,
    lora_scale: float = 1.0,
    decode: bool = True,
    item_index: Optional[jax.Array] = None,
):
    """Prefill + ``image_tokens`` sampled steps + VQ decode, for any family:
    what the scan carries between positions is whatever ``cfg.family()``'s
    ``prefill_state`` returns (one latent cache a layer; or recurrent states,
    conv windows and KV caches side by side) and ``generate`` does not look
    inside it. Returns (images ``[B, H, W, 3]`` in [0, 1] — or the sampled ids
    when ``decode=False`` — and per-image rows: ``ids`` ``[B, n]``; in a family
    with a router ``topk`` ``[B, Tmax, moe layers, k]`` (the router's choice at
    every cache slot, -1 where none), ``assign`` ``[B]`` (token–expert pairs
    computed here), ``load`` ``[B]`` (largest expert load ratio of any call,
    the same for every image of a call); ``logits`` ``[B, n / PROBE_EVERY,
    image_vocab]`` and, where the
    family says what a sequence carries, ``carried/<kind>`` ``[B]`` in bytes;
    with hyper-connections ``hc_err``, ``hc_row``, ``hc_off``, ``hc_n`` ``[B]``, :func:`_hc_gauges`).

    Sampling keys fold in the step and each image's *global* batch position
    (``item_index``), so outputs do not depend on how the batch is chunked."""
    B, P = prompt_ids.shape
    n, dt = cfg.image_tokens, cfg.compute_dtype
    fam = cfg.family()
    item_idx = jnp.arange(B) if item_index is None else item_index
    lo, hi = cfg.image_id_offset, cfg.image_id_offset + cfg.image_vocab

    with obs_scope("generate"):
        # sampling sees the image-id range only, so the decode scan is handed
        # the head cut to that range once, here (``Family.cut_head``: an
        # untied head's columns, a tied one's embedding rows). XLA sinks a
        # slice of the logits through the dot but not through an int8 node's
        # dequantization: it wrote the whole head to HBM at every position
        # (``lm_head_whole_ops`` counts what is left)
        with jax.named_scope("lm_head"):
            image_head, whole_head = fam.cut_head(params, lo, hi)
        note_program_geometry(lm_head_shape=whole_head)
        factors = expert_factors(lora, cfg, dt)
        with jax.named_scope("lm_prefill"):
            state, stats, carried_bytes = fam.prefill_state(params, cfg, prompt_ids, prompt_len, lora, lora_scale,
                                                            factors)
            assign = sum(st["assign"].sum(-1) for st in stats) if stats else jnp.zeros((B,), jnp.int32)
            load = jnp.max(jnp.stack([st["load"] for st in stats])) if stats else jnp.float32(0.0)
            # the router's choice at each prompt slot, -1 at padding and at the
            # last layer (cache_only: it routes no prompt row)
            in_prompt = (jnp.arange(P) < prompt_len[:, None])[..., None]           # [B, P, 1]
            topk_p = jnp.full((B, P, cfg.n_moe_layers, cfg.num_experts_per_tok), -1, jnp.int32)
            for j, st in enumerate(stats):
                topk_p = topk_p.at[:, :, j].set(jnp.where(in_prompt, st["topk"], -1))

        def step(carry, i):
            last, state, assign, load, probe = carry
            with jax.named_scope("lm_decode_step"):
                x, state, sts = fam.decode_layers(params, cfg, _embed(params, cfg, last), state, i, prompt_len,
                                                  lora, lora_scale, factors)
                tk = []
                for st in sts:
                    assign = assign + st["assign"]
                    load = jnp.maximum(load, st["load"])
                    tk.append(st["topk"])
                with jax.named_scope("lm_head"):
                    logits = fam.head(image_head, cfg, x)
                with jax.named_scope("sample"):
                    k_i = jax.random.fold_in(key, i)
                    keys = jax.vmap(lambda j: jax.random.fold_in(k_i, j))(item_idx)
                    ids = jax.vmap(lambda kk, row: sample_top_k_top_p(kk, row, top_k=cfg.top_k, top_p=cfg.top_p))(
                        keys, logits)
                    j = i // PROBE_EVERY
                    old = jax.lax.dynamic_slice_in_dim(probe, j, 1, axis=1)
                    probe = jax.lax.dynamic_update_slice_in_dim(
                        probe, jnp.where(i % PROBE_EVERY == 0, logits[:, None, :], old), j, axis=1)
            tk = jnp.stack(tk, axis=1) if tk else jnp.zeros((B, 0, cfg.num_experts_per_tok), jnp.int32)
            return (ids + lo, state, assign, load, probe), (ids, tk)

        probe0 = jnp.zeros((B, n // PROBE_EVERY, cfg.image_vocab), jnp.float32)
        boi = jnp.full((B,), cfg.boi_id, jnp.int32)
        (_, state, assign, load, probe), (ids, topk_d) = jax.lax.scan(
            step, (boi, state, assign, load, probe0), jnp.arange(n))
        ids = ids.T                                                           # [B, n]
        topk = jnp.concatenate([topk_p, jnp.moveaxis(topk_d, 0, 1)], axis=1)  # [B, Tmax, layers, k]

    # what the router chose and computed, in a family that has one
    routed = {"topk": topk, "assign": assign, "load": jnp.broadcast_to(load, (B,))} if cfg.n_moe_layers else {}
    rows = {"ids": ids, **routed, "logits": probe}
    rows.update({f"carried/{k}": jnp.full((B,), v, jnp.float32) for k, v in carried_bytes.items()})
    if fam.state_rows is not None:
        rows.update(fam.state_rows(cfg, state))
    if not decode:
        return ids, rows
    with obs_scope("decode"):
        f_hat = msvq.embed_ids(params["vq"], ids).reshape(B, cfg.grid, cfg.grid, cfg.vq.c_vae).astype(jnp.float32)
        # the decoder's activations (256 px x 160 channels an image) are the
        # step's largest: a member chunk decodes ``decode_batch`` images a
        # member at a time, not its whole batch
        images = jax.lax.map(lambda f: msvq.decode_img(params["vq"], cfg.vq, f[None])[0], f_hat,
                             batch_size=cfg.decode_batch or B)
        return images, rows

"""A sparse-expert decoder with sliding-window attention beside full
grouped-query attention as an autoregressive image-token generator
(``model_type: mimo_v2_flash``): a window layer's cache bounded by its window
and a full layer's cache over every position carried side by side through one
``generate``, a learned attention sink on the window layers, query-key heads
wider than value heads, two RoPE bases, and a sigmoid top-k router over
routed experts of which this chip holds a share, with no shared expert.

It is the fourth family behind :func:`models.lm.generate` (``models/lm.py``
holds the prefill-then-scan, sampling, the image-id range, the VQ decode, the
router ``lm.route`` and the routed products ``lm.routed_with_stats`` over
``ops/grouped.py``). Sizes come from a ``config.json``-shaped file
(:func:`models.lm.config_from_json` reads ``model_type``): the published keys
of a ``mimo_v2_flash`` ``config.json`` plus the share this chip holds
(``experts_held``, ``expert_offset``, ``vocab_rows_held``) and the system's use
of the model (``image_tokens``).

The layer equations (the plain float32 form is
``reference/gqa_swa_moe_reference.py``, written from the same description and
sharing no code with this file). ``N(x) = x rsqrt(mean(x^2) + eps) w`` in
float32 (``layernorm_epsilon``); block, pre-norm: ``h = x + Attn(N1(x))``,
``y = h + F(N2(h))``; final ``N``, untied head.

- attention: ``hybrid_layer_pattern[i]`` gives layer ``i``'s kind, 0 full and
  1 window, each with its own heads (``num_*`` / ``swa_num_*``), head widths
  (``head_dim`` for query and key, ``v_head_dim`` for value; ``swa_*`` alike)
  and RoPE base (``rope_theta`` / ``swa_rope_theta``). ``q = u Wq``, ``k = u
  Wk``, ``v = u Wv``, no bias; rotate-half RoPE on the first
  ``int(partial_rotary_factor · head_dim)`` numbers of each query and key
  head at the token's true position; ``v ← attention_value_scale · v``; a
  key/value head serves ``H / Hkv`` query heads; ``s_ij = q_i·k_j /
  sqrt(head_dim)``. A full layer sees keys ``j ≤ i``, a window layer ``i −
  sliding_window < j ≤ i``, counted in true positions. A window layer's
  learned per-head logit ``b_h`` (``add_swa_attention_sink_bias``) joins the
  softmax and carries no value: ``p_ij = exp(s_ij) / (exp(b_h) + Σ_j'
  exp(s_ij'))``. ``o = Σ_j p_ij v_j``, then ``Wo``;
- FFN: ``moe_layer_freq[i]`` 0 is a dense SwiGLU of ``intermediate_size``;
  1 is routed: ``s = sigmoid(f32(u) Wrᵀ)``, top-k by ``s +
  e_score_correction_bias`` (``noaux_tc``, one group), ``w = s_top / Σ
  s_top`` (``norm_topk_prob``) × ``routed_scaling_factor`` (1 where the
  file says null); ``MoE(u) = Σ_{e ∈ top-k, e held} w_e SwiGLU_e(u)`` — the
  share as in ``models/lm.py``, and no shared expert.

How it runs. A full layer's K and V live in ``lm.decode_slot``'s cache of
``cache_len`` slots. A window layer's live in a ring of ``sliding_window``
slots, whatever ``cache_len`` is: a sequence's positions are laid out as in
that cache but with the prompt right-aligned — virtual position ``v = P −
len + p`` for true position ``p`` of a prompt of ``len`` ids (``P =
max_prompt_len``), ``P + i`` for sampled position ``i`` — and slot ``v mod
W`` holds ``v``. Sampled position ``i`` is then written at slot ``(P + i) mod
W`` for every sequence alike (in place), over the one key that has just left
its window, and a slot is seen while it holds a virtual position at or after
``P − len``: the pad gap between prompt and sampled ids takes no slot and
counts for nothing. The prefill runs the prompt through both kinds as whole
masked blocks and gathers each window layer's ring from its entries. The
published model's multi-token-prediction layers have no keys in its
``config.json`` and are not written down here.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..lora import lookup
from ..ops.quant import maybe_quantize_tree
from . import lm, nn

Params = Dict[str, Any]

MODEL_TYPE = "mimo_v2_flash"
FULL, WINDOW = "full_attention", "sliding_attention"
DENSE, MOE = "dense", "moe"

# every attention projection of both kinds, the dense FFN and each held routed
# expert's three matrices; routers, correction biases, sinks, norms, embedding
# and head stay frozen
LORA_TARGETS: Tuple[str, ...] = (
    r"^layers/\d+/attn/(wq|wk|wv|wo)$",
    r"^layers/\d+/ffn/(gate|up|down)$",
    r"^layers/\d+/moe/experts/(gate|up|down)$",
)

PUBLISHED_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "v_head_dim",
    "swa_num_attention_heads", "swa_num_key_value_heads", "swa_head_dim", "swa_v_head_dim",
    "partial_rotary_factor", "rope_theta", "swa_rope_theta", "sliding_window", "attention_value_scale",
    "add_swa_attention_sink_bias", "add_full_attention_sink_bias", "layernorm_epsilon", "intermediate_size",
    "moe_intermediate_size", "n_routed_experts", "num_experts_per_tok", "n_shared_experts", "norm_topk_prob",
    "routed_scaling_factor", "hybrid_layer_pattern", "moe_layer_freq", "num_hidden_layers", "vocab_size",
    "tie_word_embeddings", "attention_bias",
)


@dataclasses.dataclass(frozen=True)
class SWALMConfig(lm.GeneratorUse):
    # --- the model's own config.json keys (MiMo-V2-Flash's as defaults)
    hidden_size: int = 4096
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 5_000_000.0
    swa_rope_theta: float = 10_000.0
    sliding_window: int = 128
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    layernorm_epsilon: float = 1e-5
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: Optional[int] = None
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    topk_method: str = "noaux_tc"
    hybrid_layer_pattern: Tuple[int, ...] = (0,) + ((1,) * 4 + (0,)) + ((1,) * 5 + (0,)) * 7
    moe_layer_freq: Tuple[int, ...] = (0,) + (1,) * 47
    num_hidden_layers: int = 48
    vocab_size: int = 152576
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    # --- this chip's share (the fields are GeneratorUse's; the defaults are this model's whole)
    vocab_rows_held: int = 152576

    model_type = MODEL_TYPE  # read by whoever has to know which family a parsed file gave

    def __post_init__(self) -> None:
        wrote = ("models/lm_swa.py writes down mimo_v2_flash with a learned sink on the window layers alone, "
                 "routed experts without a shared one over one expert group, no bias, an untied head: ")
        refused = [(self.add_full_attention_sink_bias, "add_full_attention_sink_bias true"),
                   (not self.add_swa_attention_sink_bias, "add_swa_attention_sink_bias false"),
                   (self.n_shared_experts is not None, f"n_shared_experts {self.n_shared_experts}"),
                   (self.tie_word_embeddings or self.attention_bias, "a tied head or attention bias"),
                   (self.topk_method not in ("greedy", "noaux_tc"), f"topk_method {self.topk_method!r}")]
        for bad, what in refused:
            if bad:
                raise ValueError(f"{what}: {wrote}not this")
        L = self.num_hidden_layers
        if len(self.hybrid_layer_pattern) != L or len(self.moe_layer_freq) != L \
                or set(self.hybrid_layer_pattern) - {0, 1} or set(self.moe_layer_freq) - {0, 1}:
            raise ValueError(f"hybrid_layer_pattern and moe_layer_freq must name {L} layers, each 0 or 1")
        for kind in (FULL, WINDOW):
            H, Hkv, dqk, _, _ = self.heads(kind)
            if H % Hkv or self.rope_dim(kind) % 2 or self.rope_dim(kind) > dqk:
                raise ValueError(f"{kind}: query heads a multiple of key/value heads, an even rotary width")
        self.check_use(self.n_routed_experts)

    @classmethod
    def from_raw(cls, raw: Dict[str, Any]) -> "SWALMConfig":
        kw = lm.published_from_raw(raw, PUBLISHED_KEYS, f"a {MODEL_TYPE}")
        kw["hybrid_layer_pattern"], kw["moe_layer_freq"] = (tuple(kw["hybrid_layer_pattern"]),
                                                            tuple(kw["moe_layer_freq"]))
        if kw["routed_scaling_factor"] is None:
            kw["routed_scaling_factor"] = 1.0
        if raw.get("scoring_func", "sigmoid") != "sigmoid" or raw.get("n_group", 1) != 1 \
                or raw.get("topk_group", 1) != 1:
            raise ValueError(f"a {MODEL_TYPE} config.json: this model code writes down sigmoid scoring over one "
                             f"expert group (scoring_func {raw.get('scoring_func')!r}, n_group {raw.get('n_group')}, "
                             f"topk_group {raw.get('topk_group')})")
        kw["topk_method"] = raw.get("topk_method", "noaux_tc")
        return cls(**kw, **lm.use_from_raw(raw, kw["n_routed_experts"], kw["vocab_size"]))

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(WINDOW if k else FULL for k in self.hybrid_layer_pattern)

    @property
    def ffn_types(self) -> Tuple[str, ...]:
        return tuple(MOE if k else DENSE for k in self.moe_layer_freq)

    @property
    def n_moe_layers(self) -> int:
        return sum(self.moe_layer_freq)

    def heads(self, kind: str) -> Tuple[int, int, int, int, float]:
        """(query heads, key/value heads, query-key width, value width, RoPE base) of a kind of layer."""
        if kind == WINDOW:
            return (self.swa_num_attention_heads, self.swa_num_key_value_heads, self.swa_head_dim,
                    self.swa_v_head_dim, self.swa_rope_theta)
        return self.num_attention_heads, self.num_key_value_heads, self.head_dim, self.v_head_dim, self.rope_theta

    def rope_dim(self, kind: str) -> int:
        return int(self.partial_rotary_factor * self.heads(kind)[2])

    @property
    def lora_targets(self) -> Tuple[str, ...]:
        return LORA_TARGETS

    def family(self) -> "lm.Family":
        return FAMILY


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: SWALMConfig, i: int) -> Params:
    d, dt = cfg.hidden_size, cfg.compute_dtype
    kind = cfg.layer_types[i]
    H, Hkv, dqk, dv, _ = cfg.heads(kind)
    ks = jax.random.split(key, 8)
    p: Params = {"n1": lm._norm(d), "n2": lm._norm(d), "attn": {
        "wq": lm._kernel(ks[0], (d, H * dqk), dt), "wk": lm._kernel(ks[1], (d, Hkv * dqk), dt),
        "wv": lm._kernel(ks[2], (d, Hkv * dv), dt), "wo": lm._kernel(ks[3], (H * dv, d), dt)}}
    if kind == WINDOW:
        # float32, frozen. Seeded at ln(window) + N(0, 1) (assumed): against random-weight scores the sink then
        # holds about half of a full window row's mass, so a program that dropped it fails the comparison
        p["attn"]["sink"] = math.log(cfg.sliding_window) + jax.random.normal(ks[4], (H,), jnp.float32)
    if cfg.ffn_types[i] == MOE:
        p["moe"] = {
            # float32 and never quantized: the model's code routes in float32; the selection bias seeded at
            # N(0, 0.1²) (assumed), so that a choice by the score alone differs from the model's
            "router": {"weight": jax.random.normal(ks[5], (cfg.n_routed_experts, d), jnp.float32) / math.sqrt(d),
                       "e_score_correction_bias": 0.1 * jax.random.normal(ks[6], (cfg.n_routed_experts,),
                                                                          jnp.float32)},
            "experts": lm._swiglu_init(ks[7], d, cfg.moe_intermediate_size, dt, experts=cfg.experts_held),
        }
    else:
        p["ffn"] = lm._swiglu_init(ks[7], d, cfg.intermediate_size, dt)
    return p


def init(key: jax.Array, cfg: SWALMConfig, base_quant: str = "off") -> Params:
    """Seeded parameters, each kernel quantized inside the same program when
    ``base_quant="int8"`` (``models/lm.init_lm`` says why)."""
    d, dt, L = cfg.hidden_size, cfg.compute_dtype, cfg.num_hidden_layers
    ks = jax.random.split(key, L + 3)
    q = lambda tree: maybe_quantize_tree(tree, base_quant)
    return {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_rows_held, d), jnp.float32) * 0.02).astype(dt),
        "layers": [q(_layer_init(ks[1 + i], cfg, i)) for i in range(L)],
        "final_norm": lm._norm(d),
        "head": q(lm._kernel(ks[L + 1], (d, cfg.vocab_rows_held), dt)),
        "vq": q(lm.msvq.init_msvq(ks[L + 2], cfg.vq)),
    }


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def _rms(x: jax.Array, p: Params, cfg: SWALMConfig) -> jax.Array:
    return nn.rms_norm(x, p, eps=cfg.layernorm_epsilon)


def _project(p: Params, cfg: SWALMConfig, kind: str, u: jax.Array, pos: jax.Array,
             lora: Optional[Params], path: str, scale: float, entry_only: bool = False):
    """``u [..., d]`` at true positions ``pos [...]`` → roped ``q [..., Hkv,
    H / Hkv, dqk]`` and the cache entry: roped ``k [..., Hkv, dqk]`` and
    scaled ``v [..., Hkv, dv]`` (``entry_only``: the entry alone)."""
    H, Hkv, dqk, dv, theta = cfg.heads(kind)
    rot, lead = cfg.rope_dim(kind), u.shape[:-1]

    def rope(x):
        return jnp.concatenate([lm._rope(x[..., :rot], pos[..., None], theta), x[..., rot:]], axis=-1)

    k = rope(nn.dense(p["wk"], u, lookup(lora, f"{path}/wk"), scale).reshape(*lead, Hkv, dqk))
    v = nn.dense(p["wv"], u, lookup(lora, f"{path}/wv"), scale).reshape(*lead, Hkv, dv) * cfg.attention_value_scale
    if entry_only:
        return k, v
    q = rope(nn.dense(p["wq"], u, lookup(lora, f"{path}/wq"), scale).reshape(*lead, H, dqk))
    return q.reshape(*lead, Hkv, H // Hkv, dqk), (k, v)


def _softmax(sc: jax.Array, sink: Optional[jax.Array]) -> jax.Array:
    """Softmax over the last axis; with a sink ``b`` (broadcast to ``sc``
    without its last axis) ``exp(b)`` joins the denominator and carries no
    value."""
    if sink is None:
        return jax.nn.softmax(sc, axis=-1)
    b = jnp.broadcast_to(sink, sc.shape[:-1])[..., None]
    m = jnp.maximum(sc.max(-1, keepdims=True), b)
    e = jnp.exp(sc - m)
    return e / (e.sum(-1, keepdims=True) + jnp.exp(b - m))


def _sink(p: Params, cfg: SWALMConfig, kind: str) -> Optional[jax.Array]:
    """The sink logits ``[Hkv, H / Hkv]`` of a window layer (None on a full one)."""
    if "sink" not in p:
        return None
    H, Hkv = cfg.heads(kind)[:2]
    return p["sink"].reshape(Hkv, H // Hkv)


def attn_prefill(p: Params, cfg: SWALMConfig, kind: str, u: jax.Array, pos: jax.Array, valid: jax.Array,
                 lora: Optional[Params], path: str, scale: float):
    """The whole (right-padded) prompt as one masked block: ``u [S, T, d]``,
    key validity ``valid [S, T]`` → (out ``[S, T, d]``, cache entries ``(k,
    v)`` ``[S, T, Hkv, dqk | dv]``). A window layer's mask is the band of
    ``sliding_window`` positions ending at the query, its sink in the softmax."""
    S, T, _ = u.shape
    q, (k, v) = _project(p, cfg, kind, u, pos, lora, path, scale)
    with jax.named_scope("attend"):
        sc = jnp.einsum("sqgnd,skgd->sgnqk", q, k, preferred_element_type=jnp.float32) / math.sqrt(q.shape[-1])
        i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        see = (j <= i) & ((j > i - cfg.sliding_window) if kind == WINDOW else True)
        see = see[None, None, None] & valid[:, None, None, None, :]
        sink = _sink(p, cfg, kind)
        pr = _softmax(jnp.where(see, sc, -1e30), None if sink is None else sink[..., None])
        o = jnp.einsum("sgnqk,skgd->sqgnd", pr.astype(u.dtype), v).reshape(S, T, -1)
    return nn.dense(p["wo"], o, lookup(lora, f"{path}/wo"), scale), (k, v)


def attn_decode(p: Params, cfg: SWALMConfig, kind: str, u: jax.Array, pos: jax.Array, cache, slot: jax.Array,
                valid: jax.Array, lora: Optional[Params], path: str, scale: float):
    """One position a sequence: ``u [S, d]`` at true positions ``pos [S]``;
    ``cache = (k, v)`` (a full layer's ``cache_len`` slots or a window
    layer's ring) gets the new entry at ``slot`` in place; ``valid [S,
    slots]`` names the slots the query sees."""
    q, (k, v) = _project(p, cfg, kind, u, pos, lora, path, scale)
    ck, cv = (jax.lax.dynamic_update_slice(c, e[:, None].astype(c.dtype), (0, slot, 0, 0)) for c, e in zip(cache, (k, v)))
    with jax.named_scope("attend"):
        sc = jnp.einsum("sgnd,stgd->sgnt", q, ck, preferred_element_type=jnp.float32) / math.sqrt(q.shape[-1])
        pr = _softmax(jnp.where(valid[:, None, None, :], sc, -1e30), _sink(p, cfg, kind))
        o = jnp.einsum("sgnt,stgd->sgnd", pr.astype(u.dtype), cv).reshape(u.shape[0], -1)
    return nn.dense(p["wo"], o, lookup(lora, f"{path}/wo"), scale), (ck, cv)


def moe(p: Params, cfg: SWALMConfig, u: jax.Array, row_valid: jax.Array, factors: Optional[Dict[str, Any]],
        scale: float):
    """``u [R, d]`` → (``[R, d]``, counters of this call): ``lm.route`` and
    ``lm.routed_with_stats`` as the MLA family runs them, with no shared expert."""
    with jax.named_scope("router"):
        top_i, top_w = lm.route(p, cfg, u)
    with jax.named_scope("experts"):
        return lm.routed_with_stats(p["experts"], cfg, u, top_i, top_w, row_valid, factors, scale)


def block(p: Params, cfg: SWALMConfig, li: int, x: jax.Array, attn, row_valid: jax.Array,
          lora: Optional[Params], factors, scale: float):
    """Pre-norm block on ``x [..., d]``; ``attn(u) -> (out, cache)`` is the
    layer's attention in the form the caller is in (prefill or decode), under
    ``lm_swa`` on a window layer and ``lm_attn`` on a full one. Returns (y,
    cache, MoE stats or None)."""
    with jax.named_scope("lm_swa" if cfg.layer_types[li] == WINDOW else "lm_attn"):
        a, cache = attn(_rms(x, p["n1"], cfg))
        h = x + a
    if "moe" in p:
        with jax.named_scope("lm_moe"):
            u = _rms(h, p["n2"], cfg)
            f, stats = moe(p["moe"], cfg, u.reshape(-1, u.shape[-1]), row_valid.reshape(-1), factors, scale)
            f = f.reshape(u.shape)
    else:
        with jax.named_scope("lm_dense_ffn"):
            f, stats = lm._swiglu(p["ffn"], _rms(h, p["n2"], cfg), lora, f"layers/{li}/ffn", scale), None
    return h + f, cache, stats


def head(params: Params, cfg: SWALMConfig, h: jax.Array) -> jax.Array:
    """Float32 logits over the columns of ``params["head"]``, as one array for
    every reader (``models/lm_hybrid.head`` says why the barrier)."""
    return jax.lax.optimization_barrier(
        nn.dense(params["head"], _rms(h, params["final_norm"], cfg)).astype(jnp.float32))


def prefill(params: Params, cfg: SWALMConfig, ids: jax.Array, lens: jax.Array,
            lora: Optional[Params] = None, lora_scale: float = 1.0, factors=None, cache_only: bool = False):
    """``ids [S, T]`` (right-padded, ``lens [S]`` real) through every block.
    Returns (hidden ``[S, T, d]`` before the final norm, each layer's cache
    entries ``(k, v)`` at the prompt's positions, per-MoE-layer stats with
    rows ``[S, T]``). ``cache_only`` (generation): the last layer stops at its
    cache entries — nothing reads what its attention and FFN would add — and
    the hidden state returned is None."""
    S, T = ids.shape
    pos = jnp.broadcast_to(jnp.arange(T), (S, T))
    valid = pos < lens[:, None]
    factors = factors if factors is not None else lm.expert_factors(lora, cfg, cfg.compute_dtype)
    x = lm._embed(params, cfg, ids)
    entries, stats = [], []
    for li, p in enumerate(params["layers"]):
        kind, path = cfg.layer_types[li], f"layers/{li}/attn"
        if cache_only and li == len(params["layers"]) - 1:
            with jax.named_scope("lm_swa" if kind == WINDOW else "lm_attn"):
                entries.append(_project(p["attn"], cfg, kind, _rms(x, p["n1"], cfg), pos, lora, path, lora_scale,
                                        entry_only=True))
            return None, entries, stats
        attn = lambda u, p=p, kind=kind, path=path: attn_prefill(p["attn"], cfg, kind, u, pos, valid, lora, path,
                                                                 lora_scale)
        x, entry, st = block(p, cfg, li, x, attn, valid, lora, factors[li] if factors else None, lora_scale)
        entries.append(entry)
        if st is not None:
            stats.append({"assign": st["assign"].reshape(S, T), "load": st["load"],
                          "topk": st["topk"].reshape(S, T, -1)})
    return x, entries, stats


def forward_logits(params: Params, cfg: SWALMConfig, ids: jax.Array, lens: jax.Array,
                   lora: Optional[Params] = None, lora_scale: float = 1.0) -> jax.Array:
    """Teacher-forced logits ``[S, T, vocab_rows_held]`` (tests)."""
    return head(params, cfg, prefill(params, cfg, ids, lens, lora, lora_scale)[0])


def window_ring(cfg: SWALMConfig, entry: jax.Array, lens: jax.Array) -> jax.Array:
    """A window layer's prefill entries ``[S, P, ...]`` (true positions) →
    its ring ``[S, sliding_window, ...]``: slot ``r`` holds the latest virtual
    position ``v ≡ r`` (mod W) before the first sampled one, true position ``v
    − (P − len)``; zeros where that is no prompt position."""
    W, P = cfg.sliding_window, entry.shape[1]
    r = jnp.arange(W)
    p = (r + W * ((P - 1 - r) // W))[None, :] - (P - lens)[:, None]                  # [S, W]
    ring = jnp.take_along_axis(entry, jnp.clip(p, 0, P - 1).reshape(p.shape + (1,) * (entry.ndim - 2)), axis=1)
    return jnp.where((p >= 0).reshape(p.shape + (1,) * (entry.ndim - 2)), ring, 0).astype(entry.dtype)


def window_slot(cfg: SWALMConfig, i: jax.Array, prompt_len: jax.Array):
    """Sampled position ``i`` of right-padded prompts ``prompt_len [B]`` in a
    window layer's ring: (the slot it is written to, the ring slots a query
    sees ``[B, sliding_window]``: those that hold a virtual position at or
    after ``P − len`` once this position is written)."""
    W, P = cfg.sliding_window, cfg.max_prompt_len
    r = jnp.arange(W)
    v = r + W * ((P + i - r) // W)
    return (P + i) % W, v[None, :] >= (P - prompt_len)[:, None]


def _nbytes(x: jax.Array) -> int:
    return math.prod(x.shape[1:]) * x.dtype.itemsize  # a sequence's


def prefill_state(params: Params, cfg: SWALMConfig, ids: jax.Array, lens: jax.Array,
                  lora, lora_scale: float, factors):
    """:func:`models.lm.generate`'s first hook: the prompt into what the decode
    scan carries — a full layer's K and V in ``cache_len`` slots, a window
    layer's in its ring of ``sliding_window`` — the MoE stats, and the bytes
    a sequence carries by kind."""
    B, P = ids.shape
    _, entries, stats = prefill(params, cfg, ids, lens, lora, lora_scale, factors, cache_only=True)
    dt = cfg.compute_dtype
    state, nbytes = [], {"kv_cache": 0, "window_cache": 0}
    for kind, c in zip(cfg.layer_types, entries):
        if kind == WINDOW:
            c = tuple(window_ring(cfg, e.astype(dt), lens) for e in c)
            nbytes["window_cache"] += sum(map(_nbytes, c))
        else:
            c = tuple(jnp.zeros((B, cfg.cache_len) + e.shape[2:], dt).at[:, :P].set(e.astype(dt)) for e in c)
            nbytes["kv_cache"] += sum(map(_nbytes, c))
        state.append(c)
    return tuple(state), stats, nbytes


def decode_layers(params: Params, cfg: SWALMConfig, x: jax.Array, state, i: jax.Array, prompt_len: jax.Array,
                  lora, lora_scale: float, factors):
    """:func:`models.lm.generate`'s second hook: sampled position ``i`` of
    every sequence, ``x [B, d]``, through the blocks over both kinds of cache."""
    B = x.shape[0]
    slot, pos, valid = lm.decode_slot(cfg, i, prompt_len)
    ring_slot, ring_valid = window_slot(cfg, i, prompt_len)
    new_state, stats = [], []
    for li, p in enumerate(params["layers"]):
        kind, path = cfg.layer_types[li], f"layers/{li}/attn"
        at, seen = (ring_slot, ring_valid) if kind == WINDOW else (slot, valid)
        attn = lambda u, p=p, li=li, kind=kind, path=path, at=at, seen=seen: attn_decode(
            p["attn"], cfg, kind, u, pos, state[li], at, seen, lora, path, lora_scale)
        x, c, st = block(p, cfg, li, x, attn, jnp.ones((B,), bool), lora, factors[li] if factors else None,
                         lora_scale)
        new_state.append(c)
        if st is not None:
            stats.append(st)
    return x, tuple(new_state), stats


FAMILY = lm.Family(init=init, prefill_state=prefill_state, decode_layers=decode_layers, head=head)

"""A hybrid decoder language model as an autoregressive image-token generator
(``model_type: qwen3_next``): Gated DeltaNet layers beside gated softmax
attention — a recurrent state and a KV cache carried side by side through one
``generate`` — and a softmax top-k router over many small routed experts, of
which this chip holds a share, with a sigmoid-gated shared expert.

It is the second family behind :func:`models.lm.generate` (``models/lm.py``
holds the first and the parts both share: the prefill-then-scan, sampling, the
image-id range, the VQ decode, ``routed_experts`` over ``ops/grouped.py`` and
``expert_factors``). Sizes come from a ``config.json``-shaped file
(:func:`models.lm.config_from_json` reads ``model_type``): the published keys
of a ``qwen3_next`` ``config.json`` plus the share this chip holds
(``experts_held``, ``expert_offset``, ``vocab_rows_held``) and the system's use
of the model (``image_tokens``).

The layer equations (the plain float32 form is
``reference/gdn_moe_reference.py``, written from the same description and
sharing no code with this file). ``N(x) = x rsqrt(mean(x^2) + eps) (1 + w)``
in float32 (zero-centred weight); block, pre-norm: ``h = x + Mixer(N1(x))``,
``y = h + MoE(N2(h))``; layer ``i`` is gated attention iff ``(i + 1) %
full_attention_interval == 0``, else Gated DeltaNet; final ``N``, untied head.

- gated attention: ``[q | gate] = u Wq`` per head, ``k = u Wk``, ``v = u Wv``
  over ``num_key_value_heads``; per-head ``Nq``, ``Nk``; rotary (rotate-half)
  on the first ``partial_rotary_factor · head_dim`` numbers of a head; causal
  softmax attention, a KV head serving ``H / Hkv`` query heads; ``o ⊙
  sigmoid(gate)``; ``Wo``. The cache holds roped K and V, ``[B, Tmax, Hkv,
  dh]`` each;
- Gated DeltaNet: ``[q | k | v | z] = u Wqkvz``, ``[b | a] = u Wba``;
  ``silu`` of a causal depthwise conv (kernel ``linear_conv_kernel_dim``) over
  ``[q | k | v]``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)``; ``q``, ``k`` L2-normalised, ``q`` scaled ``dk^-1/2``, a key head
  repeated to its value heads; the gated delta rule of ``ops/gated_delta.py``
  over a float32 state ``[B, Hv, dk, dv]``; ``o ← rmsnorm(o) w_g ⊙ silu(z)``;
  ``Wout``. Decode carries the state and the conv's last ``kernel - 1``
  inputs. Right-padding behind a prompt leaves both untouched: ``beta = 0``
  and ``g = 0`` at padding, and a sequence's conv window is taken from its own
  last real positions;
- MoE: ``p = softmax(f32(u) Wrᵀ)``, top-k by ``p``, ``w = p_top / Σ p_top``;
  ``MoE(u) = sigmoid(u w_s) Shared(u) + Σ_{e ∈ top-k, e held} w_e E_e(u)`` —
  the share as in ``models/lm.py``: the router keeps every output, what absent
  experts would add is left out, no code stands in for them.

The published model's multi-token-prediction module has no key in its
``config.json`` and is not written down here.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..lora import lookup
from ..obs import note_program_geometry
from ..ops import gated_delta
from ..ops.quant import maybe_quantize_tree
from . import lm, nn

Params = Dict[str, Any]

MODEL_TYPE = "qwen3_next"

# every mixer projection, the shared expert and each held routed expert's
# three matrices; Wba, the conv, routers, the shared expert's gate, norms,
# A_log, dt_bias, embedding and head stay frozen
LORA_TARGETS: Tuple[str, ...] = (
    r"^layers/\d+/gdn/(wqkvz|wout)$",
    r"^layers/\d+/attn/(wq|wk|wv|wo)$",
    r"^layers/\d+/moe/shared/(gate|up|down)$",
    r"^layers/\d+/moe/experts/(gate|up|down)$",
)

PUBLISHED_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "partial_rotary_factor",
    "rope_theta", "rms_norm_eps", "full_attention_interval", "linear_conv_kernel_dim",
    "linear_key_head_dim", "linear_num_key_heads", "linear_num_value_heads", "linear_value_head_dim",
    "moe_intermediate_size", "shared_expert_intermediate_size", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "num_hidden_layers", "vocab_size",
)

GDN_CHUNK = 64  # positions a chunk of the prefill's gated delta rule


@dataclasses.dataclass(frozen=True)
class HybridLMConfig(lm.GeneratorUse):
    # --- the model's own config.json keys (Qwen3-Next-80B-A3B's as defaults)
    hidden_size: int = 2048
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10_000_000.0
    rms_norm_eps: float = 1e-6
    full_attention_interval: int = 4
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_value_head_dim: int = 128
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    num_hidden_layers: int = 48
    vocab_size: int = 151936
    # --- this chip's share (the fields are GeneratorUse's; the defaults are this model's whole)
    experts_held: int = 512
    vocab_rows_held: int = 151936

    model_type = MODEL_TYPE  # read by whoever has to know which family a parsed file gave

    def __post_init__(self) -> None:
        self.check_use(self.num_experts)
        if self.linear_num_value_heads % self.linear_num_key_heads or \
                self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("value heads must be a multiple of key heads, query heads of KV heads")

    @classmethod
    def from_raw(cls, raw: Dict[str, Any]) -> "HybridLMConfig":
        kw = lm.published_from_raw(raw, PUBLISHED_KEYS, f"a {MODEL_TYPE}")
        if raw.get("mlp_only_layers") or raw.get("decoder_sparse_step", 1) != 1:
            raise ValueError("every layer has routed experts in this model code "
                             "(mlp_only_layers empty, decoder_sparse_step 1)")
        return cls(**kw, **lm.use_from_raw(raw, kw["num_experts"], kw["vocab_size"]))

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple("full_attention" if (i + 1) % self.full_attention_interval == 0 else "linear_attention"
                     for i in range(self.num_hidden_layers))

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def conv_channels(self) -> int:
        return 2 * self.linear_num_key_heads * self.linear_key_head_dim \
            + self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def lora_targets(self) -> Tuple[str, ...]:
        return LORA_TARGETS

    def family(self) -> "lm.Family":
        return FAMILY


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _znorm(dim: int) -> Params:
    return {"scale": jnp.zeros((dim,), jnp.float32)}  # zero-centred: the scale applied is 1 + this


def _block_init(key, cfg: HybridLMConfig, attention: bool) -> Params:
    d, dt = cfg.hidden_size, cfg.compute_dtype
    ks = jax.random.split(key, 10)
    p: Params = {"n1": _znorm(d), "n2": _znorm(d)}
    if attention:
        H, Hkv, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        p["attn"] = {
            "wq": lm._kernel(ks[0], (d, H * 2 * dh), dt),                   # per head: q, then gate
            "wk": lm._kernel(ks[1], (d, Hkv * dh), dt), "wv": lm._kernel(ks[2], (d, Hkv * dh), dt),
            "q_norm": _znorm(dh), "k_norm": _znorm(dh),
            "wo": lm._kernel(ks[3], (H * dh, d), dt),
        }
    else:
        Hv, dv, C, K = (cfg.linear_num_value_heads, cfg.linear_value_head_dim, cfg.conv_channels,
                        cfg.linear_conv_kernel_dim)
        p["gdn"] = {
            "wqkvz": lm._kernel(ks[0], (d, C + Hv * dv), dt),              # [q | k | v | z], contiguous
            "wba": lm._kernel(ks[1], (d, 2 * Hv), dt),                     # [b | a]
            "conv": {"weight": jax.random.normal(ks[2], (K, C), jnp.float32) / math.sqrt(K)},
            # as the source initialises them: A ~ U(0, 16), dt_bias ones
            "a_log": jnp.log(jax.random.uniform(ks[3], (Hv,), jnp.float32, 1e-3, 16.0)),
            "dt_bias": jnp.ones((Hv,), jnp.float32),
            "g_norm": lm._norm(dv),                                         # not zero-centred
            "wout": lm._kernel(ks[4], (Hv * dv, d), dt),
        }
    p["moe"] = {
        # float32 and never quantized: the model's code routes in float32
        "router": {"weight": jax.random.normal(ks[5], (cfg.num_experts, d), jnp.float32) / math.sqrt(d)},
        "shared_gate": {"weight": jax.random.normal(ks[6], (d, 1), jnp.float32) / math.sqrt(d)},
        "experts": lm._swiglu_init(ks[7], d, cfg.moe_intermediate_size, dt, experts=cfg.experts_held),
        "shared": lm._swiglu_init(ks[8], d, cfg.shared_expert_intermediate_size, dt),
    }
    return p


def init(key: jax.Array, cfg: HybridLMConfig, base_quant: str = "off") -> Params:
    """Seeded parameters, each kernel quantized inside the same program when
    ``base_quant="int8"`` (``models/lm.init_lm`` says why)."""
    d, dt, L = cfg.hidden_size, cfg.compute_dtype, cfg.num_hidden_layers
    ks = jax.random.split(key, L + 3)
    q = lambda tree: maybe_quantize_tree(tree, base_quant)
    kinds = cfg.layer_types
    return {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_rows_held, d), jnp.float32) * 0.02).astype(dt),
        "layers": [q(_block_init(ks[1 + i], cfg, kinds[i] == "full_attention")) for i in range(L)],
        "final_norm": _znorm(d),
        "head": q(lm._kernel(ks[L + 1], (d, cfg.vocab_rows_held), dt)),
        "vq": q(lm.msvq.init_msvq(ks[L + 2], cfg.vq)),
    }


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def _zrms(x: jax.Array, p: Params, cfg: HybridLMConfig) -> jax.Array:
    """RMSNorm with a zero-centred weight, in float32."""
    return (nn.rms_norm(x.astype(jnp.float32), eps=cfg.rms_norm_eps) * (1.0 + p["scale"])).astype(x.dtype)


def _rope_partial(x: jax.Array, pos: jax.Array, cfg: HybridLMConfig) -> jax.Array:
    """Rotary on the first ``partial_rotary_factor · head_dim`` numbers of each
    head of ``x [..., heads, dh]`` at ``pos [...]``; the rest passes through."""
    rot = int(cfg.head_dim * cfg.partial_rotary_factor)
    return jnp.concatenate([lm._rope(x[..., :rot], pos[..., None], cfg.rope_theta), x[..., rot:]], axis=-1)


def _attn_project(p: Params, cfg: HybridLMConfig, u: jax.Array, pos: jax.Array,
                  lora: Optional[Params], path: str, scale: float, entry_only: bool = False):
    """``u [..., d]`` at ``pos [...]`` → roped ``q [..., Hkv, H / Hkv, dh]``,
    ``gate [..., H · dh]`` and the cache entry: roped ``k`` and ``v``, each
    ``[..., Hkv, dh]`` (``entry_only``: the entry alone)."""
    H, Hkv, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    lead = u.shape[:-1]
    k = nn.dense(p["wk"], u, lookup(lora, f"{path}/wk"), scale).reshape(*lead, Hkv, dh)
    v = nn.dense(p["wv"], u, lookup(lora, f"{path}/wv"), scale).reshape(*lead, Hkv, dh)
    k = _rope_partial(_zrms(k, p["k_norm"], cfg), pos, cfg)
    if entry_only:
        return k, v
    qg = nn.dense(p["wq"], u, lookup(lora, f"{path}/wq"), scale).reshape(*lead, H, 2 * dh)
    q = _rope_partial(_zrms(qg[..., :dh], p["q_norm"], cfg), pos, cfg)
    return q.reshape(*lead, Hkv, H // Hkv, dh), qg[..., dh:].reshape(*lead, H * dh), (k, v)


def attn_prefill(p: Params, cfg: HybridLMConfig, u: jax.Array, pos: jax.Array, valid: jax.Array,
                 lora: Optional[Params], path: str, scale: float):
    """Whole-sequence causal gated attention: ``u [S, T, d]`` → (out ``[S, T,
    d]``, cache entries ``(k, v)`` each ``[S, T, Hkv, dh]``)."""
    S, T, _ = u.shape
    q, gate, (k, v) = _attn_project(p, cfg, u, pos, lora, path, scale)
    with jax.named_scope("attend"):
        sc = jnp.einsum("sqgnd,skgd->sgnqk", q, k, preferred_element_type=jnp.float32) / math.sqrt(cfg.head_dim)
        see = jnp.tril(jnp.ones((T, T), bool))[None, None, None] & valid[:, None, None, None, :]
        pr = jax.nn.softmax(jnp.where(see, sc, -1e30), axis=-1)
        o = jnp.einsum("sgnqk,skgd->sqgnd", pr.astype(u.dtype), v).reshape(S, T, -1)
    o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(u.dtype)
    return nn.dense(p["wo"], o, lookup(lora, f"{path}/wo"), scale), (k, v)


def attn_decode(p: Params, cfg: HybridLMConfig, u: jax.Array, pos: jax.Array, cache, slot: jax.Array,
                valid: jax.Array, lora: Optional[Params], path: str, scale: float):
    """One position a sequence over the KV cache: ``u [S, d]`` at ``pos [S]``;
    ``cache = (k, v)`` each ``[S, Tmax, Hkv, dh]`` gets the new entry at
    ``slot``; ``valid [S, Tmax]`` names the slots a query sees."""
    q, gate, (k, v) = _attn_project(p, cfg, u, pos, lora, path, scale)
    ck = jax.lax.dynamic_update_slice(cache[0], k[:, None].astype(cache[0].dtype), (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache[1], v[:, None].astype(cache[1].dtype), (0, slot, 0, 0))
    with jax.named_scope("attend"):
        sc = jnp.einsum("sgnd,stgd->sgnt", q, ck, preferred_element_type=jnp.float32) / math.sqrt(cfg.head_dim)
        pr = jax.nn.softmax(jnp.where(valid[:, None, None, :], sc, -1e30), axis=-1)
        o = jnp.einsum("sgnt,stgd->sgnd", pr.astype(u.dtype), cv).reshape(u.shape[0], -1)
    o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(u.dtype)
    return nn.dense(p["wo"], o, lookup(lora, f"{path}/wo"), scale), (ck, cv)


def _gdn_project(p: Params, cfg: HybridLMConfig, u: jax.Array, lora: Optional[Params], path: str, scale: float):
    """``u [..., d]`` → the conv's input ``[q | k | v] [..., C]``, ``z [...,
    Hv, dv]``, ``beta [..., Hv]`` and the log-decay ``g [..., Hv]`` (float32)."""
    Hv, C = cfg.linear_num_value_heads, cfg.conv_channels
    qkvz = nn.dense(p["wqkvz"], u, lookup(lora, f"{path}/wqkvz"), scale)
    # float32 out of the gates' projection: the log-decay multiplies ``a`` by up to exp(A_log) = 16, so a
    # bf16 rounding of ``a`` is a percent of alpha a position (u and the kernel are exact in bf16 either way)
    ba = nn.dense(p["wba"], u.astype(jnp.float32))
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(ba[..., Hv:] + p["dt_bias"])
    return qkvz[..., :C], qkvz[..., C:].reshape(*u.shape[:-1], Hv, cfg.linear_value_head_dim), beta, g


def _gdn_heads(cfg: HybridLMConfig, mixed: jax.Array):
    """``silu(conv)`` output ``[..., C]`` → float32 ``q, k [..., Hv, dk]``
    (L2-normalised, ``q`` scaled, a key head repeated to its value heads) and
    ``v [..., Hv, dv]``."""
    Hk, Hv, dk, dv = (cfg.linear_num_key_heads, cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                      cfg.linear_value_head_dim)
    lead, nq = mixed.shape[:-1], Hk * dk
    mixed = mixed.astype(jnp.float32)
    l2 = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
    q = l2(mixed[..., :nq].reshape(*lead, Hk, dk)) / math.sqrt(dk)
    k = l2(mixed[..., nq: 2 * nq].reshape(*lead, Hk, dk))
    rep = lambda t: jnp.repeat(t, Hv // Hk, axis=-2)
    return rep(q), rep(k), mixed[..., 2 * nq:].reshape(*lead, Hv, dv)


def _gdn_out(p: Params, cfg: HybridLMConfig, o: jax.Array, z: jax.Array, dtype,
             lora: Optional[Params], path: str, scale: float) -> jax.Array:
    """Gated RMSNorm per head over ``dv`` (weight not zero-centred), then ``Wout``."""
    o = nn.rms_norm(o, p["g_norm"], eps=cfg.rms_norm_eps) * jax.nn.silu(z.astype(jnp.float32))
    return nn.dense(p["wout"], o.reshape(*o.shape[:-2], -1).astype(dtype), lookup(lora, f"{path}/wout"), scale)


def causal_conv(mixed: jax.Array, w: jax.Array, lens: jax.Array, bias: Optional[jax.Array] = None):
    """The short causal depthwise conv of a recurrent mixer over whole
    (right-padded) sequences: ``mixed [S, T, C]``, taps ``w [K, C]`` (float32:
    the taps are summed in it), ``lens [S]`` real positions → (``silu(conv +
    bias) [S, T, C]``, the window a decode step carries on ``[S, K - 1, C]``:
    each sequence's last ``K - 1`` real inputs, zeros before its start)."""
    K, T = w.shape[0], mixed.shape[1]
    padded = jnp.pad(mixed, ((0, 0), (K - 1, 0), (0, 0)))                 # padded[t + K - 1] = mixed[t]
    taps = sum(padded[:, j: j + T] * w[j] for j in range(K))
    conv = jax.nn.silu(taps if bias is None else taps + bias)
    at = lens[:, None] + jnp.arange(K - 1)[None, :]                       # mixed[len - (K - 1) + j]
    return conv, jnp.take_along_axis(padded, at[:, :, None], axis=1)


def causal_conv_step(window: jax.Array, mixed: jax.Array, w: jax.Array, bias: Optional[jax.Array] = None):
    """One position of :func:`causal_conv`: ``window [S, K - 1, C]``, the new
    input ``mixed [S, C]`` → (``silu(conv + bias) [S, C]``, the ``K`` inputs
    ``[S, K, C]``, whose last ``K - 1`` are the next window)."""
    full = jnp.concatenate([window, mixed[:, None].astype(window.dtype)], axis=1)
    taps = (full * w).sum(1)
    return jax.nn.silu(taps if bias is None else taps + bias), full


def gdn_prefill(p: Params, cfg: HybridLMConfig, u: jax.Array, lens: jax.Array,
                lora: Optional[Params], path: str, scale: float, chunk: int = GDN_CHUNK):
    """Whole (right-padded) sequences: ``u [S, T, d]``, ``lens [S]`` real
    positions → (out ``[S, T, d]``, (state ``[S, Hv, dk, dv]`` float32 after
    each sequence's last real position, conv window ``[S, K - 1, C]``: its
    last ``K - 1`` real conv inputs, zeros before the sequence's start))."""
    S, T, _ = u.shape
    mixed, z, beta, g = _gdn_project(p, cfg, u, lora, path, scale)
    with jax.named_scope("conv"):
        conv, window = causal_conv(mixed, p["conv"]["weight"], lens)
    with jax.named_scope("delta_rule"):
        q, k, v = _gdn_heads(cfg, conv)
        real = (jnp.arange(T)[None, :] < lens[:, None])[..., None]        # padding: beta = 0, alpha = 1
        state0 = jnp.zeros((S, cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim),
                           jnp.float32)
        o, state = gated_delta.chunk_gated_delta_rule(
            q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0), state0, chunk)
    with jax.named_scope("gdn_out"):
        out = _gdn_out(p, cfg, o, z, u.dtype, lora, path, scale)
    return out, (state, window)


def gdn_decode(p: Params, cfg: HybridLMConfig, u: jax.Array, carried,
               lora: Optional[Params], path: str, scale: float):
    """One position a sequence: ``u [S, d]``, ``carried = (state, window)`` →
    (out ``[S, d]``, the new pair). The rule's step is ``ops/gated_delta``'s
    Pallas kernel on a TPU at the published 128 x 128 head (the state crosses
    HBM once in and once out, in the carry's own buffer) and the
    ``jax.numpy`` step everywhere else — a CPU, the toy heads of tier-1; a
    state carried narrower (``STATE_DTYPE``, a control) is widened before
    the step and narrowed after, whichever form runs."""
    state, window = carried
    mixed, z, beta, g = _gdn_project(p, cfg, u, lora, path, scale)
    with jax.named_scope("conv"):
        conv, full = causal_conv_step(window, mixed, p["conv"]["weight"])
    with jax.named_scope("delta_rule"):
        q, k, v = _gdn_heads(cfg, conv)
        o, state = gated_delta.gated_delta_step(q, k, v, g, beta, state.astype(jnp.float32))
    with jax.named_scope("gdn_out"):
        out = _gdn_out(p, cfg, o, z, u.dtype, lora, path, scale)
    return out, (state.astype(STATE_DTYPE), full[:, 1:])


def route(p: Params, cfg: HybridLMConfig, u: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``u [R, d]`` → (expert ids ``[R, k]`` of all ``num_experts``, weights
    ``[R, k]`` normalized over the k chosen): softmax, then top-k."""
    logits = jnp.dot(u.astype(jnp.float32), p["router"]["weight"].T, precision=jax.lax.Precision.HIGHEST)
    top_p, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.num_experts_per_tok)
    w = top_p / top_p.sum(-1, keepdims=True) if cfg.norm_topk_prob else top_p
    return top_i.astype(jnp.int32), w


def moe(p: Params, cfg: HybridLMConfig, u: jax.Array, row_valid: jax.Array, lora: Optional[Params],
        factors: Optional[Dict[str, Any]], path: str, scale: float):
    """``u [R, d]`` → (``[R, d]``, counters of this call), as ``lm.moe``."""
    with jax.named_scope("router"):
        top_i, top_w = route(p, cfg, u)
    with jax.named_scope("shared"):
        gate = jax.nn.sigmoid(u.astype(jnp.float32) @ p["shared_gate"]["weight"]).astype(u.dtype)
        shared = gate * lm._swiglu(p["shared"], u, lora, f"{path}/shared", scale)
    with jax.named_scope("experts"):
        routed, stats = lm.routed_with_stats(p["experts"], cfg, u, top_i, top_w, row_valid, factors, scale)
    return shared + routed, stats


def block(p: Params, cfg: HybridLMConfig, li: int, x: jax.Array, mixer, row_valid: jax.Array,
          lora: Optional[Params], factors, scale: float):
    """Pre-norm block on ``x [..., d]``; ``mixer(u) -> (out, carried)`` is the
    layer's own mixer in the form the caller is in (prefill or decode).
    Returns (y, carried, MoE stats)."""
    with jax.named_scope("lm_attn" if "attn" in p else "lm_gdn"):
        a, carried = mixer(_zrms(x, p["n1"], cfg))
        h = x + a
    with jax.named_scope("lm_moe"):
        u = _zrms(h, p["n2"], cfg)
        f, stats = moe(p["moe"], cfg, u.reshape(-1, u.shape[-1]), row_valid.reshape(-1), lora, factors,
                       f"layers/{li}/moe", scale)
    return h + f.reshape(u.shape), carried, stats


def head(params: Params, cfg: HybridLMConfig, h: jax.Array) -> jax.Array:
    """Float32 logits over the columns of ``params["head"]``, as ONE array for
    every reader. The TPU compiler widens the dot's bf16 result inside the
    dot's own fusion, where the accumulator's float32 bits come out — what
    ``generate``'s sampler and probe have both read in this family's step, and
    what ``expected/`` records. Left free, it may hand one reader the *rounded*
    bf16 result and widen that there: on the image-id columns it did, and the
    sampler drew ids from other logits than the probe kept (PR 34; the barrier
    keeps the widening where the dot is. The MLA family's step has always had
    its sampler on the rounded result: ``lm._head`` is left as it is)."""
    return jax.lax.optimization_barrier(
        nn.dense(params["head"], _zrms(h, params["final_norm"], cfg)).astype(jnp.float32))


def prefill(params: Params, cfg: HybridLMConfig, ids: jax.Array, lens: jax.Array,
            lora: Optional[Params] = None, lora_scale: float = 1.0, factors=None, cache_only: bool = False,
            chunk: int = GDN_CHUNK):
    """``ids [S, T]`` (right-padded, ``lens [S]`` real) through every block.
    Returns (hidden ``[S, T, d]`` before the final norm, what each layer
    carries on — a DeltaNet layer its (state, conv window), an attention layer
    its (k, v) entries ``[S, T, Hkv, dh]`` —, per-layer MoE stats with rows
    ``[S, T]``). ``cache_only`` (generation): the last layer stops at what it
    carries on — nothing reads what its mixer's output and its experts would
    add — and the hidden state returned is None."""
    S, T = ids.shape
    pos = jnp.broadcast_to(jnp.arange(T), (S, T))
    valid = pos < lens[:, None]
    factors = factors if factors is not None else lm.expert_factors(lora, cfg, cfg.compute_dtype)
    x = lm._embed(params, cfg, ids)
    carried, stats = [], []
    for li, p in enumerate(params["layers"]):
        path = f"layers/{li}"
        if "attn" in p:
            mixer = lambda u, p=p, path=path: attn_prefill(p["attn"], cfg, u, pos, valid, lora, f"{path}/attn",
                                                           lora_scale)
        else:
            mixer = lambda u, p=p, path=path: gdn_prefill(p["gdn"], cfg, u, lens, lora, f"{path}/gdn",
                                                          lora_scale, chunk)
        if cache_only and li == len(params["layers"]) - 1:
            with jax.named_scope("lm_attn" if "attn" in p else "lm_gdn"):
                u = _zrms(x, p["n1"], cfg)
                carried.append(_attn_project(p["attn"], cfg, u, pos, lora, f"{path}/attn", lora_scale,
                                             entry_only=True) if "attn" in p else mixer(u)[1])
            return None, carried, stats
        x, c, st = block(p, cfg, li, x, mixer, valid, lora, factors[li] if factors else None, lora_scale)
        carried.append(c)
        stats.append({"assign": st["assign"].reshape(S, T), "load": st["load"],
                      "topk": st["topk"].reshape(S, T, -1)})
    return x, carried, stats


def forward_logits(params: Params, cfg: HybridLMConfig, ids: jax.Array, lens: jax.Array,
                   lora: Optional[Params] = None, lora_scale: float = 1.0) -> jax.Array:
    """Teacher-forced logits ``[S, T, vocab_rows_held]`` (tests)."""
    return head(params, cfg, prefill(params, cfg, ids, lens, lora, lora_scale)[0])


def _nbytes(x: jax.Array) -> int:
    return math.prod(x.shape[1:]) * x.dtype.itemsize  # a sequence's


def prefill_state(params: Params, cfg: HybridLMConfig, ids: jax.Array, lens: jax.Array,
                  lora, lora_scale: float, factors):
    """:func:`models.lm.generate`'s first hook: the prompt into what the decode
    scan carries — a DeltaNet layer's (state, conv window) as the prefill left
    them, an attention layer's K and V in ``cache_len`` slots — the MoE stats,
    and the bytes a sequence carries by kind."""
    B, P = ids.shape
    _, carried, stats = prefill(params, cfg, ids, lens, lora, lora_scale, factors, cache_only=True)
    dt = cfg.compute_dtype
    state, nbytes = [], {"state": 0, "kv_cache": 0}
    for kind, c in zip(cfg.layer_types, carried):
        if kind == "full_attention":
            c = tuple(jnp.zeros((B, cfg.cache_len) + e.shape[2:], dt).at[:, :P].set(e.astype(dt)) for e in c)
            nbytes["kv_cache"] += sum(map(_nbytes, c))
        else:
            c = (c[0].astype(STATE_DTYPE), c[1].astype(dt))
            nbytes["state"] += sum(map(_nbytes, c))
        state.append(c)
    recurrent = [c[0] for kind, c in zip(cfg.layer_types, state) if kind != "full_attention"]
    if recurrent:
        # a traced run counts the compiled step's ops as large as a layer's state (obs/xla_cost.record_compile):
        # in the decode scan's body the update itself and nothing else, or a position pays for a copy
        note_program_geometry(recurrent_state_shape=(len(recurrent),) + recurrent[0].shape)
    return tuple(state), stats, nbytes


def decode_layers(params: Params, cfg: HybridLMConfig, x: jax.Array, state, i: jax.Array, prompt_len: jax.Array,
                  lora, lora_scale: float, factors):
    """:func:`models.lm.generate`'s second hook: sampled position ``i`` of
    every sequence, ``x [B, d]``, through the blocks over the carried state."""
    B = x.shape[0]
    slot, pos, valid = lm.decode_slot(cfg, i, prompt_len)
    new_state, stats = [], []
    for li, p in enumerate(params["layers"]):
        path = f"layers/{li}"
        if "attn" in p:
            mixer = lambda u, p=p, li=li, path=path: attn_decode(p["attn"], cfg, u, pos, state[li], slot, valid,
                                                                 lora, f"{path}/attn", lora_scale)
        else:
            mixer = lambda u, p=p, li=li, path=path: gdn_decode(p["gdn"], cfg, u, state[li], lora,
                                                                f"{path}/gdn", lora_scale)
        x, c, st = block(p, cfg, li, x, mixer, jnp.ones((B,), bool), lora, factors[li] if factors else None,
                         lora_scale)
        new_state.append(c)
        stats.append(st)
    return x, tuple(new_state), stats


# What the decode scan carries a recurrent layer's state in — a DeltaNet layer's here, a Mamba-2 layer's in
# ``models/lm_ssm.py``, which reads this one constant; the update itself is float32 either way. Both models state
# float32, and ``lm/state_bytes`` is counted from the carried arrays' own dtype, which is how the benchmark holds
# a step to it. Only a control sets another (tests, ``BENCH_BF16_STATE``).
STATE_DTYPE = jnp.float32
FAMILY = lm.Family(init=init, prefill_state=prefill_state, decode_layers=decode_layers, head=head)

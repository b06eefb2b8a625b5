"""A hybrid decoder of Mamba-2 (SSD) mixers beside grouped-query attention as
an autoregressive image-token generator (``model_type: granitemoehybrid`` with
``num_local_experts: 0``): a state-space layer's float32 state and an
attention layer's KV cache carried side by side through one ``generate``, a
dense SwiGLU in every layer, no position embedding, published multipliers on
the embedding, the residual branches and the logits, and a head tied to the
embedding.

It is the third family behind :func:`models.lm.generate` (``models/lm.py``
holds the prefill-then-scan, sampling, the image-id range and the VQ decode;
``models/lm_hybrid.py`` the carried-state plumbing this family shares: the one
:data:`models.lm_hybrid.STATE_DTYPE` and the short causal conv). Sizes come
from a ``config.json``-shaped file (:func:`models.lm.config_from_json` reads
``model_type``): the published keys of a ``granitemoehybrid`` ``config.json``
plus the system's use of the model (``image_tokens``, ``vocab_rows_held``).

The layer equations (the plain float32 form is
``reference/mamba2_gqa_reference.py``, written from the same description and
sharing no code with this file). ``N(x) = w x rsqrt(mean(x^2) + eps)`` in
float32 (weight not zero-centred); ``x_0 = embedding_multiplier · E[id]``;
block, pre-norm: ``h = x + r · Mixer(N1(x))``, ``y = h + r · FFN(N2(h))`` with
``r = residual_multiplier``; layer ``i``'s mixer is ``layer_types[i]``; final
``N``; logits ``N(x_L) Eᵀ / logits_scaling`` (tied: one matrix ``E``).

- Mamba-2 mixer (``mamba_n_heads`` H heads of ``mamba_d_head`` P, state
  ``mamba_d_state`` N, one group, conv ``mamba_d_conv`` K with bias, no
  projection bias): ``[z | xBC | dt] = u W_in`` (widths ``H P``, ``H P + 2N``,
  ``H``); ``xBC ← silu(causal depthwise conv(xBC) + b)``; ``[x | B | C] =
  xBC``; ``Δ = softplus(dt + dt_bias)``, ``A = −exp(A_log)``; per head ``S ←
  exp(Δ A) S + Δ x ⊗ B``, ``y = S C + D ⊙ x`` (``ops/ssd.py``; state ``[H, P,
  N]`` float32); ``y ← N(y ⊙ silu(z))`` over the ``H P`` values with its own
  weight; ``W_out``. The prefill takes the chunked form (``mamba_chunk_size``
  positions a chunk). Decode carries the state and the conv's last ``K − 1``
  inputs. Right-padding behind a prompt leaves both untouched: ``Δ = 0`` at
  padding, and a sequence's conv window is taken from its own last real
  positions;
- attention: ``q = u Wq`` (H heads of ``hidden / H``), ``k = u Wk``, ``v =
  u Wv`` over ``num_key_value_heads``, a KV head serving ``H / Hkv`` query
  heads; no bias, **no position embedding** (``position_embedding_type:
  nope``), causal softmax of ``q kᵀ · attention_multiplier`` (in place of
  ``dh^-1/2``); ``Wo``. The cache holds K and V ``[B, Tmax, Hkv, dh]`` each;
- FFN: ``SwiGLU(u) = (silu(u W_g) ⊙ u W_u) W_d`` of ``shared_intermediate_size``
  (the source's fused ``input_linear`` split into its two halves).

How it runs. ``layer_types`` repeats with a period (:attr:`SSMLMConfig.period`:
``[M × 5, A, M × 4]`` in the published file, 4 periods). Parameters and
carried state are stacked by position in the period — ``params["layers"][j]``
holds position ``j`` of every period, leaves ``[n_periods, ...]`` — and the
periods run under ``lax.scan`` in both the prefill and the decode step: one
period is traced, not every layer. A decode step reads a layer's state and
cache out of the stack the scan carries and writes them back into it in place
(``dynamic_update_slice`` at the period index).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..lora import lookup, slice_layer
from ..obs import note_program_geometry
from ..ops import ssd
from ..ops.quant import maybe_quantize_tree
from . import lm, lm_hybrid, nn

Params = Dict[str, Any]

MODEL_TYPE = "granitemoehybrid"
MAMBA, ATTENTION = "mamba", "attention"

# both Mamba-2 projections, the attention projections and the FFN's three matrices;
# the conv, A_log, D, dt_bias, norms and the (tied) embedding stay frozen
LORA_TARGETS: Tuple[str, ...] = (
    r"^layers/\d+/mamba/(in_proj|out_proj)$",
    r"^layers/\d+/attn/(wq|wk|wv|wo)$",
    r"^layers/\d+/ffn/(gate|up|down)$",
)

PUBLISHED_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "attention_multiplier", "embedding_multiplier",
    "residual_multiplier", "logits_scaling", "shared_intermediate_size", "layer_types", "mamba_chunk_size",
    "mamba_conv_bias", "mamba_d_conv", "mamba_d_head", "mamba_d_state", "mamba_expand", "mamba_n_groups",
    "mamba_n_heads", "mamba_proj_bias", "rms_norm_eps", "num_hidden_layers", "vocab_size",
    "position_embedding_type", "tie_word_embeddings", "attention_bias", "num_local_experts",
)


@dataclasses.dataclass(frozen=True)
class SSMLMConfig(lm.GeneratorUse):
    # --- the model's own config.json keys (granite-4.0-h-micro's as defaults)
    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    shared_intermediate_size: int = 8192
    layer_types: Tuple[str, ...] = ((MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4) * 4
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_d_conv: int = 4
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_n_heads: int = 64
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-5
    num_hidden_layers: int = 40
    vocab_size: int = 100352
    position_embedding_type: str = "nope"
    tie_word_embeddings: bool = True
    attention_bias: bool = False
    num_local_experts: int = 0
    # --- this chip's share: no experts; the whole vocabulary by default
    experts_held: int = 0
    vocab_rows_held: int = 100352

    model_type = MODEL_TYPE  # read by whoever has to know which family a parsed file gave
    n_moe_layers = 0         # no router: generate returns no routing rows
    num_experts_per_tok = 0

    def __post_init__(self) -> None:
        wrote = ("models/lm_ssm.py writes down the dense granitemoehybrid: Mamba-2 mixers of one group with a "
                 "biased conv and unbiased projections beside attention without bias or position embedding, a "
                 "head tied to the embedding; ")
        if self.num_local_experts:
            raise ValueError(f"num_local_experts {self.num_local_experts}: {wrote}the routed variant "
                             "(num_local_experts > 0) is not written down")
        stated = (self.position_embedding_type, self.tie_word_embeddings, self.attention_bias, self.mamba_proj_bias,
                  self.mamba_conv_bias, self.mamba_n_groups)
        if stated != ("nope", True, False, False, True, 1):
            raise ValueError(f"(position_embedding_type, tie_word_embeddings, attention_bias, mamba_proj_bias, "
                             f"mamba_conv_bias, mamba_n_groups) = {stated}: {wrote}not this")
        if set(self.layer_types) - {MAMBA, ATTENTION} or len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"layer_types must name {self.num_hidden_layers} layers, each mamba or attention")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_expand * self.hidden_size \
                or self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError("mamba_n_heads x mamba_d_head must be mamba_expand x hidden_size, the query heads a "
                             "multiple of the KV heads and the hidden size of the query heads")
        self.check_use(0)

    @classmethod
    def from_raw(cls, raw: Dict[str, Any]) -> "SSMLMConfig":
        kw = lm.published_from_raw(raw, PUBLISHED_KEYS, f"a {MODEL_TYPE}")
        kw["layer_types"] = tuple(kw["layer_types"])
        use = lm.use_from_raw(raw, 0, kw["vocab_size"])
        if use["vocab_rows_held"] != kw["vocab_size"]:
            raise ValueError("a tied head is the whole embedding: vocab_rows_held is vocab_size here")
        return cls(**kw, **use)

    @property
    def period(self) -> int:
        """The length of the shortest prefix of ``layer_types`` that repeats to all of it."""
        L = self.num_hidden_layers
        return next(p for p in range(1, L + 1) if L % p == 0 and self.layer_types == self.layer_types[:p] * (L // p))

    @property
    def n_periods(self) -> int:
        return self.num_hidden_layers // self.period

    @property
    def period_types(self) -> Tuple[str, ...]:
        return self.layer_types[: self.period]

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def ssm_width(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self) -> int:
        return self.ssm_width + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def lora_targets(self) -> Tuple[str, ...]:
        return LORA_TARGETS

    def family(self) -> "lm.Family":
        return FAMILY


# ---------------------------------------------------------------------------
# parameters: position j of the period holds that layer of every period
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: SSMLMConfig, kind: str) -> Params:
    d, dt, n = cfg.hidden_size, cfg.compute_dtype, cfg.n_periods
    ks = jax.random.split(key, 8)
    ones = lambda width: {"scale": jnp.ones((n, width), jnp.float32)}
    p: Params = {"n1": ones(d), "n2": ones(d),
                 "ffn": lm._swiglu_init(ks[0], d, cfg.shared_intermediate_size, dt, experts=n)}
    if kind == ATTENTION:
        H, Hkv, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        p["attn"] = {"wq": lm._kernel(ks[1], (n, d, H * dh), dt), "wk": lm._kernel(ks[2], (n, d, Hkv * dh), dt),
                     "wv": lm._kernel(ks[3], (n, d, Hkv * dh), dt), "wo": lm._kernel(ks[4], (n, H * dh, d), dt)}
        return p
    H, I, C, K = cfg.mamba_n_heads, cfg.ssm_width, cfg.conv_channels, cfg.mamba_d_conv
    kc, kb, ka, kt = jax.random.split(ks[5], 4)
    # as the source initialises them (assumed): A ~ U[1, 16], dt log-uniform in [0.001, 0.1] through
    # dt_bias = softplus^-1(dt), D = 1; the conv's taps and bias as a depthwise conv's default, fan-in K
    step = jnp.exp(jax.random.uniform(kt, (n, H), jnp.float32, math.log(1e-3), math.log(0.1)))
    p["mamba"] = {
        "in_proj": lm._kernel(ks[6], (n, d, 2 * I + 2 * cfg.mamba_d_state + H), dt),   # [z | xBC | dt]
        "conv": {"weight": jax.random.normal(kc, (n, K, C), jnp.float32) / math.sqrt(K),
                 "bias": jax.random.uniform(kb, (n, C), jnp.float32, -1 / math.sqrt(K), 1 / math.sqrt(K))},
        "a_log": jnp.log(jax.random.uniform(ka, (n, H), jnp.float32, 1.0, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "d": jnp.ones((n, H), jnp.float32),
        "norm": ones(I),
        "out_proj": lm._kernel(ks[7], (n, I, d), dt),
    }
    return p


def init(key: jax.Array, cfg: SSMLMConfig, base_quant: str = "off") -> Params:
    """Seeded parameters, each kernel quantized inside the same program when
    ``base_quant="int8"`` (``models/lm.init_lm`` says why). The embedding is
    the head as well and stays in the configuration's dtype."""
    ks = jax.random.split(key, cfg.period + 2)
    return {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_rows_held, cfg.hidden_size), jnp.float32) * 0.02
                  ).astype(cfg.compute_dtype),
        "layers": [maybe_quantize_tree(_layer_init(ks[1 + j], cfg, kind), base_quant)
                   for j, kind in enumerate(cfg.period_types)],
        "final_norm": {"scale": jnp.ones((cfg.hidden_size,), jnp.float32)},
        "vq": maybe_quantize_tree(lm.msvq.init_msvq(ks[-1], cfg.vq), base_quant),
    }


def _at(tree: Params, i) -> Params:
    """Period ``i`` of a position's stacked parameters (a traced index inside the scan)."""
    return jax.tree_util.tree_map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), tree)


def _lora_at(lora: Optional[Params], j: int, i) -> Optional[Params]:
    """Period ``i`` of position ``j``'s adapter leaves (raw or ``FactoredDelta``)."""
    if lora is None:
        return None
    return {k: slice_layer(v, i) for k, v in lora.items() if k.startswith(f"layers/{j}/")}


def _in_ssd(f, *args, **kw):
    """``f`` under the scopes of the SSM update (``lm_ssm/ssd``): a decode
    step's read of a layer's state out of its period stack and its write back
    are passes of the update over the state (under the member ``vmap`` the
    read is a gather that XLA materializes, the write a scatter that it fuses
    with the update's arithmetic), and ``ssd_update_roofline`` divides the
    update's floor by the seconds of that scope."""
    with jax.named_scope("lm_ssm"), jax.named_scope("ssd"):
        return f(*args, **kw)


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def _rms(x: jax.Array, p: Params, cfg: SSMLMConfig) -> jax.Array:
    return nn.rms_norm(x, p, eps=cfg.rms_norm_eps)


def _mamba_project(p: Params, cfg: SSMLMConfig, u: jax.Array, lora: Optional[Params], path: str, scale: float):
    """``u [..., d]`` → ``z [..., H P]``, the conv's input ``xBC [..., H P + 2N]``
    and ``dt`` before its bias ``[..., H]`` (float32)."""
    I, C = cfg.ssm_width, cfg.conv_channels
    zxbcdt = nn.dense(p["in_proj"], u, lookup(lora, f"{path}/in_proj"), scale)
    return zxbcdt[..., :I], zxbcdt[..., I: I + C], zxbcdt[..., I + C:].astype(jnp.float32)


def _ssd_inputs(p: Params, cfg: SSMLMConfig, xbc: jax.Array, dt: jax.Array):
    """``silu(conv)`` ``[..., H P + 2N]`` and ``dt`` → ``x [..., H, P]``, ``Δ
    [..., H]``, ``B``, ``C [..., N]`` (float32) and ``A [H]``."""
    I, N = cfg.ssm_width, cfg.mamba_d_state
    xbc = xbc.astype(jnp.float32)
    x = xbc[..., :I].reshape(*xbc.shape[:-1], cfg.mamba_n_heads, cfg.mamba_d_head)
    return x, jax.nn.softplus(dt + p["dt_bias"]), xbc[..., I: I + N], xbc[..., I + N:], -jnp.exp(p["a_log"])


def _mamba_out(p: Params, cfg: SSMLMConfig, y: jax.Array, z: jax.Array, dtype,
               lora: Optional[Params], path: str, scale: float) -> jax.Array:
    """Gated RMSNorm over the ``H P`` values (float32), then ``W_out``."""
    g = y.reshape(*y.shape[:-2], -1) * jax.nn.silu(z.astype(jnp.float32))
    return nn.dense(p["out_proj"], _rms(g, p["norm"], cfg).astype(dtype), lookup(lora, f"{path}/out_proj"), scale)


def mamba_prefill(p: Params, cfg: SSMLMConfig, u: jax.Array, lens: jax.Array,
                  lora: Optional[Params], path: str, scale: float, chunk: Optional[int] = None):
    """Whole (right-padded) sequences: ``u [S, T, d]``, ``lens [S]`` real
    positions → (out ``[S, T, d]``, (state ``[S, H, P, N]`` float32 after each
    sequence's last real position, conv window ``[S, K - 1, H P + 2N]``))."""
    S, T, _ = u.shape
    z, xbc, dt = _mamba_project(p, cfg, u, lora, path, scale)
    with jax.named_scope("conv"):
        xbc, window = lm_hybrid.causal_conv(xbc, p["conv"]["weight"], lens, p["conv"]["bias"])
    with jax.named_scope("ssd"):
        x, delta, B, C, A = _ssd_inputs(p, cfg, xbc, dt)
        real = (jnp.arange(T)[None, :] < lens[:, None])[..., None]      # padding: Δ = 0
        state0 = jnp.zeros((S, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state), jnp.float32)
        y, state = ssd.chunk_ssd(x, jnp.where(real, delta, 0.0), A, B, C, p["d"], state0,
                                 chunk or cfg.mamba_chunk_size)
    with jax.named_scope("ssm_out"):
        out = _mamba_out(p, cfg, y, z, u.dtype, lora, path, scale)
    return out, (state, window)


def mamba_decode(p: Params, cfg: SSMLMConfig, u: jax.Array, carried, lora: Optional[Params], path: str,
                 scale: float):
    """One position a sequence: ``u [S, d]``, ``carried = (state, window)`` →
    (out ``[S, d]``, the new pair). A state carried narrower than float32
    (``lm_hybrid.STATE_DTYPE``, a control) is widened before the step and
    narrowed after."""
    state, window = carried
    z, xbc, dt = _mamba_project(p, cfg, u, lora, path, scale)
    with jax.named_scope("conv"):
        xbc, full = lm_hybrid.causal_conv_step(window, xbc, p["conv"]["weight"], p["conv"]["bias"])
    with jax.named_scope("ssd"):
        x, delta, B, C, A = _ssd_inputs(p, cfg, xbc, dt)
        y, state = ssd.ssd_step(x, delta, A, B, C, p["d"], state.astype(jnp.float32))
    with jax.named_scope("ssm_out"):
        out = _mamba_out(p, cfg, y, z, u.dtype, lora, path, scale)
    return out, (state.astype(lm_hybrid.STATE_DTYPE), full[:, 1:])


def _attn_project(p: Params, cfg: SSMLMConfig, u: jax.Array, lora: Optional[Params], path: str, scale: float):
    """``u [..., d]`` → ``q [..., Hkv, H / Hkv, dh]`` and the cache entry ``(k,
    v)``, each ``[..., Hkv, dh]``. No position enters."""
    H, Hkv, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    lead = u.shape[:-1]
    q = nn.dense(p["wq"], u, lookup(lora, f"{path}/wq"), scale).reshape(*lead, Hkv, H // Hkv, dh)
    k = nn.dense(p["wk"], u, lookup(lora, f"{path}/wk"), scale).reshape(*lead, Hkv, dh)
    v = nn.dense(p["wv"], u, lookup(lora, f"{path}/wv"), scale).reshape(*lead, Hkv, dh)
    return q, (k, v)


def attn_prefill(p: Params, cfg: SSMLMConfig, u: jax.Array, valid: jax.Array,
                 lora: Optional[Params], path: str, scale: float):
    """Whole-sequence causal attention: ``u [S, T, d]``, key validity ``valid
    [S, T]`` → (out ``[S, T, d]``, cache entries ``(k, v)`` each ``[S, T, Hkv,
    dh]``)."""
    S, T, _ = u.shape
    q, (k, v) = _attn_project(p, cfg, u, lora, path, scale)
    with jax.named_scope("attend"):
        sc = jnp.einsum("sqgnd,skgd->sgnqk", q, k, preferred_element_type=jnp.float32) * cfg.attention_multiplier
        see = jnp.tril(jnp.ones((T, T), bool))[None, None, None] & valid[:, None, None, None, :]
        pr = jax.nn.softmax(jnp.where(see, sc, -1e30), axis=-1)
        o = jnp.einsum("sgnqk,skgd->sqgnd", pr.astype(u.dtype), v).reshape(S, T, -1)
    return nn.dense(p["wo"], o, lookup(lora, f"{path}/wo"), scale), (k, v)


def attn_decode(p: Params, cfg: SSMLMConfig, u: jax.Array, cache, i: jax.Array, slot: jax.Array, valid: jax.Array,
                lora: Optional[Params], path: str, scale: float):
    """One position a sequence over the KV cache of period ``i``: ``u [S, d]``;
    ``cache = (k, v)`` each ``[n_periods, S, Tmax, Hkv, dh]`` gets the new
    entry at ``(i, slot)`` in place; ``valid [S, Tmax]`` names the slots a
    query sees."""
    q, (k, v) = _attn_project(p, cfg, u, lora, path, scale)
    ck, cv = (jax.lax.dynamic_update_slice(c, e[None, :, None].astype(c.dtype), (i, 0, slot, 0, 0))
              for c, e in zip(cache, (k, v)))
    with jax.named_scope("attend"):
        keys, values = (jax.lax.dynamic_index_in_dim(c, i, keepdims=False) for c in (ck, cv))
        sc = jnp.einsum("sgnd,stgd->sgnt", q, keys, preferred_element_type=jnp.float32) * cfg.attention_multiplier
        pr = jax.nn.softmax(jnp.where(valid[:, None, None, :], sc, -1e30), axis=-1)
        o = jnp.einsum("sgnt,stgd->sgnd", pr.astype(u.dtype), values).reshape(u.shape[0], -1)
    return nn.dense(p["wo"], o, lookup(lora, f"{path}/wo"), scale), (ck, cv)


def block(p: Params, cfg: SSMLMConfig, x: jax.Array, mixer, lora: Optional[Params], path: str, scale: float):
    """Pre-norm block on ``x [..., d]``, each branch scaled by
    ``residual_multiplier``; ``mixer(u) -> (out, carried)`` is the layer's own
    mixer in the form the caller is in (prefill or decode)."""
    r = jnp.asarray(cfg.residual_multiplier, x.dtype)
    with jax.named_scope("lm_attn" if "attn" in p else "lm_ssm"):
        a, carried = mixer(_rms(x, p["n1"], cfg))
        h = x + r * a
    with jax.named_scope("lm_dense_ffn"):
        y = h + r * lm._swiglu(p["ffn"], _rms(h, p["n2"], cfg), lora, f"{path}/ffn", scale)
    return y, carried


def logits(rows: jax.Array, cfg: SSMLMConfig, h: jax.Array) -> jax.Array:
    """Float32 logits of the final hidden state ``h`` against embedding rows
    ``rows [V', d]`` (the tied head), ÷ ``logits_scaling``. The dot asks for
    its float32 result, so every reader sees the same numbers."""
    return jnp.einsum("...d,vd->...v", h, rows, preferred_element_type=jnp.float32) / cfg.logits_scaling


def prefill(params: Params, cfg: SSMLMConfig, ids: jax.Array, lens: jax.Array,
            lora: Optional[Params] = None, lora_scale: float = 1.0, chunk: Optional[int] = None):
    """``ids [S, T]`` (right-padded, ``lens [S]`` real) through every block, a
    ``lax.scan`` over the periods. Returns (hidden ``[S, T, d]`` before the
    final norm, what each position of the period carries on, stacked over the
    periods — a Mamba-2 layer its (state ``[n, S, H, P, N]`` in
    ``lm_hybrid.STATE_DTYPE``, conv window ``[n, S, K - 1, C]``), an attention
    layer its ``(k, v)`` entries ``[n, S, T, Hkv, dh]``). The Mamba-2 stacks
    ride in the scan's carry and each period writes its layer in place, as
    the decode step does: under a member ``vmap`` a carry keeps the member
    axis in front, where stacked scan outputs would put it second and the
    decode scan would take a transposed copy of every state."""
    S, T = ids.shape
    n, H, P, N = cfg.n_periods, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    valid = jnp.arange(T)[None, :] < lens[:, None]
    x = lm._embed(params, cfg, ids) * jnp.asarray(cfg.embedding_multiplier, cfg.compute_dtype)
    stacks = tuple((jnp.zeros((n, S, H, P, N), lm_hybrid.STATE_DTYPE),
                    jnp.zeros((n, S, cfg.mamba_d_conv - 1, cfg.conv_channels), cfg.compute_dtype))
                   for kind in cfg.period_types if kind == MAMBA)

    def period(carry, i):
        x, stacks = carry
        written, entries = [], []
        for j, kind in enumerate(cfg.period_types):
            p, lj, path = _at(params["layers"][j], i), _lora_at(lora, j, i), f"layers/{j}"
            if kind == ATTENTION:
                mixer = lambda u, p=p, lj=lj, path=path: attn_prefill(p["attn"], cfg, u, valid, lj, f"{path}/attn",
                                                                      lora_scale)
            else:
                mixer = lambda u, p=p, lj=lj, path=path: mamba_prefill(p["mamba"], cfg, u, lens, lj,
                                                                       f"{path}/mamba", lora_scale, chunk)
            x, c = block(p, cfg, x, mixer, lj, path, lora_scale)
            if kind == ATTENTION:
                entries.append(c)
            else:
                written.append(tuple(jax.lax.dynamic_update_index_in_dim(s, v.astype(s.dtype), i, axis=0)
                                     for s, v in zip(stacks[len(written)], c)))
        return (x, tuple(written)), tuple(entries)

    (x, stacks), entries = jax.lax.scan(period, (x, stacks), jnp.arange(n))
    stacks, entries = iter(stacks), iter(entries)
    return x, tuple(next(entries) if kind == ATTENTION else next(stacks) for kind in cfg.period_types)


def forward_logits(params: Params, cfg: SSMLMConfig, ids: jax.Array, lens: jax.Array,
                   lora: Optional[Params] = None, lora_scale: float = 1.0) -> jax.Array:
    """Teacher-forced logits ``[S, T, vocab_rows_held]`` (tests)."""
    h = prefill(params, cfg, ids, lens, lora, lora_scale)[0]
    return logits(params["embed"], cfg, _rms(h, params["final_norm"], cfg))


def _per_sequence(x: jax.Array) -> int:
    return x.shape[0] * math.prod(x.shape[2:]) * x.dtype.itemsize  # [n_periods, S, ...]: a sequence's, all periods


def prefill_state(params: Params, cfg: SSMLMConfig, ids: jax.Array, lens: jax.Array,
                  lora, lora_scale: float, factors):
    """:func:`models.lm.generate`'s first hook: the prompt into what the decode
    scan carries — a Mamba-2 position's (state, conv window) stacks as the
    prefill left them, an attention position's K and V in ``cache_len`` slots
    — no routing stats, and the bytes a sequence carries by kind."""
    B, P = ids.shape
    _, carried = prefill(params, cfg, ids, lens, lora, lora_scale)
    dt = cfg.compute_dtype
    state, nbytes = [], {"state": 0, "kv_cache": 0}
    for kind, c in zip(cfg.period_types, carried):
        if kind == ATTENTION:
            c = tuple(jnp.zeros(e.shape[:2] + (cfg.cache_len,) + e.shape[3:], dt).at[:, :, :P].set(e.astype(dt))
                      for e in c)
            nbytes["kv_cache"] += sum(map(_per_sequence, c))
        else:
            nbytes["state"] += sum(map(_per_sequence, c))
        state.append(c)
    # a traced run counts the compiled step's ops as large as a position's state stack or one layer's state
    # (obs/xla_cost.record_compile): the decode scan's in-place update and nothing that copies it whole
    note_program_geometry(recurrent_state_shape=next(c[0].shape for kind, c in zip(cfg.period_types, state)
                                                      if kind == MAMBA))
    return tuple(state), [], nbytes


def decode_layers(params: Params, cfg: SSMLMConfig, x: jax.Array, state, i: jax.Array, prompt_len: jax.Array,
                  lora, lora_scale: float, factors):
    """:func:`models.lm.generate`'s second hook: sampled position ``i`` of
    every sequence, ``x [B, d]`` (the embedding row, scaled here), through the
    blocks — a ``lax.scan`` over the periods whose carry holds the stacked
    states and caches, each layer's read at its period index and written back
    in place."""
    slot, _, valid = lm.decode_slot(cfg, i, prompt_len)
    at = lambda a, k: _in_ssd(jax.lax.dynamic_index_in_dim, a, k, keepdims=False)

    def period(carry, k):
        x, state = carry
        new = []
        for j, kind in enumerate(cfg.period_types):
            p, lj, path = _at(params["layers"][j], k), _lora_at(lora, j, k), f"layers/{j}"
            if kind == ATTENTION:
                mixer = lambda u, p=p, lj=lj, path=path: attn_decode(p["attn"], cfg, u, state[j], k, slot, valid, lj,
                                                                     f"{path}/attn", lora_scale)
            else:
                mixer = lambda u, p=p, lj=lj, path=path: mamba_decode(p["mamba"], cfg, u, tuple(at(s, k) for s in state[j]),
                                                                      lj, f"{path}/mamba", lora_scale)
            x, c = block(p, cfg, x, mixer, lj, path, lora_scale)
            if kind == MAMBA:
                c = tuple(_in_ssd(jax.lax.dynamic_update_index_in_dim, s, v, k, axis=0) for s, v in zip(state[j], c))
            new.append(c)
        return (x, tuple(new)), None

    x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    (x, state), _ = jax.lax.scan(period, (x, state), jnp.arange(cfg.n_periods))
    return x, state, []


def cut_head(params: Params, lo: int, hi: int):
    """The tied head's cut to the ids ``[lo, hi)``: the embedding's rows of
    that range (the one matrix; nothing of it is copied whole). Returns (the
    params :func:`models.lm.generate`'s scan is handed, the whole head's
    shape ``[vocab_rows_held, d]``)."""
    return {**params, "head_rows": params["embed"][lo:hi]}, tuple(params["embed"].shape)


def head(params: Params, cfg: SSMLMConfig, h: jax.Array) -> jax.Array:
    """:func:`models.lm.generate`'s head over the rows :func:`cut_head` handed it."""
    return logits(params["head_rows"], cfg, _rms(h, params["final_norm"], cfg))


FAMILY = lm.Family(init=init, prefill_state=prefill_state, decode_layers=decode_layers, head=head,
                   cut_head=cut_head)


"""Plain float32 reference of the sparse-expert decoder that
``models/lm_swa.py`` runs (MiMo-V2-Flash family: sliding-window attention with
a learned sink beside full grouped-query attention, a sigmoid top-k router over
routed experts with no shared expert, a dense first layer), written from the
published equations and sharing no code with the program.

One sequence at a time, the whole sequence at once: no cache of either kind
(every layer sees the whole causal sequence every call, a window layer
through an explicit band mask), no kernels, no grouped products (a loop over
the experts it is told it holds), float32 under
``jax.default_matmul_precision("highest")``. Imports nothing of this
repository, so ``benchmarks/reference/gqa_swa_moe_reference.py`` is a verbatim
copy.

``cfg`` is the model's ``config.json`` as a dict (its scalar keys) plus the
share: ``experts_held`` / ``expert_offset`` (the routed experts this chip
holds; the router still scores all ``n_routed_experts`` and normalizes over
all ``num_experts_per_tok`` chosen). A layer's kind is read from its weights:
a sink means a window layer (else full), a router means routed experts (else
the dense SwiGLU).

The equations. ``N(x) = x rsqrt(mean(x^2) + eps) w`` (``layernorm_epsilon``).
Block, pre-norm: ``h = x + Attn(N1(x))``, ``y = h + F(N2(h))``. See
:func:`attention` and :func:`moe`.

Weights of a block are a dict of float32 arrays, ``[din, dout]`` (routed
experts ``[E, din, dout]``): ``n1``, ``n2``, ``wq``, ``wk``, ``wv``, ``wo``,
a window layer's ``sink`` ``[H]``; then ``gate/up/down`` (dense) or
``router`` ``[n_routed, d]``, ``bias`` ``[n_routed]`` and
``e_gate/e_up/e_down``. :func:`block_weights` reads them from the program's
parameter tree (float or int8 nodes) and adds a member's LoRA delta
``(alpha/r) a @ b`` to each adapted matrix — a materialized ``W + dW``, which
the program never builds.

Departures from the published code, each at its line: RoPE in the rotate-half
convention on the first ``int(partial_rotary_factor · head_dim)`` numbers of a
head; ``attention_value_scale`` applied to V; no multi-token-prediction layers
(the ``config.json`` has no keys for them); and three hooks that exist only
for the comparison on the chip and are the identity when None:
``forced_topk`` (the router's choice taken from outside), ``act`` (rounds each
sub-layer's input) and ``kv_round`` (rounds the K and V a decode would carry
from position to position).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta, rot):
    """Rotary on the first ``rot`` numbers of each head at positions 0..T-1,
    rotate-half, the rest passes through: x [T, heads, dh]."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(w: Dict[str, Any], cfg: Dict[str, Any], u, kv_round=None):
    """Causal grouped-query attention over the whole sequence. A window layer
    (its weights hold ``sink``) sees the last ``sliding_window`` positions,
    itself included, and its per-head sink logit is one more column of the
    softmax, with no value behind it."""
    T = u.shape[0]
    window = "sink" in w
    pre = "swa_" if window else ""
    H, Hkv = cfg[f"{pre}num_attention_heads"], cfg[f"{pre}num_key_value_heads"]
    dqk, dv = cfg[f"{pre}head_dim"], cfg[f"{pre}v_head_dim"]
    theta = cfg["swa_rope_theta"] if window else cfg["rope_theta"]
    rot = int(cfg["partial_rotary_factor"] * dqk)
    q = rope((u @ w["wq"]).reshape(T, H, dqk), theta, rot)
    k = rope((u @ w["wk"]).reshape(T, Hkv, dqk), theta, rot)
    v = (u @ w["wv"]).reshape(T, Hkv, dv) * cfg["attention_value_scale"]   # departure: the scale on V
    if kv_round is not None:
        k, v = kv_round(k), kv_round(v)
    k, v = jnp.repeat(k, H // Hkv, axis=1), jnp.repeat(v, H // Hkv, axis=1)  # a KV head serves H / Hkv query heads
    score = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dqk)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    sees = j <= i
    if window:
        sees = sees & (j > i - cfg["sliding_window"])
    score = jnp.where(sees[None], score, -jnp.inf)
    if window:
        score = jnp.concatenate([score, jnp.broadcast_to(w["sink"][:, None, None], (H, T, 1))], axis=-1)
    p = jax.nn.softmax(score, axis=-1)[..., :T]                      # the sink's column carries no value
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(T, H * dv) @ w["wo"]


def swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def moe(w: Dict[str, Any], cfg: Dict[str, Any], u, forced_topk=None):
    """The sum over the chosen experts *held here* of ``w_e E_e(u)``: sigmoid
    scores over all experts, top-k by score + ``bias``, the weights from the
    scores normalized over the k chosen, × ``routed_scaling_factor``; every
    held expert over every token, times the token's weight for it (0 where the
    router did not choose it). No shared expert. Returns the output and the
    reference's own choice ``[T, k]`` (also when another is forced)."""
    s = jax.nn.sigmoid(u @ w["router"].T)
    own = jnp.argsort(-(s + w["bias"]), axis=-1, stable=True)[:, : cfg["num_experts_per_tok"]]
    ids = own if forced_topk is None else forced_topk  # departure: the choice is given (the chip comparison)
    wts = jnp.take_along_axis(s, ids, axis=-1)
    if cfg.get("norm_topk_prob", True):
        wts = wts / (wts.sum(-1, keepdims=True) + 1e-20)
    wts = wts * cfg.get("routed_scaling_factor", 1.0)
    out = jnp.zeros_like(u)
    lo = cfg.get("expert_offset", 0)
    for j in range(w["e_gate"].shape[0]):
        mine = ((ids == lo + j) * wts).sum(-1, keepdims=True)           # [T, 1]
        out = out + mine * swiglu(u, w["e_gate"][j], w["e_up"][j], w["e_down"][j])
    return out, own


def block(w: Dict[str, Any], cfg: Dict[str, Any], x, forced_topk=None, act=None, kv_round=None):
    """Pre-norm block of the kinds ``w`` holds. ``act`` and ``kv_round`` are
    not part of the reference proper (see the header)."""
    eps = cfg["layernorm_epsilon"]
    act = act or (lambda t: t)
    h = x + attention(w, cfg, act(rms_norm(x, w["n1"], eps)), kv_round)
    u = act(rms_norm(h, w["n2"], eps))
    if "router" in w:
        f, ids = moe(w, cfg, u, forced_topk)
    else:
        f, ids = swiglu(u, w["gate"], w["up"], w["down"]), None
    return h + f, ids


def forward(layer_weights: Callable[[int], Dict[str, Any]], n_layers: int, top: Dict[str, Any],
            cfg: Dict[str, Any], ids, forced_topk: Optional[Sequence[Any]] = None):
    """ids [T] → {"hidden" [T, d] before the final norm, "logits" [T, rows
    held], "topk": per MoE layer [T, k]}. ``layer_weights(i)`` gives block
    ``i``'s weights when it is needed; ``top``: ``embed``, ``final_norm``,
    ``head``. ``forced_topk``: per MoE layer, the routing to use instead of
    the reference's own."""
    with jax.default_matmul_precision("highest"):
        x = top["embed"][ids].astype(F32)
        chosen: List[Any] = []
        for i in range(n_layers):
            w = layer_weights(i)
            force = forced_topk[len(chosen)] if (forced_topk is not None and "router" in w) else None
            x, picked = block(w, cfg, x, force)
            if picked is not None:
                chosen.append(picked)
        logits = rms_norm(x, top["final_norm"], cfg["layernorm_epsilon"]) @ top["head"]
    return {"hidden": x, "logits": logits, "topk": chosen}


def forward_batch(layer_weights: Callable[[int], Dict[str, Any]], n_layers: int, top: Dict[str, Any],
                  cfg: Dict[str, Any], ids, forced_topk=None, act=None, kv_round=None):
    """:func:`forward` for ``ids [S, T]`` of one length (right-padding a causal
    model changes nothing before the padding), the layers outermost so that
    one layer's float32 weights are resident at a time at any ``S``.
    ``forced_topk [S, T, moe layers, k]``; ``act``, ``kv_round``: see
    :func:`block`. Same returns with ``S`` in front (``topk [S, T, moe
    layers, k]``, the reference's own choices)."""
    compiled: Dict[Any, Any] = {}  # one compiled block a kind of layer: the weights are arguments, not constants

    def run(w, x, force):
        kind = ("sink" in w, "router" in w, force is not None)
        if kind not in compiled:
            if force is None:
                compiled[kind] = jax.jit(jax.vmap(lambda w, xs: block(w, cfg, xs, None, act, kv_round),
                                                  in_axes=(None, 0)))
            else:
                compiled[kind] = jax.jit(jax.vmap(lambda w, xs, f: block(w, cfg, xs, f, act, kv_round),
                                                  in_axes=(None, 0, 0)))
        return compiled[kind](w, x) if force is None else compiled[kind](w, x, force)

    with jax.default_matmul_precision("highest"):
        x = top["embed"][ids].astype(F32)
        chosen: List[Any] = []
        for i in range(n_layers):
            w = layer_weights(i)
            force = forced_topk[:, :, len(chosen)] if (forced_topk is not None and "router" in w) else None
            x, picked = run(w, x, force)
            if picked is not None:
                chosen.append(picked)
        logits = rms_norm(x, top["final_norm"], cfg["layernorm_epsilon"]) @ top["head"]
    return {"hidden": x, "logits": logits, "topk": jnp.stack(chosen, axis=2) if chosen else None}


# ---------------------------------------------------------------------------
# reading the program's parameter tree (a checkpoint layout, no program code)
# ---------------------------------------------------------------------------

def kernel_f32(node: Dict[str, Any]):
    """A kernel node as float32: ``{"kernel": w}`` or the int8 form
    ``{"kernel_q8": {"q8", "scale"}}`` (``q8 * scale``, scale per expert and
    output channel)."""
    if "kernel" in node:
        return jnp.asarray(node["kernel"], F32)
    return node["kernel_q8"]["q8"].astype(F32) * node["kernel_q8"]["scale"].astype(F32)


def _adapted(node, lora, path, scale):
    w = kernel_f32(node)
    leaf = None if lora is None else lora.get(path)
    if leaf is None:
        return w
    with jax.default_matmul_precision("highest"):
        return w + scale * jnp.asarray(leaf["a"], F32) @ jnp.asarray(leaf["b"], F32)


def block_weights(p: Dict[str, Any], path: str, lora: Optional[Dict[str, Any]] = None, lora_scale: float = 1.0):
    """Block ``p`` of the program's tree → the reference's flat dict, with the
    adapter ``lora`` (``{site path: {"a", "b"}}``, materialized arrays)
    added into each adapted matrix."""
    a = p["attn"]
    w = {k: jnp.asarray(p[k]["scale"], F32) for k in ("n1", "n2")}
    for k in ("wq", "wk", "wv", "wo"):
        w[k] = _adapted(a[k], lora, f"{path}/attn/{k}", lora_scale)
    if "sink" in a:
        w["sink"] = jnp.asarray(a["sink"], F32)
    if "moe" in p:
        w["router"] = jnp.asarray(p["moe"]["router"]["weight"], F32)
        w["bias"] = jnp.asarray(p["moe"]["router"]["e_score_correction_bias"], F32)
        for k in ("gate", "up", "down"):
            w[f"e_{k}"] = _adapted(p["moe"]["experts"][k], lora, f"{path}/moe/experts/{k}", lora_scale)
    else:
        for k in ("gate", "up", "down"):
            w[k] = _adapted(p["ffn"][k], lora, f"{path}/ffn/{k}", lora_scale)
    return w


def top_weights(params: Dict[str, Any]):
    return {"embed": jnp.asarray(params["embed"], F32),
            "final_norm": jnp.asarray(params["final_norm"]["scale"], F32),
            "head": kernel_f32(params["head"])}

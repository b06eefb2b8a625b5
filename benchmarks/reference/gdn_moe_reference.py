"""Plain float32 reference of the hybrid decoder that ``models/lm_hybrid.py``
runs (Qwen3-Next family: Gated DeltaNet layers beside gated softmax attention,
a softmax top-k router over many small experts and a sigmoid-gated shared
expert), written from the published equations and sharing no code with the
program.

One sequence at a time, the whole sequence at once: no KV cache (attention
sees the whole causal sequence every call), no chunking (the gated delta rule
is the recurrence, position by position), no kernels, no grouped products (a
loop over the experts it is told it holds), float32 under
``jax.default_matmul_precision("highest")``. Imports nothing of this
repository, so ``benchmarks/reference/gdn_moe_reference.py`` is a verbatim
copy.

``cfg`` is the model's ``config.json`` as a dict (published keys) plus the
share: ``experts_held`` / ``expert_offset`` (the routed experts this chip
holds; the router still scores all ``num_experts`` and normalizes over all
``num_experts_per_tok`` chosen) and ``vocab_rows_held``.

The equations. ``N(x) = x rsqrt(mean(x^2) + eps) (1 + w)`` (zero-centred
weight). Block, pre-norm: ``h = x + Mixer(N1(x))``, ``y = h + MoE(N2(h))``;
layer ``i`` is gated attention iff ``(i + 1) % full_attention_interval == 0``,
else Gated DeltaNet. See :func:`gated_attention`, :func:`gated_deltanet`,
:func:`moe`.

Weights of a block are a dict of float32 arrays, ``[din, dout]`` (routed
experts ``[E, din, dout]``): ``n1``, ``n2``; a DeltaNet mixer's ``wqkvz``,
``wba``, ``conv`` ``[kernel, channels]``, ``a_log``, ``dt_bias``, ``g_norm``,
``wout``; an attention mixer's ``wq``, ``wk``, ``wv``, ``q_norm``, ``k_norm``,
``wo``; ``router`` ``[num_experts, d]``, ``e_gate/e_up/e_down``,
``s_gate/s_up/s_down`` and ``s_gatew`` ``[d, 1]``. :func:`block_weights` reads
them from the program's parameter tree (float or int8 nodes) and adds a
member's LoRA delta ``(alpha/r) a @ b`` to each adapted matrix — a
materialized ``W + dW``, which the program never builds.

Departures from the published code, each at its line: the order of
``[q | k | v | z]`` inside ``wqkvz`` and of ``[b | a]`` inside ``wba`` is
contiguous (the source interleaves by key-head group; with seeded weights any
fixed order is the same model); no multi-token-prediction module (the
``config.json`` has no key for one); and three hooks that exist only for the
comparison on the chip and are the identity when None: ``forced_topk`` (the
router's choice taken from outside), ``act`` (rounds each sub-layer's input)
and ``state_round`` (rounds the recurrent state after every update).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


def is_attention(cfg: Dict[str, Any], layer: int) -> bool:
    return (layer + 1) % cfg["full_attention_interval"] == 0


def rms_norm(x, w, eps):
    """Zero-centred weight: the scale is ``1 + w``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def rope_partial(x, pos, theta, rot):
    """Rotary on the first ``rot`` numbers of each head, rotate-half, the rest
    passes through: x [T, H, dh], pos [T]."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def gated_attention(w: Dict[str, Any], cfg: Dict[str, Any], u):
    """Causal softmax attention with per-head q/k norm, partial rotary, grouped
    KV heads and a sigmoid output gate taken from the query projection."""
    T = u.shape[0]
    H, Hkv, dh, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    qg = (u @ w["wq"]).reshape(T, H, 2 * dh)                     # per head: q, then gate
    q, gate = qg[..., :dh], qg[..., dh:]
    k = (u @ w["wk"]).reshape(T, Hkv, dh)
    v = (u @ w["wv"]).reshape(T, Hkv, dh)
    q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    rot = int(dh * cfg["partial_rotary_factor"])
    pos = jnp.arange(T)
    q, k = rope_partial(q, pos, cfg["rope_theta"], rot), rope_partial(k, pos, cfg["rope_theta"], rot)
    k, v = jnp.repeat(k, H // Hkv, axis=1), jnp.repeat(v, H // Hkv, axis=1)   # each KV head serves H / Hkv query heads
    score = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    score = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], score, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(score, axis=-1), v)
    return (o * jax.nn.sigmoid(gate)).reshape(T, H * dh) @ w["wo"]


def gated_deltanet(w: Dict[str, Any], cfg: Dict[str, Any], u, state_round=None):
    """Short causal depthwise conv over ``[q | k | v]``, the gated delta rule
    over a per-value-head ``[dk, dv]`` float32 state position by position, a
    gated RMSNorm (weight *not* zero-centred) and the output projection."""
    T = u.shape[0]
    Hk, Hv, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
                      cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])
    K, eps = cfg["linear_conv_kernel_dim"], cfg["rms_norm_eps"]
    nq, nv = Hk * dk, Hv * dv
    qkvz = u @ w["wqkvz"]                                        # departure: [q | k | v | z], contiguous
    qkv, z = qkvz[:, : 2 * nq + nv], qkvz[:, 2 * nq + nv:].reshape(T, Hv, dv)
    ba = u @ w["wba"]                                            # departure: [b | a], contiguous
    b, a = ba[:, :Hv], ba[:, Hv:]
    padded = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1]), F32), qkv], axis=0)
    conv = sum(padded[j: j + T] * w["conv"][j] for j in range(K))  # causal: y_t = sum_j w_j x_{t - (K-1) + j}
    qkv = jax.nn.silu(conv)
    q, k, v = (qkv[:, :nq].reshape(T, Hk, dk), qkv[:, nq: 2 * nq].reshape(T, Hk, dk),
               qkv[:, 2 * nq:].reshape(T, Hv, dv))
    beta = jax.nn.sigmoid(b)                                     # [T, Hv]
    alpha = jnp.exp(-jnp.exp(w["a_log"]) * jax.nn.softplus(a + w["dt_bias"]))
    l2 = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
    q, k = l2(q) / math.sqrt(dk), l2(k)
    q, k = jnp.repeat(q, Hv // Hk, axis=1), jnp.repeat(k, Hv // Hk, axis=1)  # a key head to its Hv / Hk value heads
    rnd = state_round or (lambda s: s)

    def position(S, x):                                          # S [Hv, dk, dv]
        q_t, k_t, v_t, a_t, b_t = x
        S = a_t[:, None, None] * S
        delta = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, S))
        S = rnd(S + k_t[:, :, None] * delta[:, None, :])
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    _, o = jax.lax.scan(position, jnp.zeros((Hv, dk, dv), F32), (q, k, v, alpha, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * w["g_norm"] * jax.nn.silu(z)
    return o.reshape(T, nv) @ w["wout"]


def swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def moe(w: Dict[str, Any], cfg: Dict[str, Any], u, forced_topk=None):
    """``sigmoid(u w_s) Shared(u)`` + the sum over the chosen experts *held
    here* of ``w_e E_e(u)``: softmax over all experts, top-k, the weights
    normalized over the k chosen; every held expert over every token, times the
    token's weight for it (0 where the router did not choose it). Returns the
    output and the reference's own choice ``[T, k]`` (also when another is
    forced)."""
    p = jax.nn.softmax(u @ w["router"].T, axis=-1)
    own = jnp.argsort(-p, axis=-1, stable=True)[:, : cfg["num_experts_per_tok"]]
    ids = own if forced_topk is None else forced_topk  # departure: the choice is given (the chip comparison)
    wts = jnp.take_along_axis(p, ids, axis=-1)
    if cfg.get("norm_topk_prob", True):
        wts = wts / wts.sum(-1, keepdims=True)
    out = jax.nn.sigmoid(u @ w["s_gatew"]) * swiglu(u, w["s_gate"], w["s_up"], w["s_down"])
    lo = cfg.get("expert_offset", 0)

    def expert(acc, x):
        j, gate, up, down = x
        mine = ((ids == lo + j) * wts).sum(-1, keepdims=True)           # [T, 1]
        return acc + mine * swiglu(u, gate, up, down), None

    E = w["e_gate"].shape[0]
    out, _ = jax.lax.scan(expert, out, (jnp.arange(E), w["e_gate"], w["e_up"], w["e_down"]))
    return out, own


def block(w: Dict[str, Any], cfg: Dict[str, Any], x, forced_topk=None, act=None, state_round=None):
    """Pre-norm block; the mixer is the one whose weights ``w`` holds. ``act``
    and ``state_round`` are not part of the reference proper (see the header)."""
    eps = cfg["rms_norm_eps"]
    act = act or (lambda t: t)
    u = act(rms_norm(x, w["n1"], eps))
    h = x + (gated_attention(w, cfg, u) if "wq" in w else gated_deltanet(w, cfg, u, state_round))
    f, ids = moe(w, cfg, act(rms_norm(h, w["n2"], eps)), forced_topk)
    return h + f, ids


def forward(layer_weights: Callable[[int], Dict[str, Any]], n_layers: int, top: Dict[str, Any],
            cfg: Dict[str, Any], ids, forced_topk=None):
    """ids [T] → {"hidden" [T, d] before the final norm, "logits" [T, rows
    held], "topk": per layer [T, k]}. ``layer_weights(i)`` gives block ``i``'s
    weights when it is needed; ``top``: ``embed``, ``final_norm``, ``head``."""
    with jax.default_matmul_precision("highest"):
        x = top["embed"][ids].astype(F32)
        chosen: List[Any] = []
        for i in range(n_layers):
            x, picked = block(layer_weights(i), cfg, x, None if forced_topk is None else forced_topk[i])
            chosen.append(picked)
        logits = rms_norm(x, top["final_norm"], cfg["rms_norm_eps"]) @ top["head"]
    return {"hidden": x, "logits": logits, "topk": chosen}


def forward_batch(layer_weights: Callable[[int], Dict[str, Any]], n_layers: int, top: Dict[str, Any],
                  cfg: Dict[str, Any], ids, forced_topk=None, act=None, state_round=None):
    """:func:`forward` for ``ids [S, T]`` of one length (right-padding a causal
    model changes nothing before the padding), the layers outermost so that
    one layer's float32 weights are resident at a time at any ``S``.
    ``forced_topk [S, T, layers, k]``; ``act``, ``state_round``: see
    :func:`block`. Same returns with ``S`` in front (``topk [S, T, layers,
    k]``, the reference's own choices)."""
    compiled: Dict[bool, Any] = {}  # one compiled block a mixer kind: the weights are arguments, not constants

    def run(w, x, force):
        kind = "wq" in w
        if kind not in compiled:
            if force is None:
                compiled[kind] = jax.jit(jax.vmap(lambda w, xs: block(w, cfg, xs, None, act, state_round),
                                                  in_axes=(None, 0)))
            else:
                compiled[kind] = jax.jit(jax.vmap(lambda w, xs, f: block(w, cfg, xs, f, act, state_round),
                                                  in_axes=(None, 0, 0)))
        return compiled[kind](w, x) if force is None else compiled[kind](w, x, force)

    with jax.default_matmul_precision("highest"):
        x = top["embed"][ids].astype(F32)
        chosen: List[Any] = []
        for i in range(n_layers):
            x, picked = run(layer_weights(i), x, None if forced_topk is None else forced_topk[:, :, i])
            chosen.append(picked)
        logits = rms_norm(x, top["final_norm"], cfg["rms_norm_eps"]) @ top["head"]
    return {"hidden": x, "logits": logits, "topk": jnp.stack(chosen, axis=2)}


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
    adapter ``lora`` (``{site path: {"a", "b"}}``, materialized arrays) added
    into each adapted matrix."""
    w = {k: jnp.asarray(p[k]["scale"], F32) for k in ("n1", "n2")}
    if "gdn" in p:
        m = p["gdn"]
        for k in ("wqkvz", "wout"):
            w[k] = _adapted(m[k], lora, f"{path}/gdn/{k}", lora_scale)
        w["wba"] = kernel_f32(m["wba"])
        w["conv"] = jnp.asarray(m["conv"]["weight"], F32)
        w["a_log"], w["dt_bias"] = jnp.asarray(m["a_log"], F32), jnp.asarray(m["dt_bias"], F32)
        w["g_norm"] = jnp.asarray(m["g_norm"]["scale"], F32)
    else:
        m = p["attn"]
        for k in ("wq", "wk", "wv", "wo"):
            w[k] = _adapted(m[k], lora, f"{path}/attn/{k}", lora_scale)
        w["q_norm"], w["k_norm"] = jnp.asarray(m["q_norm"]["scale"], F32), jnp.asarray(m["k_norm"]["scale"], F32)
    w["router"] = jnp.asarray(p["moe"]["router"]["weight"], F32)
    w["s_gatew"] = jnp.asarray(p["moe"]["shared_gate"]["weight"], F32)
    for k in ("gate", "up", "down"):
        w[f"e_{k}"] = _adapted(p["moe"]["experts"][k], lora, f"{path}/moe/experts/{k}", lora_scale)
        w[f"s_{k}"] = _adapted(p["moe"]["shared"][k], lora, f"{path}/moe/shared/{k}", lora_scale)
    return w


def top_weights(params: Dict[str, Any]):
    return {"embed": jnp.asarray(params["embed"], F32),
            "final_norm": jnp.asarray(params["final_norm"]["scale"], F32),
            "head": kernel_f32(params["head"])}

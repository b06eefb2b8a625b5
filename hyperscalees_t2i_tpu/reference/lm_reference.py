"""Plain float32 reference of the sparse-expert MLA decoder that
``models/lm.py`` runs (openPangu-Ultra-MoE / DeepSeek-V3 family), written from
the published description and sharing no code with the program.

One sequence at a time, the whole sequence at once: no cache (K and V are
expanded for every position, every call), no kernels, no grouped or batched
products (a dense Python loop over the experts it is told it holds), float32
under ``jax.default_matmul_precision("highest")``. Imports nothing of this
repository, so ``benchmarks/reference/lm_reference.py`` is a verbatim copy.

``cfg`` is the model's ``config.json`` as a dict (published keys) plus the
share: ``experts_held`` / ``expert_offset`` (the routed experts this chip
holds; the router still scores all ``n_routed_experts`` and normalizes over
all ``num_experts_per_tok`` chosen) and ``vocab_rows_held``.

Weights of a block are a dict of float32 arrays, ``[din, dout]`` (routed
experts ``[E, din, dout]``): ``n1..n4``, ``wdq``, ``q_norm``, ``wuq``,
``wdkv``, ``kv_norm``, ``wukv``, ``wo``, then ``gate/up/down`` (dense) or
``router`` ``[n_routed, d]``, ``e_gate/e_up/e_down``, ``s_gate/s_up/s_down``.
:func:`block_weights` reads them from the program's parameter tree (float or
int8 nodes) and adds a member's LoRA delta ``(alpha/r) a @ b`` to each adapted
matrix — a materialized ``W + dW``, which the program never builds.

Departures from the published description, each at its line: plain RoPE in
the rotate-half convention (the config carries no scaling keys); sigmoid
scoring with no expert groups and no selection bias (the config has no
``n_group`` / ``topk_group`` / ``scoring_func``); the MTP module's structure
(DeepSeek-V3's); and ``forced_topk`` — the router's choice taken from outside
instead of computed, which exists only for the comparison on the chip.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x [..., T, dr] (T second to last), pos [T]. Plain, rotate-half (assumed)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mla(w: Dict[str, Any], cfg: Dict[str, Any], u):
    """Multi-head latent attention, expanded form over the whole sequence."""
    T = u.shape[0]
    H, dn, dr, dv, c = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"], cfg["kv_lora_rank"])
    pos = jnp.arange(T)
    cq = rms_norm(u @ w["wdq"], w["q_norm"], cfg["rms_norm_eps"])
    q = (cq @ w["wuq"]).reshape(T, H, dn + dr).transpose(1, 0, 2)        # [H, T, dn + dr]
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, cfg["rope_theta"])
    kvr = u @ w["wdkv"]
    ckv = rms_norm(kvr[:, :c], w["kv_norm"], cfg["rms_norm_eps"])
    kr = rope(kvr[:, c:], pos, cfg["rope_theta"])                        # [T, dr], shared by all heads
    kv = (ckv @ w["wukv"]).reshape(T, H, dn + dv).transpose(1, 0, 2)     # [H, T, dn + dv]
    k_nope, v = kv[..., :dn], kv[..., dn:]
    score = (jnp.einsum("hqj,hkj->hqk", q_nope, k_nope) + jnp.einsum("hqr,kr->hqk", q_rope, kr)) / math.sqrt(dn + dr)
    score = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], score, -jnp.inf)
    o = jnp.einsum("hqk,hkv->qhv", jax.nn.softmax(score, axis=-1), v).reshape(T, H * dv)
    return o @ w["wo"]


def swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def _weights(s, ids, cfg):
    top = jnp.take_along_axis(s, ids, axis=-1)
    if cfg.get("norm_topk_prob", True):
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    return top * cfg["routed_scaling_factor"]


def moe(w: Dict[str, Any], cfg: Dict[str, Any], u, forced_topk=None):
    """Shared(u) + sum over the chosen experts *held here* of w_e E_e(u):
    a dense loop, every held expert over every token, times the token's
    weight for it (0 where the router did not choose it). Returns the output
    and the reference's own choice ``[T, k]`` (also when another is forced)."""
    s = jax.nn.sigmoid(u @ w["router"].T)
    own = jnp.argsort(-s, axis=-1, stable=True)[:, : cfg["num_experts_per_tok"]]
    ids = own if forced_topk is None else forced_topk  # departure: the choice is given (the chip comparison)
    wts = _weights(s, ids, cfg)
    out = swiglu(u, w["s_gate"], w["s_up"], w["s_down"])
    lo = cfg.get("expert_offset", 0)
    for j in range(w["e_gate"].shape[0]):
        mine = ((ids == lo + j) * wts).sum(-1, keepdims=True)           # [T, 1]
        out = out + mine * swiglu(u, w["e_gate"][j], w["e_up"][j], w["e_down"][j])
    return out, own


def block(w: Dict[str, Any], cfg: Dict[str, Any], x, forced_topk=None, act=None):
    """Sandwich norm: a norm before and after each sub-layer. ``act`` (not part
    of the reference proper, identity when None) rounds each sub-layer's input:
    the benchmark uses it to read what an activation path of lower precision
    than the configuration states would give."""
    eps = cfg["rms_norm_eps"]
    act = act or (lambda t: t)
    h = x + rms_norm(mla(w, cfg, act(rms_norm(x, w["n1"], eps))), w["n2"], eps)
    u = act(rms_norm(h, w["n3"], eps))
    if "router" in w:
        f, ids = moe(w, cfg, u, forced_topk)
    else:
        f, ids = swiglu(u, w["gate"], w["up"], w["down"]), None
    return h + rms_norm(f, w["n4"], eps), ids


def forward(layer_weights: Callable[[int], Dict[str, Any]], n_layers: int, top: Dict[str, Any],
            cfg: Dict[str, Any], ids, forced_topk: Optional[Sequence[Any]] = None):
    """ids [T] → {"hidden" [T, d] before the final norm, "logits" [T, rows
    held], "topk": per MoE layer [T, k]}. ``layer_weights(i)`` gives block
    ``i``'s weights when it is needed (one layer resident at a time);
    ``top``: ``embed``, ``final_norm``, ``head``. ``forced_topk``: per MoE
    layer, the routing to use instead of the reference's own."""
    with jax.default_matmul_precision("highest"):
        x = top["embed"][ids].astype(F32)
        chosen: List[Any] = []
        for i in range(n_layers):
            w = layer_weights(i)
            force = forced_topk[len(chosen)] if (forced_topk is not None and "router" in w) else None
            x, picked = block(w, cfg, x, force)
            if picked is not None:
                chosen.append(picked)
        logits = rms_norm(x, top["final_norm"], cfg["rms_norm_eps"]) @ top["head"]
    return {"hidden": x, "logits": logits, "topk": chosen}


def forward_batch(layer_weights: Callable[[int], Dict[str, Any]], n_layers: int, top: Dict[str, Any],
                  cfg: Dict[str, Any], ids, forced_topk=None, act=None):
    """:func:`forward` for ``ids [S, T]`` of one length (right-padding a
    causal model changes nothing before the padding), the layers outermost so
    that one layer's float32 weights are resident at a time at any ``S``.
    ``forced_topk [S, T, moe layers, k]``; ``act``: see :func:`block`. Same
    returns with ``S`` in front (``topk [S, T, moe layers, k]``, the
    reference's own choices)."""
    with jax.default_matmul_precision("highest"):
        x = top["embed"][ids].astype(F32)
        chosen: List[Any] = []
        for i in range(n_layers):
            w = layer_weights(i)
            # the weights are arguments of the compiled block, not constants in it
            if "router" in w and forced_topk is not None:
                x, picked = jax.jit(jax.vmap(lambda w, xs, f: block(w, cfg, xs, f, act), in_axes=(None, 0, 0)))(
                    w, x, forced_topk[:, :, len(chosen)])
            else:
                x, picked = jax.jit(jax.vmap(lambda w, xs: block(w, cfg, xs, None, act), in_axes=(None, 0)))(w, x)
            if picked is not None:
                chosen.append(picked)
        logits = rms_norm(x, top["final_norm"], cfg["rms_norm_eps"]) @ top["head"]
    return {"hidden": x, "logits": logits, "topk": jnp.stack(chosen, axis=2) if chosen else None}


def mtp(w: Dict[str, Any], top: Dict[str, Any], cfg: Dict[str, Any], hidden, next_ids):
    """One multi-token-prediction module (DeepSeek-V3's structure, assumed):
    ``h' = Block([Nh(h_i) ; Ne(Emb(t_{i+1}))] Wp)``, then the main model's
    final norm and head → logits for ``t_{i+2}``. ``w``: ``nh``, ``ne``,
    ``proj`` and the block's own weights."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = jnp.concatenate([rms_norm(hidden, w["nh"], eps),
                             rms_norm(top["embed"][next_ids].astype(F32), w["ne"], eps)], axis=-1) @ w["proj"]
        y, _ = block(w, cfg, x)
        return rms_norm(y, top["final_norm"], eps) @ top["head"]


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
    m = p["mla"]
    w = {k: jnp.asarray(p[k]["scale"], F32) for k in ("n1", "n2", "n3", "n4")}
    w["q_norm"], w["kv_norm"] = jnp.asarray(m["q_norm"]["scale"], F32), jnp.asarray(m["kv_norm"]["scale"], F32)
    for k in ("wdq", "wuq", "wdkv", "wukv", "wo"):
        w[k] = _adapted(m[k], lora, f"{path}/mla/{k}", lora_scale)
    if "moe" in p:
        w["router"] = jnp.asarray(p["moe"]["router"]["weight"], F32)
        for k in ("gate", "up", "down"):
            w[f"e_{k}"] = _adapted(p["moe"]["experts"][k], lora, f"{path}/moe/experts/{k}", lora_scale)
            w[f"s_{k}"] = _adapted(p["moe"]["shared"][k], lora, f"{path}/moe/shared/{k}", lora_scale)
    else:
        for k in ("gate", "up", "down"):
            w[k] = _adapted(p["ffn"][k], lora, f"{path}/ffn/{k}", lora_scale)
    return w


def top_weights(params: Dict[str, Any]):
    return {"embed": jnp.asarray(params["embed"], F32),
            "final_norm": jnp.asarray(params["final_norm"]["scale"], F32),
            "head": kernel_f32(params["head"])}


def mtp_weights(p: Dict[str, Any]):
    w = block_weights(p["block"], "mtp")
    w.update(nh=jnp.asarray(p["nh"]["scale"], F32), ne=jnp.asarray(p["ne"]["scale"], F32),
             proj=kernel_f32(p["proj"]))
    return w

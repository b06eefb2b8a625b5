"""Plain float32 reference of the ``xing4_0`` decoder that ``models/lm.py``
runs: the DeepSeek-V3 block (pre-norm, multi-head latent attention with
yarn-scaled RoPE, a sigmoid router that chooses by score plus a selection bias,
one shared expert) inside manifold-constrained hyper-connections (*mHC*,
arXiv:2512.24880: ``hc_mult`` residual streams a token, read and written by
learned input-dependent rows and mixed by a Sinkhorn-projected matrix).
Written from the published description and sharing no code with the program.

One sequence at a time, the whole sequence at once: no cache (K and V are
expanded for every position, every call), no kernels, no grouped or batched
products (a dense Python loop over the experts it is told it holds), float32
under ``jax.default_matmul_precision("highest")``. Imports nothing of this
repository, so ``benchmarks/reference/mhc_moe_reference.py`` is a verbatim copy.

``cfg`` is the model's ``config.json`` as a dict (published keys) plus the
share: ``experts_held`` / ``expert_offset`` (the routed experts held here; the
router scores all ``n_routed_experts`` and normalizes over all
``num_experts_per_tok`` chosen) and ``vocab_rows_held``. The ``rope_scaling``
group may be nested as published or flat as ``rope_scaling_<key>`` (what a
dataclass of scalars holds).

Weights of a block are a dict of float32 arrays, ``[din, dout]`` (routed
experts ``[E, din, dout]``): ``n1``, ``n3`` (the attention's and the FFN's
pre-norm), ``wdq``, ``q_norm``, ``wuq``, ``wdkv``, ``kv_norm``, ``wukv``,
``wo``; ``gate/up/down`` (dense) or ``router`` ``[n_routed, d]``, ``bias``
``[n_routed]``, ``e_gate/e_up/e_down``, ``s_gate/s_up/s_down``; and per
sub-layer ``hc_attn`` / ``hc_ffn``: ``phi`` ``[nC, n + n + n²]`` (columns
``pre | post | res``, ``res`` row-major), ``b`` ``[n + n + n²]``, ``alpha``
``[3]``. :func:`block_weights` reads them from the program's parameter tree
(float or int8 nodes) and adds a member's LoRA delta ``(alpha/r) a @ b`` to
each adapted matrix — a materialized ``W + dW``, which the program never builds.

Departures from the published description and conventions taken where the
config is silent, each at its line: the streams start as ``hc_mult`` copies of
the embedding and the output is their sum (the hyper-connections convention);
Sinkhorn normalizes columns first, then rows, with ``hc_eps`` added to each
sum, and ``hc_eps`` is also the ε of the weightless norm over the stream;
rotate-half RoPE layout; yarn's correction range rounded outward as
DeepSeek-V3's modelling code does; the MTP module is DeepSeek-V3's, its block
run over ``hc_mult`` copies of the projected input; and three hooks that are no
part of the reference proper and exist only for the comparison on the chip —
``forced_topk`` (the router's choice taken from outside), ``act`` (rounds each
sub-layer's input) and ``coeff_round`` (rounds every value of the
hyper-connection coefficient path).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


# ---------------------------------------------------------------------------
# rotary embedding, yarn
# ---------------------------------------------------------------------------

def rope_scaling_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    nested = cfg.get("rope_scaling")
    if nested:
        return dict(nested)
    return {k[len("rope_scaling_"):]: v for k, v in cfg.items() if k.startswith("rope_scaling_")}


def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def inverse_frequencies(cfg: Dict[str, Any], dim: int):
    """``dim / 2`` rotary frequencies and the factor on cos / sin."""
    base = float(cfg["rope_theta"])
    plain = base ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    rs = rope_scaling_of(cfg)
    factor = float(rs.get("factor", 1.0))
    if factor <= 1:
        return plain, 1.0
    span = rs["original_max_position_embeddings"]

    def turns_at(rotations):  # the (real-valued) pair index that turns `rotations` times over `span` positions
        return dim * math.log(span / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns_at(rs["beta_fast"])), 0)          # rounded outward (DeepSeek-V3's code)
    high = min(math.ceil(turns_at(rs["beta_slow"])), dim - 1)
    if high == low:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low), 0.0, 1.0)
    inv = plain / factor * ramp + plain * (1.0 - ramp)           # slow pairs are stretched, fast ones kept
    return inv, yarn_mscale(factor, rs.get("mscale", 1.0)) / yarn_mscale(factor, rs.get("mscale_all_dim", 0.0))


def rope(x, pos, cfg):
    """x [..., T, dr] (T second to last), pos [T]. Rotate-half layout (assumed)."""
    half = x.shape[-1] // 2
    inv, amp = inverse_frequencies(cfg, x.shape[-1])
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mla(w: Dict[str, Any], cfg: Dict[str, Any], u):
    """Multi-head latent attention, expanded form over the whole sequence."""
    T = u.shape[0]
    H, dn, dr, dv, c = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"], cfg["kv_lora_rank"])
    pos = jnp.arange(T)
    eps = cfg["rms_norm_eps"]
    cq = rms_norm(u @ w["wdq"], w["q_norm"], eps)
    q = (cq @ w["wuq"]).reshape(T, H, dn + dr).transpose(1, 0, 2)        # [H, T, dn + dr]
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, cfg)
    kvr = u @ w["wdkv"]
    ckv = rms_norm(kvr[:, :c], w["kv_norm"], eps)
    kr = rope(kvr[:, c:], pos, cfg)                                      # [T, dr], shared by all heads
    kv = (ckv @ w["wukv"]).reshape(T, H, dn + dv).transpose(1, 0, 2)     # [H, T, dn + dv]
    k_nope, v = kv[..., :dn], kv[..., dn:]
    rs = rope_scaling_of(cfg)
    m = yarn_mscale(float(rs.get("factor", 1.0)), rs.get("mscale_all_dim", 0.0))
    scale = m * m / math.sqrt(dn + dr)                                   # yarn: the softmax scale grows by mscale²
    score = (jnp.einsum("hqj,hkj->hqk", q_nope, k_nope) + jnp.einsum("hqr,kr->hqk", q_rope, kr)) * scale
    score = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], score, -jnp.inf)
    o = jnp.einsum("hqk,hkv->qhv", jax.nn.softmax(score, axis=-1), v).reshape(T, H * dv)
    return o @ w["wo"]


def swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def moe(w: Dict[str, Any], cfg: Dict[str, Any], u, forced_topk=None):
    """Shared(u) + sum over the chosen experts *held here* of w_e E_e(u).
    ``noaux_tc``: the k experts are chosen by ``s + bias`` (one group), the
    weights are the plain scores of the chosen, normalized and scaled. A dense
    loop: every held expert over every token, times the token's weight for it.
    Returns the output and the reference's own choice ``[T, k]`` (also when
    another is forced)."""
    s = jax.nn.sigmoid(u @ w["router"].T)
    own = jnp.argsort(-(s + w["bias"]), axis=-1, stable=True)[:, : cfg["num_experts_per_tok"]]
    ids = own if forced_topk is None else forced_topk  # departure: the choice is given (the chip comparison)
    top = jnp.take_along_axis(s, ids, axis=-1)
    if cfg.get("norm_topk_prob", True):
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    wts = top * cfg["routed_scaling_factor"]
    out = swiglu(u, w["s_gate"], w["s_up"], w["s_down"])
    lo = cfg.get("expert_offset", 0)
    for j in range(w["e_gate"].shape[0]):
        mine = ((ids == lo + j) * wts).sum(-1, keepdims=True)           # [T, 1]
        out = out + mine * swiglu(u, w["e_gate"][j], w["e_up"][j], w["e_down"][j])
    return out, own


# ---------------------------------------------------------------------------
# manifold-constrained hyper-connections
# ---------------------------------------------------------------------------

def sinkhorn(logits, iters: int, eps: float, rnd=lambda t: t):
    """``exp``, then ``iters`` times: every column divided by its sum + eps,
    then every row by its sum + eps. ``logits [..., n, n]``."""
    m = rnd(jnp.exp(logits))
    for _ in range(iters):
        m = rnd(m / rnd(m.sum(-2, keepdims=True) + eps))
        m = rnd(m / rnd(m.sum(-1, keepdims=True) + eps))
    return m


def hc_coefficients(hc: Dict[str, Any], cfg: Dict[str, Any], X, coeff_round=None):
    """``X [T, n, C]`` → ``H_pre [T, n]``, ``H_post [T, n]``, ``H_res [T, n, n]``."""
    n, eps = cfg["hc_mult"], cfg["hc_eps"]
    rnd = coeff_round or (lambda t: t)
    v = rnd(X.reshape(X.shape[0], -1))
    xt = rnd(v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps))      # no weight; eps = hc_eps (assumed)
    z = rnd(xt @ rnd(hc["phi"]))
    a, b = hc["alpha"], hc["b"]
    pre = rnd(jax.nn.sigmoid(a[0] * z[:, :n] + b[:n]))
    post = rnd(2.0 * jax.nn.sigmoid(a[1] * z[:, n: 2 * n] + b[n: 2 * n]))
    raw = (a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
    raw = rnd(jnp.clip(raw, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))
    return pre, post, sinkhorn(raw, cfg["hc_sinkhorn_iters"], eps, rnd)


def hc_sublayer(hc: Dict[str, Any], cfg: Dict[str, Any], X, F: Callable, coeff_round=None):
    """``u = H_pre X``; ``y = F(u)``; ``X ← H_res X + H_postᵀ y``. ``F`` returns
    ``(y, extra)``; ``extra`` is passed through."""
    pre, post, res = hc_coefficients(hc, cfg, X, coeff_round)
    u = jnp.einsum("tn,tnc->tc", pre, X)
    y, extra = F(u)
    return jnp.einsum("tmn,tnc->tmc", res, X) + post[:, :, None] * y[:, None, :], extra


def block(w: Dict[str, Any], cfg: Dict[str, Any], X, forced_topk=None, act=None, coeff_round=None):
    """Pre-norm sub-layers inside hyper-connections, streams ``X [T, n, C]``.
    ``act`` (identity when None) rounds each sub-layer's input after its norm:
    the benchmark reads with it what an activation path of lower precision
    than the configuration states would give; ``coeff_round`` likewise for the
    coefficient path."""
    eps = cfg["rms_norm_eps"]
    act = act or (lambda t: t)
    X, _ = hc_sublayer(w["hc_attn"], cfg, X, lambda u: (mla(w, cfg, act(rms_norm(u, w["n1"], eps))), None),
                       coeff_round)

    def ffn(u):
        u = act(rms_norm(u, w["n3"], eps))
        if "router" in w:
            return moe(w, cfg, u, forced_topk)
        return swiglu(u, w["gate"], w["up"], w["down"]), None

    return hc_sublayer(w["hc_ffn"], cfg, X, ffn, coeff_round)


def streams_in(cfg, x):
    """``X₀``: ``hc_mult`` copies of each token's vector (assumed)."""
    return jnp.repeat(x[..., None, :], cfg["hc_mult"], axis=-2)


def head_logits(h, head, columns: int = 16384):
    """``h @ head`` with an int8 head dequantized a block of columns at a time
    (131 072 x 3584 in float32 is 1.9 GB the comparison on the chip cannot spare)."""
    if not isinstance(head, dict):
        return h @ head
    q8, scale = head["kernel_q8"]["q8"], head["kernel_q8"]["scale"]
    return jnp.concatenate([h @ (q8[:, i: i + columns].astype(F32) * scale[:, i: i + columns].astype(F32))
                            for i in range(0, q8.shape[1], columns)], axis=-1)


def forward(layer_weights: Callable[[int], Dict[str, Any]], n_layers: int, top: Dict[str, Any],
            cfg: Dict[str, Any], ids, forced_topk: Optional[Sequence[Any]] = None):
    """ids [T] → {"hidden" [T, d] before the final norm (the streams summed),
    "logits" [T, rows held], "topk": per MoE layer [T, k]}. ``layer_weights(i)``
    gives block ``i``'s weights when it is needed (one layer resident at a
    time); ``top``: ``embed``, ``final_norm``, ``head``. ``forced_topk``: per
    MoE layer, the routing to use instead of the reference's own."""
    with jax.default_matmul_precision("highest"):
        X = streams_in(cfg, top["embed"][ids].astype(F32))
        chosen: List[Any] = []
        for i in range(n_layers):
            w = layer_weights(i)
            force = forced_topk[len(chosen)] if (forced_topk is not None and "router" in w) else None
            X, picked = block(w, cfg, X, force)
            if picked is not None:
                chosen.append(picked)
        x = X.sum(-2)                                                    # the output: the streams summed (assumed)
        logits = head_logits(rms_norm(x, top["final_norm"], cfg["rms_norm_eps"]), top["head"])
    return {"hidden": x, "logits": logits, "topk": chosen}


def forward_batch(layer_weights: Callable[[int], Dict[str, Any]], n_layers: int, top: Dict[str, Any],
                  cfg: Dict[str, Any], ids, forced_topk=None, act=None, coeff_round=None):
    """:func:`forward` for ``ids [S, T]`` of one length (right-padding a
    causal model changes nothing before the padding), the layers outermost so
    that one layer's float32 weights are resident at a time at any ``S``.
    ``forced_topk [S, T, moe layers, k]``; ``act``, ``coeff_round``: see
    :func:`block`. Same returns with ``S`` in front (``topk [S, T, moe layers,
    k]``, the reference's own choices)."""
    with jax.default_matmul_precision("highest"):
        X = streams_in(cfg, top["embed"][ids].astype(F32))
        chosen: List[Any] = []
        # the weights are arguments of the compiled block, not constants in it: one program a kind of layer
        run = jax.jit(jax.vmap(lambda w, xs, f: block(w, cfg, xs, f, act, coeff_round), in_axes=(None, 0, 0)))
        free = jax.jit(jax.vmap(lambda w, xs: block(w, cfg, xs, None, act, coeff_round), in_axes=(None, 0)))
        for i in range(n_layers):
            w = layer_weights(i)
            if "router" in w and forced_topk is not None:
                X, picked = run(w, X, forced_topk[:, :, len(chosen)])
            else:
                X, picked = free(w, X)
            if picked is not None:
                chosen.append(picked)
            del w  # before the next layer's weights are built
        x = X.sum(-2)
        logits = head_logits(rms_norm(x, top["final_norm"], cfg["rms_norm_eps"]), top["head"])
    return {"hidden": x, "logits": logits, "topk": jnp.stack(chosen, axis=2) if chosen else None}


def mtp(w: Dict[str, Any], top: Dict[str, Any], cfg: Dict[str, Any], hidden, next_ids):
    """One multi-token-prediction module (DeepSeek-V3's structure, assumed):
    ``h' = Block([Nh(h_i) ; Ne(Emb(t_{i+1}))] Wp)`` with the block run over
    ``hc_mult`` copies of the projected input and its streams summed, then the
    main model's final norm and head → logits for ``t_{i+2}``. ``w``: ``nh``,
    ``ne``, ``proj`` and the block's own weights."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = jnp.concatenate([rms_norm(hidden, w["nh"], eps),
                             rms_norm(top["embed"][next_ids].astype(F32), w["ne"], eps)], axis=-1) @ w["proj"]
        Y, _ = block(w, cfg, streams_in(cfg, x))
        return head_logits(rms_norm(Y.sum(-2), top["final_norm"], eps), top["head"])


# ---------------------------------------------------------------------------
# reading the program's parameter tree (a checkpoint layout, no program code)
# ---------------------------------------------------------------------------

@jax.jit
def _dequantized(q8, scale):
    return q8.astype(F32) * scale.astype(F32)


@jax.jit
def _plus_delta(w, a, b, s):
    with jax.default_matmul_precision("highest"):
        return w + s * (a @ b)


def kernel_f32(node: Dict[str, Any]):
    """A kernel node as float32: ``{"kernel": w}`` or the int8 form
    ``{"kernel_q8": {"q8", "scale"}}`` (``q8 * scale``, scale per expert and
    output channel)."""
    if "kernel" in node:
        return jnp.asarray(node["kernel"], F32)
    return _dequantized(node["kernel_q8"]["q8"], node["kernel_q8"]["scale"])


def _adapted(node, lora, path, scale):
    w = kernel_f32(node)
    leaf = None if lora is None else lora.get(path)
    if leaf is None:
        return w
    return _plus_delta(w, jnp.asarray(leaf["a"], F32), jnp.asarray(leaf["b"], F32), scale)


def block_weights(p: Dict[str, Any], path: str, lora: Optional[Dict[str, Any]] = None, lora_scale: float = 1.0):
    """Block ``p`` of the program's tree → the reference's flat dict, with the
    adapter ``lora`` (``{site path: {"a", "b"}}``, materialized arrays)
    added into each adapted matrix. The hyper-connection parameters, the
    router and its bias are float32 in the tree and taken as they are."""
    m = p["mla"]
    w: Dict[str, Any] = {k: jnp.asarray(p[k]["scale"], F32) for k in ("n1", "n3")}
    w["q_norm"], w["kv_norm"] = jnp.asarray(m["q_norm"]["scale"], F32), jnp.asarray(m["kv_norm"]["scale"], F32)
    for k in ("hc_attn", "hc_ffn"):
        w[k] = {name: jnp.asarray(p[k][name], F32) for name in ("phi", "b", "alpha")}
    for k in ("wdq", "wuq", "wdkv", "wukv", "wo"):
        w[k] = _adapted(m[k], lora, f"{path}/mla/{k}", lora_scale)
    if "moe" in p:
        w["router"] = jnp.asarray(p["moe"]["router"]["weight"], F32)
        w["bias"] = jnp.asarray(p["moe"]["router"]["e_score_correction_bias"], F32)
        for k in ("gate", "up", "down"):
            w[f"e_{k}"] = _adapted(p["moe"]["experts"][k], lora, f"{path}/moe/experts/{k}", lora_scale)
            w[f"s_{k}"] = _adapted(p["moe"]["shared"][k], lora, f"{path}/moe/shared/{k}", lora_scale)
    else:
        for k in ("gate", "up", "down"):
            w[k] = _adapted(p["ffn"][k], lora, f"{path}/ffn/{k}", lora_scale)
    return w


def top_weights(params: Dict[str, Any]):
    """``embed`` stays in the tree's own dtype (rows are widened as they are
    looked up) and an int8 ``head`` stays a node (:func:`head_logits`)."""
    head = params["head"]
    return {"embed": params["embed"], "final_norm": jnp.asarray(params["final_norm"]["scale"], F32),
            "head": head if "kernel_q8" in head else jnp.asarray(head["kernel"], F32)}


def mtp_weights(p: Dict[str, Any]):
    w = block_weights(p["block"], "mtp")
    w.update(nh=jnp.asarray(p["nh"]["scale"], F32), ne=jnp.asarray(p["ne"]["scale"], F32),
             proj=kernel_f32(p["proj"]))
    return w

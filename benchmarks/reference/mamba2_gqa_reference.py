"""Plain float32 reference of the dense hybrid decoder that ``models/lm_ssm.py``
runs (granite-4.0-h family, ``model_type: granitemoehybrid`` with no routed
experts: Mamba-2 mixers beside grouped-query attention without position
embedding, a SwiGLU in every layer, a head tied to the embedding), written
from the published equations and sharing no code with the program.

One sequence at a time, the whole sequence at once: no KV cache (attention
sees the whole causal sequence every call), no chunking (the state-space
recurrence runs position by position), no kernels, float32 under
``jax.default_matmul_precision("highest")``. Imports nothing of this
repository, so ``benchmarks/reference/mamba2_gqa_reference.py`` is a verbatim
copy.

``cfg`` is the model's ``config.json`` as a dict (published keys).

The equations. ``N(x) = w x rsqrt(mean(x^2) + eps)``. ``x_0 =
embedding_multiplier E[id]``. Block ``i``, pre-norm: ``h = x + r Mixer(N1(x))``,
``y = h + r SwiGLU(N2(h))``, ``r = residual_multiplier``, the mixer named by
``layer_types[i]``. Logits ``N(x_L) E^T / logits_scaling``. See
:func:`mamba2`, :func:`attention`, :func:`swiglu`.

Weights of a block are a dict of float32 arrays ``[din, dout]``: ``n1``, ``n2``;
a Mamba-2 mixer's ``in_proj``, ``conv`` ``[kernel, channels]``, ``conv_bias``,
``a_log``, ``dt_bias``, ``d``, ``norm``, ``out_proj``; an attention mixer's
``wq``, ``wk``, ``wv``, ``wo``; ``gate``, ``up``, ``down``.
:func:`block_weights` reads them from the program's parameter tree (float or
int8 nodes, stacked by position in the layer pattern's period) and adds a
member's LoRA delta ``(alpha/r) a @ b`` to each adapted matrix — a
materialized ``W + dW``, which the program never builds.

Departures from the published code: the SwiGLU's fused ``input_linear`` is
two matrices ``gate`` and ``up`` (with seeded weights the same model), and two
hooks that exist only for the comparison on the chip and are the identity
when None: ``act`` (rounds each sub-layer's input) and ``state_round`` (rounds
the recurrent state after every update).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mamba2(w: Dict[str, Any], cfg: Dict[str, Any], u, state_round=None):
    """``[z | xBC | dt] = u W_in``; a short causal depthwise conv with bias
    over ``xBC``, then silu; ``[x | B | C] = xBC``; ``dt = softplus(dt +
    dt_bias)``, ``A = -exp(A_log)``; per head the recurrence ``S = exp(dt A) S
    + dt x B^T``, ``y = S C + D x`` over a ``[head_dim, d_state]`` float32
    state position by position; ``y * silu(z)`` normalized over all heads with
    its own weight; ``W_out``."""
    T = u.shape[0]
    H, P, N, K = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    I = H * P
    zxbcdt = u @ w["in_proj"]
    z, xbc, dt = zxbcdt[:, :I], zxbcdt[:, I: 2 * I + 2 * N], zxbcdt[:, 2 * I + 2 * N:]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc], axis=0)
    xbc = jax.nn.silu(sum(padded[j: j + T] * w["conv"][j] for j in range(K)) + w["conv_bias"])
    x, b, c = xbc[:, :I].reshape(T, H, P), xbc[:, I: I + N], xbc[:, I + N:]
    dt = jax.nn.softplus(dt + w["dt_bias"])                      # [T, H]
    a = -jnp.exp(w["a_log"])
    rnd = state_round or (lambda s: s)

    def position(S, xs):                                         # S [H, P, N]
        x_t, dt_t, b_t, c_t = xs
        S = rnd(jnp.exp(dt_t * a)[:, None, None] * S + dt_t[:, None, None] * x_t[:, :, None] * b_t[None, None, :])
        return S, jnp.einsum("hpn,n->hp", S, c_t) + w["d"][:, None] * x_t

    _, y = jax.lax.scan(position, jnp.zeros((H, P, N), F32), (x, dt, b, c))
    y = y.reshape(T, I) * jax.nn.silu(z)
    return rms_norm(y, w["norm"], cfg["rms_norm_eps"]) @ w["out_proj"]


def attention(w: Dict[str, Any], cfg: Dict[str, Any], u):
    """Causal softmax attention, no position embedding, grouped KV heads,
    scores times ``attention_multiplier``."""
    T = u.shape[0]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // H
    q = (u @ w["wq"]).reshape(T, H, dh)
    k = jnp.repeat((u @ w["wk"]).reshape(T, Hkv, dh), H // Hkv, axis=1)   # each KV head serves H / Hkv query heads
    v = jnp.repeat((u @ w["wv"]).reshape(T, Hkv, dh), H // Hkv, axis=1)
    score = jnp.einsum("qhd,khd->hqk", q, k) * cfg["attention_multiplier"]
    score = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], score, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(score, axis=-1), v).reshape(T, H * dh) @ w["wo"]


def swiglu(w: Dict[str, Any], u):
    return (jax.nn.silu(u @ w["gate"]) * (u @ w["up"])) @ w["down"]


def block(w: Dict[str, Any], cfg: Dict[str, Any], x, act=None, state_round=None):
    """Pre-norm block; the mixer is the one whose weights ``w`` holds. ``act``
    and ``state_round`` are not part of the reference proper (see the header)."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    act = act or (lambda t: t)
    u = act(rms_norm(x, w["n1"], eps))
    h = x + r * (attention(w, cfg, u) if "wq" in w else mamba2(w, cfg, u, state_round))
    return h + r * swiglu(w, act(rms_norm(h, w["n2"], eps)))


def _top(top: Dict[str, Any], cfg: Dict[str, Any], ids):
    return top["embed"][ids].astype(F32) * cfg["embedding_multiplier"]


def _logits(top: Dict[str, Any], cfg: Dict[str, Any], x):
    return rms_norm(x, top["final_norm"], cfg["rms_norm_eps"]) @ top["embed"].T / cfg["logits_scaling"]


def forward(layer_weights: Callable[[int], Dict[str, Any]], n_layers: int, top: Dict[str, Any],
            cfg: Dict[str, Any], ids):
    """ids [T] → {"hidden" [T, d] before the final norm, "logits" [T, vocab]}.
    ``layer_weights(i)`` gives block ``i``'s weights when it is needed; ``top``:
    ``embed`` (also the head), ``final_norm``."""
    with jax.default_matmul_precision("highest"):
        x = _top(top, cfg, ids)
        for i in range(n_layers):
            x = block(layer_weights(i), cfg, x)
        return {"hidden": x, "logits": _logits(top, cfg, x)}


def forward_batch(layer_weights: Callable[[int], Dict[str, Any]], n_layers: int, top: Dict[str, Any],
                  cfg: Dict[str, Any], ids, act=None, state_round=None):
    """:func:`forward` for ``ids [S, T]`` of one length (right-padding a causal
    model changes nothing before the padding), the layers outermost so that
    one layer's float32 weights are resident at a time at any ``S``. ``act``,
    ``state_round``: see :func:`block`."""
    compiled: Dict[bool, Any] = {}  # one compiled block a mixer kind: the weights are arguments, not constants

    def run(w, x):
        kind = "wq" in w
        if kind not in compiled:
            compiled[kind] = jax.jit(jax.vmap(lambda w, xs: block(w, cfg, xs, act, state_round), in_axes=(None, 0)))
        return compiled[kind](w, x)

    with jax.default_matmul_precision("highest"):
        x = _top(top, cfg, ids)
        for i in range(n_layers):
            x = run(layer_weights(i), x)
        return {"hidden": x, "logits": _logits(top, cfg, x)}


# ---------------------------------------------------------------------------
# reading the program's parameter tree (a checkpoint layout, no program code)
# ---------------------------------------------------------------------------

def kernel_f32(node: Dict[str, Any]):
    """A kernel node as float32: ``{"kernel": w}`` or the int8 form
    ``{"kernel_q8": {"q8", "scale"}}`` (``q8 * scale``, scale per output
    channel)."""
    if "kernel" in node:
        return jnp.asarray(node["kernel"], F32)
    return node["kernel_q8"]["q8"].astype(F32) * node["kernel_q8"]["scale"].astype(F32)


def _layer_of(tree, p: int):
    """Period ``p`` of a position's parameters: every leaf is stacked ``[periods, ...]``."""
    return jax.tree_util.tree_map(lambda a: a[p], tree)


def block_weights(params: Dict[str, Any], layer: int, lora: Optional[Dict[str, Any]] = None,
                  lora_scale: float = 1.0):
    """Layer ``layer`` of the program's tree → the reference's flat dict. The
    tree holds position ``j`` of the period of every period in
    ``params["layers"][j]``, so layer ``i`` is position ``i % period`` of
    period ``i // period``; the adapter ``lora`` (``{site path: {"a", "b"}}``,
    materialized arrays stacked alike) is added into each adapted matrix."""
    period = len(params["layers"])
    j, p = layer % period, layer // period
    blk = _layer_of(params["layers"][j], p)

    def adapted(node, path):
        w = kernel_f32(node)
        leaf = None if lora is None else lora.get(f"layers/{j}/{path}")
        if leaf is None:
            return w
        with jax.default_matmul_precision("highest"):
            return w + lora_scale * jnp.asarray(leaf["a"][p], F32) @ jnp.asarray(leaf["b"][p], F32)

    w = {k: jnp.asarray(blk[k]["scale"], F32) for k in ("n1", "n2")}
    if "mamba" in blk:
        m = blk["mamba"]
        for k in ("in_proj", "out_proj"):
            w[k] = adapted(m[k], f"mamba/{k}")
        w["conv"], w["conv_bias"] = jnp.asarray(m["conv"]["weight"], F32), jnp.asarray(m["conv"]["bias"], F32)
        for k in ("a_log", "dt_bias", "d"):
            w[k] = jnp.asarray(m[k], F32)
        w["norm"] = jnp.asarray(m["norm"]["scale"], F32)
    else:
        for k in ("wq", "wk", "wv", "wo"):
            w[k] = adapted(blk["attn"][k], f"attn/{k}")
    for k in ("gate", "up", "down"):
        w[k] = adapted(blk["ffn"][k], f"ffn/{k}")
    return w


def top_weights(params: Dict[str, Any]):
    return {"embed": jnp.asarray(params["embed"], F32), "final_norm": jnp.asarray(params["final_norm"]["scale"], F32)}

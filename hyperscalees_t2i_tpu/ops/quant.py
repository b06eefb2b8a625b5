"""Int8 weight-only quantization of the frozen base — the ES hot path's
byte diet, and the runtime form of the reference's GGUF quantized-transformer
option (``/root/reference/models/zImageTurbo.py:140-197``, config
``es_backend.py:479-483``).

Per-output-channel symmetric int8: ``w ≈ q · scale`` with ``q ∈ int8``,
``scale = max|w| / 127`` per output channel. Kernels are stored int8 in HBM
(half of bf16, a quarter of f32 — the reason GGUF exists) and dequantized at
each use site; a native-int8 chip keeps the dequant in registers so the MXU
still sees bf16 operands while HBM only ever moves the int8 bytes. The
trained delta never touches the base: LoRA factors and the factored ES noise
live in their own trees, so every LoRA-targeted kernel stays quantizable
(``lora.init_lora`` adapts ``kernel_q8/q8`` paths like ``kernel`` ones).

Kernel layouts (the repo's conventions — models/nn.py initializers):

- 2D ``[din, dout]`` dense                      → scale ``[1, dout]``
- 3D ``[L, din, dout]`` scan-stacked dense      → scale ``[L, 1, dout]``
- 3D ``[E, din, dout]`` routed experts          → scale ``[E, 1, dout]`` (the
  same rule: each expert is an independent matrix, one scale per expert and
  output channel — models/lm.py, tests/test_lm.py)
- 4D ``[kh, kw, cin, cout]`` conv HWIO          → scale ``[1, 1, 1, cout]``
- 5D ``[L, kh, kw, cin, cout]`` stacked conv    → scale ``[L, 1, 1, 1, cout]``

Odd ranks carry a leading scan-stack axis whose layers each keep their own
scales (each stacked layer is an independent matrix); every other non-output
axis is reduced. ``dequantize_kernel`` additionally accepts *block-scale*
nodes (``scale [..., nb, dout]`` with ``nb·block == din``) — the exact int8
payload of a GGUF Q8_0 tensor (``weights/gguf.py``), preserved without
requantization.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]

# Layers below this many parameters stay float under the ``--base_quant``
# knob (quantizing tiny layers costs accuracy for no bandwidth win — the
# same policy GGUF applies to norms/embeddings). Env override exists for
# tests and small-geometry experiments, where nothing clears the default.
DEFAULT_MIN_SIZE = 1 << 16
MIN_SIZE_ENV = "HSES_BASE_QUANT_MIN_SIZE"

BASE_QUANT_MODES = ("off", "int8")


def _scale_axes(ndim: int) -> Tuple[int, ...]:
    """Reduction axes of the per-output-channel amax for one kernel layout:
    everything except the output channels (last axis) and, for odd ranks,
    the leading scan-stack axis (each stacked layer scales independently)."""
    if ndim < 2:
        raise ValueError(f"kernel must be at least 2D, got ndim={ndim}")
    lead = 1 if ndim % 2 else 0
    return tuple(range(lead, ndim - 1))


def quantize_kernel(w: jax.Array) -> Dict[str, jax.Array]:
    """float kernel → ``{"q8": int8, "scale": f32}`` (see layout table above)."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=_scale_axes(w.ndim), keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return {"q8": q, "scale": scale.astype(jnp.float32)}


def dequantize_kernel(qk: Dict[str, jax.Array], dtype=jnp.bfloat16) -> jax.Array:
    """``q · scale`` in f32, cast to ``dtype`` at the use site (the convert
    fuses into the consuming dot/conv operand read on native-int8 chips).

    Handles both scale forms: broadcastable per-output-channel scales
    (:func:`quantize_kernel`) and GGUF Q8_0 *block* scales ``[..., nb, dout]``
    where ``nb`` evenly tiles ``din`` (``weights/gguf.py`` nodes)."""
    q, scale = qk["q8"], qk["scale"]
    nb = scale.shape[-2]
    if nb > 1 and nb != q.shape[-2]:
        if q.shape[-2] % nb:
            raise ValueError(
                f"block scales {scale.shape} do not tile kernel {q.shape}"
            )
        block = q.shape[-2] // nb
        qb = q.reshape(*q.shape[:-2], nb, block, q.shape[-1])
        w = qb.astype(jnp.float32) * scale[..., :, None, :]
        return w.reshape(q.shape).astype(dtype)
    return (q.astype(jnp.float32) * scale).astype(dtype)


def dequant_matmul(x: jax.Array, qk: Dict[str, jax.Array]) -> jax.Array:
    """``x @ dequant(qk)`` — what every 2D ``kernel_q8`` consumer without a
    member's factored adapter lowers (``nn.dense``, the matmul-equivalent
    convs, the base half of ``ops/fused_qlora``'s composition): the dequant is
    left to XLA's operand fusion, so only the s8 bytes move through HBM."""
    return x @ dequantize_kernel(qk, x.dtype)


def kernel_shape(p: Params) -> Tuple[int, ...]:
    """Static shape of a node's kernel, float or int8-quantized — for call
    sites that read geometry off the kernel (e.g. depthwise conv groups)."""
    if "kernel" in p:
        return tuple(p["kernel"].shape)
    return tuple(p["kernel_q8"]["q8"].shape)


def quantize_tree(
    params: Params,
    min_size: int = DEFAULT_MIN_SIZE,
    predicate: Optional[Callable[[str, jax.Array], bool]] = None,
) -> Params:
    """Replace every large ``{"kernel": w}`` node (dense, stacked-dense, conv,
    stacked-conv) with ``{"kernel_q8": {...}, "bias": ...}``. Layers below
    ``min_size`` params stay float. Idempotent on already-quantized nodes."""

    def walk(node, path=""):
        if isinstance(node, dict):
            if "kernel" in node and hasattr(node["kernel"], "ndim"):
                w = node["kernel"]
                ok = w.ndim >= 2 and w.size >= min_size
                if predicate is not None:
                    ok = ok and predicate(path, w)
                if ok:
                    out = {k: v for k, v in node.items() if k != "kernel"}
                    out["kernel_q8"] = quantize_kernel(w)
                    return out
            return {k: walk(v, f"{path}/{k}") for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            t = type(node)
            return t(walk(v, f"{path}/{i}") for i, v in enumerate(node))
        return node

    return walk(params)


def resolve_base_quant_min_size(min_size: Optional[int] = None) -> int:
    """The ``min_size`` the ``--base_quant`` knob applies: explicit value >
    ``HSES_BASE_QUANT_MIN_SIZE`` env > the GGUF-style default."""
    if min_size is not None:
        return min_size
    return int(os.environ.get(MIN_SIZE_ENV, DEFAULT_MIN_SIZE))


def maybe_quantize_tree(
    tree: Params, base_quant: str, min_size: Optional[int] = None
) -> Params:
    """Apply the ``--base_quant`` knob to one frozen param tree.

    ``off`` returns the tree UNTOUCHED (same object); ``int8`` rewrites every
    kernel node at or above the min-size floor. The single entry point bench/preflight/trainer
    share, so "quantized base" means the same thing at every site."""
    if base_quant in (None, "", "off", False):
        return tree
    if base_quant != "int8":
        raise ValueError(
            f"base_quant must be one of {BASE_QUANT_MODES}, got {base_quant!r}"
        )
    return quantize_tree(tree, min_size=resolve_base_quant_min_size(min_size))


def quantize_frozen(tree: Params, base_quant: str) -> Params:
    """:func:`maybe_quantize_tree` over device-resident frozen trees (one
    tree, or a dict of them) as ONE compiled pass with the float input
    donated — every leaf the pass leaves untouched keeps its buffer, and
    the float kernels are released as soon as the call returns instead of
    living on behind a second reference. What bench.build and train.cli run
    at the multi-GB geometries."""
    if base_quant in (None, "", "off", False):
        return tree
    return jax.jit(
        lambda t: maybe_quantize_tree(t, base_quant), donate_argnums=(0,)
    )(tree)


def tree_int8_bytes(tree: Any) -> int:
    """Total bytes of int8 leaves in a tree — a diagnostic for sizing a
    quantized base (tests/tools; the preflight's chip-true accounting
    instead *measures* the legalization copies from the optimized HLO,
    obs/xla_cost.legalization_stats)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if getattr(leaf, "dtype", None) == jnp.int8:
            n = 1
            for d in leaf.shape:
                n *= d
            total += n
    return total


def resolve_kernel(p: Params, dtype) -> jax.Array:
    """Fetch a node's kernel, dequantizing if stored int8 (used by nn.dense
    and the model-side einsum consumers)."""
    if "kernel" in p:
        return p["kernel"].astype(dtype)
    return dequantize_kernel(p["kernel_q8"], dtype)

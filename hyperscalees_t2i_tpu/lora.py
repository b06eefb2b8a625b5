"""Functional LoRA: adapter params as a pytree, delta applied inside forward.

The reference injects PEFT LoRA modules into live torch models and mutates
their weights per ES candidate (``/root/reference/es_backend.py:193-200``,
``unifed_es.py:159-163``). TPU-first redesign: base params are a frozen
pytree; the adapter is a *separate* pytree ``lora`` mirroring the model's
structure sparsely; every adapted dense computes

    y = x @ W  +  (alpha/r) * (x @ A) @ B

so ``W + ΔW`` is never materialized, the population can be vmapped over the
``lora`` tree, and XLA fuses the two matmuls into the surrounding graph.

Conventions
-----------
- dense kernels are ``[d_in, d_out]`` (or stacked ``[L, d_in, d_out]`` for
  scan-over-layers blocks); LoRA factors are ``a: [.., d_in, r]``,
  ``b: [.., r, d_out]``. The leading axis of a 3D kernel may equally be an
  **expert axis** (``[E, d_in, d_out]``, models/lm.py): every expert gets its
  own factors, its own EGGROLL noise (``es/noiser.sample_noise`` draws
  ``U: [base, E, m, r]``) and, in a :class:`FactoredDelta`, its own
  ``u[e] v[e]ᵀ`` under the member's one coefficient; ``ops/grouped.py``
  consumes them.
- init matches PEFT: ``a ~ N(0, 1/d_in)``, ``b = 0`` → the adapter starts as
  the identity, exactly like ``get_peft_model`` with default init.
- targeting is by parameter-path substring match, compatible in spirit with
  the reference's module-name target lists (``unifed_es.py:391,406,472,485``).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Pytree = Any


class FactoredDelta(NamedTuple):
    """A LoRA factor carrying its ES perturbation *in factored form*.

    Represents ``w_k = w + c · u @ vᵀ`` without a per-member staged adapter:
    ``w`` is the unperturbed factor (``a: [.., m, n]`` or ``b: [.., m, n]``),
    ``u: [.., m, r_e]`` / ``v: [.., n, r_e]`` are member ``k``'s slice of
    the EGGROLL noise factors (possibly bf16 — the HBM store dtype), and
    ``c`` is the member's scalar coefficient ``σ·s_k/√r_e`` (f32). XLA
    consumers apply it via :func:`effective_factor` — ONE fused operand
    build per use site, f32 accumulation over the noise store, the
    consuming dot reading the activations exactly once. Do NOT apply it as
    a chained ``x@w + c·(x@u)@vᵀ`` expansion in XLA: that form re-reads the
    activations per term and was measured to move MORE bytes (PERF.md
    round 12 dead end); the chain is right only inside a Pallas kernel
    (ops/fused_qlora.py), where the token tile is VMEM-resident. A
    NamedTuple, so it flows through jit/vmap/lax.map/shard_map as an
    ordinary pytree node.
    """

    w: jax.Array  # base LoRA factor [.., m, n]
    u: jax.Array  # noise left factor [.., m, r_e] (store dtype)
    v: jax.Array  # noise right factor [.., n, r_e] (store dtype)
    c: jax.Array  # scalar σ·s/√r_e, f32


def effective_factor(f: Any, dtype: Any) -> jax.Array:
    """The perturbed factor ``w_k = w + c·u@vᵀ`` of a :class:`FactoredDelta`,
    built in one fused expression at the point of use (raw arrays pass
    through). The thin ``u@vᵀ`` product (f32 accumulation over the bf16
    store) fuses with the scale-and-add into a single operand build — no
    separate ε buffer is ever written, and the consuming dot reads the
    activations exactly once (a chained ``x@w + c·(x@u)@vᵀ`` form re-reads
    ``x`` per term, which the XLA ledger showed moves *more* bytes at
    generation-activation scale — PERF.md round 12)."""
    if not isinstance(f, FactoredDelta):
        return f.astype(dtype)
    # precision="highest" matches materialize_member_eps exactly: on TPU the
    # default f32 matmul path drops mantissa bits and the fused-vs-
    # materialized θ-parity tolerance is pinned against the full-precision
    # reference (CPU ignores the setting, so only TPU behavior changes).
    d = jnp.einsum(
        "...mr,...nr->...mn", f.u.astype(jnp.float32), f.v.astype(jnp.float32),
        precision="highest", preferred_element_type=jnp.float32,
    )
    return (f.w.astype(jnp.float32) + f.c * d).astype(dtype)


def matmul_factored(x: jax.Array, f: Any) -> jax.Array:
    """``x @ f`` where ``f`` is a raw factor array or a :class:`FactoredDelta`
    (applied via :func:`effective_factor` — one dot, one fused operand
    build). Output dtype follows ``x`` (the surrounding compute dtype),
    matching the raw path's ``leaf.astype(x.dtype)`` contract."""
    return x @ effective_factor(f, x.dtype)


@dataclasses.dataclass(frozen=True)
class LoRASpec:
    """Static adapter spec — one per model, like the reference's LoraConfig."""

    rank: int = 8
    alpha: float = 16.0
    targets: Tuple[str, ...] = ()  # path patterns (regex, searched) on kernel paths

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def iter_kernel_paths(params: Pytree) -> List[Tuple[str, jax.Array]]:
    """All (path, leaf) pairs for kernel-like leaves (ndim >= 2)."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if hasattr(leaf, "ndim") and leaf.ndim >= 2:
            out.append((_path_str(path), leaf))
    return out


def match_targets(path: str, targets: Sequence[str]) -> bool:
    return any(re.search(t, path) for t in targets)


def init_lora(key: jax.Array, params: Pytree, spec: LoRASpec) -> Dict[str, Dict[str, jax.Array]]:
    """Build the adapter tree for every targeted dense kernel.

    Returned tree is *flat*: ``{path: {"a": ..., "b": ...}}`` keyed by the
    kernel's parameter path (minus the trailing ``/kernel``). A flat dict keeps
    the ES noiser agnostic to model structure and makes PEFT-style export
    trivial. Kernels may be 2D ``[din, dout]`` or stacked 3D ``[L, din, dout]``
    (scan-over-layers); the factors follow suit.
    """
    tree: Dict[str, Dict[str, jax.Array]] = {}
    # float kernels end in ".../kernel"; int8-quantized ones (ops/quant.py)
    # end in ".../kernel_q8/q8" — both are adaptable (the reference likewise
    # attaches LoRA on top of GGUF-quantized transformers,
    # zImageTurbo.py:140-197 + es_backend.py:592-608).
    kernels = [
        (p, l)
        for p, l in iter_kernel_paths(params)
        if p.endswith("kernel") or p.endswith("kernel_q8/q8")
    ]
    keys = jax.random.split(key, max(len(kernels), 1))
    for k, (path, leaf) in zip(keys, kernels):
        name = re.sub(r"/?(kernel|kernel_q8/q8)$", "", path)
        if not match_targets(name, spec.targets):
            continue
        if leaf.ndim == 2:
            din, dout = leaf.shape
            a = jax.random.normal(k, (din, spec.rank), jnp.float32) / jnp.sqrt(din)
            b = jnp.zeros((spec.rank, dout), jnp.float32)
        elif leaf.ndim == 3:
            L, din, dout = leaf.shape
            a = jax.random.normal(k, (L, din, spec.rank), jnp.float32) / jnp.sqrt(din)
            b = jnp.zeros((L, spec.rank, dout), jnp.float32)
        elif leaf.ndim == 4:
            # conv kernel [kh, kw, cin, cout] — PEFT's Conv2d LoRA factors as
            # an r-channel conv (A) followed by a 1×1 conv (B). The reference
            # uses this for the Z-Image VAE-decoder adapter
            # (es_backend.py:599-629).
            kh, kw, cin, cout = leaf.shape
            fan = kh * kw * cin
            a = jax.random.normal(k, (kh, kw, cin, spec.rank), jnp.float32) / jnp.sqrt(fan)
            b = jnp.zeros((spec.rank, cout), jnp.float32)
        else:
            continue
        tree[name] = {"a": a, "b": b}
    return tree


def lora_delta(x: jax.Array, leaf: Optional[Dict[str, jax.Array]], scale: float) -> Optional[jax.Array]:
    """(alpha/r)·(x@A)@B for 2D factors; None when the layer is unadapted."""
    if leaf is None:
        return None
    a = leaf["a"].astype(x.dtype)
    b = leaf["b"].astype(x.dtype)
    return (x @ a) @ b * scale


def factored_lora_delta(x: jax.Array, leaf: Dict[str, Any], scale: float) -> jax.Array:
    """(alpha/r)·(x@a_k)@b_k where either factor may be a :class:`FactoredDelta`
    — a training member's LoRA delta on every platform: two dots whose
    perturbed operands ``a_k``/``b_k`` are each built in ONE fused expression
    at the point of use (:func:`effective_factor`), f32 accumulation over the
    noise store, the activations read once per dot."""
    h = matmul_factored(x, leaf["a"])
    return matmul_factored(h, leaf["b"]) * jnp.asarray(scale, x.dtype)


def stack_adapters(trees: Sequence[Pytree]) -> Pytree:
    """N same-structure adapter trees → ONE tree whose every leaf carries a
    leading ``[N]`` adapter axis — the serving batch argument.

    The multi-tenant engine (``serve/``) hands a whole adapter *batch* to one
    AOT-compiled generate program as an ordinary jit argument; inside, each
    ``lax.map`` lane selects its slot via ``es.stacked_adapter_theta`` — the
    same member-axis contract the training hot path uses for perturbations,
    so serving a new user is a new *argument*, never a new program. Structure
    or shape mismatches raise naming the offending adapter index (a silently
    broadcast wrong-rank adapter would serve garbage to a real request).
    Leaves are stacked host-side (numpy): adapter trees arrive from the
    store's host-resident copies and the stack is the dispatch-time
    host→device transfer.
    """
    import numpy as np

    if not trees:
        raise ValueError("stack_adapters needs at least one adapter tree")
    ref_def = jax.tree_util.tree_structure(trees[0])
    ref_leaves = jax.tree_util.tree_leaves(trees[0])
    stacked: List[Any] = [[np.asarray(l)] for l in ref_leaves]
    for i, tree in enumerate(trees[1:], start=1):
        if jax.tree_util.tree_structure(tree) != ref_def:
            raise ValueError(
                f"adapter {i} has a different tree structure than adapter 0 "
                "(was it trained against a different target list / rank?)"
            )
        for j, leaf in enumerate(jax.tree_util.tree_leaves(tree)):
            arr = np.asarray(leaf)
            if arr.shape != stacked[j][0].shape or arr.dtype != stacked[j][0].dtype:
                raise ValueError(
                    f"adapter {i} leaf {j}: shape/dtype {arr.shape}/{arr.dtype} "
                    f"!= adapter 0's {stacked[j][0].shape}/{stacked[j][0].dtype}"
                )
            stacked[j].append(arr)
    return jax.tree_util.tree_unflatten(
        ref_def, [np.stack(ls, axis=0) for ls in stacked]
    )


def lookup(lora: Optional[Dict[str, Any]], path: str) -> Optional[Dict[str, jax.Array]]:
    """Fetch the adapter leaf for a kernel path (flat-dict adapter tree)."""
    if lora is None:
        return None
    return lora.get(path)


def _slice_factor(f: Any, i) -> Any:
    """Layer ``i`` of one stacked factor — raw array or FactoredDelta (whose
    ``w``/``u``/``v`` all carry the ``[L, ...]`` stack; ``c`` is per-member,
    not per-layer)."""
    if isinstance(f, FactoredDelta):
        return FactoredDelta(f.w[i], f.u[i], f.v[i], f.c)
    return f[i]


def slice_layer(leaf: Optional[Dict[str, jax.Array]], i) -> Optional[Dict[str, jax.Array]]:
    """Select layer ``i`` from stacked ``[L, ...]`` factors (inside lax.scan)."""
    if leaf is None:
        return None
    return {"a": _slice_factor(leaf["a"], i), "b": _slice_factor(leaf["b"], i)}

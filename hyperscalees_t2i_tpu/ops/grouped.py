"""Grouped matmul over the routed experts a chip holds, with a per-(member,
expert) factored LoRA delta.

``ops/fused_qlora.py`` assumes one dense base shared by every member and a
token block made of whole members, each row meeting its own member's factors
(its ``vmap`` rule lays members of few rows side by side in one block). A
routed expert layer breaks both: a member's rows scatter over experts, and an
expert sees rows of several members in no order. The formulation here keeps
the two parts apart:

- **base** (:func:`grouped_matmul`): every (row, expert) pair of the call —
  all members of a ``lax.map`` chunk together — is sorted by expert and
  multiplied by ``jax.lax.ragged_dot`` against the ``[E, din, dout]`` kernel,
  so an expert's base is read once a call, not once a member. The member
  axis reaches it through ``custom_vmap``: the batching rule flattens
  (member, row) into rows instead of batching the kernel's read. On the TPU
  an int8 kernel goes to ``ragged_dot`` as it is (the compiler's grouped
  kernel takes a bf16 x s8 product; the per-(expert, channel) scale is applied
  to the result), so no dequantized copy of the experts is written: compiled
  for a v5e, the dequantized form costs 504 MB of scratch a matrix and five
  times the bytes.
- **delta** (:func:`expert_lora_factors`, :func:`expert_lora_rows`): a
  member's perturbed factors of all ``E`` held experts lie side by side,
  ``[din, E·r]`` and ``[E·r, dout]`` — one MXU-wide thin operand a side
  instead of ``E`` thin ones — and a pair keeps its own expert's ``r``
  columns by a mask. These are per member, so plain ``vmap`` batches them.

Pairs whose expert is not held carry the sentinel ``E``: they sort behind
every group, join no product, and come back as zero rows. No pair is dropped
and no capacity exists.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..lora import effective_factor

Params = Dict[str, Any]


def _ragged_rows(xp: jax.Array, e: jax.Array, w: jax.Array, scale: Optional[jax.Array]) -> jax.Array:
    """``xp[i] @ w[e[i]]`` (zero where ``e[i] == E``) for ``xp [R, din]``,
    ``e [R]``, ``w [E, din, dout]`` float or int8 with ``scale [E, 1, dout]``."""
    E = w.shape[0]
    order = jnp.argsort(e)  # stable: the sentinel E sorts last
    e_sorted = e[order]
    group_sizes = jnp.bincount(e, length=E + 1)[:E].astype(jnp.int32)
    xs = xp[order]
    if w.dtype == jnp.int8 and jax.default_backend() != "tpu":
        w = w.astype(xs.dtype)  # XLA:CPU has no mixed-type ragged product
    y = jax.lax.ragged_dot(xs, w, group_sizes, preferred_element_type=xs.dtype)
    if scale is not None:
        y = y.astype(jnp.float32) * scale[:, 0, :][jnp.minimum(e_sorted, E - 1)]
    # rows behind the last group belong to no product: whatever the kernel
    # left there is not a result
    y = jnp.where((e_sorted < E)[:, None], y, 0).astype(xp.dtype)
    return y[jnp.argsort(order)]  # back to the callers' row order (a gather, not a scatter)


def _flatten_members(fn, n_row_args: int):
    """``custom_vmap`` of ``fn(*row_args, *shared_args)``: a batch axis on the
    row arguments becomes more rows of one call. The shared arguments (the
    experts' kernel) have none: the frozen base is one for every member."""
    wrapped = jax.custom_batching.custom_vmap(fn)

    @wrapped.def_vmap
    def rule(axis_size, in_batched, *args):
        rows, shared = args[:n_row_args], args[n_row_args:]
        if any(in_batched[n_row_args:]):
            raise NotImplementedError("a kernel per member: the grouped product shares one base")
        rows = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(rows, in_batched)]
        flat = [a.reshape((axis_size * a.shape[1],) + a.shape[2:]) for a in rows]
        out = wrapped(*flat, *shared)
        per_row = out.ndim > 0 and out.shape[0] == flat[0].shape[0]
        if per_row:
            return out.reshape((axis_size, -1) + out.shape[1:]), True
        return out, False

    return wrapped


_grouped_float = _flatten_members(lambda xp, e, w: _ragged_rows(xp, e, w, None), 2)
_grouped_q8 = _flatten_members(_ragged_rows, 2)


def grouped_matmul(xp: jax.Array, e: jax.Array, node: Params) -> jax.Array:
    """``xp [R, din]`` rows, each against the expert ``e [R]`` names
    (``0 <= e < E`` held, ``e == E`` none) of an ``[E, din, dout]`` kernel node
    (``{"kernel": ...}`` or ``{"kernel_q8": {"q8", "scale"}}``, ops/quant.py)
    → ``[R, dout]``. Under ``vmap`` the members' rows join one call."""
    if "kernel" in node:
        return _grouped_float(xp, e, node["kernel"].astype(xp.dtype))
    qk = node["kernel_q8"]
    return _grouped_q8(xp, e, qk["q8"], qk["scale"])


def _load_ratio(e: jax.Array, n_experts: int) -> jax.Array:
    counts = jnp.bincount(e, length=n_experts + 1)[:n_experts].astype(jnp.float32)
    return jnp.where(counts.sum() > 0, counts.max() / jnp.maximum(counts.mean(), 1e-9), 0.0)


def expert_load_ratio(e: jax.Array, n_experts: int) -> jax.Array:
    """Largest number of rows one held expert sees in this call over the mean
    a held expert sees (0 when none sees any). Under ``vmap`` the call is the
    whole member chunk's, as :func:`grouped_matmul`'s is."""
    return _flatten_members(lambda ee: _load_ratio(ee, n_experts), 1)(e)


def expert_lora_factors(leaf: Optional[Params], dtype) -> Optional[Tuple[jax.Array, jax.Array]]:
    """One member's LoRA factors of all held experts, side by side: ``a``
    ``[E, din, r]`` → ``[din, E·r]``, ``b`` ``[E, r, dout]`` → ``[E·r, dout]``.
    Either factor may be a raw array (the materialized perturbation) or a
    ``lora.FactoredDelta`` whose ``w``/``u``/``v`` carry the expert axis in
    front (``w_k[e] = w[e] + c·u[e] v[e]ᵀ``, one coefficient a member)."""
    if leaf is None:
        return None
    a = effective_factor(leaf["a"], dtype)
    b = effective_factor(leaf["b"], dtype)
    E, din, r = a.shape
    return a.transpose(1, 0, 2).reshape(din, E * r), b.reshape(E * r, b.shape[-1])


def expert_lora_rows(z: jax.Array, e: jax.Array, b_side: jax.Array, n_experts: int) -> jax.Array:
    """``z [R, E·r]`` (rows times the side-by-side ``a``), each row keeping
    the ``r`` columns of its expert ``e [R]``, times ``b_side [E·r, dout]``."""
    r = z.shape[-1] // n_experts
    keep = jnp.repeat(jax.nn.one_hot(e, n_experts, dtype=z.dtype), r, axis=-1)
    return (z * keep) @ b_side

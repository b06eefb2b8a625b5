"""Pallas TPU kernel for the member-batched perturbed LoRA matmul.

The fused ES hot path (lora.py ``FactoredDelta``) applies member ``k``'s
perturbed adapter

    delta = scale · (x @ a_k) @ b_k,   a_k = a + c_a·U_a V_aᵀ,  b_k = b + c_b·U_b V_bᵀ

In XLA the right shape is the *one-dot* form (``lora.effective_factor``):
a chained ``x@a + c·(x@U)@Vᵀ`` expansion re-reads the ``[T, din]``
activations from HBM per term, which the ledger measured as MORE bytes
moved (PERF.md round 12). Inside a Pallas kernel that trade inverts — the
token tile is VMEM-resident, so the chain costs nothing extra to read and
skips building ``a_k``/``b_k`` buffers entirely: one pass per token tile
computes the whole four-matmul chain with the ``[bt, r_l]``/``[bt, r_e]``
intermediates never leaving VMEM.

Ships **behind a flag**:

- ``HSES_POP_FUSE_PALLAS=1`` + a TPU backend → the Pallas kernel (a Mosaic
  refusal raises at the enclosing compile);
- anything else → :func:`xla_member_lora_delta`, the same math in plain jnp.

CPU correctness is proven in interpret mode (tests/test_fused.py) — the
same contract as ops/attention.py's decode kernel: the CPU tier can lower
and *interpret* the kernel; only a TPU executes it
(``tools/kernel_check.py`` is that run).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .pallas_gate import backend_is_tpu, env_requested


def use_fused_pallas() -> bool:
    """Gate for the member-batched LoRA kernel. Opt-in (the XLA one-dot
    form is the default): the env flag AND a TPU backend
    (ops/pallas_gate.py)."""
    return env_requested("HSES_POP_FUSE_PALLAS") is True and backend_is_tpu()


def xla_member_lora_delta(x, a, b, scale):
    """The XLA form: scale·((x@a_k)@b_k) as chained thin jnp matmuls with f32
    accumulation over the noise factors (same math `lora.matmul_factored`
    composes — kept here so kernel and XLA form are compared in one place)."""
    from ..lora import matmul_factored

    h = matmul_factored(x, a)
    return matmul_factored(h, b) * jnp.asarray(scale, x.dtype)


def _chain_kernel(
    x_ref, aw_ref, au_ref, av_ref, bw_ref, bu_ref, bv_ref, ca_ref, cb_ref, o_ref,
    *, scale: float,
):
    """One token tile of the perturbed chain, fully in VMEM, f32 accumulation.

    All factor operands are thin ([d, r_l] / [d, r_e]) and loaded whole; the
    only tiled operand is ``x`` (and the output)."""
    f32 = jnp.float32
    x = x_ref[...].astype(f32)  # [bt, din]
    ca = ca_ref[0, 0]
    cb = cb_ref[0, 0]

    def dot(p, q):
        # full-precision f32 passes: the kernel is parity-pinned against the
        # materialized path, which computes its ε at precision="highest"
        return jax.lax.dot_general(
            p, q, (((1,), (0,)), ((), ())), preferred_element_type=f32,
            precision=jax.lax.Precision.HIGHEST,
        )

    # x @ a_k = x@a + ca·(x@U_a)@V_aᵀ   → [bt, r_l]
    xa = dot(x, aw_ref[...].astype(f32))
    xa = xa + ca * dot(dot(x, au_ref[...].astype(f32)), av_ref[...].astype(f32).T)
    # (x@a_k) @ b_k = xa@b + cb·(xa@U_b)@V_bᵀ   → [bt, dout]
    y = dot(xa, bw_ref[...].astype(f32))
    y = y + cb * dot(dot(xa, bu_ref[...].astype(f32)), bv_ref[...].astype(f32).T)
    o_ref[...] = (y * scale).astype(o_ref.dtype)


def _pallas_member_lora_delta(x2, a, b, scale, block_t: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, din = x2.shape
    dout = b.w.shape[-1]
    block_t = min(block_t, T)
    n_blk = -(-T // block_t)
    T_pad = n_blk * block_t
    if T_pad != T:
        x2 = jnp.pad(x2, ((0, T_pad - T), (0, 0)))

    whole = lambda arr: pl.BlockSpec(arr.shape, lambda t: (0,) * arr.ndim)
    scalar = pl.BlockSpec((1, 1), lambda t: (0, 0), memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_chain_kernel, scale=float(scale)),
        out_shape=jax.ShapeDtypeStruct((T_pad, dout), x2.dtype),
        grid=(n_blk,),
        in_specs=[
            pl.BlockSpec((block_t, din), lambda t: (t, 0)),
            whole(a.w), whole(a.u), whole(a.v),
            whole(b.w), whole(b.u), whole(b.v),
            scalar, scalar,
        ],
        out_specs=pl.BlockSpec((block_t, dout), lambda t: (t, 0)),
        interpret=interpret,
        name="member_lora_delta",
    )(
        x2, a.w, a.u, a.v, b.w, b.u, b.v,
        a.c.astype(jnp.float32).reshape(1, 1),
        b.c.astype(jnp.float32).reshape(1, 1),
    )
    return out[:T]


def member_lora_delta(
    x: jax.Array,
    a,  # lora.FactoredDelta, w [din, r_l]
    b,  # lora.FactoredDelta, w [r_l, dout]
    scale: float,
    *,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    block_t: int = 256,
) -> jax.Array:
    """scale·((x@a_k)@b_k) for one member's factored 2D adapter leaf.

    ``x`` may have any leading shape (``[..., din]``); it is flattened to a
    token-tile grid for the kernel. ``use_pallas=None`` auto-selects via
    :func:`use_fused_pallas`; a selected kernel that fails to trace or
    compile raises. ``interpret`` is for tests only."""
    if use_pallas is None:
        use_pallas = use_fused_pallas()
    if not (use_pallas or interpret):
        return xla_member_lora_delta(x, a, b, scale)
    lead = x.shape[:-1]
    out = _pallas_member_lora_delta(
        x.reshape(-1, x.shape[-1]), a, b, scale, block_t, interpret
    )
    return out.reshape(*lead, out.shape[-1])

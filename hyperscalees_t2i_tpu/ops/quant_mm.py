"""Pallas TPU kernel for the int8-dequant matmul (``--base_quant int8``).

The XLA path (``models/nn.dense`` → ``ops/quant.dequantize_kernel``) leaves
the dequant to operand fusion: on a native-int8 chip XLA folds
``convert(s8)·scale`` into the dot's operand read, so only the s8 bytes move
through HBM. This kernel makes that contract *explicit* — each grid step
loads a ``[bk, bn]`` s8 kernel tile into VMEM, dequantizes it in registers
(convert + per-output-channel scale), and feeds the MXU — for platforms or
XLA versions where the fusion heuristic materializes the dequantized copy
instead (the failure mode the preflight's ``int8_dequant_copy_bytes``
instrument measures on CPU).

Ships **behind a flag**, mirroring ``ops/fused_lora.py``:

- ``HSES_BASE_QUANT_PALLAS=1`` + a TPU backend → the Pallas kernel (a
  Mosaic refusal raises at the enclosing compile);
- anything else → :func:`xla_int8_matmul`, the same math in plain jnp.

CPU correctness is proven in interpret mode (tests/test_quant.py) — the
ops/attention.py / ops/fused_lora.py contract: the CPU tier can lower and
*interpret* the kernel; only a TPU executes it (``tools/kernel_check.py``
is that run).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .pallas_gate import backend_is_tpu, env_requested


def use_base_quant_pallas() -> bool:
    """Opt-in gate (the XLA dequant fusion is the default): the env flag
    AND a TPU backend (ops/pallas_gate.py)."""
    return env_requested("HSES_BASE_QUANT_PALLAS") is True and backend_is_tpu()


# The kernel keeps a node's whole [din, dout] s8 matrix resident in VMEM (it
# tiles tokens only). Mosaic's default scoped limit on v5e is 16 MiB: the
# 4.8 MiB [2240, 2240] Sana attention node compiles, the 23.9 MiB
# [2240, 11200] FFN node is refused at 24.11 MiB (v5e compile, PR 21), so
# only nodes up to half the limit are the kernel's.
MAX_RESIDENT_BYTES = 8 * 2**20


def _kernel_handles(q8: jax.Array, scale: jax.Array) -> bool:
    """2D per-output-channel nodes small enough to sit in VMEM whole. GGUF
    block-scale nodes (``scale.shape[-2] > 1``) and wide nodes are XLA's."""
    return (
        q8.ndim == 2 and scale.ndim == 2 and scale.shape[0] == 1
        and q8.size <= MAX_RESIDENT_BYTES
    )


def dequant_matmul(x: jax.Array, qk: dict) -> jax.Array:
    """``x @ dequant(qk)`` — THE dequant-matmul contract every 2D
    ``kernel_q8`` consumer resolves through: ``nn.dense`` (float path aside),
    the matmul-equivalent conv/patch-embed sites (ops/fused_qlora.py), and
    the unified kernel's XLA composition. One definition, so "consumes an
    int8 base" means the same lowering everywhere: the explicit in-VMEM
    Pallas dequant kernel when :func:`use_base_quant_pallas` gates it on
    and the node is one :func:`_kernel_handles`, the XLA operand-fused
    dequant otherwise."""
    if use_base_quant_pallas() and _kernel_handles(qk["q8"], qk["scale"]):
        return int8_matmul(x, qk["q8"], qk["scale"], use_pallas=True)
    from .quant import dequantize_kernel

    return x @ dequantize_kernel(qk, x.dtype)


def xla_int8_matmul(x: jax.Array, q8: jax.Array, scale: jax.Array) -> jax.Array:
    """The XLA form: ``x @ (q8·scale)`` with the dequant left to XLA operand
    fusion — exactly what ``nn.dense`` lowers via ``dequantize_kernel``."""
    from .quant import dequantize_kernel

    return x @ dequantize_kernel({"q8": q8, "scale": scale}, x.dtype)


def _int8_mm_kernel(x_ref, q_ref, s_ref, o_ref):
    """One token tile: dequantize the s8 kernel in registers, hit the MXU.

    f32 accumulation; the dequantized tile never exists outside VMEM."""
    f32 = jnp.float32
    x = x_ref[...].astype(f32)                      # [bt, din]
    w = q_ref[...].astype(f32) * s_ref[...].astype(f32)  # [din, dout] in VMEM
    o_ref[...] = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=f32,
    ).astype(o_ref.dtype)


def _pallas_int8_matmul(x2, q8, scale, block_t: int, interpret: bool):
    from jax.experimental import pallas as pl

    T, din = x2.shape
    dout = q8.shape[-1]
    block_t = min(block_t, T)
    n_blk = -(-T // block_t)
    T_pad = n_blk * block_t
    if T_pad != T:
        x2 = jnp.pad(x2, ((0, T_pad - T), (0, 0)))
    out = pl.pallas_call(
        _int8_mm_kernel,
        out_shape=jax.ShapeDtypeStruct((T_pad, dout), x2.dtype),
        grid=(n_blk,),
        in_specs=[
            pl.BlockSpec((block_t, din), lambda t: (t, 0)),
            pl.BlockSpec((din, dout), lambda t: (0, 0)),
            pl.BlockSpec((1, dout), lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, dout), lambda t: (t, 0)),
        interpret=interpret,
        name="int8_matmul",
    )(x2, q8, scale)
    return out[:T]


def int8_matmul(
    x: jax.Array,
    q8: jax.Array,     # s8 [din, dout]
    scale: jax.Array,  # f32 [1, dout] (per-output-channel)
    *,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    block_t: int = 256,
) -> jax.Array:
    """``x @ (q8·scale)`` for one 2D per-output-channel int8 kernel node.

    ``x`` may have any leading shape (``[..., din]``). Nodes the kernel
    does not handle (:func:`_kernel_handles`) take the XLA path whatever
    ``use_pallas`` says. ``use_pallas=None`` auto-selects via
    :func:`use_base_quant_pallas`; a selected kernel that fails to trace or
    compile raises. ``interpret`` is for tests only."""
    if use_pallas is None:
        use_pallas = use_base_quant_pallas()
    if not _kernel_handles(q8, scale):
        use_pallas = False
    if not (use_pallas or interpret):
        return xla_int8_matmul(x, q8, scale)
    lead = x.shape[:-1]
    out = _pallas_int8_matmul(
        x.reshape(-1, x.shape[-1]), q8, scale, block_t, interpret
    )
    return out.reshape(*lead, out.shape[-1])

"""One true kernel: fused int8-dequant + member-LoRA matmul (round 15).

Every rung is bandwidth-bound (PERF.md rounds 12-14) and the two biggest
remaining byte sinks are exactly what the two existing Pallas kernels attack
*separately*: the int8 dequant cone (ops/quant_mm.py) and the per-member
base re-reads of the LoRA chain (ops/fused_lora.py). Composed at the XLA
level those two paths re-move the base bytes per term; "Run LoRA Run"
(arXiv 2312.03415) and "LoRA Is Slower Than You Think" (arXiv 2507.08833)
both show the adapter chain only wins when the activations/base stay
resident — which is precisely what ONE kernel gives us and two sequential
kernels cannot.

:func:`fused_qlora_dense` computes, for member ``k``'s factored 2D adapter
leaf over an int8 base node::

    y = x @ (q8 · scale)  +  lora_scale · (x @ a_k) @ b_k
        a_k = a + c_a·U_a V_aᵀ,   b_k = b + c_b·U_b V_bᵀ

In the Pallas kernel each grid step loads a ``[din, bn]`` s8 base tile
into VMEM **once**, dequantizes it in registers (convert + per-output-channel
scale — the s8 bytes are the only base bytes that ever cross HBM), and runs
the whole perturbed-LoRA chain against the SAME VMEM-resident token tile:
the ``[bt, r]`` intermediates never leave VMEM, and the chain form is
correct here for the same reason it was the measured XLA dead end (PERF.md
round 12) — in-kernel the activations cost nothing to re-read.

Promotion discipline (this kernel is the *default* on TPU, not an opt-in):

- gate: :func:`use_fused_qlora_pallas` — ON on a TPU backend
  (ops/pallas_gate.py), layer by layer wherever :func:`_fit_blocks` finds
  tiles that fit VMEM; ``HSES_FUSED_QLORA_PALLAS=0`` opts out. Nothing is
  probed and nothing is caught: a layer the gate selected and Mosaic refuses
  fails the enclosing compile.
- elsewhere: :func:`xla_fused_qlora` is the EXACT pre-round-15 composition
  (the separate dequant-matmul contract + the one-fused-operand LoRA
  delta), so on every non-kernel platform the unified resolution lowers
  the byte-identical program the round-14 ledger proved — CI diffs the
  preflight ledgers and fails if that form ever moves more bytes.
- parity: interpret-mode tests in tier-1 (tests/test_fused_qlora.py) on the
  CPU; on the chip ``tools/kernel_check.py`` compiles, runs and compares the
  kernel at the flagship call shapes (``chip_smoke.py`` runs that check).

Routing (``HSES_FUSED_QLORA``): the *trace-time* knob that decides whether
``kernel_q8`` consumers resolve through the unified contract at all.
Default on; ``HSES_FUSED_QLORA=off`` restores the round-14 lowering
(separate dequant + delta, conv sites dequant-then-conv) — the reference
program the CI ledger gate diffs against. Distinct from the kernel flag
above: routing shapes the XLA program, the kernel flag picks Mosaic vs XLA
for a program already routed.

Conv/patch-embed coverage: :func:`conv_kernel_q8_matmul` routes the
matmul-equivalent ``kernel_q8`` convs through the same dequant contract as
``dense`` (ops/quant_mm.dequant_matmul): 1×1 stride-1 convs (glumb_conv's
inverted/point projections) contract the channel axis directly, and
non-overlapping p×p stride-p patch convs (CLIP/Sana patch_embed) go through
an exact reshape-only im2col to a per-channel-flattened ``[p·p·cin, dout]``
layout — per-output-channel scales survive flattening unchanged. Overlapping
/ grouped / block-scale convs keep the dequant-then-conv path.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .pallas_gate import backend_is_tpu, env_requested

ROUTING_ENV = "HSES_FUSED_QLORA"
KERNEL_ENV = "HSES_FUSED_QLORA_PALLAS"

# Per-layer VMEM working-set ceiling for electing the Pallas path. The grid
# tiles tokens AND output channels — the resident set per step is the
# [din, block_n] base tile, the [block_t, din] x tile, and the thin factors
# — but ``din`` is the contraction axis and stays whole, so block sizes ADAPT
# DOWNWARD (:func:`_fit_blocks` halves block_t then block_n to the 128-lane
# floor) before a wide-input layer is declined at trace time and takes the
# XLA composition.
#
# What Mosaic really allocates was read off v5e compiles (libtpu 0.0.34,
# compile-only topology, PR 21) at din 2240: (512, 256) blocks 19.97 MiB,
# (512, 512) 21.56, (1024, 256) 38.98, (1024, 1024) 45.26, and everything
# at or under (256, 512) inside the 16 MiB default scoped limit. The
# estimate below lands within ±25 % of those, so the kernel asks Mosaic for
# a 48 MiB scoped limit (the chip has 128 MiB of VMEM) and elects blocks
# whose estimate is at most half of it.
VMEM_LIMIT_BYTES = 48 * 2**20
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES // 2
MIN_BLOCK = 128  # lane-aligned floor for both tile axes
_LANES, _SUBLANES = 128, 8


def _vmem_tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of one [rows, cols] block: the last dim fills whole
    128-lane registers and the second-to-last whole sublane groups (8 rows
    of 32-bit, 16 of 16-bit, 32 of 8-bit), so a thin [din, r] factor costs
    as much as a [din, 128] one."""
    sub = _SUBLANES * max(1, 4 // itemsize)
    return -(-rows // sub) * sub * -(-cols // _LANES) * _LANES * itemsize


def _kernel_vmem_bytes(q8, a, b, block_t: int, block_n: int) -> int:
    """Working-set estimate for one grid step: every input and the output
    block double-buffered by the Pallas pipeline (``x`` counted at 4 bytes —
    a bf16 tile plus its in-kernel f32 upcast cost the same), plus the
    in-kernel f32 values (the dequantized base tile, the upcast noise
    factors, ``xa`` and the two [block_t, block_n] partial results)."""
    din, dout = q8.shape
    bn = min(block_n, dout)
    r_l, r_e = a.w.shape[-1], a.u.shape[-1]
    isz = lambda f: jnp.dtype(f.dtype).itemsize
    pipelined = 2 * (
        _vmem_tile_bytes(block_t, din, 4)            # x
        + _vmem_tile_bytes(din, bn, 1)               # s8 base tile
        + _vmem_tile_bytes(1, bn, 4)                 # scale
        + _vmem_tile_bytes(din, r_l, isz(a.w))
        + _vmem_tile_bytes(din, r_e, isz(a.u))
        + _vmem_tile_bytes(r_l, r_e, isz(a.v))
        + _vmem_tile_bytes(r_l, bn, isz(b.w))        # b.w arrives dout-tiled
        + _vmem_tile_bytes(r_l, r_e, isz(b.u))
        + _vmem_tile_bytes(bn, r_e, isz(b.v))        # b.v arrives dout-tiled
        + _vmem_tile_bytes(block_t, bn, 4)           # out
    )
    values = (
        _vmem_tile_bytes(din, bn, 4)                 # f32 dequant of the tile
        + _vmem_tile_bytes(din, r_e, 4)              # a.u upcast
        + _vmem_tile_bytes(bn, r_e, 4)               # b.v upcast
        + _vmem_tile_bytes(block_t, r_l, 4)          # xa
        + 2 * _vmem_tile_bytes(block_t, bn, 4)       # y, d
    )
    return pipelined + values


def _fit_blocks(q8, a, b, block_t: int, block_n: int) -> Optional[tuple]:
    """Largest (block_t, block_n) at or under the requested sizes whose
    working set fits :data:`VMEM_BUDGET_BYTES` — halving block_t first (the
    cheap axis: more token sweeps, same base-tile residency) then block_n,
    both floored at :data:`MIN_BLOCK`. None = the layer cannot fit even at
    the floor (the kernel is not selected for it; XLA composition)."""
    while _kernel_vmem_bytes(q8, a, b, block_t, block_n) > VMEM_BUDGET_BYTES:
        if block_t > MIN_BLOCK:
            block_t //= 2
        elif block_n > MIN_BLOCK:
            block_n //= 2
        else:
            return None
    return block_t, block_n


def unified_routing_enabled() -> bool:
    """Trace-time routing knob: ``HSES_FUSED_QLORA=off`` (or ``0``) restores
    the round-14 composition — separate dequant matmul + LoRA delta, conv
    sites dequant-then-conv — which is the CI ledger gate's reference
    program. Anything else (the default) routes ``kernel_q8`` consumers
    through the unified contract."""
    return env_requested(ROUTING_ENV) is not False


def use_fused_qlora_pallas() -> bool:
    """The unified kernel's gate — ON BY DEFAULT on a TPU backend (this is
    the promoted kernel; the separate opt-in kernels it unifies stay behind
    their own flags for A/B). ``HSES_FUSED_QLORA_PALLAS=0`` opts out."""
    return env_requested(KERNEL_ENV) is not False and backend_is_tpu()


def fused_qlora_applies(leaf: Dict[str, Any]) -> bool:
    """True when the lora leaf at an int8 dense site should resolve through
    :func:`fused_qlora_dense`: routing on, and the leaf carries the fused
    hot path's factored perturbations (both factors ``lora.FactoredDelta``).
    Base-node shape details (stacked nodes are sliced to 2D before
    ``dense``; GGUF block scales; the VMEM budget) are the resolver's own
    business — its XLA composition handles every layout the old one
    handled."""
    from ..lora import FactoredDelta

    return (
        unified_routing_enabled()
        and isinstance(leaf.get("a"), FactoredDelta)
        and isinstance(leaf.get("b"), FactoredDelta)
    )


def xla_fused_qlora(
    x: jax.Array, qk: Dict[str, jax.Array], leaf: Dict[str, Any], lora_scale
) -> jax.Array:
    """The XLA composition — EXACTLY what ``nn.dense`` lowered before the
    unified kernel existed: the shared dequant-matmul contract (which itself
    resolves the opt-in int8 Pallas kernel or the XLA operand fusion) plus
    the one-fused-operand LoRA delta. Byte-for-byte the round-14 program, so
    promoting the unified resolution can never regress a non-kernel
    platform (the CI ledger gate holds this line)."""
    from ..lora import fused_lora_delta
    from .quant_mm import dequant_matmul

    return dequant_matmul(x, qk) + fused_lora_delta(x, leaf, lora_scale)


def _qlora_kernel(
    x_ref, q_ref, s_ref, aw_ref, au_ref, av_ref, bw_ref, bu_ref, bv_ref,
    ca_ref, cb_ref, o_ref, *, lora_scale: float,
):
    """One (token, dout) tile of base-dequant + perturbed-LoRA chain, fully
    in VMEM.

    The [din, bn] s8 base tile is dequantized in registers (convert +
    per-channel scale) and fed to the MXU; the LoRA factors are thin
    ([d, r]) — the din-side ones loaded whole, the dout-side ones (b.w,
    b.v) arriving dout-tiled like the base; every intermediate ([bt, r_l] /
    [bt, r_e]) lives and dies in VMEM. The thin ``xa`` chain is recomputed
    per dout tile — r_l·din extra FLOPs against din·bn·bt saved residency,
    a ~r/bn ratio. f32 accumulation throughout; the chain dots run at
    precision=HIGHEST like ops/fused_lora.py (the parity pin is against the
    materialized path's full-precision ε)."""
    f32 = jnp.float32
    x = x_ref[...].astype(f32)  # [bt, din]
    ca = ca_ref[0, 0]
    cb = cb_ref[0, 0]

    def dot(p, q, high=True):
        return jax.lax.dot_general(
            p, q, (((1,), (0,)), ((), ())), preferred_element_type=f32,
            precision=jax.lax.Precision.HIGHEST if high else None,
        )

    # base term: dequantize the s8 tile in registers, one MXU pass — the
    # dequantized tile never exists outside VMEM (ops/quant_mm contract)
    w = q_ref[...].astype(f32) * s_ref[...].astype(f32)  # [din, bn]
    y = dot(x, w, high=False)
    # x @ a_k = x@a + ca·(x@U_a)@V_aᵀ   → [bt, r_l]
    xa = dot(x, aw_ref[...].astype(f32))
    xa = xa + ca * dot(dot(x, au_ref[...].astype(f32)), av_ref[...].astype(f32).T)
    # (x@a_k) @ b_k = xa@b + cb·(xa@U_b)@V_bᵀ   → [bt, bn]
    d = dot(xa, bw_ref[...].astype(f32))
    d = d + cb * dot(dot(xa, bu_ref[...].astype(f32)), bv_ref[...].astype(f32).T)
    o_ref[...] = (y + d * lora_scale).astype(o_ref.dtype)


def _pallas_fused_qlora(
    x2, q8, scale, a, b, lora_scale, block_t: int, block_n: int, interpret: bool
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, din = x2.shape
    dout = q8.shape[-1]
    block_t = min(block_t, T)
    n_tblk = -(-T // block_t)
    T_pad = n_tblk * block_t
    if T_pad != T:
        x2 = jnp.pad(x2, ((0, T_pad - T), (0, 0)))
    block_n = min(block_n, dout)
    n_nblk = -(-dout // block_n)
    N_pad = n_nblk * block_n
    bw, bv = b.w, b.v
    if N_pad != dout:
        # padded output channels compute garbage columns sliced away below;
        # b.v pads ROWS (its dout axis) — they only feed padded columns
        q8 = jnp.pad(q8, ((0, 0), (0, N_pad - dout)))
        scale = jnp.pad(scale, ((0, 0), (0, N_pad - dout)))
        bw = jnp.pad(bw, ((0, 0), (0, N_pad - dout)))
        bv = jnp.pad(bv, ((0, N_pad - dout), (0, 0)))

    # din-side operands use constant index maps over the dout grid axis:
    # Pallas keeps revisiting the same VMEM-resident tile, so each s8 base
    # tile crosses HBM once per token sweep, not once per (t, n) step
    whole = lambda arr: pl.BlockSpec(arr.shape, lambda t, n: (0,) * arr.ndim)
    scalar = pl.BlockSpec((1, 1), lambda t, n: (0, 0), memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_qlora_kernel, lora_scale=float(lora_scale)),
        out_shape=jax.ShapeDtypeStruct((T_pad, N_pad), x2.dtype),
        grid=(n_tblk, n_nblk),
        in_specs=[
            pl.BlockSpec((block_t, din), lambda t, n: (t, 0)),
            pl.BlockSpec((din, block_n), lambda t, n: (0, n)),
            pl.BlockSpec((1, block_n), lambda t, n: (0, n)),
            whole(a.w), whole(a.u), whole(a.v),
            pl.BlockSpec((bw.shape[0], block_n), lambda t, n: (0, n)),
            whole(b.u),
            pl.BlockSpec((block_n, bv.shape[1]), lambda t, n: (n, 0)),
            scalar, scalar,
        ],
        out_specs=pl.BlockSpec((block_t, block_n), lambda t, n: (t, n)),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="fused_qlora",
    )(
        x2, q8, scale,
        a.w, a.u, a.v, bw, b.u, bv,
        a.c.astype(jnp.float32).reshape(1, 1),
        b.c.astype(jnp.float32).reshape(1, 1),
    )
    return out[:T, :dout]


def fused_qlora_dense(
    x: jax.Array,
    qk: Dict[str, jax.Array],   # {"q8": s8 [din, dout], "scale": f32 [1, dout]}
    leaf: Dict[str, Any],       # {"a": FactoredDelta, "b": FactoredDelta}
    lora_scale: float,
    *,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    block_t: int = 256,
    block_n: int = 256,
) -> jax.Array:
    """``x @ dequant(qk) + lora_scale·(x@a_k)@b_k`` for one member's factored
    2D adapter leaf over an int8 base node — the unified resolution
    ``nn.dense`` applies when both are present.

    ``x`` may have any leading shape (``[..., din]``). The Pallas kernel
    handles 2D per-output-channel nodes with both factors factored whose
    tiles fit VMEM (:func:`_fit_blocks`); every other layout (GGUF block
    scales, mixed leaf types) and every non-kernel platform takes
    :func:`xla_fused_qlora` — the byte-identical round-14 composition.
    ``use_pallas=None`` auto-selects via :func:`use_fused_qlora_pallas`.
    The selection is final: a selected kernel that fails to trace or
    compile raises. ``interpret`` is for tests only.

    Parity boundary: at an f32 serving dtype kernel and XLA composition
    agree to ~1e-5. At bf16 the difference is bf16-ROUNDING class (measured
    ~0.5% rel): the XLA form rounds the perturbed operands ``a_k``/``b_k``
    to the serving dtype before its dots (``lora.effective_factor``'s
    contract), while the kernel keeps the whole chain in f32 — the kernel
    is the more precise side, the same boundary the round-12
    fused-vs-materialized θ parity documents for bf16 configs."""
    from ..lora import FactoredDelta

    if use_pallas is None:
        use_pallas = use_fused_qlora_pallas()
    a, b = leaf["a"], leaf["b"]
    q8, scale = qk["q8"], qk["scale"]
    fitted = None
    if (
        isinstance(a, FactoredDelta) and isinstance(b, FactoredDelta)
        and a.w.ndim == 2 and b.w.ndim == 2
        and q8.ndim == 2 and scale.ndim == 2 and scale.shape[0] == 1
    ):
        fitted = _fit_blocks(q8, a, b, block_t, block_n)
    if fitted is None or not (use_pallas or interpret):
        return xla_fused_qlora(x, qk, leaf, lora_scale)
    lead = x.shape[:-1]
    out = _pallas_fused_qlora(
        x.reshape(-1, x.shape[-1]), q8, scale, a, b, lora_scale, *fitted, interpret
    )
    return out.reshape(*lead, out.shape[-1])


def conv_kernel_q8_matmul(
    x: jax.Array,
    qk: Dict[str, jax.Array],
    stride: int,
    padding: str,
    groups: int,
) -> Optional[jax.Array]:
    """Route a matmul-equivalent ``kernel_q8`` conv through the SAME dequant
    contract as ``dense`` (ops/quant_mm.dequant_matmul) — None when the conv
    is not matmul-equivalent (the caller keeps dequant-then-conv).

    Two exact rewrites, both value-identical to the conv up to float
    summation order:

    - **1×1 stride-1** (glumb_conv's inverted/point projections, DC-AE
      shortcut convs): the conv IS a per-pixel matmul — contract the channel
      axis directly, no data movement at all.
    - **p×p stride-p on a p-divisible grid** (CLIP/Sana patch_embed): the
      patches don't overlap, so im2col is a pure reshape/transpose to a
      per-channel-flattened ``[B, H/p, W/p, p·p·cin]`` layout against the
      kernel reshaped ``[p·p·cin, cout]``. HWIO kernel order == the patch's
      (h, w, c) raveling, and the per-OUTPUT-channel scale is untouched by
      flattening the reduction axes.

    Grouped/depthwise convs, overlapping windows, explicit padding configs,
    and GGUF-style block scales all return None. Routing off
    (``HSES_FUSED_QLORA=off``) returns None everywhere — the round-14
    lowering."""
    if not unified_routing_enabled() or groups != 1:
        return None
    if not isinstance(padding, str) or padding.upper() not in ("SAME", "VALID"):
        return None
    q8, scale = qk["q8"], qk["scale"]
    if q8.ndim != 4 or scale.shape[:-1] != (1, 1, 1):
        return None
    kh, kw, cin, cout = q8.shape
    flat_scale = scale.reshape(1, cout)
    from .quant_mm import dequant_matmul

    if kh == 1 and kw == 1 and stride == 1:
        return dequant_matmul(x, {"q8": q8.reshape(cin, cout), "scale": flat_scale})
    B, H, W, C = x.shape
    if kh == kw == stride and H % kh == 0 and W % kw == 0 and C == cin:
        p = kh
        xp = x.reshape(B, H // p, p, W // p, p, C)
        xp = xp.transpose(0, 1, 3, 2, 4, 5).reshape(B, H // p, W // p, p * p * C)
        return dequant_matmul(
            xp, {"q8": q8.reshape(p * p * cin, cout), "scale": flat_scale}
        )
    return None

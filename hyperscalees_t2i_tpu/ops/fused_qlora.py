"""One kernel a dense site: fused int8-dequant + member-LoRA matmul.

:func:`fused_qlora_dense` computes, for member ``k``'s factored 2D adapter
leaf over an int8 base node::

    y = x @ (q8 · scale)  +  lora_scale · (x @ a_k) @ b_k
        a_k = a + c_a·U_a V_aᵀ,   b_k = b + c_b·U_b V_bᵀ

The Pallas kernel tiles tokens (outer) and output channels (inner, in
order): grid (token blocks, dout tiles). A grid step dequantizes one
``[din, bn]`` s8 base tile in VMEM (convert + per-output-channel scale,
rounded to the activations' dtype — the s8 bytes are the only base bytes that
cross HBM) and gives it to the MXU with the token block as it arrives: bf16
operands, f32 accumulation, the same values an f32 dot at default precision
produced from the same tile without the f32 copy of ``x``. The
perturbed-LoRA chain is f32 at ``Precision.HIGHEST``; its din side
(``x @ a_k``, and ``·U_b``) does not depend on the output tile and is
computed once a token block into a VMEM scratch, its dout side is one thin
dot a tile (:func:`_qlora_kernel`).

What crosses HBM how often: ``x`` and the din-side factors once a token
block, the s8 base once a token block too — every dout tile of it, in order.
So the base's bytes are paid per token block, and a call is bound by them
when its blocks hold few rows.

The member axis (PR 28). ``pop_eval`` reaches the kernel through ``vmap``
inside ``lax.map(batch_size=member_batch)``. Pallas's default rule puts a
batch axis in front of the grid: each of ``M`` members sweeps the dout tiles
over its own rows, and the base crosses HBM ``M`` times a call — at a decode
step of the ``lm_ar`` cell (8 members x 8 rows against a 141.6 MB base) nine
tenths of the call. The kernel call therefore has its own rule
(:func:`_members_into_rows`, a ``custom_vmap`` like
``ops/grouped.py::_flatten_members``): with the base and the unperturbed
``w`` shared and ``x``, ``u``, ``v``, ``c`` per member, ``x`` is viewed
``[M·T, din]`` and a token block is ``g`` whole members
(:func:`_members_per_block`: where a member has fewer rows than the MXU's
128, the largest divisor of ``M`` whose ``g·T`` rows fit the fitted block),
grid (``M/g`` member groups, dout tiles). The base tile follows only the
dout index, so it is fetched, dequantized and multiplied once a group — with
``g = M`` once a call. The base dot is the one-member kernel's, row for row;
in the chain the ``g`` members' thin operands lie side by side (``[din, r_l +
g·r_e]``, one 128-lane operand like one member's) and a row keeps its own
member's columns by a mask (:func:`_qlora_group_kernel`). Around the call XLA
still meets ``[M, T, ·]`` arrays (two optimization barriers): left to carry
the reshapes into its own fusions it sums an RMS norm between two sites in
another order and the sampled tokens of the ``lm_ar`` cell change. ``g = 1``
— 128 rows a member or more, as at Sana's 1024 and every prefill — or a base
per member is the default batching of the one-member call, the program it
always was. Nothing selects this: ``g`` follows from ``M``, ``T`` and the
fitted block, and the call says it in its metadata (``members_per_block``,
which ``obs/xla_cost`` writes into ``programs.jsonl``).

What the chip says about it (PERF.md §5–§6; TPU v5e). The kernel is bound by
MXU *passes*, not by HBM and not by the FLOPs it needs: a thin dot of 8 or
12 columns occupies the 128-wide MXU like a 128-column one, and ``HIGHEST``
on f32 operands is six bf16 passes. Until PR 26 both thin chains ran in
every (token, dout) step — six times the base dot's passes, a tenth of the
roofline; the hoist and the single thin operand brought the call at Sana's
attention site from 961 to ≈ 240 µs. What is left of the gap to the
roofline is the same six passes on the two thin dots that remain, and XLA's
own composition (:func:`xla_fused_qlora`, which rounds ``a_k``/``b_k`` to
bf16 and spends one pass) is still the faster program at every site the
cells call — PERF.md §6 has the numbers and §7 what follows from them.

Promotion discipline (this kernel is the *default* on TPU, not an opt-in):

- gate: :func:`use_fused_qlora_pallas` — ON on a TPU backend
  (ops/pallas_gate.py), layer by layer wherever :func:`_fit_blocks` finds
  tiles that fit VMEM; ``HSES_FUSED_QLORA_PALLAS=0`` opts out. Nothing is
  probed and nothing is caught: a layer the gate selected and Mosaic refuses
  fails the enclosing compile.
- elsewhere: :func:`xla_fused_qlora`, the sum of the two lowerings a site
  takes when it has only an int8 base or only a member's factored adapter
  (``ops/quant.dequant_matmul`` + ``lora.factored_lora_delta``): the program
  of every platform without Mosaic, and of the layers ``_fit_blocks``
  declines on a TPU.
- parity: interpret-mode tests in tier-1 (tests/test_fused_qlora.py) on the
  CPU; on the chip ``tools/kernel_check.py`` compiles, runs and compares the
  kernel at the flagship call shapes (``chip_smoke.py`` runs that check).

Conv/patch-embed coverage: :func:`conv_kernel_q8_matmul` routes the
matmul-equivalent ``kernel_q8`` convs through the same dequant-matmul as
``dense`` (ops/quant.dequant_matmul): 1×1 stride-1 convs (glumb_conv's
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

from ..lora import FactoredDelta, factored_lora_delta
from .pallas_gate import backend_is_tpu, env_requested
from .quant import dequant_matmul

KERNEL_ENV = "HSES_FUSED_QLORA_PALLAS"

# Per-layer VMEM working-set ceiling for electing the Pallas path. The grid
# tiles tokens AND output channels, but ``din`` is the contraction axis and
# stays whole, so block sizes ADAPT DOWNWARD (:func:`_fit_blocks` halves
# block_t then block_n to the 128-lane floor) before a wide-input layer is
# declined at trace time and takes the XLA composition.
#
# The kernel asks Mosaic for a 48 MiB scoped limit (a v5e has 128 MiB of
# VMEM; the default limit is 16) and elects blocks whose estimate
# (:func:`_kernel_vmem_bytes`) is at most two thirds of it. The estimate is
# from above: the smallest limit the v5e compiler accepted (libtpu 0.0.34,
# compile-only topology, bisected, PR 26) was 0.3-0.8 of it — din 2240:
# (1024, 256) blocks 10.0 MiB against 28.6; din 4096: (512, 256) 16.1
# against 30.1; din 1024: (1024, 256) 11.2 against 15.1.
VMEM_LIMIT_BYTES = 48 * 2**20
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES * 2 // 3
MIN_BLOCK = 128  # lane-aligned floor for both tile axes
# The blocks asked for, before the budget halves them. Measured on the chip
# (PR 26, PERF.md §6): a token block of 1024 rows against 512 is 2-4 % of the
# call at every site (the s8 base crosses HBM once per token block); 512
# output channels a tile lose at Sana's 2240 (five tiles pad it to 2560) and
# at din 4096.
BLOCK_T, BLOCK_N = 1024, 256
_LANES, _SUBLANES = 128, 8


def _vmem_tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of one [rows, cols] block: the last dim fills whole
    128-lane registers and the second-to-last whole sublane groups (8 rows
    of 32-bit, 16 of 16-bit, 32 of 8-bit), so a thin [din, r] factor costs
    as much as a [din, 128] one."""
    sub = _SUBLANES * max(1, 4 // itemsize)
    return -(-rows // sub) * sub * -(-cols // _LANES) * _LANES * itemsize


def _declared_blocks(din, a, b, block_t, block_n, x_dtype, g: int = 1):
    """What the kernel declares to Pallas, as ``(shape, dtype)``: the block
    of every operand in call order with the output's last — each one
    double-buffered by the pipeline — and the one VMEM scratch. Both
    :func:`_pallas_fused_qlora` and :func:`_kernel_vmem_bytes` read this.
    ``a``, ``b`` are one member's factors; with ``g`` > 1 members a token
    block (``block_t`` = their rows together) the noise factors of the ``g``
    lie side by side: ``r`` = r_l + g·r_e columns, inside one 128-lane
    operand like one member's r_l + r_e."""
    f32, r_l, r_e = jnp.float32, a.w.shape[-1], a.u.shape[-1]
    r = r_l + g * r_e
    blocks = [
        ((block_t, din), x_dtype),       # x: one token block, din whole
        ((din, block_n), jnp.int8),      # s8 base tile
        ((1, block_n), f32),             # per-channel scale
        ((din, r), f32),                 # [a.w | a.u]
        # a.v [r_l, r_e]; the g members' a.vᵀ stacked [g·r_e, r_l]
        (a.v.shape if g == 1 else (g * r_e, r_l), a.v.dtype),
        ((r_l, g * r_e), b.u.dtype),     # b.u, the g members' side by side
        ((r, block_n), f32),             # [b.w ; b.vᵀ], dout-tiled
        ((block_t, block_n), x_dtype),   # out
    ]
    return blocks, ((block_t, r), f32)   # scratch z = [xa | cb·(xa@b.u)]


def _kernel_vmem_bytes(q8, a, b, block_t: int, block_n: int, x_dtype) -> int:
    """Working-set estimate for one grid step: the declared blocks twice
    (the pipeline's two buffers), the scratch, and the values the body
    holds — the f32 view of ``x`` the chain's HIGHEST dot reads at a token
    block's first dout step, the base tile dequantized in f32 and rounded to
    ``x``'s dtype, the
    thin ``[bt, r]`` chain result and the two ``[bt, bn]`` f32 partial
    results. An estimate from above (:data:`VMEM_LIMIT_BYTES`)."""
    din, dout = q8.shape
    bn = min(block_n, dout)
    tile = lambda shape, dtype: _vmem_tile_bytes(*shape, jnp.dtype(dtype).itemsize)
    blocks, scratch = _declared_blocks(din, a, b, block_t, bn, x_dtype)
    values = (
        tile((block_t, din), jnp.float32)
        + tile((din, bn), jnp.float32) + tile((din, bn), x_dtype)
        + tile(scratch[0], jnp.float32)
        + 2 * tile((block_t, bn), jnp.float32)
    )
    return 2 * sum(tile(*blk) for blk in blocks) + tile(*scratch) + values


def _fit_blocks(q8, a, b, block_t: int, block_n: int, x_dtype) -> Optional[tuple]:
    """Largest (block_t, block_n) at or under the requested sizes whose
    working set fits :data:`VMEM_BUDGET_BYTES` — halving block_t first (the
    cheap axis: more token sweeps, same base-tile residency) then block_n,
    both floored at :data:`MIN_BLOCK`. None = the layer cannot fit even at
    the floor (the kernel is not selected for it; XLA composition)."""
    while _kernel_vmem_bytes(q8, a, b, block_t, block_n, x_dtype) > VMEM_BUDGET_BYTES:
        if block_t > MIN_BLOCK:
            block_t //= 2
        elif block_n > MIN_BLOCK:
            block_n //= 2
        else:
            return None
    return block_t, block_n


def use_fused_qlora_pallas() -> bool:
    """The kernel's gate — ON BY DEFAULT on a TPU backend;
    ``HSES_FUSED_QLORA_PALLAS=0`` opts out."""
    return env_requested(KERNEL_ENV) is not False and backend_is_tpu()


def fused_qlora_applies(leaf: Dict[str, Any]) -> bool:
    """True when the lora leaf at an int8 dense site resolves through
    :func:`fused_qlora_dense`: it is a training member's, both factors
    ``lora.FactoredDelta``. Base-node shape details (stacked nodes are sliced
    to 2D before ``dense``; GGUF block scales; the VMEM budget) are the
    resolver's own business — its XLA composition handles every layout."""
    return isinstance(leaf.get("a"), FactoredDelta) and isinstance(leaf.get("b"), FactoredDelta)


def xla_fused_qlora(
    x: jax.Array, qk: Dict[str, jax.Array], leaf: Dict[str, Any], lora_scale
) -> jax.Array:
    """The XLA composition: the dequantized base dot (XLA's operand fusion
    keeps the dequant in the dot's read) plus the member's two-dot LoRA
    delta — the kernel's reference in tests and ``tools/kernel_check``."""
    return dequant_matmul(x, qk) + factored_lora_delta(x, leaf, lora_scale)


def _dot(p, q, precision=jax.lax.Precision.HIGHEST):
    return jax.lax.dot_general(
        p, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=precision,
    )


def _base_plus_thin(x_ref, q_ref, s_ref, b_ref, z_ref, o_ref, lora_scale: float):
    """What every (token block, dout tile) step does: the base dot plus
    ``z @ [b.w ; b.vᵀ]`` (= xa@b_k), the one thin dot a tile.

    The base term is ``ops/quant.dequant_matmul``'s: the tile is dequantized in
    VMEM (convert + per-channel scale in f32) and handed to the MXU in x's
    dtype. For bf16 activations that is bit for bit what an f32 dot at
    default precision did with the same tile — the MXU rounds f32 operands
    to bf16 (measured on the v5e, PR 26: 0 of 2-8 M outputs differ) —
    without the f32 copy of x. 16-bit operands take one MXU pass whatever
    the process's default precision asks of f32 dots; an f32 caller's dot
    follows that default, as it always did. A row's result does not depend on
    which other rows share its block, so a block of several members' rows
    (:func:`_qlora_group_kernel`) leaves it as it was."""
    f32 = jnp.float32
    x = x_ref[...]
    w = (q_ref[...].astype(f32) * s_ref[...].astype(f32)).astype(x.dtype)
    y = _dot(x, w, None if x.dtype == f32 else jax.lax.Precision.DEFAULT)
    d = _dot(z_ref[...], b_ref[...])
    o_ref[...] = (y + d * lora_scale).astype(o_ref.dtype)


def _qlora_kernel(
    x_ref, q_ref, s_ref, a_ref, av_ref, bu_ref, b_ref, ca_ref, cb_ref,
    o_ref, z_ref, *, lora_scale: float, r_l: int,
):
    """One (token block, dout tile) step of one member's rows: the base dot
    plus one thin dot.

    The dout axis is the inner, sequential grid axis. Everything that does
    not depend on the dout tile is done once a token block, at its first
    dout step, and kept in the VMEM scratch ``z_ref`` ``[bt, r_l + r_e]``::

        [x@a.w | x@a.u] = x @ [a.w | a.u]            one thin dot, not two
        xa              = x@a.w + ca·(x@a.u)@a.vᵀ     = x @ a_k
        z               = [xa | cb·(xa@b.u)]

    Every dout step then adds ``z @ [b.w ; b.vᵀ]`` (= xa@b_k, K = r_l + r_e)
    to the base dot (:func:`_base_plus_thin`). The chain is f32 at
    ``Precision.HIGHEST`` throughout (the parity pin is against
    ``es.perturb_member``'s full-precision ε). The base dot takes ``x`` as it
    arrives and the s8 tile dequantized to ``x``'s dtype; f32 accumulation."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _():
        xc = _dot(x_ref[...].astype(f32), a_ref[...])  # [bt, r_l + r_e]
        xa = xc[:, :r_l] + ca_ref[0, 0] * _dot(xc[:, r_l:], av_ref[...].astype(f32).T)
        xb = cb_ref[0, 0] * _dot(xa, bu_ref[...].astype(f32))
        z_ref[...] = jnp.concatenate([xa, xb], axis=1)

    _base_plus_thin(x_ref, q_ref, s_ref, b_ref, z_ref, o_ref, lora_scale)


def _qlora_group_kernel(
    x_ref, q_ref, s_ref, a_ref, av_ref, bu_ref, b_ref, ca_ref, cb_ref,
    o_ref, z_ref, *, lora_scale: float, r_l: int, g: int, rows: int,
):
    """One (member group, dout tile) step: :func:`_qlora_kernel` for a token
    block that holds ``g`` whole members of ``rows`` rows each, so the base
    tile is fetched, dequantized and multiplied once for all of them.

    The members' noise factors lie side by side (``a_ref`` ``[din, r_l +
    g·r_e]``, ``av_ref`` the ``g`` ``a.vᵀ`` stacked, ``bu_ref`` ``[r_l,
    g·r_e]``, ``b_ref`` ``[r_l + g·r_e, bn]``; ``a.w``, ``b.w`` are every
    member's), one MXU-wide thin operand where a member alone had one, and a
    row keeps the ``r_e`` columns of its own member: the others are set to
    exact zeros before they meet a ``v``, so each row's chain is its member's
    ``x @ a_k`` and ``xa @ b_k`` term for term. ``ca_ref``/``cb_ref`` hold
    every group's ``g`` coefficients in SMEM; a row takes its member's."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    group, bt, wide = pl.program_id(0), x_ref.shape[0], bu_ref.shape[1]
    r_e = wide // g

    @pl.when(pl.program_id(1) == 0)
    def _():

        def of_member(k, shape, axis, step):  # index // step == k, without a division
            at = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
            return (at >= k * step) & (at < (k + 1) * step)

        own = functools.reduce(jnp.logical_or, (
            of_member(k, (bt, wide), 0, rows) & of_member(k, (bt, wide), 1, r_e)
            for k in range(g)
        ))
        per_row = lambda c_ref: sum(
            jnp.where(of_member(k, (bt, 1), 0, rows), c_ref[group, k], 0.0) for k in range(g)
        )
        xc = _dot(x_ref[...].astype(f32), a_ref[...])  # [bt, r_l + g·r_e]
        xu = jnp.where(own, xc[:, r_l:], 0.0)
        xa = xc[:, :r_l] + per_row(ca_ref) * _dot(xu, av_ref[...].astype(f32))
        xb = per_row(cb_ref) * jnp.where(own, _dot(xa, bu_ref[...].astype(f32)), 0.0)
        z_ref[...] = jnp.concatenate([xa, xb], axis=1)

    _base_plus_thin(x_ref, q_ref, s_ref, b_ref, z_ref, o_ref, lora_scale)


def _token_blocks(T: int, block_t: int) -> tuple:
    """(block_t, number of blocks) for ``T`` rows: as few blocks as
    ``block_t`` allows. One block takes the rows as they are (a block equal
    to the array needs no padding: 300 caption rows are one block of 300, not
    two of 256); several spread the rows evenly, rounded up to a whole bf16
    sublane tile (1352 rows are three blocks of 464, not six of 256)."""
    n = -(-T // block_t)
    return (T if n == 1 else -(-T // (n * 16)) * 16), n


def _members_per_block(M: int, T: int, block_t: int, r_l: int, r_e: int, itemsize: int) -> int:
    """How many of ``M`` members of ``T`` rows each share a token block.

    Few rows a member — under :data:`MIN_BLOCK`, the MXU's 128: such a
    member's dout step costs the MXU a full pass whatever its rows and lasts
    as long as the base tile's read (``din·bn`` bytes at 819 GB/s against
    ``2·T·din·bn`` FLOPs at 197 TFLOP/s: the read is the longer below 120
    rows) — take the largest divisor ``g`` of ``M`` whose ``g·T`` rows fit
    the fitted ``block_t`` and whose ``r_l + g·r_e`` thin columns fit one
    128-lane operand (so ``g`` members cost the MXU what one did). A block
    that is not the whole array has to be made of whole sublane tiles. From
    128 rows a member on the step is bound by its own product and sharing
    the tile saves grid steps only; in the VAR cell blocks of 2 x 512 rows
    cost 20 ms a step more in the ops around the call than the kernel saved
    (PERF.md §6, PR 28): 1 = a block is a member's own rows, the program it
    always was."""
    if T >= MIN_BLOCK:
        return 1
    sub = _SUBLANES * max(1, 4 // itemsize)
    for g in range(M, 1, -1):
        if (M % g == 0 and g * T <= block_t and r_l + g * r_e <= _LANES
                and (g == M or g * T % sub == 0)):
            return g
    return 1


def _side_by_side(a, b, g: int):
    """The thin operands of :func:`_qlora_group_kernel` from factors whose
    ``u``, ``v`` carry the member axis in front: per group of ``g``
    consecutive members ``[a.w | a.u…]`` ``[din, r_l + g·r_e]``, the ``a.vᵀ``
    stacked ``[g·r_e, r_l]``, the ``b.u`` ``[r_l, g·r_e]`` and ``[b.w ;
    b.vᵀ…]`` ``[r_l + g·r_e, dout]`` — f32 where the one-member kernel has
    f32, the store dtype where it has that."""
    f32, n = jnp.float32, a.u.shape[0] // g

    def cols(t):  # [M, m, r_e] -> [n, m, g·r_e]: a group's members side by side
        m, r_e = t.shape[1:]
        return t.reshape(n, g, m, r_e).transpose(0, 2, 1, 3).reshape(n, m, g * r_e)

    def rows(t):  # [M, m, r_e] -> [n, g·r_e, m]: a group's members' transposes stacked
        return t.transpose(0, 2, 1).reshape(n, g * t.shape[2], t.shape[1])

    shared = lambda w: jnp.broadcast_to(w.astype(f32), (n, *w.shape))
    a_cat = jnp.concatenate([shared(a.w), cols(a.u.astype(f32))], axis=2)
    b_cat = jnp.concatenate([shared(b.w), rows(b.v.astype(f32))], axis=1)
    return a_cat, rows(a.v), cols(b.u), b_cat


def _pallas_fused_qlora(
    x2, q8, scale, a, b, lora_scale, block_t: int, block_n: int, interpret: bool,
    g: int = 1,
):
    """The one Pallas call of a site. ``g`` = 1: ``x2`` ``[T, din]`` and the
    factors are one member's. ``g`` > 1: ``x2`` is ``[M·T, din]``, the rows of
    ``M`` members in order, ``a``/``b`` carry the member axis in front of
    ``u``, ``v``, ``c`` (``w`` is shared), and a token block is ``g`` whole
    members (:func:`_members_per_block`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    T, din = x2.shape
    dout = q8.shape[-1]
    block_n = min(block_n, dout)
    n_nblk = -(-dout // block_n)
    r_l = a.w.shape[-1]
    if g == 1:
        block_t, n_tblk = _token_blocks(T, block_t)
        # the thin factors, side by side: one MXU operand each side of the chain
        # ([din, r_l + r_e] and [r_l + r_e, dout], f32 — b.vᵀ lies lane-dense)
        a_cat = jnp.concatenate([a.w.astype(f32), a.u.astype(f32)], axis=1)
        b_cat = jnp.concatenate([b.w.astype(f32), b.v.astype(f32).T], axis=0)
        thin = (a_cat, a.v, b.u, b_cat)
        kernel = functools.partial(_qlora_kernel, lora_scale=float(lora_scale), r_l=r_l)
    else:
        M = a.c.shape[0]
        rows, n_tblk = T // M, M // g
        block_t = g * rows
        thin = _side_by_side(a, b, g)
        kernel = functools.partial(
            _qlora_group_kernel, lora_scale=float(lora_scale), r_l=r_l, g=g, rows=rows
        )
    c_shape = (n_tblk, g) if g > 1 else (1, 1)

    # Nothing is padded in HBM: where the rows or the output channels do not
    # fill the last block, Pallas reads it short (the rest of the VMEM block
    # holds whatever it held) and writes only what lies inside the output —
    # the garbage rows and columns feed nothing but themselves.
    #
    # The s8 tile, its scale and [b.w ; b.vᵀ] follow the dout step; x and the
    # din-side factors keep their index over it, so Pallas leaves them where
    # they are, and the s8 base crosses HBM once per token block. A member
    # group's thin operands carry the group axis in front (squeezed away in
    # the block) and follow the group as x does.
    by_t, by_n, whole = (lambda t, n: (t, 0)), (lambda t, n: (0, n)), (lambda t, n: (0, 0))
    blocks, scratch = _declared_blocks(din, a, b, block_t, block_n, x2.dtype, g)
    if g == 1:
        index_maps = [by_t, by_n, by_n, whole, whole, whole, by_n, lambda t, n: (t, n)]
    else:
        of_group, of_group_by_n = (lambda t, n: (t, 0, 0)), (lambda t, n: (t, 0, n))
        index_maps = [by_t, by_n, by_n, of_group, of_group, of_group, of_group_by_n,
                      lambda t, n: (t, n)]
        blocks = [((None, *shape) if 3 <= i <= 6 else shape, dt)
                  for i, (shape, dt) in enumerate(blocks)]
    *in_specs, out_spec = (
        pl.BlockSpec(shape, imap) for (shape, _), imap in zip(blocks, index_maps)
    )
    scalar = pl.BlockSpec(c_shape, whole, memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((T, dout), x2.dtype),
        grid=(n_tblk, n_nblk),
        in_specs=[*in_specs, scalar, scalar],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM(*scratch)],
        compiler_params=pltpu.CompilerParams(
            # the scratch is written at a token block's first dout step and
            # read at the others: the dout axis runs in order
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="fused_qlora",
        # read back from the lowered step by obs/xla_cost.stablehlo_stats:
        # programs.jsonl says for every site how many members share a block
        metadata={"members_per_block": str(g)},
    )(
        x2, q8, scale, *thin,
        a.c.astype(f32).reshape(c_shape),
        b.c.astype(f32).reshape(c_shape),
    )
    return out


def _members_into_rows(lora_scale, block_t: int, block_n: int, interpret: bool):
    """The kernel call of one site with its own rule for ``vmap`` (the
    repo's precedent: ``ops/grouped.py::_flatten_members``). Pallas's default
    puts a batch axis in front of the grid: every member runs the whole dout
    sweep over its few rows and pulls the whole s8 base across HBM again. The
    rule sees what ``pop_eval`` hands it — ``x`` and the noise slices (``u``,
    ``v``, ``c`` of both factors) per member, ``q8``, ``scale`` and the
    unperturbed ``w`` shared — and makes the member axis rows of ONE call
    whose token blocks hold :func:`_members_per_block` whole members. Where
    that is 1, or the base or a ``w`` is per member, it is the default
    batching of the one-member call: the parent's program."""
    def one_member(x2, q8, scale, a, b):
        return _pallas_fused_qlora(x2, q8, scale, a, b, lora_scale, block_t, block_n, interpret)

    call = jax.custom_batching.custom_vmap(one_member)

    @call.def_vmap
    def rule(M, in_batched, x2, q8, scale, a, b):
        x_b, q_b, s_b, a_b, b_b = in_batched
        T, g = x2.shape[-2], 1
        if not (q_b or s_b or a_b.w or b_b.w):
            g = _members_per_block(
                M, T, block_t, a.w.shape[-1], a.u.shape[-1], x2.dtype.itemsize
            )
        if g == 1:
            in_axes = jax.tree_util.tree_map(lambda batched: 0 if batched else None, in_batched)
            return jax.vmap(one_member, in_axes=in_axes)(x2, q8, scale, a, b), True
        # what the members share of their own (activations every member
        # reads, an antithetic pair's one (u, v)) gets the member axis too
        per_member = lambda t, batched: t if batched else jnp.broadcast_to(t, (M, *t.shape))
        x2 = per_member(x2, x_b)
        a, b = (
            FactoredDelta(f.w, *map(per_member, f[1:], f_b[1:]))
            for f, f_b in ((a, a_b), (b, b_b))
        )
        # XLA meets the shapes the default batching showed it, [M, T, ·] on
        # both sides: without the barriers it carries the two reshapes into the
        # neighbouring fusions (an RMS norm between two sites becomes a 2D
        # reduce) and their rounding is no longer the parent program's
        x2 = jax.lax.optimization_barrier(x2)
        out = _pallas_fused_qlora(
            x2.reshape(M * T, -1), q8, scale, a, b, lora_scale, block_t, block_n, interpret, g
        )
        return jax.lax.optimization_barrier(out.reshape(M, T, -1)), True

    return call


def fused_qlora_dense(
    x: jax.Array,
    qk: Dict[str, jax.Array],   # {"q8": s8 [din, dout], "scale": f32 [1, dout]}
    leaf: Dict[str, Any],       # {"a": FactoredDelta, "b": FactoredDelta}
    lora_scale: float,
    *,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    block_t: int = BLOCK_T,
    block_n: int = BLOCK_N,
) -> jax.Array:
    """``x @ dequant(qk) + lora_scale·(x@a_k)@b_k`` for one member's factored
    2D adapter leaf over an int8 base node — the unified resolution
    ``nn.dense`` applies when both are present.

    ``x`` may have any leading shape (``[..., din]``). The Pallas kernel
    handles 2D per-output-channel nodes with both factors factored whose
    tiles fit VMEM (:func:`_fit_blocks`); every other layout (GGUF block
    scales, stacked nodes, mixed leaf types) and every non-kernel platform takes
    :func:`xla_fused_qlora`.
    ``use_pallas=None`` auto-selects via :func:`use_fused_qlora_pallas`.
    The selection is final: a selected kernel that fails to trace or
    compile raises. ``interpret`` is for tests only. Under ``vmap`` the
    kernel call follows its own rule (:func:`_members_into_rows`): members of
    few rows share a token block and the base is read once for them.

    Parity boundary: at an f32 serving dtype kernel and XLA composition
    agree to ~1e-5. At bf16 the difference is bf16-ROUNDING class (measured
    ~0.5% rel): the XLA form rounds the perturbed operands ``a_k``/``b_k``
    to the serving dtype before its dots (``lora.effective_factor``'s
    contract), while the kernel keeps the whole chain in f32 — the kernel
    is the more precise side."""
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
        fitted = _fit_blocks(q8, a, b, block_t, block_n, x.dtype)
    if fitted is None or not (use_pallas or interpret):
        return xla_fused_qlora(x, qk, leaf, lora_scale)
    lead = x.shape[:-1]
    call = _members_into_rows(lora_scale, *fitted, interpret)
    out = call(x.reshape(-1, x.shape[-1]), q8, scale, a, b)
    return out.reshape(*lead, out.shape[-1])


def conv_kernel_q8_matmul(
    x: jax.Array,
    qk: Dict[str, jax.Array],
    stride: int,
    padding: str,
    groups: int,
) -> Optional[jax.Array]:
    """Route a matmul-equivalent ``kernel_q8`` conv through the same
    dequant-matmul as ``dense`` (ops/quant.dequant_matmul) — None when the
    conv is not matmul-equivalent (the caller keeps dequant-then-conv).

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
    and GGUF-style block scales all return None."""
    if groups != 1:
        return None
    if not isinstance(padding, str) or padding.upper() not in ("SAME", "VALID"):
        return None
    q8, scale = qk["q8"], qk["scale"]
    if q8.ndim != 4 or scale.shape[:-1] != (1, 1, 1):
        return None
    kh, kw, cin, cout = q8.shape
    flat_scale = scale.reshape(1, cout)
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

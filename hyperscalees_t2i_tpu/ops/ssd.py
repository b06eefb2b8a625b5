"""The state-space recurrence of a Mamba-2 mixer (SSD, "state-space duality"):
``jax.numpy``, and a Pallas kernel for the decode step.

Per (sequence, head) a float32 state ``S [P, N]`` (``P`` the head's width,
``N`` the state size), ``S_0 = 0``, and per position the head's input ``x_t
[P]``, its step ``dt_t >= 0``, its decay rate ``A < 0`` (one number a head)
and the group's ``B_t, C_t [N]`` (one group: every head reads the same
``B``, ``C``)::

    S   <- exp(dt_t A) S + dt_t x_t (x) B_t
    y_t  = S C_t + D x_t

Three forms of the same sums, all float32:

- :func:`ssd_step_at`: one position a sequence on period ``k`` of a layer's
  period stack (``[n_periods, S, H, P, N]``, the carry of a decode scan over
  the periods), what a decode step runs. It is bound by reading and writing
  the state, so the output is taken from the *old* state (``y = exp(dt A)
  (S C) + dt (B . C) x + D x``): the update and the reduction then read the
  same operand and depend on nothing of each other. Which code runs where:
  on a TPU, for a float32 stack whose ``N`` is a multiple of the 128-lane
  tile (the published 64 x 128 head), the Pallas kernel (``name="ssd_step"``)
  — the period index is a prefetched scalar that the block index maps read,
  a grid step holds one sequence's heads of period ``k`` in VMEM, takes
  ``S C`` and the update from one read of each tile and writes the new state
  into the buffer the stack came in (``input_output_aliases``), so period
  ``k``'s state crosses HBM once in and once out a call, no other period is
  touched, and under the member ``vmap`` (``pallas_call``'s own rule: a
  leading grid axis) nothing gathers or scatters the stack. Everywhere else
  — a CPU, the toy heads of tier-1, a state carried narrower than float32 —
  :func:`ssd_step` between a read of period ``k`` out of the stack and an
  in-place write back (under the member ``vmap`` a gather and a scatter).
  Both multiply and add in float32 — elementwise on the VPU; a dot would send
  the float32 state through the MXU at its default precision. No flag: the
  backend and the shapes select (``use_pallas`` is for tests and
  ``tools/kernel_check``), and the ``jax.numpy`` step stays the oracle the
  kernel is held to.
- :func:`chunk_ssd`: a whole (padded) sequence in chunks of ``chunk``
  positions, what the prefill runs: inside a chunk the recurrence is a
  masked ``[chunk, chunk]`` product (``C_t . B_s`` times the decay from ``s``
  to ``t``), and the state is touched once a chunk. Products at ``highest``.
- :func:`recurrent_ssd`: :func:`ssd_step` under ``lax.scan``, position by
  position (tests hold the chunked form to it).

A position with ``dt = 0`` leaves the state as it was, bit for bit in every
form (``exp(0) = 1``, nothing written): that is how right-padding behind a
prompt is made invisible to the state.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .pallas_gate import backend_is_tpu

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# One sequence's 64 heads of 64 x 128 are 2 MB: in and out, double-buffered, 8 MB of the kernel's VMEM.
STATE_VMEM_BYTES = 8 * 1024 * 1024
VMEM_LIMIT_BYTES = 16 * 1024 * 1024


def ssd_step(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array, D: jax.Array,
             state: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``x [..., H, P]``, ``dt [..., H]``, ``A, D [H]``, ``B, C [..., N]``,
    ``state [..., H, P, N]`` float32 → (``y [..., H, P]`` float32, the new
    state): the step in ``jax.numpy``, the oracle of the other forms."""
    x, dt, B, C = (t.astype(F32) for t in (x, dt, B, C))
    decay = jnp.exp(dt * A)                                            # [..., H]
    sc = (state * C[..., None, None, :]).sum(-1)                       # [..., H, P]: S C of the old state
    new = decay[..., None, None] * state + (dt[..., None] * x)[..., None] * B[..., None, None, :]
    return _output(x, dt, decay, B, C, D, sc), new


def _output(x, dt, decay, B, C, D, sc):
    """``y = exp(dt A) (S C) + dt (B . C) x + D x`` from ``S C`` of the old state."""
    bc = (B * C).sum(-1)[..., None, None]                              # [..., 1, 1]
    return decay[..., None] * sc + dt[..., None] * bc * x + D[:, None] * x


def use_ssd_pallas() -> bool:
    """The kernel's gate: on a TPU backend, and nothing else to set."""
    return backend_is_tpu()


def kernel_head_block(x: jax.Array, stack: jax.Array) -> Optional[int]:
    """The kernel's fit check: how many heads of one sequence a grid step
    holds, or None where the call is not the kernel's — a stack that is not
    float32 ``[n_periods, S, H, P, N]`` beside ``x [S, H, P]`` with ``N`` a
    multiple of the 128-lane tile and ``P`` of the 8-sublane one (the tier-1
    toys at 16 x 8, a bf16 state), or a head whose tiles alone pass the VMEM
    budget. A block holds all ``H`` heads or a multiple of 128 of them: the
    per-head decay reaches the kernel as a row with the heads on lanes."""
    if stack.dtype != F32 or stack.ndim != 5 or x.ndim != 3 or tuple(x.shape) != tuple(stack.shape[1:4]):
        return None
    H, P, N = stack.shape[-3:]
    if N % 128 or P % 8:
        return None
    # the state block goes in and comes out, each double-buffered: four copies
    blocks = [h for h in range(H, 0, -1)
              if H % h == 0 and (h == H or h % 128 == 0) and 4 * h * P * N * 4 <= STATE_VMEM_BYTES]
    return blocks[0] if blocks else None


def _step_kernel(k_ref, dtx_ref, decay_ref, b_ref, c_ref, s_ref, sc_ref, new_ref):
    """One sequence's block of heads in period ``k`` of the stack: ``dt x
    [hb, P]``, the decay as a row ``[1, hb]``, ``B`` and ``C`` as rows ``[1,
    N]``, the state ``[hb, P, N]``. A head's tile is read from VMEM once: ``S
    C`` (a sum over ``N``, the lane axis, so it comes out a column ``[P, 1]``)
    and the update are taken from that one value, in float32 on the VPU.
    ``dt x`` reaches the tile as columns (transposed here) and the heads' ``S
    C`` columns are gathered lane by lane and written back transposed. The
    period index ``k_ref`` is read by the block index maps alone."""
    del k_ref
    dtxT, decay, B, C = dtx_ref[0].T, decay_ref[0], b_ref[0], c_ref[0]  # [P, hb], [1, hb], [1, N], [1, N]
    P, hb = dtxT.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (P, hb), 1)
    scT = jnp.zeros((P, hb), F32)
    for h in range(hb):
        S = s_ref[0, 0, h]
        scT = jnp.where(lane == h, jnp.sum(S * C, axis=1, keepdims=True), scT)
        new_ref[0, 0, h] = decay[:, h:h + 1] * S + dtxT[:, h:h + 1] * B
    sc_ref[0] = scT.T


def _pallas_ssd_step(k, dtx, decay, B, C, stack, hb: int, interpret: bool):
    """``k`` int32 ``[1]``, ``dtx [S, H, P]``, ``decay [S, 1, H]``, ``B, C [S,
    1, N]`` float32, ``stack [n_periods, S, H, P, N]`` → (``S C`` of period
    ``k``'s old state ``[S, H, P]``, the stack with period ``k`` advanced, in
    the buffer the old one came in)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, S, H, P, N = stack.shape
    heads = pl.BlockSpec((1, hb, P), lambda s, h, k: (s, h, 0))
    tile = pl.BlockSpec((1, 1, hb, P, N), lambda s, h, k: (k[0], s, h, 0, 0))
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(S, H // hb),
        in_specs=[heads, pl.BlockSpec((1, 1, hb), lambda s, h, k: (s, 0, h)),
                  pl.BlockSpec((1, 1, N), lambda s, h, k: (s, 0, 0)),
                  pl.BlockSpec((1, 1, N), lambda s, h, k: (s, 0, 0)), tile],
        out_specs=(heads, tile))
    return pl.pallas_call(
        _step_kernel,
        grid_spec=grid,
        out_shape=(jax.ShapeDtypeStruct((S, H, P), F32), jax.ShapeDtypeStruct(stack.shape, F32)),
        # period k is updated in the stack's own buffer (operand 5: the prefetched index is operand 0)
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"),
                                             vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ssd_step",
        metadata={"heads_per_block": str(hb)},
    )(k, dtx, decay, B, C, stack)


def ssd_step_at(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array, D: jax.Array,
                stack: jax.Array, k: jax.Array, *, use_pallas: Optional[bool] = None,
                interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """:func:`ssd_step` on period ``k`` of a layer's period stack: ``x [S, H,
    P]``, ``dt [S, H]``, ``A, D [H]``, ``B, C [S, N]``, ``stack [n_periods, S,
    H, P, N]``, ``k`` an int32 scalar (the scan's period index) → (``y [S, H,
    P]`` float32, the stack with period ``k`` advanced and every other period
    as it was, in the stack's dtype).

    ``use_pallas=None`` selects by :func:`use_ssd_pallas` and the call's
    shapes (:func:`kernel_head_block`); every other call runs :func:`ssd_step`
    on the period read out of the stack (widened to float32 for the step,
    narrowed back) and writes it back. The selection is final: a selected
    kernel that Mosaic refuses raises at the enclosing compile. ``interpret``
    is for tests. Under ``vmap`` the call takes ``pallas_call``'s own rule, a
    leading grid axis: ``k`` stays one unbatched scalar."""
    if use_pallas is None:
        use_pallas = use_ssd_pallas()
    hb = kernel_head_block(x, stack) if use_pallas or interpret else None
    if hb is None:
        state = jax.lax.dynamic_index_in_dim(stack, k, keepdims=False)
        y, new = ssd_step(x, dt, A, B, C, D, state.astype(F32))
        return y, jax.lax.dynamic_update_index_in_dim(stack, new.astype(stack.dtype), k, axis=0)
    x, dt, B, C = (t.astype(F32) for t in (x, dt, B, C))
    decay = jnp.exp(dt * A)
    sc, new = _pallas_ssd_step(jnp.reshape(k, (1,)).astype(jnp.int32), dt[..., None] * x, decay[:, None, :],
                               B[:, None, :], C[:, None, :], stack, hb, interpret)
    return _output(x, dt, decay, B, C, D, sc), new


def recurrent_ssd(x, dt, A, B, C, D, state):
    """``x [Bt, T, H, P]``, ``dt [Bt, T, H]``, ``B, C [Bt, T, N]``, ``state
    [Bt, H, P, N]`` → (``y [Bt, T, H, P]``, final state)."""
    def one(s, xs):
        y, s = ssd_step(xs[0], xs[1], A, xs[2], xs[3], D, s)
        return s, y

    state, y = jax.lax.scan(one, state.astype(F32), tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1), state


def chunk_ssd(x, dt, A, B, C, D, state, chunk: int = 256):
    """The recurrence over ``T`` positions in chunks (shapes as
    :func:`recurrent_ssd`; ``T`` is padded up to a multiple of the chunk with
    positions of ``dt = 0``). With ``cum`` the running sum of ``dt A`` inside
    a chunk, for ``t`` of the chunk::

        y_t = sum_{s <= t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s  +  exp(cum_t) S_in C_t  +  D x_t
        S_out = exp(cum_last) S_in + sum_s exp(cum_last - cum_s) dt_s x_s (x) B_s

    Every exponent is at most 0: ``dt A <= 0``."""
    Bt, T, H, P = x.shape
    L = min(chunk, T)
    pad = (-T) % L
    if pad:
        x, dt, B, C = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)) for a in (x, dt, B, C))
    n = (T + pad) // L
    split = lambda a: jnp.moveaxis(a.astype(F32).reshape((Bt, n, L) + a.shape[2:]), 1, 0)  # [n, Bt, L, ...]
    mm = lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    lower = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None]                             # [1, t, s, 1]

    def one(S, xs):
        x, dt, B, C = xs                                                # [Bt, L, H, P], [Bt, L, H], [Bt, L, N] x 2
        cum = jnp.cumsum(dt * A, axis=1)                                # [Bt, L, H]
        seg = jnp.where(lower, cum[:, :, None, :] - cum[:, None, :, :], 0.0)
        w = jnp.where(lower, jnp.exp(seg), 0.0) * mm("btn,bsn->bts", C, B)[..., None]   # [Bt, t, s, H]
        xdt = x * dt[..., None]
        y = mm("btsh,bshp->bthp", w, xdt) + jnp.exp(cum)[..., None] * mm("bhpn,btn->bthp", S, C)
        last = cum[:, -1]                                               # [Bt, H]
        S = jnp.exp(last)[..., None, None] * S \
            + mm("bshp,bsn->bhpn", xdt * jnp.exp(last[:, None] - cum)[..., None], B)
        return S, y + D[:, None] * x

    state, y = jax.lax.scan(one, state.astype(F32), tuple(map(split, (x, dt, B, C))))
    return jnp.moveaxis(y, 0, 1).reshape(Bt, n * L, H, P)[:, :T], state

"""The gated delta rule of a Gated DeltaNet layer: ``jax.numpy``, and a
Pallas kernel for the decode step.

Per (sequence, value head) a float32 state ``S [dk, dv]``, ``S_0 = 0``, and per
position ``q_t, k_t [dk]`` (L2-normalised by the caller, ``q`` scaled),
``v_t [dv]``, a decay ``alpha_t = exp(g_t)`` in (0, 1] and a write strength
``beta_t`` in [0, 1]::

    S   <- alpha_t S
    D_t  = beta_t (v_t - k_t^T S)
    S   <- S + k_t (x) D_t
    o_t  = q_t^T S

Three forms of the same sums:

- :func:`gated_delta_step`: one position a sequence, what a decode step runs.
  It is bound by reading and writing the state, so both reductions over the
  old state (``k^T S`` and ``q^T S``) are taken in one pass and the output is
  finished from them (``o = alpha q^T S + (q . k) D``). Which code runs where:
  on a TPU, for a float32 state whose ``dk`` and ``dv`` are multiples of the
  128-lane tile (the published 128 x 128), the Pallas kernel
  (``name="gated_delta_step"``) — a grid step holds one sequence's heads in
  VMEM, takes the reductions and the update from one read of each tile and
  writes the new state into the buffer the old one came in
  (``input_output_aliases``), so the state crosses HBM once in and once out a
  call and a decode scan's carry is updated in place. Everywhere else — a
  CPU, the toy heads of tier-1, a state carried narrower than float32 —
  :func:`xla_gated_delta_step`, the same sums in ``jax.numpy``: XLA does not
  fuse the reduce over the state with its elementwise update, so there the
  state is read twice and written once. Both multiply and add in float32 —
  elementwise on the VPU; a dot would send the float32 state through the MXU
  at its default precision. No flag: the backend and the shapes select
  (``use_pallas`` is for tests and ``tools/kernel_check``), and the
  ``jax.numpy`` step stays the oracle the kernel is held to.
- :func:`chunk_gated_delta_rule`: a whole (padded) sequence in chunks of
  ``chunk`` positions, what the prefill runs: inside a chunk the WY / UT
  transform turns the recurrence into products of ``[chunk, chunk]`` and
  ``[chunk, d]`` matrices, and the state is touched once a chunk.
- :func:`recurrent_gated_delta_rule`: :func:`xla_gated_delta_step` under
  ``lax.scan``, position by position (tests hold the other two to it).

A position with ``beta = 0`` and ``g = 0`` leaves the state as it was, bit for
bit in either step: that is how right-padding behind a prompt is made
invisible to a recurrent state.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .pallas_gate import backend_is_tpu

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# One sequence's 32 heads of 128 x 128 are 2 MB: in and out, double-buffered, 8 MB of the kernel's VMEM.
STATE_VMEM_BYTES = 8 * 1024 * 1024
VMEM_LIMIT_BYTES = 16 * 1024 * 1024


def xla_gated_delta_step(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
                         state: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The step in ``jax.numpy`` (shapes as :func:`gated_delta_step`): the
    oracle of the other forms and what a CPU runs."""
    q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
    alpha = jnp.exp(g.astype(F32))[..., None]
    qk = jnp.stack([q, k], axis=-2)                                    # [..., 2, dk]
    both = (qk[..., :, :, None] * state[..., None, :, :]).sum(-2)      # [..., 2, dv]: q^T S and k^T S, one pass
    delta = beta.astype(F32)[..., None] * (v - alpha * both[..., 1, :])
    new = alpha[..., None] * state + k[..., :, None] * delta[..., None, :]
    o = alpha * both[..., 0, :] + (q * k).sum(-1, keepdims=True) * delta
    return o, new


def use_gated_delta_pallas() -> bool:
    """The kernel's gate: on a TPU backend, and nothing else to set."""
    return backend_is_tpu()


def kernel_head_block(q: jax.Array, v: jax.Array, state: jax.Array) -> Optional[int]:
    """The kernel's fit check: how many heads of one sequence a grid step
    holds, or None where the call is not the kernel's — a state that is not
    float32 ``[..., H, dk, dv]`` with ``dk`` and ``dv`` multiples of the
    128-lane tile (the tier-1 toys at 8 x 8, a bf16 state), or a head whose
    tiles alone pass the VMEM budget."""
    if state.dtype != F32 or state.ndim < 4 or q.ndim != state.ndim - 1 or v.ndim != q.ndim:
        return None
    H, dk, dv = state.shape[-3:]
    if dk % 128 or dv % 128 or q.shape[-1] != dk or v.shape[-1] != dv:
        return None
    # the state block goes in and comes out, each double-buffered: four copies
    blocks = [h for h in range(H, 0, -1)
              if H % h == 0 and (h == H or h % 8 == 0) and 4 * h * dk * dv * 4 <= STATE_VMEM_BYTES]
    return blocks[0] if blocks else None


def _step_kernel(q_ref, k_ref, v_ref, a_ref, b_ref, s_ref, o_ref, new_ref):
    """One sequence's block of heads: ``q, k [hb, dk]``, ``v``, the decay and
    the write strength as rows ``[hb, dv]``, the state ``[hb, dk, dv]``. A
    head's tile is read from VMEM once: both reductions over ``dk`` (the
    sublane axis, so ``q`` and ``k`` reach the tile as columns, transposed
    here) and the update are taken from that one value, all in float32 on the
    VPU."""
    q, k = q_ref[0], k_ref[0]
    qk = jnp.sum(q * k, axis=-1, keepdims=True)                        # [hb, 1]
    qT, kT = q.T, k.T                                                  # [dk, hb]
    for h in range(q.shape[0]):
        S = s_ref[0, h]
        kc, row = kT[:, h:h + 1], slice(h, h + 1)
        alpha = a_ref[0, row]
        kS = jnp.sum(kc * S, axis=0, keepdims=True)                    # [1, dv]
        qS = jnp.sum(qT[:, h:h + 1] * S, axis=0, keepdims=True)
        delta = b_ref[0, row] * (v_ref[0, row] - alpha * kS)
        new_ref[0, h] = alpha * S + kc * delta
        o_ref[0, row] = alpha * qS + qk[row] * delta


def _pallas_gated_delta_step(q, k, v, alpha, beta, state, hb: int, interpret: bool):
    """``q, k [B, H, dk]``, ``v, alpha, beta [B, H, dv]`` float32 (the two
    per-head scalars as rows of ``dv`` lanes), ``state [B, H, dk, dv]`` →
    (``o [B, H, dv]``, the new state in the old one's buffer)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, dk, dv = state.shape
    vec = lambda d: pl.BlockSpec((1, hb, d), lambda b, h: (b, h, 0))
    tile = pl.BlockSpec((1, hb, dk, dv), lambda b, h: (b, h, 0, 0))
    return pl.pallas_call(
        _step_kernel,
        out_shape=(jax.ShapeDtypeStruct((B, H, dv), F32), jax.ShapeDtypeStruct(state.shape, F32)),
        grid=(B, H // hb),
        in_specs=[vec(dk), vec(dk), vec(dv), vec(dv), vec(dv), tile],
        out_specs=(vec(dv), tile),
        # the decode scan's carry is updated in place: no second copy of a layer's state
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"),
                                             vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="gated_delta_step",
    )(q, k, v, alpha, beta, state)


def gated_delta_step(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
                     state: jax.Array, *, use_pallas: Optional[bool] = None,
                     interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """``q, k [..., dk]``, ``v [..., dv]``, ``g, beta [...]``, ``state
    [..., dk, dv]`` float32 → (``o [..., dv]`` float32, the new state).

    ``use_pallas=None`` selects by :func:`use_gated_delta_pallas` and the
    call's shapes (:func:`kernel_head_block`); every other call takes
    :func:`xla_gated_delta_step`. The selection is final: a selected kernel
    that Mosaic refuses raises at the enclosing compile. ``interpret`` is for
    tests. Under ``vmap`` the call takes ``pallas_call``'s own rule, a
    leading grid axis, so a member axis moves nothing."""
    if use_pallas is None:
        use_pallas = use_gated_delta_pallas()
    hb = kernel_head_block(q, v, state) if use_pallas or interpret else None
    if hb is None:
        return xla_gated_delta_step(q, k, v, g, beta, state)
    lead, (H, dk, dv) = state.shape[:-3], state.shape[-3:]
    rows = lambda t: t.astype(F32).reshape(-1, H, t.shape[-1])
    lanes = lambda t: rows(jnp.broadcast_to(t[..., None], (*lead, H, dv)))     # a per-head scalar as a row
    o, new = _pallas_gated_delta_step(rows(q), rows(k), rows(v), lanes(jnp.exp(g.astype(F32))), lanes(beta),
                                      state.reshape(-1, H, dk, dv), hb, interpret)
    return o.reshape(*lead, H, dv), new.reshape(state.shape)


def recurrent_gated_delta_rule(q, k, v, g, beta, state):
    """``q, k [B, T, H, dk]``, ``v [B, T, H, dv]``, ``g, beta [B, T, H]``,
    ``state [B, H, dk, dv]`` → (``o [B, T, H, dv]``, final state)."""
    def one(s, x):
        o, s = xla_gated_delta_step(*x, s)
        return s, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(one, state.astype(F32), xs)
    return jnp.moveaxis(o, 0, 1), state


def _unit_lower_inverse(L: jax.Array) -> jax.Array:
    """``(I + L)^-1`` for strictly lower-triangular ``L [..., C, C]``, by
    forward substitution a row at a time in float32 (row ``i`` of the inverse
    is ``e_i - L[i, :i] @ inverse[:i]``)."""
    C = L.shape[-1]

    def row(i, T):
        r = jnp.where(jnp.arange(C) < i, -jax.lax.dynamic_index_in_dim(L, i, axis=-2, keepdims=False), 0.0)
        new = r + jnp.einsum("...j,...jk->...k", r, T, precision=HIGHEST)
        return jax.lax.dynamic_update_index_in_dim(T, new, i, axis=-2)

    T = jax.lax.fori_loop(1, C, row, jnp.zeros_like(L))
    return T + jnp.eye(C, dtype=L.dtype)


def chunk_gated_delta_rule(q, k, v, g, beta, state, chunk: int = 64):
    """The recurrence over ``T`` positions in chunks (shapes as
    :func:`recurrent_gated_delta_rule`; ``T`` is padded up to a multiple of
    ``chunk`` with positions that leave the state alone). Float32 throughout,
    the small products at ``highest``."""
    B, T, H, dk = q.shape
    C = min(chunk, T)
    pad = (-T) % C
    if pad:
        q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)) for a in (q, k, v, g, beta))
    n = (T + pad) // C
    # [n, B, H, C, ...]: the chunk axis outermost for the scan, heads before positions for the products
    split = lambda a: jnp.moveaxis(a.astype(F32).reshape((B, n, C) + a.shape[2:]), (1, 3), (0, 2))
    q, k, v, g, beta = map(split, (q, k, v, g, beta))
    mm = lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)

    def one(S, x):
        q, k, v, g, beta = x                                            # [B, H, C, d] / [B, H, C]
        cum = jnp.cumsum(g, axis=-1)                                    # log-decay from the chunk's start
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
        kb, vb = k * beta[..., None], v * beta[..., None]
        Tm = _unit_lower_inverse(jnp.where(strict, mm("bhid,bhjd->bhij", kb, k) * decay, 0.0))
        u = mm("bhij,bhjd->bhid", Tm, vb)                               # the chunk's writes, state apart
        w = mm("bhij,bhjd->bhid", Tm, kb * jnp.exp(cum)[..., None])     # what they read of the incoming state
        v_new = u - mm("bhid,bhdv->bhiv", w, S)
        inner = jnp.where(lower, mm("bhid,bhjd->bhij", q, k) * decay, 0.0)
        o = mm("bhid,bhdv->bhiv", q * jnp.exp(cum)[..., None], S) + mm("bhij,bhjv->bhiv", inner, v_new)
        last = cum[..., -1:]
        S = S * jnp.exp(last)[..., None] + mm("bhid,bhiv->bhdv", k * jnp.exp(last - cum)[..., None], v_new)
        return S, o

    state, o = jax.lax.scan(one, state.astype(F32), (q, k, v, g, beta))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, n * C, H, -1)        # [n, B, H, C, dv] -> [B, T, H, dv]
    return o[:, :T], state

"""The gated delta rule of a Gated DeltaNet layer, in ``jax.numpy``.

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
  finished from them (``o = alpha q^T S + (q . k) D``): the state is read
  twice and written once a step, not read a third time for the output. The
  products are elementwise multiplies and sums in float32 — a dot would send
  the float32 state through the MXU at its default precision.
- :func:`chunk_gated_delta_rule`: a whole (padded) sequence in chunks of
  ``chunk`` positions, what the prefill runs: inside a chunk the WY / UT
  transform turns the recurrence into products of ``[chunk, chunk]`` and
  ``[chunk, d]`` matrices, and the state is touched once a chunk.
- :func:`recurrent_gated_delta_rule`: :func:`gated_delta_step` under
  ``lax.scan``, position by position (tests hold the other two to it).

A position with ``beta = 0`` and ``g = 0`` leaves the state as it was: that is
how right-padding behind a prompt is made invisible to a recurrent state.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def gated_delta_step(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
                     state: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``q, k [..., dk]``, ``v [..., dv]``, ``g, beta [...]``, ``state
    [..., dk, dv]`` float32 → (``o [..., dv]`` float32, the new state)."""
    q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
    alpha = jnp.exp(g.astype(F32))[..., None]
    qk = jnp.stack([q, k], axis=-2)                                    # [..., 2, dk]
    both = (qk[..., :, :, None] * state[..., None, :, :]).sum(-2)      # [..., 2, dv]: q^T S and k^T S, one pass
    delta = beta.astype(F32)[..., None] * (v - alpha * both[..., 1, :])
    new = alpha[..., None] * state + k[..., :, None] * delta[..., None, :]
    o = alpha * both[..., 0, :] + (q * k).sum(-1, keepdims=True) * delta
    return o, new


def recurrent_gated_delta_rule(q, k, v, g, beta, state):
    """``q, k [B, T, H, dk]``, ``v [B, T, H, dv]``, ``g, beta [B, T, H]``,
    ``state [B, H, dk, dv]`` → (``o [B, T, H, dv]``, final state)."""
    def one(s, x):
        o, s = gated_delta_step(*x, s)
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

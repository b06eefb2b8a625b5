"""The state-space recurrence of a Mamba-2 mixer (SSD, "state-space duality"),
in ``jax.numpy``: a one-position step for decode and a chunked form for prefill.

Per (sequence, head) a float32 state ``S [P, N]`` (``P`` the head's width,
``N`` the state size), ``S_0 = 0``, and per position the head's input ``x_t
[P]``, its step ``dt_t >= 0``, its decay rate ``A < 0`` (one number a head)
and the group's ``B_t, C_t [N]`` (one group: every head reads the same
``B``, ``C``)::

    S   <- exp(dt_t A) S + dt_t x_t (x) B_t
    y_t  = S C_t + D x_t

Three forms of the same sums, all float32:

- :func:`ssd_step`: one position a sequence, what a decode step runs. It is
  bound by reading and writing the state, so the output is taken from the
  *old* state (``y = exp(dt A) (S C) + dt (B . C) x + D x``): the update and
  the reduction then read the same operand and depend on nothing of each
  other. Multiplies and adds on the vector unit; a dot would send the
  float32 state through the MXU at its default precision.
- :func:`chunk_ssd`: a whole (padded) sequence in chunks of ``chunk``
  positions, what the prefill runs: inside a chunk the recurrence is a
  masked ``[chunk, chunk]`` product (``C_t . B_s`` times the decay from ``s``
  to ``t``), and the state is touched once a chunk. Products at ``highest``.
- :func:`recurrent_ssd`: :func:`ssd_step` under ``lax.scan``, position by
  position (tests hold the chunked form to it).

A position with ``dt = 0`` leaves the state as it was, bit for bit in either
form (``exp(0) = 1``, nothing written): that is how right-padding behind a
prompt is made invisible to the state.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def ssd_step(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array, D: jax.Array,
             state: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``x [..., H, P]``, ``dt [..., H]``, ``A, D [H]``, ``B, C [..., N]``,
    ``state [..., H, P, N]`` float32 → (``y [..., H, P]`` float32, the new
    state)."""
    x, dt, B, C = (t.astype(F32) for t in (x, dt, B, C))
    decay = jnp.exp(dt * A)                                            # [..., H]
    sc = (state * C[..., None, None, :]).sum(-1)                       # [..., H, P]: S C of the old state
    new = decay[..., None, None] * state + (dt[..., None] * x)[..., None] * B[..., None, None, :]
    bc = (B * C).sum(-1)[..., None, None]                              # [..., 1, 1]
    y = decay[..., None] * sc + dt[..., None] * bc * x + D[:, None] * x
    return y, new


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

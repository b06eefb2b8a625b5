"""The decode step's SSD update as a Pallas kernel over a layer's period stack
(ops/ssd.py ``ssd_step_at`` with ``use_pallas`` / ``interpret``), interpreted
on the CPU, against the ``jax.numpy`` step and the reference's layer.

Tolerance 1e-5 relative (of the reference's largest magnitude): kernel and
``jax.numpy`` step multiply and add the same float32 terms in the same order
and round nothing narrower; what may differ is the order of the 128 terms of
``S C``. A bf16 product inside the kernel would sit near 4e-3, a state
rounded to bf16 near 2e-3: both two orders above. The whole stack is
compared, not only the outputs: the period the call names against the step,
every other period bit for bit against what went in.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from hyperscalees_t2i_tpu.models import lm, lm_ssm
from hyperscalees_t2i_tpu.ops import pallas_gate, ssd
from hyperscalees_t2i_tpu.reference import mamba2_gqa_reference as ref
from tests.test_lm import rel
from tests.test_lm_ssm import randomized, toy_cfg

TOL = 1e-5
PUBLISHED_HEAD = {"hidden_size": 128, "mamba_n_heads": 4, "mamba_d_head": 64, "mamba_d_state": 128}


def step_inputs(key, n=3, S=2, H=64, P=64, N=128, lead=()):
    """One position's inputs and a random period stack ``[*lead, n, S, H, P, N]``."""
    ks = jax.random.split(key, 7)
    x = jax.random.normal(ks[0], (*lead, S, H, P))
    dt = jax.random.uniform(ks[1], (*lead, S, H), minval=0.01, maxval=0.5)
    A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    B, C = jax.random.normal(ks[3], (*lead, S, N)), jax.random.normal(ks[4], (*lead, S, N))
    D = jax.random.normal(ks[5], (H,))
    return x, dt, A, B, C, D, jax.random.normal(ks[6], (*lead, n, S, H, P, N))


def kernel(x, dt, A, B, C, D, stack, k):
    return ssd.ssd_step_at(x, dt, A, B, C, D, stack, k, interpret=True)


def assert_only_period_k_moved(new, old, k):
    for p in range(old.shape[0]):
        if p != k:
            assert np.array_equal(np.asarray(new[p]), np.asarray(old[p])), f"period {p} moved"


@pytest.mark.parametrize("H,P,N", [(64, 64, 128), (4, 16, 128), (2, 8, 256)],
                         ids=["published-64x64x128", "small-128-lane-state", "two-lane-tiles"])
def test_kernel_is_the_jax_numpy_step(H, P, N):
    args = step_inputs(jax.random.PRNGKey(0), H=H, P=P, N=N)
    x, dt, A, B, C, D, stack = args
    assert ssd.kernel_head_block(x, stack) == H
    k = jnp.int32(1)
    want_y, want_s = ssd.ssd_step(x, dt, A, B, C, D, stack[1])
    y, new = kernel(*args, k)
    assert y.dtype == new.dtype == jnp.float32 and y.shape == want_y.shape and new.shape == stack.shape
    assert rel(y, want_y) < TOL and rel(new[1], want_s) < TOL
    assert_only_period_k_moved(new, stack, 1)


@pytest.mark.parametrize("k", [0, 2], ids=["first-period", "last-period"])
def test_only_period_k_of_the_stack_changes(k):
    """The call names one period; the stack comes back with that period
    advanced and every other one bit for bit as it went in — what lets the
    decode scan hand the kernel its whole carry."""
    args = step_inputs(jax.random.PRNGKey(1), H=4, P=16)
    y, new = kernel(*args, jnp.int32(k))
    want_y, want_s = ssd.ssd_step(*args[:6], args[6][k])
    assert rel(y, want_y) < TOL and rel(new[k], want_s) < TOL
    assert_only_period_k_moved(new, args[6], k)
    assert not np.array_equal(np.asarray(new[k]), np.asarray(args[6][k]))


def test_kernel_under_vmap_over_members_is_the_unbatched_calls():
    """``pop_eval`` vmaps a chunk's members over the decode scan: the call
    takes ``pallas_call``'s own batching rule (a leading grid axis; the
    period index stays one unbatched prefetched scalar), and every member's
    result is that of its own call, bit for bit."""
    x, dt, A, B, C, D, stack = step_inputs(jax.random.PRNGKey(2), H=4, P=16, lead=(2,))
    k = jnp.int32(2)
    y, new = jax.vmap(lambda x, dt, B, C, s: kernel(x, dt, A, B, C, D, s, k))(x, dt, B, C, stack)
    for m in range(2):
        y_m, new_m = kernel(x[m], dt[m], A, B[m], C[m], D, stack[m], k)
        assert np.array_equal(np.asarray(y[m]), np.asarray(y_m)) and np.array_equal(np.asarray(new[m]),
                                                                                    np.asarray(new_m))
        want_y, want_s = ssd.ssd_step(x[m], dt[m], A, B[m], C[m], D, stack[m, 2])
        assert rel(y[m], want_y) < TOL and rel(new[m, 2], want_s) < TOL
        assert_only_period_k_moved(new[m], stack[m], 2)


def test_dt_0_returns_the_state_bit_for_bit():
    """How right-padding stays invisible to the state: such a position
    multiplies by exactly 1 and adds exactly 0."""
    x, dt, A, B, C, D, stack = step_inputs(jax.random.PRNGKey(3), H=4, P=16)
    _, new = kernel(x, jnp.zeros_like(dt), A, B, C, D, stack, jnp.int32(1))
    assert np.array_equal(np.asarray(new), np.asarray(stack))


def test_one_mamba2_layer_decoded_through_the_kernel_carries_the_references_state(tmp_path, monkeypatch):
    """One Mamba-2 layer at the published 64 x 128 head, 16 positions decoded
    one at a time through the kernel from an empty state and conv window, in
    the second period of the stacks: the layer's outputs and the *state it
    carries at the end* are the reference layer's
    (``mamba2_gqa_reference.mamba2``; its state is read through its
    ``state_round`` hook, the scan run as a loop), and the first period of
    both stacks is left as it was."""
    cfg, raw = toy_cfg(tmp_path, **PUBLISHED_HEAD)
    params = randomized(lm.init_lm(jax.random.PRNGKey(0), cfg), jax.random.PRNGKey(99))
    p, T, period = lm_ssm._at(params["layers"][0], 1)["mamba"], 16, 1
    u = jax.random.normal(jax.random.PRNGKey(4), (T, cfg.hidden_size))
    seen = []
    with jax.disable_jit(), jax.default_matmul_precision("highest"):
        want = ref.mamba2(ref.block_weights(params, cfg.period * period), raw, u,
                          state_round=lambda s: seen.append(s) or s)
    assert len(seen) == T

    real, verdicts = ssd.ssd_step_at, []

    def forced(x, dt, A, B, C, D, stack, k):
        verdicts.append(ssd.kernel_head_block(x, stack))
        return real(x, dt, A, B, C, D, stack, k, interpret=True)

    monkeypatch.setattr(ssd, "ssd_step_at", forced)
    n, H, P, N = cfg.n_periods, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    carried = (jnp.zeros((n, 1, H, P, N), jnp.float32), jnp.zeros((n, 1, cfg.mamba_d_conv - 1, cfg.conv_channels)))
    out = []
    for t in range(T):
        y, carried = lm_ssm.mamba_decode(p, cfg, u[t][None], carried, jnp.int32(period), None, "x", 1.0)
        out.append(y[0])
    assert verdicts == [H] * T
    assert rel(jnp.stack(out), want) < 1e-4          # through W_out: tests/test_lm_ssm.py's TOL
    assert rel(carried[0][period, 0], seen[-1]) < TOL
    assert not np.any(np.asarray(carried[0][0])) and not np.any(np.asarray(carried[1][0]))


@pytest.mark.parametrize("x,stack,dtype,want", [
    ((8, 64, 64), (4, 8, 64, 64, 128), jnp.float32, 64),        # the granite cell's call: 64 heads, 2 MB, a step
    ((8, 512, 16), (4, 8, 512, 16, 128), jnp.float32, 256),     # a sequence's heads over the VMEM budget
    ((8, 192, 64), (4, 8, 192, 64, 128), jnp.float32, None),    # over it, and no block of 128 heads divides 192
    ((2, 4, 16), (2, 2, 4, 16, 8), jnp.float32, None),          # the tier-1 toy head
    ((2, 4, 64), (2, 2, 4, 64, 64), jnp.float32, None),         # N off the 128-lane tile
    ((2, 4, 4), (2, 2, 4, 4, 128), jnp.float32, None),          # P off the 8-sublane tile
    ((8, 64, 64), (4, 8, 64, 64, 128), jnp.bfloat16, None),     # a state carried narrower is not the kernel's
    ((8, 64, 64), (8, 64, 64, 128), jnp.float32, None),         # one layer's state, no period axis
    ((4, 64, 64), (4, 8, 64, 64, 128), jnp.float32, None),      # x of other sequences than the stack's
    ((2, 8, 64, 64), (2, 4, 8, 64, 64, 128), jnp.float32, None),  # a member axis is vmap's to add
], ids=["cell-call", "heads-over-budget", "no-block-fits", "toy-16x8", "N-64", "P-4", "bf16-state",
        "no-period-axis", "other-sequences", "member-axis-by-hand"])
def test_fit_check(x, stack, dtype, want):
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt)
    assert ssd.kernel_head_block(sd(x), sd(stack, dtype)) == want


def pallas_names(fn, *args):
    """The ``pallas_call``s of ``fn``'s jaxpr, nested ones too, by kernel name
    (traced anew each time: the gate is read at trace time). Walked, not
    searched as text: the kernel's own body names the ``jnp.where`` it calls."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["name"])
                continue
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    if isinstance(sub, (ClosedJaxpr, Jaxpr)):
                        walk(getattr(sub, "jaxpr", sub))

    walk(jax.make_jaxpr(lambda *a: fn(*a))(*args).jaxpr)
    return found


def test_selected_by_backend_and_shape_alone(monkeypatch):
    """No knob: off a TPU every call is the ``jax.numpy`` step between a read
    and a write of the period; on one, the calls that fit are the kernel's and
    the others (the toys, a bf16 state) stay where they were. ``use_pallas``
    is the tests' and ``tools/kernel_check``'s handle."""
    args = step_inputs(jax.random.PRNGKey(5), H=4, P=16) + (jnp.int32(1),)
    toy = step_inputs(jax.random.PRNGKey(5), H=4, P=16, N=8) + (jnp.int32(1),)
    bf16 = args[:6] + (args[6].astype(jnp.bfloat16), args[7])
    assert not ssd.use_ssd_pallas() and not pallas_gate.selected_kernels()["ssd_step"]
    assert pallas_names(ssd.ssd_step_at, *args) == []
    monkeypatch.setattr(ssd, "backend_is_tpu", lambda: True)
    assert pallas_gate.selected_kernels()["ssd_step"]
    assert pallas_names(ssd.ssd_step_at, *args) == ["ssd_step"]
    assert pallas_names(jax.vmap(ssd.ssd_step_at, in_axes=(0, 0, None, 0, 0, None, 0, None)),
                        *step_inputs(jax.random.PRNGKey(5), H=4, P=16, lead=(2,)), jnp.int32(1)) == ["ssd_step"]
    assert pallas_names(ssd.ssd_step_at, *toy) == []
    assert pallas_names(ssd.ssd_step_at, *bf16) == []
    assert pallas_names(lambda *a: ssd.ssd_step_at(*a, use_pallas=False), *args) == []
    # the fallback is the oracle between a read and a write of the period, in the stack's dtype
    y, new = ssd.ssd_step_at(*bf16, use_pallas=False)
    want_y, want_s = ssd.ssd_step(*args[:6], bf16[6][1].astype(jnp.float32))
    assert new.dtype == jnp.bfloat16 and np.array_equal(np.asarray(y), np.asarray(want_y))
    assert np.array_equal(np.asarray(new[1]), np.asarray(want_s.astype(jnp.bfloat16)))
    assert_only_period_k_moved(new, bf16[6], 1)

"""The decode step's gated delta rule as a Pallas kernel (ops/gated_delta.py
``gated_delta_step`` with ``use_pallas`` / ``interpret``), interpreted on the
CPU, against the ``jax.numpy`` step, a float64 loop and the reference's layer.

Tolerance 1e-5 relative (of the reference's largest magnitude): kernel and
``jax.numpy`` step multiply and add the same float32 terms and round nothing
narrower; what differs is the order of the 128 terms of ``k^T S`` and
``q^T S``. A bf16 product inside the kernel would sit at 4e-3, a state rounded
to bf16 at 2e-3: both two orders above. The final *state* is compared, not
only the outputs — nine heads of ten forget within a position at the source's
initialisation, so outputs alone say little about what is carried.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperscalees_t2i_tpu.models import lm, lm_hybrid as hy
from hyperscalees_t2i_tpu.ops import gated_delta, pallas_gate
from hyperscalees_t2i_tpu.reference import gdn_moe_reference as ref

from test_lm_hybrid import force_kernel, loop_delta_rule, randomized_norms, rel, toy_cfg

TOL = 1e-5
PUBLISHED_HEAD = {"linear_key_head_dim": 128, "linear_value_head_dim": 128}


def step_inputs(key, lead=(2,), H=4, dk=128, dv=128):
    ks = jax.random.split(key, 6)
    l2 = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = l2(jax.random.normal(ks[0], (*lead, H, dk))) / np.sqrt(dk)
    k = l2(jax.random.normal(ks[1], (*lead, H, dk)))
    v = jax.random.normal(ks[2], (*lead, H, dv))
    g = -jax.random.uniform(ks[3], (*lead, H), minval=0.01, maxval=3.0)
    beta = jax.random.uniform(ks[4], (*lead, H))
    return q, k, v, g, beta, jax.random.normal(ks[5], (*lead, H, dk, dv))


def kernel(*args):
    return gated_delta.gated_delta_step(*args, interpret=True)


@pytest.mark.parametrize("dk,dv", [(128, 128), (256, 128), (128, 256)],
                         ids=["published-128x128", "two-key-tiles", "two-value-tiles"])
def test_kernel_is_the_jax_numpy_step(dk, dv):
    args = step_inputs(jax.random.PRNGKey(0), dk=dk, dv=dv)
    assert gated_delta.kernel_head_block(args[0], args[2], args[5]) == 4
    want_o, want_s = gated_delta.xla_gated_delta_step(*args)
    o, s = kernel(*args)
    assert o.dtype == s.dtype == jnp.float32 and o.shape == want_o.shape and s.shape == want_s.shape
    assert rel(o, want_o) < TOL and rel(s, want_s) < TOL


def test_kernel_under_vmap_over_members_is_the_unbatched_calls():
    """``pop_eval`` vmaps a chunk's members over ``gdn_decode``: the call
    takes ``pallas_call``'s own batching rule (a leading grid axis), and every
    member's result is that of its own call, bit for bit."""
    args = step_inputs(jax.random.PRNGKey(1), lead=(2, 2))
    o, s = jax.vmap(kernel)(*args)
    for m in range(2):
        o_m, s_m = kernel(*(a[m] for a in args))
        assert np.array_equal(np.asarray(o[m]), np.asarray(o_m)) and np.array_equal(np.asarray(s[m]), np.asarray(s_m))
    want_o, want_s = gated_delta.xla_gated_delta_step(*args)
    assert rel(o, want_o) < TOL and rel(s, want_s) < TOL


def test_beta_0_and_g_0_return_the_state_bit_for_bit():
    """How right-padding stays invisible to a recurrent state: such a position
    multiplies by exactly 1 and adds exactly 0."""
    q, k, v, g, beta, S = step_inputs(jax.random.PRNGKey(2))
    _, s = kernel(q, k, v, jnp.zeros_like(g), jnp.zeros_like(beta), S)
    assert np.array_equal(np.asarray(s), np.asarray(S))


def test_sixteen_positions_through_the_kernel_against_the_float64_loop():
    """The probe PERF.md asked for before a kernel touched ``delta_rule``: the
    carried state after 16 kernel steps from a random state, not only the 16
    outputs, against the recurrence in float64."""
    T = 16
    ks = jax.random.split(jax.random.PRNGKey(3), T + 1)
    steps = [step_inputs(k) for k in ks[:T]]
    q, k, v, g, beta = (jnp.stack([s[i] for s in steps], axis=1) for i in range(5))   # [B, T, H, ...]
    S0 = jax.random.normal(ks[T], steps[0][5].shape)
    want_o, want_s = loop_delta_rule(q, k, v, g, beta, S0)

    def one(S, x):
        o, S = kernel(*x, S)
        return S, o

    got_s, got_o = jax.lax.scan(one, S0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    assert rel(jnp.moveaxis(got_o, 0, 1), want_o) < TOL and rel(got_s, want_s) < TOL


def test_one_deltanet_layer_decoded_through_the_kernel_carries_the_references_state(tmp_path, monkeypatch):
    """One Gated DeltaNet layer at the published 128 x 128 head, 16 positions
    decoded one at a time through the kernel from an empty state and conv
    window: the layer's outputs and the *state it carries at the end* are the
    reference layer's (``gdn_moe_reference.gated_deltanet``; its state is read
    through its ``state_round`` hook, the scan run as a loop)."""
    cfg, raw = toy_cfg(tmp_path, **PUBLISHED_HEAD)
    params = randomized_norms(lm.init_lm(jax.random.PRNGKey(0), cfg), jax.random.PRNGKey(99))
    p, T = params["layers"][0]["gdn"], 16
    u = jax.random.normal(jax.random.PRNGKey(4), (T, cfg.hidden_size))
    seen = []
    with jax.disable_jit(), jax.default_matmul_precision("highest"):
        want = ref.gated_deltanet(ref.block_weights(params["layers"][0], "x"), raw, u,
                                  state_round=lambda s: seen.append(s) or s)
    assert len(seen) == T

    verdicts = force_kernel(monkeypatch)
    carried = (jnp.zeros((1, cfg.linear_num_value_heads, 128, 128), hy.STATE_DTYPE),
               jnp.zeros((1, cfg.linear_conv_kernel_dim - 1, cfg.conv_channels), jnp.float32))
    out = []
    for t in range(T):
        y, carried = hy.gdn_decode(p, cfg, u[t][None], carried, None, "x", 1.0)
        out.append(y[0])
    assert verdicts == [cfg.linear_num_value_heads] * T
    assert rel(jnp.stack(out), want) < 1e-4          # through Wout: tests/test_lm_hybrid.py's TOL
    assert rel(carried[0][0], seen[-1]) < TOL


@pytest.mark.parametrize("lead,H,dk,dv,dtype,want", [
    ((64,), 32, 128, 128, jnp.float32, 32),
    ((8, 8), 32, 128, 128, jnp.float32, 32),
    ((2,), 64, 128, 128, jnp.float32, 32),       # a sequence's heads over the VMEM budget: half of them a block
    ((2,), 4, 8, 8, jnp.float32, None),          # the tier-1 toy head
    ((2,), 4, 128, 64, jnp.float32, None),
    ((2,), 4, 128, 128, jnp.bfloat16, None),     # a state carried narrower is not the kernel's
    ((), 4, 128, 128, jnp.float32, None),        # no sequence axis to put on the grid
], ids=["cell-call", "cell-call-member-axis", "heads-over-budget", "toy-8x8", "dv-64", "bf16-state", "no-batch"])
def test_fit_check(lead, H, dk, dv, dtype, want):
    sd = lambda *shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt)
    got = gated_delta.kernel_head_block(sd(*lead, H, dk), sd(*lead, H, dv), sd(*lead, H, dk, dv, dt=dtype))
    assert got == want


def pallas_names(fn, *args):
    """The ``pallas_call``s of ``fn``'s jaxpr, nested ones too, by kernel name
    (traced anew each time: the gate is read at trace time)."""
    return re.findall(r"pallas_call\[.*?name=(\w+)", str(jax.make_jaxpr(lambda *a: fn(*a))(*args)), flags=re.S)


def test_selected_by_backend_and_shape_alone(monkeypatch):
    """No knob: off a TPU every call is the ``jax.numpy`` step; on one, the
    calls that fit are the kernel's and the others (the toys, a bf16 state)
    stay where they were. ``use_pallas`` is the tests' and
    ``tools/kernel_check``'s handle."""
    args, toy = step_inputs(jax.random.PRNGKey(5)), step_inputs(jax.random.PRNGKey(5), dk=8, dv=8)
    assert not gated_delta.use_gated_delta_pallas() and not pallas_gate.selected_kernels()["gated_delta_step"]
    assert pallas_names(gated_delta.gated_delta_step, *args) == []
    monkeypatch.setattr(gated_delta, "backend_is_tpu", lambda: True)
    assert pallas_gate.selected_kernels()["gated_delta_step"]
    assert pallas_names(gated_delta.gated_delta_step, *args) == ["gated_delta_step"]
    assert pallas_names(gated_delta.gated_delta_step, *toy) == []
    assert pallas_names(gated_delta.gated_delta_step, *args[:5], args[5].astype(jnp.bfloat16)) == []
    assert pallas_names(lambda *a: gated_delta.gated_delta_step(*a, use_pallas=False), *args) == []
    # the oracle stays on the code it ran before the kernel existed
    seq = tuple(a[:, None] for a in args[:5]) + (args[5],)
    assert pallas_names(gated_delta.recurrent_gated_delta_rule, *seq) == []
    o, s = gated_delta.recurrent_gated_delta_rule(*seq)
    want_o, want_s = gated_delta.xla_gated_delta_step(*args)
    assert rel(o[:, 0], want_o) < 1e-6 and rel(s, want_s) < 1e-6

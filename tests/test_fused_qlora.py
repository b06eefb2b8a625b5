"""Unified int8-dequant + member-LoRA kernel (ops/fused_qlora.py, round 15).

The contract under test, layer by layer:

- **kernel parity** — the Pallas kernel (interpret mode on CPU — the
  ops/attention.py precedent: the CPU tier lowers and *interprets* the
  kernel, only real TPU executes it) matches :func:`xla_fused_qlora`, the
  byte-identical round-14 composition, across {2D, stacked-3D} × {f32,
  bf16 noise factors} × antithetic signs, with tile padding and the
  member-vmap batching pop_eval applies.
- **dense resolution** — ``nn.dense`` with an int8 node AND FactoredDelta
  factors resolves through the unified path, bitwise-equal to the old
  composition on CPU (off the TPU it IS that composition) and within float
  tolerance of an explicit dequantize-then-materialize reference.
- **conv contract** — matmul-equivalent ``kernel_q8`` convs (1×1 stride-1,
  non-overlapping p×p stride-p patch embeds) route through the same
  dequant contract as ``dense``; everything else (overlapping windows,
  depthwise groups) keeps the dequant-then-conv lowering, and
  ``HSES_FUSED_QLORA=off`` restores the round-14 program everywhere.
- **gate mechanics** — the shared ops/pallas_gate env/backend reads every
  kernel gate is built on; no gate probes, none falls back after an error.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperscalees_t2i_tpu.lora import FactoredDelta, slice_layer
from hyperscalees_t2i_tpu.models import nn
from hyperscalees_t2i_tpu.ops import pallas_gate
from hyperscalees_t2i_tpu.ops.fused_qlora import (
    ROUTING_ENV,
    conv_kernel_q8_matmul,
    fused_qlora_applies,
    fused_qlora_dense,
    unified_routing_enabled,
    use_fused_qlora_pallas,
    xla_fused_qlora,
)
from hyperscalees_t2i_tpu.ops.quant import dequantize_kernel, quantize_kernel


# ---------------------------------------------------------------------------
# operand builders
# ---------------------------------------------------------------------------

def _factored_pair(key, din=16, rl=4, re=2, dout=24, noise_dtype=jnp.float32, sign=1.0):
    """(x, qk, leaf): an int8 base node and a factored 2D adapter leaf whose
    noise factors live in ``noise_dtype`` with coefficient sign ``sign``
    (antithetic members share (U, V) and flip c)."""
    ks = jax.random.split(key, 8)
    qk = quantize_kernel(jax.random.normal(ks[7], (din, dout)) * 0.1)
    a = FactoredDelta(
        jax.random.normal(ks[0], (din, rl)),
        jax.random.normal(ks[1], (din, re)).astype(noise_dtype),
        jax.random.normal(ks[2], (rl, re)).astype(noise_dtype),
        jnp.float32(0.03 * sign),
    )
    b = FactoredDelta(
        jax.random.normal(ks[3], (rl, dout)),
        jax.random.normal(ks[4], (rl, re)).astype(noise_dtype),
        jax.random.normal(ks[5], (dout, re)).astype(noise_dtype),
        jnp.float32(-0.04 * sign),
    )
    x = jax.random.normal(ks[6], (3, 7, din))
    return x, qk, {"a": a, "b": b}


def _assert_close(out, ref, tol=1e-5):
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# Pallas kernel: interpret-mode parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("noise_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sign", [1.0, -1.0])
def test_kernel_interpret_parity_2d(noise_dtype, sign):
    x, qk, leaf = _factored_pair(
        jax.random.PRNGKey(40), noise_dtype=noise_dtype, sign=sign
    )
    ref = xla_fused_qlora(x, qk, leaf, 2.0)
    out = fused_qlora_dense(x, qk, leaf, 2.0, interpret=True)
    assert out.shape == ref.shape
    _assert_close(out, ref)


def test_kernel_interpret_parity_stacked3d():
    """Stacked nodes reach ``dense`` sliced to 2D (nn.slice_stacked per scan
    layer, lora.slice_layer on the FactoredDelta stack) — every layer of the
    stack must agree with the fallback through that exact slicing path."""
    L, din, rl, re, dout = 3, 12, 4, 2, 20
    ks = jax.random.split(jax.random.PRNGKey(41), 8)
    node = {"kernel_q8": quantize_kernel(jax.random.normal(ks[0], (L, din, dout)) * 0.1)}
    leaf = {
        "a": FactoredDelta(
            jax.random.normal(ks[1], (L, din, rl)),
            jax.random.normal(ks[2], (L, din, re)),
            jax.random.normal(ks[3], (L, rl, re)),
            jnp.float32(0.05),
        ),
        "b": FactoredDelta(
            jax.random.normal(ks[4], (L, rl, dout)),
            jax.random.normal(ks[5], (L, rl, re)),
            jax.random.normal(ks[6], (L, dout, re)),
            jnp.float32(-0.02),
        ),
    }
    x = jax.random.normal(ks[7], (5, din))
    for i in range(L):
        nq = nn.slice_stacked(node, i)
        lf = slice_layer(leaf, i)
        ref = xla_fused_qlora(x, nq["kernel_q8"], lf, 1.5)
        out = fused_qlora_dense(x, nq["kernel_q8"], lf, 1.5, interpret=True)
        _assert_close(out, ref)


def test_kernel_tile_padding():
    """Token AND output-channel counts that don't divide their tiles run
    correctly (padded rows/columns computed then sliced away — the q8/
    scale/b.w/b.v dout pads only ever feed discarded columns)."""
    x, qk, leaf = _factored_pair(jax.random.PRNGKey(42))
    x2 = x.reshape(-1, x.shape[-1])[:5]  # 5 rows vs block_t=4 → padded tile
    ref = xla_fused_qlora(x2, qk, leaf, 1.0)
    out = fused_qlora_dense(x2, qk, leaf, 1.0, interpret=True, block_t=4)
    _assert_close(out, ref)
    # dout=24 vs block_n=16 → one padded dout tile
    out = fused_qlora_dense(
        x2, qk, leaf, 1.0, interpret=True, block_t=4, block_n=16
    )
    _assert_close(out, ref)


def test_kernel_vmap_members():
    """The member axis arrives via vmap in pop_eval — the kernel must batch,
    with the int8 base BROADCAST (unbatched) across members, antithetic
    pairs sharing (U, V) with opposite c."""
    x, qk, leaf = _factored_pair(jax.random.PRNGKey(43))
    a, b = leaf["a"], leaf["b"]
    cs = jnp.array([0.01, -0.01, 0.05])  # members 0/1 are an antithetic pair
    am = jax.vmap(lambda c: FactoredDelta(a.w, a.u, a.v, c))(cs)
    bm = jax.vmap(lambda c: FactoredDelta(b.w, b.u, b.v, -c))(cs)
    ref = jax.vmap(
        lambda aa, bb: xla_fused_qlora(x, qk, {"a": aa, "b": bb}, 1.5)
    )(am, bm)
    out = jax.vmap(
        lambda aa, bb: fused_qlora_dense(x, qk, {"a": aa, "b": bb}, 1.5, interpret=True)
    )(am, bm)
    _assert_close(out, ref)


def test_kernel_declines_oversize_layer():
    """A layer whose base tile cannot fit the per-layer VMEM budget must
    decline the Pallas path AT TRACE TIME (bitwise the XLA composition,
    even when the kernel is requested): a Mosaic rejection would surface at
    the enclosing ES-step compile — nothing catches it there, so the shape
    gate has to decline first. The dout axis is grid-tiled and block sizes
    adapt downward first (_fit_blocks), so only a pathological CONTRACTION
    width (din, which must stay whole) trips it — every site the kernel
    really serves (the LoRA-targeted Sana denses) fits."""
    from hyperscalees_t2i_tpu.ops.fused_qlora import (
        MIN_BLOCK,
        VMEM_BUDGET_BYTES,
        _fit_blocks,
        _kernel_vmem_bytes,
    )

    din, dout = 32768, 512  # over budget even at the (128, 128) floor
    ks = jax.random.split(jax.random.PRNGKey(60), 7)
    qk = quantize_kernel(jax.random.normal(ks[0], (din, dout)) * 0.02)
    a = FactoredDelta(jax.random.normal(ks[1], (din, 4)),
                      jax.random.normal(ks[2], (din, 2)),
                      jax.random.normal(ks[3], (4, 2)), jnp.float32(0.01))
    b = FactoredDelta(jax.random.normal(ks[4], (4, dout)),
                      jax.random.normal(ks[5], (4, 2)),
                      jax.random.normal(ks[6], (dout, 2)), jnp.float32(0.01))
    assert _kernel_vmem_bytes(
        qk["q8"], a, b, MIN_BLOCK, MIN_BLOCK
    ) > VMEM_BUDGET_BYTES
    assert _fit_blocks(qk["q8"], a, b, 256, 256) is None
    x = jax.random.normal(jax.random.PRNGKey(61), (3, din))
    leaf = {"a": a, "b": b}
    out = fused_qlora_dense(x, qk, leaf, 1.0, use_pallas=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(xla_fused_qlora(x, qk, leaf, 1.0))
    )

    # every LoRA-targeted Sana flagship dense must FIT (adapting blocks if
    # needed) — the gate must not turn the promoted default off at exactly
    # the geometry it exists for (tools/kernel_check.py compiles these on
    # the chip)
    def mk(din_, dout_, r=8, re_=4):
        q = {"q8": jnp.zeros((din_, dout_), jnp.int8),
             "scale": jnp.zeros((1, dout_))}
        af = FactoredDelta(jnp.zeros((din_, r)), jnp.zeros((din_, re_)),
                           jnp.zeros((r, re_)), jnp.float32(0.0))
        bf = FactoredDelta(jnp.zeros((r, dout_)), jnp.zeros((r, re_)),
                           jnp.zeros((dout_, re_)), jnp.float32(0.0))
        return q, af, bf

    for din_, dout_ in (
        (2240, 2240),   # attention QKV/out, caption_proj/linear_2
        (2304, 2240),   # caption_proj/linear_1 (the widest din)
        (2240, 13440),  # time_embed/linear (AdaLN 6·d)
        (2240, 32),     # proj_out
    ):
        q, af, bf = mk(din_, dout_)
        fitted = _fit_blocks(q["q8"], af, bf, 256, 256)
        assert fitted is not None, (din_, dout_)
        bt, bn = fitted
        assert bt >= MIN_BLOCK and bn >= MIN_BLOCK
        assert _kernel_vmem_bytes(q["q8"], af, bf, bt, bn) <= VMEM_BUDGET_BYTES
    # and a toy layer sits far under the budget at full blocks
    _, qk_s, leaf_s = _factored_pair(jax.random.PRNGKey(62))
    assert _fit_blocks(qk_s["q8"], leaf_s["a"], leaf_s["b"], 256, 256) == (256, 256)


def test_gate_default_off_the_tpu_backend(monkeypatch):
    """On the CPU test platform the kernel gate auto-selects OFF (it is the
    default only where Mosaic runs) and the unified resolution lowers the
    XLA composition bit-for-bit; HSES_FUSED_QLORA_PALLAS=0 is an explicit
    opt-out everywhere."""
    monkeypatch.delenv("HSES_FUSED_QLORA_PALLAS", raising=False)
    assert not use_fused_qlora_pallas()
    x, qk, leaf = _factored_pair(jax.random.PRNGKey(44))
    np.testing.assert_array_equal(
        np.asarray(fused_qlora_dense(x, qk, leaf, 1.0)),
        np.asarray(xla_fused_qlora(x, qk, leaf, 1.0)),
    )
    monkeypatch.setenv("HSES_FUSED_QLORA_PALLAS", "0")
    assert not use_fused_qlora_pallas()


# ---------------------------------------------------------------------------
# dense resolution
# ---------------------------------------------------------------------------

def test_dense_unified_matches_legacy_bitwise_and_materialized():
    """``nn.dense`` with kernel_q8 + FactoredDelta resolves through the
    unified path: bitwise-equal to the round-14 composition on CPU (the
    fallback IS that composition — the ledger gate's premise) and within
    float tolerance of dequantize-then-materialize."""
    x, qk, leaf = _factored_pair(jax.random.PRNGKey(45))
    node = {"kernel_q8": qk, "bias": jnp.linspace(0, 1, 24)}
    assert fused_qlora_applies(leaf)
    y = nn.dense(node, x, lora=leaf, lora_scale=2.0)
    np.testing.assert_array_equal(
        np.asarray(y),
        np.asarray(xla_fused_qlora(x, qk, leaf, 2.0) + node["bias"]),
    )

    def mat(f):
        return f.w + f.c * (f.u.astype(jnp.float32) @ f.v.astype(jnp.float32).T)

    ref = (
        x @ dequantize_kernel(qk, x.dtype)
        + 2.0 * ((x @ mat(leaf["a"])) @ mat(leaf["b"]))
        + node["bias"]
    )
    _assert_close(y, ref, tol=1e-4)


def test_dense_raw_lora_keeps_legacy_branch():
    """Raw-array LoRA factors (the materialized path) must NOT take the
    unified resolution — its HLO is pinned by the all-knobs-off golden."""
    x, qk, _ = _factored_pair(jax.random.PRNGKey(46))
    raw = {"a": jax.random.normal(jax.random.PRNGKey(1), (16, 4)),
           "b": jax.random.normal(jax.random.PRNGKey(2), (4, 24))}
    assert not fused_qlora_applies(raw)
    node = {"kernel_q8": qk}
    y = nn.dense(node, x, lora=raw, lora_scale=2.0)
    ref = x @ dequantize_kernel(qk, x.dtype) + ((x @ raw["a"]) @ raw["b"]) * 2.0
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))


def test_routing_env_off_disables_applies(monkeypatch):
    monkeypatch.setenv(ROUTING_ENV, "off")
    assert not unified_routing_enabled()
    _, qk, leaf = _factored_pair(jax.random.PRNGKey(47))
    assert not fused_qlora_applies(leaf)
    monkeypatch.setenv(ROUTING_ENV, "1")
    assert unified_routing_enabled()
    assert fused_qlora_applies(leaf)


# ---------------------------------------------------------------------------
# conv/patch-embed: the same dequant contract as dense
# ---------------------------------------------------------------------------

def _conv_ref(x, qk, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, dequantize_kernel(qk, x.dtype), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def test_conv_1x1_routes_through_dense_contract():
    x = jax.random.normal(jax.random.PRNGKey(50), (2, 8, 8, 16))
    qk = quantize_kernel(jax.random.normal(jax.random.PRNGKey(51), (1, 1, 16, 12)) * 0.1)
    y = nn.conv2d({"kernel_q8": qk, "bias": jnp.ones(12)}, x)
    _assert_close(y, _conv_ref(x, qk) + 1.0)
    # the routed program is a different lowering than dequant-then-conv
    routed = jax.jit(lambda v: nn.conv2d({"kernel_q8": qk}, v)).lower(x).as_text()
    assert "convolution" not in routed
    assert conv_kernel_q8_matmul(x, qk, 1, "SAME", 1) is not None


@pytest.mark.parametrize("padding", ["SAME", "VALID"])
def test_conv_patch_embed_routes_im2col(padding):
    """p×p stride-p on a p-divisible grid (the CLIP/Sana patch_embed shape):
    exact reshape-only im2col into the per-channel-flattened layout."""
    x = jax.random.normal(jax.random.PRNGKey(52), (2, 8, 8, 6))
    qk = quantize_kernel(jax.random.normal(jax.random.PRNGKey(53), (4, 4, 6, 10)) * 0.1)
    y = nn.conv2d({"kernel_q8": qk}, x, stride=4, padding=padding)
    _assert_close(y, _conv_ref(x, qk, stride=4, padding=padding))
    routed = jax.jit(
        lambda v: nn.conv2d({"kernel_q8": qk}, v, stride=4, padding=padding)
    ).lower(x).as_text()
    assert "convolution" not in routed


def test_conv_nonequivalent_keeps_conv_lowering(monkeypatch):
    """Overlapping windows, depthwise groups, and a non-divisible grid keep
    the dequant-then-conv path — bitwise the HSES_FUSED_QLORA=off program."""
    x = jax.random.normal(jax.random.PRNGKey(54), (2, 8, 8, 16))
    q3 = quantize_kernel(jax.random.normal(jax.random.PRNGKey(55), (3, 3, 16, 12)) * 0.1)
    assert conv_kernel_q8_matmul(x, q3, 1, "SAME", 1) is None
    y = nn.conv2d({"kernel_q8": q3}, x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(_conv_ref(x, q3)))
    # depthwise: groups > 1 never routes
    qd = quantize_kernel(jax.random.normal(jax.random.PRNGKey(56), (3, 3, 1, 16)) * 0.1)
    assert conv_kernel_q8_matmul(x, qd, 1, "SAME", 16) is None
    # 5×5 stride 5 on an 8-grid: patches would straddle the edge → conv path
    q5 = quantize_kernel(jax.random.normal(jax.random.PRNGKey(57), (5, 5, 16, 12)) * 0.1)
    assert conv_kernel_q8_matmul(x, q5, 5, "SAME", 1) is None
    # routing off restores the conv lowering for the matmul-equivalent case
    q1 = quantize_kernel(jax.random.normal(jax.random.PRNGKey(58), (1, 1, 16, 12)) * 0.1)
    monkeypatch.setenv(ROUTING_ENV, "off")
    assert conv_kernel_q8_matmul(x, q1, 1, "SAME", 1) is None
    off_text = jax.jit(lambda v: nn.conv2d({"kernel_q8": q1}, v)).lower(x).as_text()
    assert "convolution" in off_text


def test_routing_shapes_the_q8_step_program():
    """The unified routing is not a no-op on an int8+fused ES-step program
    (the ledger-diff columns compare real alternatives), while the all-off
    tiny program — no kernel_q8 anywhere — is untouched by the knob (the
    StableHLO golden in test_fused.py stays the authority)."""
    import os

    from hyperscalees_t2i_tpu.ops.quant import MIN_SIZE_ENV
    from hyperscalees_t2i_tpu.rungs import DEFAULT_OPT, RUNG_PLAN
    from hyperscalees_t2i_tpu.tools.preflight import abstract_step_inputs
    from hyperscalees_t2i_tpu.train.trainer import make_es_step

    scale, pop, m, mb = RUNG_PLAN["tiny"]

    def lower_text(routing: str) -> str:
        old_route = os.environ.get(ROUTING_ENV)
        old_floor = os.environ.get(MIN_SIZE_ENV)
        os.environ[ROUTING_ENV] = routing
        os.environ[MIN_SIZE_ENV] = "1"  # tiny layers quantize for the probe
        try:
            (backend, reward_fn, tc, frozen, theta, ids, key_s, nu) = (
                abstract_step_inputs(
                    scale, pop, m, mb,
                    {**DEFAULT_OPT, "pop_fuse": True, "base_quant": "int8"},
                )
            )
            step = make_es_step(backend, reward_fn, tc, nu, 1, None)
            return step.lower(frozen, theta, ids, key_s).as_text()
        finally:
            for k, v in ((ROUTING_ENV, old_route), (MIN_SIZE_ENV, old_floor)):
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    sha = lambda t: hashlib.sha256(t.encode()).hexdigest()
    assert sha(lower_text("1")) != sha(lower_text("off"))


# ---------------------------------------------------------------------------
# shared gate mechanics (ops/pallas_gate.py)
# ---------------------------------------------------------------------------

def test_env_requested_tristate(monkeypatch):
    monkeypatch.delenv("HSES_TEST_FLAG", raising=False)
    assert pallas_gate.env_requested("HSES_TEST_FLAG") is None
    for v, want in (("1", True), ("0", False), ("off", False), ("OFF", False),
                    ("maybe", None)):
        monkeypatch.setenv("HSES_TEST_FLAG", v)
        assert pallas_gate.env_requested("HSES_TEST_FLAG") is want


def test_active_flags_and_marks(monkeypatch):
    for f in pallas_gate.PALLAS_ENV_FLAGS:
        monkeypatch.delenv(f, raising=False)
    assert pallas_gate.active_pallas_flags() == {}
    monkeypatch.setenv("HSES_FUSED_QLORA_PALLAS", "1")
    monkeypatch.setenv("HSES_USE_PALLAS", "0")
    flags = pallas_gate.active_pallas_flags()
    assert flags == {"HSES_FUSED_QLORA_PALLAS": "1", "HSES_USE_PALLAS": "0"}
    # deterministic order (the PALLAS_ENV_FLAGS table), opt-outs suffixed
    assert pallas_gate.pallas_flag_marks(flags) == "flash-,qlora"
    assert pallas_gate.pallas_flag_marks({}) == ""
    from hyperscalees_t2i_tpu.rungs import kernel_marks

    rec = {"pop_fuse": True, "pallas_env": {"HSES_FUSED_QLORA_PALLAS": "1"}}
    assert kernel_marks(rec) == ["fuse", "P:qlora"]


def _as_tpu(monkeypatch, on: bool):
    """Every gate module binds backend_is_tpu at import; flip them all."""
    from hyperscalees_t2i_tpu.ops import fused_lora, fused_qlora, quant_mm

    for mod in (pallas_gate, fused_qlora, fused_lora, quant_mm):
        monkeypatch.setattr(mod, "backend_is_tpu", lambda: on)


def test_gates_select_by_backend_and_flag_alone(monkeypatch):
    """Selection is by platform (and, per layer, shape) plus the one
    tri-state flag: default-ON kernels are on exactly on a TPU backend unless
    opted out, opt-in kernels exactly on a TPU backend when asked for, and a
    request never forces a kernel onto a backend that cannot run Mosaic."""
    for f in pallas_gate.PALLAS_ENV_FLAGS:
        monkeypatch.delenv(f, raising=False)
    off = {"fused_qlora": False, "decode_attention": False,
           "member_lora_delta": False, "int8_matmul": False}
    assert pallas_gate.selected_kernels() == off
    for f in pallas_gate.PALLAS_ENV_FLAGS:  # =1 off the TPU selects nothing
        monkeypatch.setenv(f, "1")
    assert pallas_gate.selected_kernels() == off

    _as_tpu(monkeypatch, True)
    assert pallas_gate.selected_kernels() == {k: True for k in off}
    for f in pallas_gate.PALLAS_ENV_FLAGS:
        monkeypatch.delenv(f)
    assert pallas_gate.selected_kernels() == {
        "fused_qlora": True, "decode_attention": True,
        "member_lora_delta": False, "int8_matmul": False,
    }
    # the opt-out wins where the kernel is the backend default — the
    # pallas_env stamp ("flash-") has to describe the path that ran
    monkeypatch.setenv("HSES_USE_PALLAS", "0")
    monkeypatch.setenv("HSES_FUSED_QLORA_PALLAS", "off")
    assert not any(pallas_gate.selected_kernels().values())


def test_gate_inside_jit_selects_and_never_falls_back(monkeypatch):
    """The gate consulted from inside a trace (where nn.dense reaches it:
    under jit, scan and lax.map) must mean what it says. The old probe ran
    there on tracers — block_until_ready of a tracer is a no-op, so it
    "passed" without compiling anything — and a selected kernel that then
    failed was swapped for the XLA composition behind one stderr line. Now
    the gate reads the backend and the flag only (nothing to run, so nothing
    differs under a trace), and a selected kernel's failure propagates."""
    from hyperscalees_t2i_tpu.ops import fused_qlora

    x, qk, leaf = _factored_pair(jax.random.PRNGKey(70))
    seen = {}

    @jax.jit
    def traced(x):
        seen["gate"] = use_fused_qlora_pallas()
        return fused_qlora_dense(x, qk, leaf, 1.0)

    # CPU backend: consulted under the trace, says no, lowers the XLA form
    ref = jax.jit(lambda x: xla_fused_qlora(x, qk, leaf, 1.0))(x)
    np.testing.assert_array_equal(np.asarray(traced(x)), np.asarray(ref))
    assert seen["gate"] is False

    # selected (as on a TPU) and the kernel fails: the error is the caller's
    _as_tpu(monkeypatch, True)

    def refuse(*a, **k):
        raise RuntimeError("mosaic said no")

    monkeypatch.setattr(fused_qlora, "_pallas_fused_qlora", refuse)
    with pytest.raises(RuntimeError, match="mosaic said no"):
        jax.jit(lambda x: fused_qlora_dense(x, qk, leaf, 1.0))(x)

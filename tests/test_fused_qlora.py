"""Int8-dequant + member-LoRA kernel (ops/fused_qlora.py) and ``nn.dense``'s table.

The contract under test, layer by layer:

- **kernel parity** — the Pallas kernel (interpret mode on CPU — the
  ops/attention.py precedent: the CPU tier lowers and *interprets* the
  kernel, only real TPU executes it) matches :func:`xla_fused_qlora`, the
  XLA composition, across {2D, stacked-3D} × {f32,
  bf16 noise factors} × antithetic signs, with tile padding and the
  member-vmap batching pop_eval applies.
- **dense resolution** — ``nn.dense``'s table, base node × adapter leaf:
  every case against the float32 formula, and an int8 node with
  FactoredDelta factors bitwise-equal to the composition on CPU (off the TPU
  it IS that composition).
- **conv contract** — matmul-equivalent ``kernel_q8`` convs (1×1 stride-1,
  non-overlapping p×p stride-p patch embeds) route through the same
  dequant-matmul as ``dense``; everything else (overlapping windows,
  depthwise groups) keeps the dequant-then-conv lowering.
- **gate mechanics** — the shared ops/pallas_gate env/backend reads every
  kernel gate is built on; no gate probes, none falls back after an error.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperscalees_t2i_tpu.lora import FactoredDelta, slice_layer
from hyperscalees_t2i_tpu.models import nn
from hyperscalees_t2i_tpu.ops import pallas_gate
from hyperscalees_t2i_tpu.ops.fused_qlora import (
    conv_kernel_q8_matmul,
    fused_qlora_applies,
    fused_qlora_dense,
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


def _formula(x, qk, leaf, lora_scale, ca=None, cb=None):
    """(base, delta) of ``y = x @ (q8·scale) + lora_scale·(x @ a_k) @ b_k`` in
    plain f32 — the formula itself, no code of the system beyond the pytree.
    The dequantized weights are rounded to the activations' dtype first, as
    ops/quant.dequant_matmul does (a no-op for f32 activations)."""
    f32 = jnp.float32
    w = (qk["q8"].astype(f32) * qk["scale"]).astype(x.dtype).astype(f32)
    x = x.astype(f32)
    a, b = leaf["a"], leaf["b"]
    mat = lambda f, c: f.w.astype(f32) + c * (f.u.astype(f32) @ f.v.astype(f32).T)
    a_k = mat(a, a.c if ca is None else ca)
    b_k = mat(b, b.c if cb is None else cb)
    return x @ w, lora_scale * ((x @ a_k) @ b_k)


def _assert_within_output_rounding(out, ref, dtype):
    """f32 out: 1e-5. bf16 out: one rounding of the f32 result (half a
    spacing, 2^-9 relative) plus the f32 sums' own reordering noise."""
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    if dtype == jnp.float32:
        return _assert_close(out, ref)
    bound = 2.0 ** -8 * np.abs(ref) + 1e-5 * np.abs(ref).max()
    assert np.all(np.abs(out - ref) <= bound), float(np.max(np.abs(out - ref) - bound))


@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("noise_dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_f32_formula(x_dtype, noise_dtype):
    """bf16 ``x`` is what every benchmark cell runs: the base dot then takes
    bf16 operands (``x`` as it arrives, the tile dequantized to bf16) with
    f32 accumulation and the chain stays f32, so the only error left is the
    output's own rounding. The XLA composition is not the reference here: it
    also rounds ``a_k``/``b_k`` and both partial products to bf16."""
    x, qk, leaf = _factored_pair(jax.random.PRNGKey(48), noise_dtype=noise_dtype)
    x = x.astype(x_dtype)
    out = fused_qlora_dense(x, qk, leaf, 2.0, interpret=True)
    assert out.dtype == x_dtype
    base, delta = _formula(x, qk, leaf, 2.0)
    _assert_within_output_rounding(out, base + delta, x_dtype)


@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_grid_of_blocks_under_member_vmap(x_dtype):
    """3 token blocks x 3 dout tiles x 2 members, every member with its own
    activations, noise slice ``u`` and coefficient ``c``. The din-side chain
    is computed once a token block into a VMEM scratch and read at the other
    dout steps: a scratch carried stale across a token block, or across a
    member (vmap puts the member axis in front of the grid), gives another
    member's or another block's ``x @ a_k`` and fails here."""
    members, T, din, dout = 2, 40, 16, 300
    x, qk, leaf = _factored_pair(jax.random.PRNGKey(49), din=din, dout=dout)
    ks = jax.random.split(jax.random.PRNGKey(50), 3)
    xs = jax.random.normal(ks[0], (members, T, din)).astype(x_dtype)
    a, b = leaf["a"], leaf["b"]
    am = FactoredDelta(a.w, jax.random.normal(ks[1], (members, *a.u.shape)), a.v,
                       jnp.array([0.03, -0.05]))
    bm = FactoredDelta(b.w, jax.random.normal(ks[2], (members, *b.u.shape)), b.v,
                       jnp.array([-0.04, 0.02]))
    axes = FactoredDelta(None, 0, None, 0)
    out = jax.vmap(
        lambda xx, aa, bb: fused_qlora_dense(
            xx, qk, {"a": aa, "b": bb}, 1.5, interpret=True, block_t=16, block_n=128
        ),
        in_axes=(0, axes, axes),
    )(xs, am, bm)
    for k in range(members):
        pick = lambda f: FactoredDelta(f.w, f.u[k], f.v, f.c[k])
        base, delta = _formula(xs[k], qk, {"a": pick(am), "b": pick(bm)}, 1.5)
        _assert_within_output_rounding(out[k], base + delta, x_dtype)
    assert not np.allclose(np.asarray(out[0], np.float32), np.asarray(out[1], np.float32))


def test_kernel_lora_path_is_live():
    """What the benchmark's ``correct`` cannot see (PERF.md §2, limit 2): the
    low-rank path carries the member's perturbation. The kernel's output
    minus its output at ``a.c = b.c = 0`` is the formula's perturbation term,
    and minus its base-only output (``lora_scale`` 0) the whole LoRA delta —
    both far above the comparison's noise, in a grid of several blocks."""
    x, qk, leaf = _factored_pair(jax.random.PRNGKey(63), din=32, dout=300)
    x = jax.random.normal(jax.random.PRNGKey(64), (40, 32))
    run = lambda lf, s: fused_qlora_dense(
        x, qk, lf, s, interpret=True, block_t=16, block_n=128
    )
    zero = lambda f: FactoredDelta(f.w, f.u, f.v, jnp.float32(0.0))
    unperturbed = {"a": zero(leaf["a"]), "b": zero(leaf["b"])}
    out, out_c0, out_base = run(leaf, 2.0), run(unperturbed, 2.0), run(leaf, 0.0)
    base, delta = _formula(x, qk, leaf, 2.0)
    _, delta_c0 = _formula(x, qk, leaf, 2.0, ca=0.0, cb=0.0)
    perturbation = np.asarray(delta - delta_c0)
    scale = np.abs(np.asarray(out)).max()
    assert np.abs(perturbation).max() > 1e-2 * scale  # the signal is there to lose
    _assert_close(out_base, base)
    np.testing.assert_allclose(np.asarray(out - out_c0), perturbation, atol=2e-5 * scale)
    np.testing.assert_allclose(np.asarray(out - out_base), np.asarray(delta), atol=2e-5 * scale)


# (rows, din, dout, member axis) of calls the benchmark's cells make
_CELL_CALLS = {
    "sana-attn": (1024, 2240, 2240, 2),
    "sana-caption_proj": (300, 2304, 2240, 2),
    "var-fc1": (2048, 1024, 4096, 4),
    "var-fc2": (2048, 4096, 1024, 4),
    "var-qkv-scale0": (8, 1024, 3072, 4),
    "var-fc1-scale2": (72, 1024, 4096, 4),
    "lm-ffn-gate-decode": (8, 7680, 18432, 8),
}


def _cell_operands(rows, din, dout, r_l=8, r_e=4):
    sd = jax.ShapeDtypeStruct
    bf16, f32 = jnp.bfloat16, jnp.float32
    qk = {"q8": sd((din, dout), jnp.int8), "scale": sd((1, dout), f32)}
    fac = lambda m, n: FactoredDelta(sd((m, n), f32), sd((m, r_e), bf16),
                                     sd((n, r_e), bf16), sd((), f32))
    return sd((rows, din), bf16), qk, fac(din, r_l), fac(r_l, dout)


@pytest.mark.parametrize("call", sorted(_CELL_CALLS))
def test_vmem_estimate_reads_the_declared_blocks(call, monkeypatch):
    """``_kernel_vmem_bytes`` is what elects the blocks, so it has to price
    the blocks and the scratch the kernel really hands to ``pallas_call`` —
    at the cells' call shapes, bf16 activations and noise."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from hyperscalees_t2i_tpu.ops import fused_qlora as fq

    rows, din, dout, _ = _CELL_CALLS[call]
    x, qk, a, b = _cell_operands(rows, din, dout)
    seen = {}

    def recorder(kernel, *, out_shape, grid, in_specs, out_specs, scratch_shapes, **kw):
        def call_(*operands):
            seen.update(grid=grid, scratch=scratch_shapes, specs=[
                (tuple(spec.block_shape), op.dtype)
                for spec, op in zip([*in_specs, out_specs], [*operands, out_shape])
                if spec.memory_space != pltpu.SMEM
            ])
            return jnp.zeros(out_shape.shape, out_shape.dtype)

        return call_

    monkeypatch.setattr(pl, "pallas_call", recorder)
    jax.eval_shape(
        lambda x, qk, a, b: fused_qlora_dense(x, qk, {"a": a, "b": b}, 2.0, interpret=True),
        x, qk, a, b,
    )
    block_t, block_n = fq._fit_blocks(qk["q8"], a, b, fq.BLOCK_T, fq.BLOCK_N, x.dtype)
    block_t = fq._token_blocks(rows, block_t)[0]
    blocks, scratch = fq._declared_blocks(din, a, b, block_t, block_n, x.dtype)
    assert seen["specs"] == [(tuple(shape), jnp.dtype(dt)) for shape, dt in blocks]
    (z,) = seen["scratch"]
    assert (tuple(z.shape), z.dtype) == (scratch[0], jnp.dtype(scratch[1]))
    assert seen["grid"] == (-(-rows // block_t), -(-dout // block_n))
    tile = lambda shape, dt: fq._vmem_tile_bytes(*shape, jnp.dtype(dt).itemsize)
    declared = 2 * sum(tile(*blk) for blk in seen["specs"]) + tile(*scratch)
    total = fq._kernel_vmem_bytes(qk["q8"], a, b, block_t, block_n, x.dtype)
    # the rest is the body's own values: at least the f32 view of x the chain
    # reads and the converted base tile
    assert total - declared >= tile((block_t, din), jnp.float32) + tile((din, block_n), x.dtype)
    assert total <= fq.VMEM_BUDGET_BYTES


@pytest.fixture(scope="module")
def v5e_chip():
    """A described (not attached) TPU v5e, for Mosaic's verdict without a
    chip. Described inside the fixture: only the worker that runs this file
    loads libtpu (on-chip-measurement guide, section 2)."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize(
    "call", ["sana-attn", "var-fc2", "lm-ffn-gate-decode", "var-fc1-scale2", "sana-caption_proj"])
def test_mosaic_compiles_the_largest_cell_calls(call, v5e_chip):
    """The two largest calls of the benchmark's cells — Sana's attention
    site (the most work) and VAR's fc2 (the widest contraction, where
    ``_fit_blocks`` halves) — compile for a v5e at their real shapes with the
    member axis in front: block shapes, the scratch and the VMEM limit are
    Mosaic's to refuse, and the interpreter accepts anything. PR 28: and the
    calls whose token block is several whole members — the ``lm_ar`` cell's
    dense-FFN gate at a decode step (8 members x 8 rows), a small VAR scale
    (4 x 72) and Sana's caption projection (2 x 300, rows that are no
    multiple of 16: at 128 rows a member or more the rule keeps the one-member
    call, batched) — whose masks and stacked thin operands are Mosaic's to
    refuse too."""
    from hyperscalees_t2i_tpu.tools import kernel_check

    rows, din, dout, members = _CELL_CALLS[call]
    case = kernel_check._qlora_case(call, rows, din, dout, members=members)
    rec = kernel_check.run_case(case, compile_only_device=v5e_chip)
    assert rec["ok"] and rec["compiled_for"] == "TPU v5 lite"


@pytest.mark.parametrize("label", [
    "var scale 0:", "var scale 8, member axis 4", "var scale 9, member axis 4",
    "infinity cross-attn: q[8,256,16,64] vs text[8,16,16,64] + mask",
])
def test_mosaic_compiles_decode_attention_with_all_heads_a_step(label, v5e_chip):
    """``ops/attention.py`` (here because one file of a test run may load
    libtpu) at the VAR cell's smallest call and its two largest — scale 8's
    last query block holds 41 rows of 128, scale 9's last kv block 168 of 512,
    the rest zeros — under the member axis, and at Infinity's masked
    cross-attention: sixteen 64-lane slices of a 1024-lane block (every other
    one off a 128-lane tile), 8 MB of blocks and scratch under a raised VMEM
    limit are Mosaic's to refuse, and the interpreter accepts anything.
    The one-head-a-step twin ``kernel_check`` runs beside it compiles too."""
    from hyperscalees_t2i_tpu.tools import kernel_check

    case = next(c for c in kernel_check.cases()
                if c.kernel == "decode_attention" and c.label.startswith(label))
    assert case.twin[0] == "one_head_a_step" and case.twin_exact
    rec = kernel_check.run_case(case, compile_only_device=v5e_chip)
    assert rec["ok"] and rec["compiled_for"] == "TPU v5 lite"


@pytest.mark.parametrize("members", [False, True], ids=["64-sequences", "member-axis-8x8"])
def test_mosaic_compiles_the_gated_delta_step_of_the_hybrid_cell(members, v5e_chip):
    """The third default-on kernel (``ops/gated_delta.py``, here because one
    file of a test run may load libtpu) at the hybrid cell's call, a DeltaNet
    layer's 64 states ``f32[64, 32, 128, 128]``: Mosaic takes the in-kernel
    transpose of the ``q``/``k`` rows and the 8 MB of state blocks; the state
    comes out in the buffer it went in by; and under ``vmap`` over the members
    nothing as large as the state exists beside the call's own operand and
    result — the batching is a grid axis, not a copy."""
    from jax.sharding import SingleDeviceSharding
    from hyperscalees_t2i_tpu.tools import kernel_check

    case = next(c for c in kernel_check.cases()
                if c.kernel == "gated_delta_step" and ("member axis" in c.label) == members)
    rec = kernel_check.run_case(case, compile_only_device=v5e_chip)
    assert rec["ok"] and rec["compiled_for"] == "TPU v5 lite"
    s = SingleDeviceSharding(v5e_chip)
    args = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                                  jax.eval_shape(case.make, jax.random.PRNGKey(0)))
    compiled = jax.jit(case.kernel_fn, donate_argnums=5).lower(*args).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    state_bytes = 64 * 32 * 128 * 128 * 4
    assert mem.alias_size_in_bytes == state_bytes and mem.temp_size_in_bytes < state_bytes // 16
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    from hyperscalees_t2i_tpu.obs.xla_cost import kv_cache_whole_ops
    assert kv_cache_whole_ops(compiled, args[5].shape) == {"custom-call": 1}


@pytest.mark.parametrize("family", ["mla", "mla-streams", "hybrid"])
def test_the_decode_scan_reads_the_head_as_the_tpu_compiler_builds_it(family, v5e_chip, tmp_path, monkeypatch):
    """``models/lm.generate`` under ``vmap`` over eight members at toy widths
    in bfloat16 over an int8 head, compiled for a v5e (PR 34; here because one
    file of a test run may load libtpu). (1) ``lm_head_whole_ops`` as
    ``record_compile`` counts it: nothing is as large as the whole head, where
    the parent's form (``fam.head(params, cfg, x)[:, lo:hi]``) carries it whole
    through the scan (at the cells' widths it also dequantizes it whole there,
    one ``fusion`` a position: PERF.md §5). (2) The hybrid family's logits
    leave the dot's own fusion as ONE float32 array, which the sampler and the
    probe both read: without ``lm_hybrid.head``'s ``optimization_barrier`` that
    fusion hands the sampler a second, bf16 result (the accumulator rounded) —
    on the chip the hybrid cell then drew ids from other logits than it probed,
    and than ``expected/`` records. (The MLA family's step has always had its
    sampler on the rounded result, in the parent's program too; it is left so.)"""
    import re

    from jax.sharding import SingleDeviceSharding
    from hyperscalees_t2i_tpu.models import lm
    from hyperscalees_t2i_tpu.obs import xla_cost
    from tests import test_lm_head_columns as cols

    cfg, _, params = cols.toy(tmp_path, monkeypatch, family, cols.OFFSETS["off-128s"], "int8", torch_dtype="bfloat16")
    assert cfg.compute_dtype == jnp.bfloat16
    M, B = 8, 8
    s = SingleDeviceSharding(v5e_chip)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=s)
    args = (jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), params), sds((B, cfg.max_prompt_len), jnp.int32),
            sds((B,), jnp.int32), sds((M, 2), jnp.uint32))

    def compiled_generate():
        def members(params, prompt, lens, keys):  # the head is shared, the sequences are a member's own
            return jax.vmap(lambda k: lm.generate(params, cfg, prompt, lens, k, decode=False))(keys)

        with jax.default_matmul_precision("default"):  # the program as deployed, not conftest's "highest"
            compiled = jax.jit(members).lower(*args).compile()
        shape = xla_cost.program_record(site="test", label="generate")["geometry"]["lm_head_shape"]  # as noted
        assert tuple(shape) == (cfg.hidden_size, cols.ROWS_HELD)
        return compiled, xla_cost.kv_cache_whole_ops(compiled, shape)

    compiled, whole = compiled_generate()
    assert whole == {}
    text = compiled.as_text()
    fused = set(re.findall(r"calls=%?([\w.\-]+)", text))
    logits, current = [], None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = re.match(r"(?:ENTRY )?%?([\w.\-]+) ", line)
            current = m.group(1) if m else current
        elif current not in fused and "lm_decode_step/lm_head" in line:
            result = line.split(" = ", 1)[1].split("(", 1)[0] if not line.split(" = ", 1)[1].startswith("(") \
                else line.split(" = ", 1)[1].split(") ", 1)[0]
            logits += re.findall(rf"([a-z0-9]+)\[{M},{B},{cols.IMAGE_VOCAB}\]", result)
    if family == "hybrid":
        assert logits == ["f32"], logits

    fam = cfg.family()
    monkeypatch.setattr(type(cfg), "family", lambda self: fam._replace(head=cols.parents_source(fam, cfg, params["head"])))
    _, whole = compiled_generate()
    assert whole.get("while", 0) >= 1, whole


# ---------------------------------------------------------------------------
# the member axis: a token block made of whole members (PR 28)
# ---------------------------------------------------------------------------

_MEMBER_AXES = FactoredDelta(None, 0, 0, 0)  # what pop_eval batches: u, v, c


def _members(key, M, T, din=32, dout=300, r_l=8, r_e=4, x_dtype=jnp.float32,
             noise_dtype=jnp.bfloat16):
    """(x [M, T, din], qk, a, b): ``M`` members over one int8 base, each with
    its own activations, noise slices and coefficient; ``w`` shared. dout 300
    is not a multiple of the tests' 128-channel tile."""
    ks = jax.random.split(key, 10)
    qk = quantize_kernel(jax.random.normal(ks[0], (din, dout)) * 0.1)
    noise = lambda k, *shape: jax.random.normal(k, shape).astype(noise_dtype)
    a = FactoredDelta(jax.random.normal(ks[1], (din, r_l)), noise(ks[2], M, din, r_e),
                      noise(ks[3], M, r_l, r_e), 0.03 * jax.random.normal(ks[4], (M,)))
    b = FactoredDelta(jax.random.normal(ks[5], (r_l, dout)), noise(ks[6], M, r_l, r_e),
                      noise(ks[7], M, dout, r_e), 0.04 * jax.random.normal(ks[8], (M,)))
    return jax.random.normal(ks[9], (M, T, din)).astype(x_dtype), qk, a, b


def _member(f, k):
    return FactoredDelta(f.w, f.u[k], f.v[k], f.c[k])


def _by_vmap(x, qk, a, b, lora_scale=2.0, axes=_MEMBER_AXES, x_axis=0, **kw):
    return jax.vmap(
        lambda xx, aa, bb: fused_qlora_dense(
            xx, qk, {"a": aa, "b": bb}, lora_scale, interpret=True, **kw),
        in_axes=(x_axis, axes, axes),
    )(x, a, b)


def _by_member(x, qk, a, b, lora_scale=2.0, **kw):
    """The per-member form: the same kernel called once a member."""
    return jnp.stack([
        fused_qlora_dense(x[k], qk, {"a": _member(a, k), "b": _member(b, k)},
                          lora_scale, interpret=True, **kw)
        for k in range(x.shape[0])
    ])


def _recorded_calls(monkeypatch):
    """Every ``pl.pallas_call`` the code under test builds, as a dict of its
    grid, metadata, scratch and (block shape, dtype) of each VMEM operand
    with the output's last; the call itself returns zeros."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    calls = []

    def recorder(kernel, *, out_shape, grid, in_specs, out_specs, scratch_shapes,
                 metadata=None, **kw):
        def call_(*operands):
            calls.append(dict(grid=grid, scratch=scratch_shapes, metadata=metadata, specs=[
                (tuple(d for d in spec.block_shape if d is not None), op.dtype)
                for spec, op in zip([*in_specs, out_specs], [*operands, out_shape])
                if spec.memory_space != pltpu.SMEM
            ]))
            return jnp.zeros(out_shape.shape, out_shape.dtype)

        return call_

    monkeypatch.setattr(pl, "pallas_call", recorder)
    return calls


# (M, T, block_t) -> members a token block: every one when their rows fit, the
# largest divisor of M otherwise (a block short of the whole array is made of
# whole sublane tiles), 1 when nothing divides, a member alone fills it, or a
# member has the MXU's 128 rows or more
_GROUPINGS = {
    (8, 8, 128): 8, (4, 72, 512): 4, (3, 8, 128): 3, (3, 8, 16): 1, (1, 8, 128): 1,
    (6, 5, 128): 6, (4, 40, 128): 2, (4, 72, 128): 1, (6, 5, 16): 1,
    (2, 128, 512): 1, (4, 200, 1024): 1,
}


@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,T,block_t", sorted(_GROUPINGS))
def test_member_groups_match_the_per_member_kernel(M, T, block_t, x_dtype):
    """Under ``vmap`` the kernel's own rule puts ``g`` whole members into a
    token block (rows not a multiple of 8, a dout that does not fill its last
    tile, groups short of the whole chunk). Every member's rows come out as
    the per-member kernel's — the f32 sums' order aside, which belongs to the
    machine's dot — and as the formula's; where ``g`` is 1 the call is the
    per-member one and the outputs are its outputs bit for bit."""
    from hyperscalees_t2i_tpu.ops.fused_qlora import _members_per_block

    g = _members_per_block(M, T, block_t, 8, 4, jnp.dtype(x_dtype).itemsize)
    assert g == _GROUPINGS[M, T, block_t]
    x, qk, a, b = _members(jax.random.PRNGKey(100 * M + T), M, T, x_dtype=x_dtype)
    kw = dict(block_t=block_t, block_n=128)
    out, per = _by_vmap(x, qk, a, b, **kw), _by_member(x, qk, a, b, **kw)
    assert out.shape == per.shape == (M, T, 300) and out.dtype == x_dtype
    if g == 1:
        np.testing.assert_array_equal(np.asarray(out, np.float32), np.asarray(per, np.float32))
    for k in range(M):
        base, delta = _formula(x[k], qk, {"a": _member(a, k), "b": _member(b, k)}, 2.0)
        if x_dtype == jnp.float32:  # f32 sums of ~300 terms, relative to the largest
            for ref in (base + delta, per[k]):
                np.testing.assert_allclose(
                    np.asarray(out[k]), np.asarray(ref), atol=2e-6 * float(jnp.abs(ref).max()))
        else:  # two bf16 roundings of f32 results an ulp apart: at most one spacing
            _assert_within_output_rounding(out[k], base + delta, x_dtype)
            got, ref = np.asarray(out[k], np.float32), np.asarray(per[k], np.float32)
            assert np.all(np.abs(got - ref) <= 2.0 ** -7 * np.abs(ref) + 1e-5 * np.abs(ref).max())
    if x_dtype == jnp.float32:  # the file's tolerance of the XLA composition
        ref = jax.vmap(
            lambda xx, aa, bb: xla_fused_qlora(xx, qk, {"a": aa, "b": bb}, 2.0),
            in_axes=(0, _MEMBER_AXES, _MEMBER_AXES),
        )(x, a, b)
        scale = float(jnp.abs(ref).max())
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5 * scale, rtol=1e-5)


@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,T", [(8, 8), (3, 8), (6, 5), (4, 40)])
def test_member_groups_leave_the_base_term_bit_identical(M, T, x_dtype):
    """``lora_scale`` 0 leaves the base dot. Integer activations and
    power-of-two scales make every product and every partial sum exact in
    f32, so whatever order a machine's dot sums in, a row's base term is one
    number: grouped and per-member must agree bit for bit, and with the
    formula. (On the chip, where the order is the MXU's, ``tools/kernel_check``
    counts the outputs that differ on seeded normal data.)"""
    x, qk, a, b = _members(jax.random.PRNGKey(7), M, T, din=512, x_dtype=x_dtype)
    x = jnp.round(x.astype(jnp.float32) * 3).astype(x_dtype)
    qk = {"q8": qk["q8"], "scale": 2.0 ** -jnp.round(6 + jnp.abs(qk["scale"]) * 1e3 % 3)}
    kw = dict(lora_scale=0.0, block_t=128, block_n=128)
    out, per = _by_vmap(x, qk, a, b, **kw), _by_member(x, qk, a, b, **kw)
    np.testing.assert_array_equal(np.asarray(out, np.float32), np.asarray(per, np.float32))
    base = jnp.stack([_formula(x[k], qk, {"a": _member(a, k), "b": _member(b, k)}, 0.0)[0]
                      for k in range(M)])
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(base.astype(x_dtype), np.float32))


def test_member_groups_antithetic_pair_shares_its_noise():
    """An antithetic pair is one (u, v) with opposite ``c``: only ``c`` (and
    the activations) carry the member axis, the rule gives the rest one, and
    the pair's outputs are base ± perturbation around the unperturbed."""
    x, qk, a, b = _members(jax.random.PRNGKey(71), 2, 8)
    pair = lambda f, c: FactoredDelta(f.w, f.u[0], f.v[0], jnp.array([c, -c]))
    a, b = pair(a, 0.03), pair(b, -0.04)
    axes = FactoredDelta(None, None, None, 0)
    out = _by_vmap(x, qk, a, b, axes=axes, block_t=128, block_n=128)
    for k in range(2):
        pick = lambda f: FactoredDelta(f.w, f.u, f.v, f.c[k])
        base, delta = _formula(x[k], qk, {"a": pick(a), "b": pick(b)}, 2.0)
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(base + delta),
                                   atol=2e-6 * float(jnp.abs(base + delta).max()))
    # shared activations too (a caption every member reads): x has no axis
    out = _by_vmap(x[0], qk, a, b, axes=axes, x_axis=None, block_t=128, block_n=128)
    zero = lambda f: FactoredDelta(f.w, f.u, f.v, jnp.float32(0.0))
    mid = fused_qlora_dense(x[0], qk, {"a": zero(a), "b": zero(b)}, 2.0, interpret=True)
    half = np.asarray(out[0] - out[1]) / 2
    assert np.abs(half).max() > 1e-3 * np.abs(np.asarray(mid)).max()
    # the pair straddles the unperturbed output up to the second-order term c_a·c_b
    second = np.abs(np.asarray((out[0] + out[1]) / 2 - mid)).max()
    assert second < 0.1 * np.abs(half).max()


def test_member_groups_leak_nothing_between_members():
    """A row keeps only its own member's ``r_e`` columns (the others are
    exact zeros before they meet a ``v``): another member's noise, however
    large, does not move a bit of this member's output."""
    x, qk, a, b = _members(jax.random.PRNGKey(72), 4, 8)
    kw = dict(block_t=128, block_n=128)
    out = _by_vmap(x, qk, a, b, **kw)
    loud = lambda f: FactoredDelta(
        f.w, f.u.at[2].multiply(1e3), f.v.at[2].multiply(-7.0), f.c.at[2].set(0.9))
    out2 = _by_vmap(x, qk, loud(a), loud(b), **kw)
    for k in (0, 1, 3):
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(out2[k]))
    assert not np.allclose(np.asarray(out[2]), np.asarray(out2[2]))


def test_member_axis_rule_by_what_is_batched(monkeypatch):
    """The rule engages on the pattern pop_eval hands it (base and ``w``
    shared). A base per member keeps Pallas's default batching of the
    one-member call, and so does ``g`` = 1: the parent's call — a member's
    own rows a token block, the one-member blocks — with ``members_per_block``
    in the call's metadata either way."""
    from hyperscalees_t2i_tpu.ops import fused_qlora as fq

    M, T, din, dout = 4, 8, 32, 300
    x, qk, a, b = _members(jax.random.PRNGKey(73), M, T, din=din, dout=dout)
    qks = jax.vmap(lambda s: quantize_kernel(
        jax.random.normal(jax.random.PRNGKey(0), (din, dout)) * s))(jnp.arange(1.0, M + 1))
    per_base = lambda xx, q, aa, bb: fused_qlora_dense(
        xx, q, {"a": aa, "b": bb}, 2.0, interpret=True, block_t=128, block_n=128)
    out = jax.vmap(per_base, in_axes=(0, 0, _MEMBER_AXES, _MEMBER_AXES))(x, qks, a, b)
    for k in range(M):
        one = per_base(x[k], jax.tree_util.tree_map(lambda t: t[k], qks),
                       _member(a, k), _member(b, k))
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(one))

    calls = _recorded_calls(monkeypatch)

    def last_call(thunk):
        # custom_vmap traces the one-member call before vmap asks its rule:
        # the call that reaches the program is the last one built
        del calls[:]
        thunk()
        return calls[-1]

    made = [
        last_call(lambda: jax.vmap(per_base, in_axes=(0, 0, _MEMBER_AXES, _MEMBER_AXES))(
            x, qks, a, b)),                                            # a base a member
        last_call(lambda: _by_vmap(x, qk, a, b, block_t=16, block_n=128)),   # 2 x 8 rows fit 16
        last_call(lambda: _by_vmap(x, qk, a, b, block_t=8, block_n=128)),    # a member fills it
        last_call(lambda: _by_vmap(x, qk, a, b, block_t=128, block_n=128)),  # every member
        last_call(lambda: per_base(x[0], qk, _member(a, 0), _member(b, 0))),  # no vmap at all
    ]
    want = [(T, 1, 1), (2 * T, 2, 2), (T, 1, 1), (4 * T, 4, 1), (T, 1, 1)]  # block rows, g, groups
    for c, (bt, g, groups) in zip(made, want):
        assert c["metadata"] == {"members_per_block": str(g)}
        assert c["grid"] == (groups, 3)
        blocks, scratch = fq._declared_blocks(
            din, _member(a, 0), _member(b, 0), bt, 128, x.dtype, g)
        assert c["specs"] == [(tuple(shape), jnp.dtype(dt)) for shape, dt in blocks]
        (z,) = c["scratch"]
        assert (tuple(z.shape), z.dtype) == (scratch[0], jnp.dtype(scratch[1]))


def test_members_per_block_reaches_the_program_record():
    """``programs.jsonl`` says for every ``fused_qlora`` site how many
    members share a token block: the call's metadata, read back from the
    lowered text (here lowered for the TPU without one) beside
    ``pallas_kernels``."""
    from hyperscalees_t2i_tpu.obs.xla_cost import stablehlo_stats

    x, qk, a, b = _members(jax.random.PRNGKey(74), 4, 8, din=128, dout=256, x_dtype=jnp.bfloat16)

    def two_sites(x, a, b):
        run = lambda xx, aa, bb, bt: fused_qlora_dense(
            xx, qk, {"a": aa, "b": bb}, 2.0, use_pallas=True, block_t=bt, block_n=128)
        grouped = jax.vmap(lambda *m: run(*m, 128), in_axes=(0, _MEMBER_AXES, _MEMBER_AXES))
        alone = jax.vmap(lambda *m: run(*m, 8), in_axes=(0, _MEMBER_AXES, _MEMBER_AXES))
        return grouped(x, a, b) + alone(x, a, b) + run(x[0], _member(a, 0), _member(b, 0), 128)

    lowered = jax.jit(two_sites).trace(x, a, b).lower(lowering_platforms=("tpu",))
    stats = stablehlo_stats(lowered)
    assert stats["pallas_kernels"] == {"fused_qlora": 3}
    assert stats["pallas_members_per_block"] == {"fused_qlora": {"4": 1, "1": 2}}


def test_member_groups_inside_shard_map():
    """pop_eval vmaps a member chunk inside ``shard_map`` over the pop axis
    (the four-chip cell): the kernel's own batching rule has to trace there
    as it does under a bare ``vmap``."""
    from jax.sharding import Mesh, PartitionSpec as P

    from hyperscalees_t2i_tpu.parallel.mesh import shard_map

    M = 4  # two members a device on a two-device pop axis
    x, qk, a, b = _members(jax.random.PRNGKey(75), M, 8)
    mesh = Mesh(np.array(jax.devices()[:2]), ("pop",))
    spec = FactoredDelta(P(), P("pop"), P("pop"), P("pop"))
    local = lambda x, a, b: _by_vmap(x, qk, a, b, block_t=128, block_n=128)
    out = shard_map(local, mesh=mesh, in_specs=(P("pop"), spec, spec),
                    out_specs=P("pop"), check_vma=False)(x, a, b)
    ref = _by_member(x, qk, a, b, block_t=128, block_n=128)
    _assert_close(out, ref, tol=1e-5 * float(jnp.abs(ref).max()))


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
        BLOCK_N,
        BLOCK_T,
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
        qk["q8"], a, b, MIN_BLOCK, MIN_BLOCK, jnp.float32
    ) > VMEM_BUDGET_BYTES
    assert _fit_blocks(qk["q8"], a, b, BLOCK_T, BLOCK_N, jnp.float32) is None
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
        fitted = _fit_blocks(q["q8"], af, bf, BLOCK_T, BLOCK_N, jnp.bfloat16)
        assert fitted is not None, (din_, dout_)
        bt, bn = fitted
        assert bt >= MIN_BLOCK and bn >= MIN_BLOCK
        assert _kernel_vmem_bytes(q["q8"], af, bf, bt, bn, jnp.bfloat16) <= VMEM_BUDGET_BYTES
    # and a toy layer sits far under the budget at full blocks
    _, qk_s, leaf_s = _factored_pair(jax.random.PRNGKey(62))
    assert _fit_blocks(
        qk_s["q8"], leaf_s["a"], leaf_s["b"], BLOCK_T, BLOCK_N, jnp.float32
    ) == (BLOCK_T, BLOCK_N)


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

@pytest.mark.parametrize("leaf_kind", ["none", "raw", "factored"])
@pytest.mark.parametrize("base", ["kernel", "kernel_q8"])
def test_dense_table(base, leaf_kind):
    """``nn.dense``'s table (its docstring), base node × adapter leaf: each
    lowering against the float32 formula; off the TPU an int8 base under a
    member's factored leaf is bit for bit the sum of the two lowerings a site
    takes with only one of them (:func:`xla_fused_qlora`), and raw factors
    never reach ``fused_qlora_dense``."""
    x, qk, factored = _factored_pair(jax.random.PRNGKey(45))
    raw = {"a": jax.random.normal(jax.random.PRNGKey(1), (16, 4)),
           "b": jax.random.normal(jax.random.PRNGKey(2), (4, 24))}
    leaf = {"none": None, "raw": raw, "factored": factored}[leaf_kind]
    bias = jnp.linspace(0, 1, 24)
    w = dequantize_kernel(qk, jnp.float32)
    node = {"kernel": w, "bias": bias} if base == "kernel" else {"kernel_q8": qk, "bias": bias}
    y = nn.dense(node, x, lora=leaf, lora_scale=2.0)

    want = x @ w
    if leaf_kind == "raw":
        want = want + ((x @ raw["a"]) @ raw["b"]) * 2.0
    elif leaf_kind == "factored":
        want = want + _formula(x, qk, factored, 2.0)[1]
    want = want + bias
    _assert_close(y, want, tol=1e-4)

    assert fused_qlora_applies(factored) and not fused_qlora_applies(raw)
    if base == "kernel_q8" and leaf_kind == "factored":
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(xla_fused_qlora(x, qk, factored, 2.0) + bias)
        )
    elif leaf_kind != "factored":
        # no member's leaf: the program is the plain one, bit for bit
        np.testing.assert_array_equal(np.asarray(y), np.asarray(want))


# ---------------------------------------------------------------------------
# conv/patch-embed: the same dequant-matmul as dense
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


def test_conv_nonequivalent_keeps_conv_lowering():
    """Overlapping windows, depthwise groups, and a non-divisible grid keep
    the dequant-then-conv path, bit for bit."""
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


# ---------------------------------------------------------------------------
# shared gate mechanics (ops/pallas_gate.py)
# ---------------------------------------------------------------------------

def test_the_environment_switches_the_source_names():
    """Two kernel opt-outs and one size override: every ``HSES_*`` name in the
    package and the chip smoke. A fourth is a new option: it needs its reason
    (PERF.md) and this list."""
    import re
    from pathlib import Path

    import hyperscalees_t2i_tpu

    pkg = Path(hyperscalees_t2i_tpu.__file__).parent
    sources = [*pkg.rglob("*.py"), pkg.parent / "chip_smoke.py"]
    names = {n for f in sources for n in re.findall(r"HSES_[A-Z0-9_]+", f.read_text())}
    assert names == {"HSES_FUSED_QLORA_PALLAS", "HSES_USE_PALLAS", "HSES_BASE_QUANT_MIN_SIZE"}
    assert set(pallas_gate.PALLAS_ENV_FLAGS) == names - {"HSES_BASE_QUANT_MIN_SIZE"}


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

    rec = {"base_quant": "int8", "pallas_env": {"HSES_FUSED_QLORA_PALLAS": "1"}}
    assert kernel_marks(rec) == ["q8", "P:qlora"]


def _as_tpu(monkeypatch, on: bool):
    """Every gate module binds backend_is_tpu at import; flip them all."""
    from hyperscalees_t2i_tpu.ops import fused_qlora, gated_delta

    for mod in (pallas_gate, fused_qlora, gated_delta):
        monkeypatch.setattr(mod, "backend_is_tpu", lambda: on)


def test_gates_select_by_backend_and_flag_alone(monkeypatch):
    """Selection is by platform (and, per layer, shape) plus the one
    tri-state flag: the kernels are on exactly on a TPU backend unless
    opted out, and a request never forces a kernel onto a backend that cannot
    run Mosaic. ``gated_delta_step`` (PR 32) has no flag: the backend and the
    call's shapes alone select it."""
    for f in pallas_gate.PALLAS_ENV_FLAGS:
        monkeypatch.delenv(f, raising=False)
    off = {"fused_qlora": False, "decode_attention": False, "gated_delta_step": False}
    assert pallas_gate.selected_kernels() == off
    for f in pallas_gate.PALLAS_ENV_FLAGS:  # =1 off the TPU selects nothing
        monkeypatch.setenv(f, "1")
    assert pallas_gate.selected_kernels() == off

    _as_tpu(monkeypatch, True)
    assert pallas_gate.selected_kernels() == {k: True for k in off}
    for f in pallas_gate.PALLAS_ENV_FLAGS:
        monkeypatch.delenv(f)
    assert pallas_gate.selected_kernels() == {k: True for k in off}
    # the opt-out wins where the kernel is the backend default — the
    # pallas_env stamp ("flash-") has to describe the path that ran
    monkeypatch.setenv("HSES_USE_PALLAS", "0")
    monkeypatch.setenv("HSES_FUSED_QLORA_PALLAS", "off")
    assert pallas_gate.selected_kernels() == {**off, "gated_delta_step": True}


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

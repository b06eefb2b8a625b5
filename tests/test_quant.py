"""ops/quant.py coverage (ISSUE 10): per-geometry round-trip bounds, the
``min_size`` skip policy, int8 nodes through ``slice_stacked``/``conv2d``/
``glumb_conv`` (the 4D-conv mismatch regression), block-scale (GGUF Q8_0)
dequant, the ``--base_quant`` knob resolver, and end-to-end tiny-rung parity:
per-member reward rows and the θ trajectory with an int8 frozen base must
track the float base within tested tolerances.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperscalees_t2i_tpu.models import nn
from hyperscalees_t2i_tpu.ops.quant import (
    DEFAULT_MIN_SIZE,
    dequantize_kernel,
    kernel_shape,
    maybe_quantize_tree,
    quantize_kernel,
    quantize_tree,
    resolve_base_quant_min_size,
    tree_int8_bytes,
)


# ---------------------------------------------------------------------------
# quantize/dequantize round trip per kernel geometry
# ---------------------------------------------------------------------------

GEOMETRIES = {
    "dense-2d": (64, 96),
    "stacked-3d": (3, 64, 96),
    "conv-4d": (3, 3, 32, 48),
    "stacked-conv-5d": (4, 3, 3, 16, 48),
}


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_roundtrip_error_bound(name):
    """|deq(quant(w)) − w| ≤ scale/2 elementwise — the symmetric-int8
    rounding bound, per output channel (the scale is that channel's
    amax/127, so the bound is relative to the channel's own range)."""
    shape = GEOMETRIES[name]
    w = jax.random.normal(jax.random.PRNGKey(3), shape) * 0.1
    qk = quantize_kernel(w)
    assert qk["q8"].dtype == jnp.int8 and qk["q8"].shape == w.shape
    # scale broadcastable, output axis preserved, stack axis (odd ranks) kept
    assert qk["scale"].shape[-1] == shape[-1]
    if len(shape) % 2:
        assert qk["scale"].shape[0] == shape[0]
    wd = dequantize_kernel(qk, jnp.float32)
    err = jnp.abs(wd - w)
    bound = qk["scale"] * 0.5 + 1e-7
    assert bool(jnp.all(err <= bound)), float(jnp.max(err - bound))


def test_quantize_kernel_rejects_vectors():
    with pytest.raises(ValueError, match="at least 2D"):
        quantize_kernel(jnp.zeros((8,)))


# ---------------------------------------------------------------------------
# tree policy
# ---------------------------------------------------------------------------

def _tree():
    k = jax.random.PRNGKey(0)
    return {
        "big": {"kernel": jax.random.normal(k, (64, 64)), "bias": jnp.zeros(64)},
        "small": {"kernel": jax.random.normal(k, (4, 4))},
        "conv": {"kernel": jax.random.normal(k, (3, 3, 16, 16)) * 0.1,
                 "bias": jnp.zeros(16)},
        "norm": {"scale": jnp.ones(64)},
        "stack": [{"kernel": jax.random.normal(k, (2, 32, 32))}],
    }


def test_min_size_skip_policy():
    q = quantize_tree(_tree(), min_size=1024)
    assert "kernel_q8" in q["big"] and "kernel" not in q["big"]
    assert "bias" in q["big"]
    # below the floor: untouched (same leaf object, not just equal)
    assert "kernel" in q["small"]
    assert "kernel_q8" in q["conv"]  # 2304 params ≥ 1024
    assert "kernel_q8" in q["stack"][0]
    assert q["norm"] == {"scale": q["norm"]["scale"]}  # non-kernel node intact

    # everything below a huge floor stays float
    q2 = quantize_tree(_tree(), min_size=1 << 20)
    assert all("kernel" in q2[k] for k in ("big", "small", "conv"))


def test_quantize_tree_idempotent():
    q = quantize_tree(_tree(), min_size=16)
    q2 = quantize_tree(q, min_size=16)
    np.testing.assert_array_equal(
        np.asarray(q["big"]["kernel_q8"]["q8"]),
        np.asarray(q2["big"]["kernel_q8"]["q8"]),
    )


def test_predicate_filters_paths():
    q = quantize_tree(_tree(), min_size=16,
                      predicate=lambda path, w: "conv" not in path)
    assert "kernel_q8" in q["big"]
    assert "kernel" in q["conv"]


def test_maybe_quantize_knob(monkeypatch):
    t = _tree()
    assert maybe_quantize_tree(t, "off") is t  # untouched, same object
    q = maybe_quantize_tree(t, "int8", min_size=32)
    assert "kernel_q8" in q["big"]
    assert "kernel" in q["small"]  # 16 params < 32
    with pytest.raises(ValueError, match="base_quant"):
        maybe_quantize_tree(t, "int4")
    # env floor override (the tiny-rung tests rely on it)
    assert resolve_base_quant_min_size() == DEFAULT_MIN_SIZE
    monkeypatch.setenv("HSES_BASE_QUANT_MIN_SIZE", "32")
    assert resolve_base_quant_min_size() == 32
    assert resolve_base_quant_min_size(7) == 7
    assert tree_int8_bytes(q) == sum(
        int(np.prod(s)) for s in ((64, 64), (3, 3, 16, 16), (2, 32, 32))
    )


# ---------------------------------------------------------------------------
# int8 nodes through the nn consumers (the conv-4D mismatch regression)
# ---------------------------------------------------------------------------

def test_conv2d_consumes_quantized_node():
    """The ISSUE-10 satellite regression: quantize_tree quantizes a 4D conv
    kernel and conv2d must resolve kernel_q8 instead of KeyErroring."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 16))
    node = {"kernel": jax.random.normal(jax.random.PRNGKey(2), (3, 3, 16, 24)) * 0.1,
            "bias": jnp.ones(24) * 0.5}
    qnode = quantize_tree({"c": node}, min_size=1)["c"]
    assert "kernel_q8" in qnode
    y = nn.conv2d(node, x)
    yq = nn.conv2d(qnode, x)  # KeyError before the fix
    assert yq.shape == y.shape
    # dequantized conv tracks the float conv within the per-channel bound
    # (3·3·16 MACs of ≤scale/2 error each, against O(1) activations)
    np.testing.assert_allclose(np.asarray(yq), np.asarray(y), atol=0.08)


def test_kernel_shape_on_quantized():
    """(``nn.dense`` over a quantized node: tests/test_fused_qlora.py's table.)"""
    node = {"kernel": jax.random.normal(jax.random.PRNGKey(4), (64, 32)) * 0.2,
            "bias": jnp.zeros(32)}
    qnode = quantize_tree({"d": node}, min_size=1)["d"]
    assert kernel_shape(node) == (64, 32)
    assert kernel_shape(qnode) == (64, 32)
    assert nn.kernel_shape(qnode) == (64, 32)


def test_slice_stacked_int8():
    node = {"kernel": jax.random.normal(jax.random.PRNGKey(6), (3, 16, 24)),
            "bias": jnp.arange(3 * 24, dtype=jnp.float32).reshape(3, 24)}
    qnode = quantize_tree({"s": node}, min_size=1)["s"]
    sl = nn.slice_stacked(qnode, 1)
    assert sl["kernel_q8"]["q8"].shape == (16, 24)
    assert sl["kernel_q8"]["scale"].shape == (1, 24)
    np.testing.assert_array_equal(np.asarray(sl["bias"]), np.asarray(node["bias"][1]))
    # layer slice of the quantized stack == quantization of the layer slice
    per_layer = quantize_kernel(node["kernel"][1])
    np.testing.assert_array_equal(
        np.asarray(sl["kernel_q8"]["q8"]), np.asarray(per_layer["q8"])
    )


@pytest.mark.parametrize("family", ["msvq", "bsq"])
def test_phi_apply_consumes_quantized_node(family):
    """The residual-blend φ convs are a stacked-conv node like any other: with
    the floor low enough to quantize them ``phi_apply`` raised ``KeyError:
    'kernel'`` (it indexed the float kernel behind ops/quant.py's back), in
    VAR's MSVQ and in Infinity's BSQ alike."""
    from hyperscalees_t2i_tpu.models import bsq, msvq

    if family == "msvq":
        mod, cfg = msvq, msvq.MSVQConfig(
            vocab_size=16, c_vae=8, patch_nums=(1, 2, 4), phi_partial=2, ch=8,
            ch_mult=(1, 1), num_res_blocks=1, compute_dtype=jnp.float32)
        params, C = msvq.init_msvq(jax.random.PRNGKey(0), cfg), cfg.c_vae
    else:
        mod, cfg = bsq, bsq.BSQConfig(
            bits=4, patch_nums=(1, 2, 4), phi_partial=2, dec_ch=(8, 8), compute_dtype=jnp.float32)
        params, C = bsq.init_bsq(jax.random.PRNGKey(0), cfg), cfg.bits
    q = quantize_tree(params, min_size=1)
    assert "kernel_q8" in q["phi"] and q["phi"]["kernel_q8"]["q8"].shape == (2, 3, 3, C, C)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 4, C))
    for si in range(cfg.num_scales):
        want = mod.phi_apply(params, cfg, h, si)
        got = mod.phi_apply(q, cfg, h, si)
        # 3·3·C MACs of ≤ scale/2 error each, halved by the blend
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.05)
        assert not np.array_equal(np.asarray(got), np.asarray(h))


def test_glumb_conv_quantized_groups():
    """glumb_conv reads the depthwise group count off the kernel node —
    must resolve through kernel_q8 (models/nn.kernel_shape)."""
    p = nn.glumb_conv_init(jax.random.PRNGKey(7), 16, ratio=2.0)
    q = quantize_tree(p, min_size=1)
    assert "kernel_q8" in q["conv_depth"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, 16))
    out = nn.glumb_conv(q, x, (4, 4))
    ref = nn.glumb_conv(p, x, (4, 4))
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=0.08)


def test_block_scale_dequant():
    """GGUF Q8_0 block scales ([nb, dout], nb·32 == din) dequantize exactly
    per block — the weights/gguf.py node form."""
    rng = np.random.RandomState(0)
    w = rng.randn(64, 16).astype(np.float32)
    nb = 2
    scales = (np.abs(w).reshape(nb, 32, 16).max(1) / 127.0).astype(np.float32)
    q = np.clip(np.round(w.reshape(nb, 32, 16) / scales[:, None, :]), -127, 127)
    node = {"q8": jnp.asarray(q.reshape(64, 16).astype(np.int8)),
            "scale": jnp.asarray(scales)}
    ref = (q * scales[:, None, :]).reshape(64, 16)
    np.testing.assert_array_equal(
        np.asarray(dequantize_kernel(node, jnp.float32)), ref.astype(np.float32)
    )
    bad = {"q8": node["q8"], "scale": jnp.zeros((3, 16))}  # 3 does not tile 64
    with pytest.raises(ValueError, match="tile"):
        dequantize_kernel(bad, jnp.float32)


# ---------------------------------------------------------------------------
# LoRA targeting on a quantized base
# ---------------------------------------------------------------------------

def test_init_lora_identical_on_quantized_base():
    """Adapter structure AND init values must not depend on base_quant —
    the θ a run trains against an int8 base is the θ a float run trains."""
    from hyperscalees_t2i_tpu.lora import LoRASpec, init_lora

    tree = _tree()
    spec = LoRASpec(rank=2, alpha=4.0, targets=("big", "conv", "stack"))
    a = init_lora(jax.random.PRNGKey(9), tree, spec)
    b = init_lora(jax.random.PRNGKey(9), quantize_tree(tree, min_size=16), spec)
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# end-to-end tiny rung: int8 base vs float base
# ---------------------------------------------------------------------------

def _tiny_setup(tmp_path, sub):
    import tests.test_memopt as memopt

    (tmp_path / sub).mkdir(exist_ok=True)
    backend = memopt.tiny_backend(tmp_path / sub)
    backend.setup()
    return backend


def test_reward_rows_and_theta_trajectory_int8_base(tmp_path, monkeypatch):
    """End-to-end ``--base_quant int8`` on the tiny rung: quantize the frozen
    base (min-size floor lowered so the tiny kernels actually engage), run
    the same evaluation and a short training run — per-member reward rows
    and the θ trajectory must track the float base within the documented
    tolerances. The LoRA/ES delta lives in the adapter, so the *mechanism*
    is exact; the drift is pure base-weight rounding."""
    import tests.test_memopt as memopt
    from hyperscalees_t2i_tpu.backends.base import generate_parts, make_frozen
    from hyperscalees_t2i_tpu.es.noiser import EggRollConfig, sample_noise
    from hyperscalees_t2i_tpu.parallel.pop_eval import make_population_evaluator
    from hyperscalees_t2i_tpu.train import TrainConfig, run_training
    from hyperscalees_t2i_tpu.utils.pytree import tree_to_flat

    monkeypatch.setenv("HSES_BASE_QUANT_MIN_SIZE", "1")

    backend = _tiny_setup(tmp_path, "f32")
    qbackend = _tiny_setup(tmp_path, "q8")
    qbackend.params = maybe_quantize_tree(backend.params, "int8")
    qbackend.vae_params = maybe_quantize_tree(backend.vae_params, "int8")
    qbackend.prompts = backend.prompts
    qbackend.prompt_embeds = backend.prompt_embeds
    qbackend.prompt_mask = backend.prompt_mask

    # --- per-member reward rows -------------------------------------------
    pop, es_cfg = 6, EggRollConfig(sigma=0.05, rank=2, antithetic=True)
    theta = backend.init_theta(jax.random.PRNGKey(1))
    noise = sample_noise(jax.random.PRNGKey(2), theta, pop, es_cfg)
    ids = jnp.asarray([0, 1, 2, 0], jnp.int32)

    def rows(be):
        gen_p, _ = generate_parts(be)
        ev = make_population_evaluator(
            gen_p, lambda fz, imgs, i: memopt.brightness_reward(imgs, i),
            pop, es_cfg, member_batch=3,
        )
        out = ev(make_frozen(be, None), theta, noise, ids, jax.random.PRNGKey(3))
        return np.asarray(out["combined"])

    r_f, r_q = rows(backend), rows(qbackend)
    assert r_f.shape == (pop, 4)
    # brightness rewards live in [0, 1]; int8 base rounding moves them by
    # far less than the inter-member spread the fitness shaping consumes
    np.testing.assert_allclose(r_q, r_f, atol=0.02)
    assert not np.array_equal(r_q, r_f)  # the quantized program really ran

    # --- θ trajectory over a short run ------------------------------------
    def run(be, sub, base_quant):
        tc = TrainConfig(
            num_epochs=4, pop_size=6, sigma=0.05, lr_scale=1.5, egg_rank=2,
            antithetic=True, promptnorm=True, prompts_per_gen=2,
            batches_per_gen=2, member_batch=3, seed=11, resume=False,
            save_every=0, log_hist_every=0, base_quant=base_quant,
            run_dir=str(tmp_path / sub / "runs"),
        )
        state = run_training(be, memopt.brightness_reward, tc)
        return np.asarray(tree_to_flat(state.theta))

    th_f = run(backend, "f32", "off")
    th_q = run(qbackend, "q8", "int8")
    denom = max(float(np.linalg.norm(th_f)), 1e-9)
    drift = float(np.linalg.norm(th_q - th_f)) / denom
    # quantization perturbs rewards → fitness → update; the trajectory must
    # stay in the same basin (measured drift ~1e-2 of ‖θ‖ over 4 epochs)
    assert drift < 0.25, drift
    assert np.all(np.isfinite(th_q))

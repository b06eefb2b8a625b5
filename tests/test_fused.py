"""The member path against its plain reference.

The contract under test: a step program never materializes a member's dense
perturbation — adapters reach the forward as ``lora.FactoredDelta`` leaves
applied via one fused operand build per use — and what it computes matches
the reference that does materialize it (``es.perturb_member``, looped over
members in Python here, in the test) within float-rounding tolerance across
noise dtypes, antithetic pairs, every LoRA leaf geometry (2D, stacked-3D,
conv-4D), the ``reward_tile`` interaction, and every backend the CLI can
build. There is no other member path to fall back to, so a backend on which
the factored leaves were wrong would have nothing else to run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperscalees_t2i_tpu.es import (
    EggRollConfig,
    factored_member_theta,
    member_maps,
    perturb_member,
    sample_noise,
)
from hyperscalees_t2i_tpu.lora import (
    FactoredDelta,
    effective_factor,
    matmul_factored,
    slice_layer,
)
from hyperscalees_t2i_tpu.models import nn


def make_theta():
    """One leaf of every adaptable geometry: 2D, stacked-3D, conv-4D (the
    conv ``a`` is dense-noised, its ``b`` low-rank — the zimage VAE layout)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    return {
        "d": {"a": jax.random.normal(ks[0], (16, 4)), "b": jax.random.normal(ks[1], (4, 16))},
        "stk": {"a": jax.random.normal(ks[2], (3, 16, 4)), "b": jax.random.normal(ks[3], (3, 4, 16))},
        "cv": {"a": jax.random.normal(ks[4], (3, 3, 8, 4)), "b": jax.random.normal(ks[5], (4, 8))},
    }


# ---------------------------------------------------------------------------
# factored-member construction
# ---------------------------------------------------------------------------

def test_factored_member_leaf_types():
    theta = make_theta()
    cfg = EggRollConfig(rank=2, antithetic=True)
    noise = sample_noise(jax.random.PRNGKey(1), theta, 6, cfg)
    tf = factored_member_theta(theta, noise, 0, 6, cfg)
    # low-rank leaves stay factored; the dense-noised conv-4D a materializes
    assert isinstance(tf["d"]["a"], FactoredDelta)
    assert isinstance(tf["stk"]["b"], FactoredDelta)
    assert isinstance(tf["cv"]["b"], FactoredDelta)
    assert not isinstance(tf["cv"]["a"], FactoredDelta)
    assert tf["cv"]["a"].shape == theta["cv"]["a"].shape
    # factored w is the UNperturbed base — the delta lives in (u, v, c)
    np.testing.assert_array_equal(np.asarray(tf["d"]["a"].w), np.asarray(theta["d"]["a"]))


@pytest.mark.parametrize("noise_dtype", ["float32", "bfloat16"])
def test_effective_factor_matches_materialized(noise_dtype):
    """effective_factor(FactoredDelta) == the perturb_member leaf, for every
    leaf geometry and both antithetic signs."""
    theta = make_theta()
    cfg = EggRollConfig(sigma=0.05, rank=2, antithetic=True, noise_dtype=noise_dtype)
    pop = 6
    noise = sample_noise(jax.random.PRNGKey(2), theta, pop, cfg)
    for k in (0, 3, 5):  # +pair, −pair; 5 pairs with 2
        tm = perturb_member(theta, noise, k, pop, cfg)
        tf = factored_member_theta(theta, noise, k, pop, cfg)
        for path in (("d", "a"), ("d", "b"), ("stk", "a"), ("stk", "b"), ("cv", "a"), ("cv", "b")):
            want = np.asarray(tm[path[0]][path[1]], np.float32)
            got = np.asarray(effective_factor(tf[path[0]][path[1]], jnp.float32))
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_antithetic_pair_shares_factors_opposite_sign():
    """Members k and k+pop/2 share (u, v) slices and differ only in c — the
    antithetic structure survives the factored representation exactly."""
    theta = {"d": {"a": jnp.ones((8, 2)), "b": jnp.ones((2, 8))}}
    cfg = EggRollConfig(sigma=0.1, rank=1, antithetic=True)
    noise = sample_noise(jax.random.PRNGKey(3), theta, 4, cfg)
    fp = factored_member_theta(theta, noise, 0, 4, cfg)["d"]["a"]
    fn = factored_member_theta(theta, noise, 2, 4, cfg)["d"]["a"]
    np.testing.assert_array_equal(np.asarray(fp.u), np.asarray(fn.u))
    np.testing.assert_array_equal(np.asarray(fp.v), np.asarray(fn.v))
    assert float(fp.c) == -float(fn.c)


def test_member_maps_cached_and_threadable():
    from hyperscalees_t2i_tpu.es.noiser import _cached_member_tables

    s1, b1 = _cached_member_tables(8, True)
    s2, b2 = _cached_member_tables(8, True)
    assert s1 is s2 and b1 is b2  # the numpy rebuild happens once
    assert not s1.flags.writeable
    # threading precomputed maps is value-identical to in-call construction
    theta = {"d": {"a": jnp.ones((4, 2)), "b": jnp.zeros((2, 4))}}
    cfg = EggRollConfig(rank=1, antithetic=True)
    noise = sample_noise(jax.random.PRNGKey(4), theta, 8, cfg)
    maps = member_maps(8, True)
    for k in (0, 5, 7):
        a = factored_member_theta(theta, noise, k, 8, cfg)["d"]["a"]
        b = factored_member_theta(theta, noise, k, 8, cfg, maps)["d"]["a"]
        np.testing.assert_array_equal(np.asarray(a.u), np.asarray(b.u))
        assert float(a.c) == float(b.c)


# ---------------------------------------------------------------------------
# apply-site parity: dense / stacked scan slice / conv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("noise_dtype", ["float32", "bfloat16"])
def test_apply_parity_dense_stacked_conv(noise_dtype):
    theta = make_theta()
    cfg = EggRollConfig(sigma=0.05, rank=2, antithetic=True, noise_dtype=noise_dtype)
    noise = sample_noise(jax.random.PRNGKey(5), theta, 6, cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (5, 16))
    xi = jax.random.normal(jax.random.PRNGKey(7), (2, 6, 6, 8))
    p2 = {"kernel": jnp.eye(16)}
    pc = {"kernel": jax.random.normal(jax.random.PRNGKey(8), (3, 3, 8, 8)) * 0.1}
    for k in (0, 4):
        tm = perturb_member(theta, noise, k, 6, cfg)
        tf = factored_member_theta(theta, noise, k, 6, cfg)
        np.testing.assert_allclose(
            np.asarray(nn.dense(p2, x, tf["d"], 2.0)),
            np.asarray(nn.dense(p2, x, tm["d"], 2.0)), rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(nn.dense(p2, x, slice_layer(tf["stk"], 1), 1.0)),
            np.asarray(nn.dense(p2, x, slice_layer(tm["stk"], 1), 1.0)),
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(nn.conv2d(pc, xi, lora=tf["cv"], lora_scale=0.5)),
            np.asarray(nn.conv2d(pc, xi, lora=tm["cv"], lora_scale=0.5)),
            rtol=1e-5, atol=1e-5,
        )


def test_matmul_factored_raw_passthrough():
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 8))
    w = jax.random.normal(jax.random.PRNGKey(10), (8, 4))
    np.testing.assert_array_equal(np.asarray(matmul_factored(x, w)), np.asarray(x @ w))


# ---------------------------------------------------------------------------
# end to end: the evaluator and the step against a test-local reference that
# loops the members in Python over es.perturb_member
# ---------------------------------------------------------------------------

def make_reference_evaluator(generate_p, reward_apply, pop_size, es_cfg, member_batch=0,
                             mesh=None, reward_tile=0, host_slice=None):
    """``make_population_evaluator``'s signature and result, written the plain
    way: one member after another, each perturbation materialized
    (``perturb_member``), raw leaves through the same generate → reward, no
    member batching, no tiling, no mesh."""
    assert mesh is None and host_slice is None

    def eval_pop(frozen, theta, noise, flat_ids, gen_key):
        item_index = jnp.arange(flat_ids.shape[0])
        rows = []
        for k in range(pop_size):
            theta_k = perturb_member(theta, noise, k, pop_size, es_cfg)
            out = generate_p(frozen["gen"], theta_k, flat_ids, gen_key, item_index)
            images = out[0] if isinstance(out, tuple) else out
            rows.append(dict(reward_apply(frozen["reward"], images, flat_ids)))
        return {name: jnp.stack([r[name] for r in rows]) for name in rows[0]}

    return eval_pop


_TINY_CACHE = {}


def _tiny_setup():
    if "v" in _TINY_CACHE:  # one backend + reward tower for every e2e test
        return _TINY_CACHE["v"]
    from hyperscalees_t2i_tpu.backends.base import make_frozen
    from hyperscalees_t2i_tpu.backends.sana_backend import SanaBackend, SanaBackendConfig
    from hyperscalees_t2i_tpu.models import clip as clip_mod
    from hyperscalees_t2i_tpu.models import dcae, sana
    from hyperscalees_t2i_tpu.rewards.suite import clip_text_embed_table, make_clip_reward_fn

    model = sana.SanaConfig(
        in_channels=4, out_channels=4, d_model=32, n_layers=2, n_heads=4,
        cross_n_heads=4, caption_dim=16, ff_ratio=2.0, compute_dtype=jnp.float32,
    )
    vae = dcae.DCAEConfig(
        latent_channels=4, channels=(16, 16), blocks_per_stage=(1, 1),
        attn_stages=(), compute_dtype=jnp.float32,
    )
    backend = SanaBackend(SanaBackendConfig(model=model, vae=vae, width_latent=8, height_latent=8))
    backend.setup()
    tower = clip_mod.CLIPTowerConfig(16, 2, 2, 32)
    ccfg = clip_mod.CLIPConfig(
        vision=tower, text=tower, image_size=32, patch_size=16,
        vocab_size=64, max_positions=8, projection_dim=16,
    )
    cparams = clip_mod.init_clip(jax.random.PRNGKey(3), ccfg)
    table = clip_text_embed_table(
        cparams, ccfg, jnp.zeros((backend.num_items + 2, 8), jnp.int32)
    )
    reward_fn = make_clip_reward_fn(cparams, ccfg, table)
    _TINY_CACHE["v"] = (backend, reward_fn, make_frozen(backend, reward_fn))
    return _TINY_CACHE["v"]


def _run_epochs(backend, reward_fn, frozen, tc, epochs=2):
    from hyperscalees_t2i_tpu.es import epoch_key
    from hyperscalees_t2i_tpu.train.trainer import make_es_step

    step = make_es_step(backend, reward_fn, tc, 1, 4)
    theta = backend.init_theta(jax.random.PRNGKey(17))
    for e in range(epochs):
        info = backend.step_info(e, 1, 4)
        theta, metrics, _ = step(
            frozen, theta, jnp.asarray(np.asarray(info.flat_ids, np.int32)),
            epoch_key(0, e),
        )
    return np.concatenate(
        [np.asarray(leaf, np.float32).ravel() for leaf in jax.tree_util.tree_leaves(theta)]
    )


# two cells cover both noise dtypes AND the reward_tile interaction without
# doubling the compile bill (each cell = 2 tiny-step compiles; the full
# 2×2 matrix was measured against the tier-1 wall-clock budget and cut —
# (f32, tile) and (bf16, untiled) add no new code path over these two)
@pytest.mark.parametrize(
    "noise_dtype,reward_tile", [("float32", 0), ("bfloat16", 2)],
)
def test_theta_trajectory_parity(noise_dtype, reward_tile, monkeypatch):
    """θ after two epochs of ``make_es_step`` against the same step built on
    the reference evaluator: fitness shaping and the update are shared code,
    so what differs is how a member's adapter met its base."""
    from hyperscalees_t2i_tpu.parallel import pop_eval
    from hyperscalees_t2i_tpu.train.config import TrainConfig

    backend, reward_fn, frozen = _tiny_setup()
    tc = TrainConfig(
        pop_size=4, sigma=0.02, egg_rank=2, prompts_per_gen=1,
        batches_per_gen=4, member_batch=2, promptnorm=True,
        noise_dtype=noise_dtype, reward_tile=reward_tile,
    )
    got = _run_epochs(backend, reward_fn, frozen, tc)
    monkeypatch.setattr(pop_eval, "make_population_evaluator", make_reference_evaluator)
    want = _run_epochs(backend, reward_fn, frozen, tc)
    rel = np.linalg.norm(want - got) / (np.linalg.norm(want) or 1.0)
    # rounding-tight, not bitwise: the factored leaves change contraction order
    # (measured ≤4e-6 rel over 3 epochs at this geometry — pinned with slack)
    assert rel < 1e-4, rel
    assert np.max(np.abs(want - got)) < 1e-4


def test_evaluator_rewards_match_reference():
    """Per-member reward rows agree with the reference's — the member axis
    batching (lax.map over factored adapters) changes no member's identity,
    sign, or noise slice. Pop 5: two antithetic pairs and the unpaired member."""
    from hyperscalees_t2i_tpu.backends.base import generate_parts, reward_parts
    from hyperscalees_t2i_tpu.parallel.pop_eval import make_population_evaluator

    backend, reward_fn, frozen = _tiny_setup()
    gen_p, _ = generate_parts(backend)
    rew_p, _ = reward_parts(reward_fn)
    cfg = EggRollConfig(sigma=0.05, rank=2, antithetic=True)
    theta = backend.init_theta(jax.random.PRNGKey(21))
    noise = sample_noise(jax.random.PRNGKey(22), theta, 5, cfg)
    ids = jnp.zeros((4,), jnp.int32)
    key = jax.random.PRNGKey(23)
    got, want = (
        jax.device_get(jax.jit(make(gen_p, rew_p, 5, cfg, member_batch=2))(
            frozen, theta, noise, ids, key))
        for make in (make_population_evaluator, make_reference_evaluator)
    )
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-4, atol=2e-4)


# every backend train.cli can build, at its tiny width, over an int8 base (the
# cells' option; the floor lowered so toy kernels quantize): float-base sites
# are the tests above
BACKENDS = ["sana_one_step", "sana_pipeline", "var", "zimage", "infinity", "lm_ar"]


@pytest.mark.parametrize("name", BACKENDS)
def test_backend_member_rows_match_reference(name, tmp_path, monkeypatch):
    import json

    from hyperscalees_t2i_tpu.backends.base import generate_parts
    from hyperscalees_t2i_tpu.ops.quant import MIN_SIZE_ENV, quantize_frozen
    from hyperscalees_t2i_tpu.parallel.pop_eval import make_population_evaluator
    from hyperscalees_t2i_tpu.train.cli import build_backend, build_parser

    monkeypatch.setenv(MIN_SIZE_ENV, "1")
    prompts = tmp_path / "p.txt"
    prompts.write_text("a red square on a table\na blue circle\nthree green triangles\n")
    flags = ["--backend", name, "--model_scale", "tiny", "--prompts_txt", str(prompts),
             "--lora_r", "2", "--lora_alpha", "4"]
    if name == "lm_ar":
        from test_lm import TOY

        (tmp_path / "config.json").write_text(json.dumps({**TOY, "num_nextn_predict_layers": 0}))
        flags += ["--lm_config", str(tmp_path / "config.json")]
    backend = build_backend(build_parser().parse_args(flags))
    backend.setup()
    backend.params = quantize_frozen(backend.params, "int8")
    if getattr(backend, "vae_params", None) is not None:
        backend.vae_params = quantize_frozen(backend.vae_params, "int8")
    gen_p, frozen_gen = generate_parts(backend)
    assert any(
        leaf.dtype == jnp.int8 for leaf in jax.tree_util.tree_leaves(frozen_gen)
    ), "no kernel went int8: the test would not reach the kernel_q8 rows"

    def reward(fz, images, ids):  # per-image rows that see every pixel
        x = images.astype(jnp.float32)
        return {"mean": x.mean(axis=(1, 2, 3)), "contrast": x.std(axis=(1, 2, 3))}

    # σ large enough that a token-sampling backend samples other tokens (at toy
    # widths σ = 0.01 changes none, and every member would score the same)
    pop, cfg = 4, EggRollConfig(sigma=0.5, rank=2, antithetic=True)
    theta = backend.init_theta(jax.random.PRNGKey(31))
    noise = sample_noise(jax.random.PRNGKey(32), theta, pop, cfg)
    ids = jnp.asarray(np.asarray(backend.step_info(0, 2, 1).flat_ids, np.int32))
    frozen = {"gen": frozen_gen, "reward": {}}
    got, want = (
        jax.device_get(jax.jit(make(gen_p, reward, pop, cfg, member_batch=2))(
            frozen, theta, noise, ids, jax.random.PRNGKey(33)))
        for make in (make_population_evaluator, make_reference_evaluator)
    )
    assert np.ptp(want["mean"], axis=0).max() > 0, "the perturbation never reached an image"
    for k in ("mean", "contrast"):
        np.testing.assert_allclose(got[k], want[k], rtol=2e-4, atol=2e-4)

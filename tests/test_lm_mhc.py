"""The ``xing4_0`` member of the MLA family (models/lm.py: pre-norm inside
manifold-constrained hyper-connections, yarn RoPE, a selection-bias router)
against its plain float32 reference (reference/mhc_moe_reference.py), at toy
widths on the CPU.

Tolerances: both sides compute in float32 — conftest pins
``jax_default_matmul_precision`` to ``highest`` and the toy states
``torch_dtype: float32`` — so what is left is the order of float32 sums.
1e-5 for one hyper-connection sub-layer (a norm over 128 numbers, a
``[128, 24]`` product, 40 normalisations of a 4 x 4 matrix, three mixes);
1e-4 relative for logits through three layers (as tests/test_lm.py, whose
reasons hold here). Each of five omissions — the mathematics a faster
program might leave out — has to miss that 1e-4 by ten times or more.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperscalees_t2i_tpu.models import lm
from hyperscalees_t2i_tpu.reference import mhc_moe_reference as ref
from tests.test_lm import random_lora, rel

TOL = 1e-4
SUBLAYER_TOL = 1e-5

TOY = {
    "model_type": "xing4_0",
    "hidden_size": 32, "num_attention_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 8,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8, "rope_theta": 10000.0,
    "rope_scaling": {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096},
    "rms_norm_eps": 1e-6, "intermediate_size": 48, "moe_intermediate_size": 16,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 2.0, "norm_topk_prob": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "num_nextn_predict_layers": 1, "vocab_size": 64,
    "experts_held": 8, "expert_offset": 0, "vocab_rows_held": 64,
    "image_tokens": {"image_vocab": 16, "image_id_offset": 48, "boi_id": 1, "grid": 4,
                     "max_prompt_len": 6, "top_k": 0, "top_p": 0.0},
    "vq": {"c_vae": 8, "phi_partial": 2, "ch": 8, "ch_mult": [1, 1], "num_res_blocks": 1},
    "torch_dtype": "float32",
}


def toy_cfg(tmp_path, **over):
    raw = {k: v for k, v in {**TOY, **over}.items() if v is not None}
    path = tmp_path / "config.json"
    path.write_text(json.dumps(raw))
    return lm.config_from_json(str(path)), raw


@pytest.fixture()
def toy(tmp_path):
    cfg, raw = toy_cfg(tmp_path)
    return cfg, raw, lm.init_lm(jax.random.PRNGKey(0), cfg)


def ref_forward(params, raw, ids, lora=None, scale=1.0):
    n = len(params["layers"])
    return ref.forward(lambda i: ref.block_weights(params["layers"][i], f"layers/{i}", lora, scale),
                       n, ref.top_weights(params), raw, ids)


# (a) the file is read as the family's, with the path its keys state -----------

def test_config_json_gives_the_mla_family_with_streams(toy):
    cfg, raw, params = toy
    assert isinstance(cfg, lm.LMConfig) and cfg.family() is lm.MLA_FAMILY
    assert (cfg.model_type, cfg.hc_mult, cfg.sandwich_norm, cfg.topk_method) == ("xing4_0", 4, False, "noaux_tc")
    assert cfg.rope_scaling_factor == 64 and cfg.rope_scaling_original_max_position_embeddings == 4096
    assert cfg.score_divisor == pytest.approx(4.0 / (0.1 * np.log(64) + 1) ** 2)
    p = params["layers"][1]
    assert set(p) == {"n1", "n3", "mla", "moe", "hc_attn", "hc_ffn"}  # pre-norm: no n2, n4
    assert p["hc_attn"]["phi"].shape == (128, 24) and p["hc_attn"]["phi"].dtype == jnp.float32
    assert p["moe"]["router"]["e_score_correction_bias"].shape == (8,)
    # the adapter's targets are the family's: nothing of the residual path or the router evolves
    from hyperscalees_t2i_tpu.lora import init_lora

    assert not any("hc_" in k or "router" in k for k in init_lora(jax.random.PRNGKey(0), params, cfg.lora_spec()))


@pytest.mark.parametrize("over, says", [
    ({"hc_mult": None}, "hc_mult"), ({"n_group": 2}, "one expert group"),
    ({"scoring_func": "softmax"}, "sigmoid"), ({"topk_method": "group_limited_greedy"}, "topk_method"),
    ({"rope_scaling": {"type": "linear", "factor": 2}}, "yarn"),
])
def test_config_json_refuses_what_is_not_written_down(tmp_path, over, says):
    with pytest.raises(ValueError, match=says):
        toy_cfg(tmp_path, **over)


def test_int8_init_leaves_the_float32_parts_float32(toy, monkeypatch):
    cfg, _, _ = toy
    monkeypatch.setenv("HSES_BASE_QUANT_MIN_SIZE", "0")
    q = lm.init_lm(jax.random.PRNGKey(0), cfg, base_quant="int8")
    layer = q["layers"][1]
    assert "kernel_q8" in layer["moe"]["experts"]["gate"] and "kernel_q8" in layer["mla"]["wo"]
    for leaf in jax.tree_util.tree_leaves((layer["hc_attn"], layer["hc_ffn"], layer["moe"]["router"])):
        assert leaf.dtype == jnp.float32


# (b) one hyper-connection sub-layer -------------------------------------------

def _streams(key, cfg, T=5):
    return jax.random.normal(key, (T, cfg.hc_mult, cfg.hidden_size))


def test_hc_sublayer_against_reference(toy):
    cfg, raw, params = toy
    hc = params["layers"][0]["hc_attn"]
    hc = dict(hc, b=0.3 * jax.random.normal(jax.random.PRNGKey(2), hc["b"].shape), alpha=jnp.array([0.7, 1.3, 0.9]))
    X = _streams(jax.random.PRNGKey(1), cfg)
    W = jax.random.normal(jax.random.PRNGKey(3), (cfg.hidden_size, cfg.hidden_size)) / 6.0
    F = lambda u: (jnp.tanh(u @ W), None)
    pre, post, res = lm.hc_coefficients(hc, cfg, X)
    got, _ = lm._sublayer(hc, cfg, X, F, "lm_mla", None)
    with jax.default_matmul_precision("highest"):
        w_pre, w_post, w_res = ref.hc_coefficients(hc, raw, X)
        want, _ = ref.hc_sublayer(hc, raw, X, F)
    for g, w in ((pre, w_pre), (post, w_post), (res, w_res), (got, want)):
        assert rel(g, w) < SUBLAYER_TOL
    # doubly stochastic after 20 iterations, and no two tokens share a matrix
    assert np.abs(np.asarray(res.sum(-1)) - 1).max() < 1e-5 and np.abs(np.asarray(res.sum(-2)) - 1).max() < 1e-5
    assert float(jnp.abs(res[0] - res[1]).max()) > 1e-2 and float(jnp.abs(res - jnp.eye(4)).max()) > 0.1
    assert float(post.max()) > 1.0  # 2σ(·): a sub-layer's output can be written at more than weight 1
    gauges = lm._hc_gauges([res[:, None]], jnp.ones((5, 1), bool))
    assert float(gauges["row"].max()) < 3e-6 and float(gauges["err"].max()) < 1e-5 and 0.1 < float(gauges["off"].sum() / gauges["n"].sum()) < 0.9


def test_hc_coefficient_path_in_bfloat16_fails_the_sublayer_tolerance(toy, monkeypatch):
    """The control the benchmark runs (``lm.HC_DTYPE``): the path in bfloat16
    is a thousand times the tolerance away, and its marginals say so."""
    cfg, raw, params = toy
    hc, X = params["layers"][0]["hc_attn"], _streams(jax.random.PRNGKey(1), cfg)
    with jax.default_matmul_precision("highest"):
        want = ref.hc_coefficients(hc, raw, X)[2]
        rounded = ref.hc_coefficients(hc, raw, X, lambda t: t.astype(jnp.bfloat16).astype(jnp.float32))[2]
    monkeypatch.setattr(lm, "HC_DTYPE", jnp.bfloat16)
    res = lm.hc_coefficients(hc, cfg, X)[2]
    assert res.dtype == jnp.bfloat16 and rel(res, want) > 100 * SUBLAYER_TOL and rel(rounded, want) > 100 * SUBLAYER_TOL
    assert float(lm._hc_gauges([res[:, None]], jnp.ones((5, 1), bool))["row"].max()) > 1e-3


# (c) the whole stack, teacher-forced, and the MTP module -----------------------

def test_whole_stack_logits_against_reference(toy):
    cfg, raw, params = toy
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0, cfg.vocab_rows_held)
    lens = jnp.array([6, 4])
    got = lm.forward_logits(params, cfg, ids, lens)
    for s in range(2):
        n = int(lens[s])
        assert rel(got[s, :n], ref_forward(params, raw, ids[s, :n])["logits"]) < TOL


def test_mtp_module_against_reference(toy):
    cfg, raw, params = toy
    ids = jax.random.randint(jax.random.PRNGKey(6), (1, 6), 0, cfg.vocab_rows_held)
    lens = jnp.array([5])
    hidden, _, _ = lm.prefill(params, cfg, ids[:, :5], lens)
    got = lm.mtp_logits(params, cfg, hidden, ids[:, 1:6], lens)
    main = ref_forward(params, raw, ids[0, :5])
    assert rel(hidden[0], main["hidden"]) < TOL
    want = ref.mtp(ref.mtp_weights(params["mtp"][0]), ref.top_weights(params), raw, main["hidden"], ids[0, 1:6])
    assert rel(got[0], want) < TOL


# (d) prefill, then cached absorbed decode, against the full forward -----------

def _generate_against_reference(params, cfg, raw, ref_params=None):
    """Largest relative distance, over two sequences, between the logits the
    decode scan kept (position 0 of each) and the reference's full forward
    (over ``ref_params`` where the program's tree was tampered with) over
    ``[prompt ‖ begin-of-image ‖ sampled ids]``; also the rows."""
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, cfg.max_prompt_len), 2, 48)
    lens = jnp.array([6, 3])
    lora = random_lora(jax.random.PRNGKey(4), params, cfg)
    ids, rows = lm.generate(params, cfg, prompt, lens, jax.random.PRNGKey(5), lora=lora, lora_scale=2.0, decode=False)
    worst, outs = 0.0, []
    for s in range(2):
        L = int(lens[s])
        seq = jnp.concatenate([prompt[s, :L], jnp.array([cfg.boi_id]), ids[s, :-1] + cfg.image_id_offset])
        out = ref_forward(ref_params or params, raw, seq, lora, 2.0)
        want = out["logits"][L:, cfg.image_id_offset: cfg.image_id_offset + cfg.image_vocab]
        worst = max(worst, rel(rows["logits"][s, 0], want[0]))
        outs.append((L, out, want))
    return worst, rows, ids, outs, (prompt, lens, lora)


def test_prefill_then_cached_decode_against_full_forward(toy):
    cfg, raw, params = toy
    worst, rows, ids, outs, (prompt, lens, lora) = _generate_against_reference(params, cfg, raw)
    assert worst < TOL
    for s, (L, out, want) in enumerate(outs):
        # every position, teacher-forced on the sampled ids through the cache a position at a time
        got = _decode_logits(params, cfg, prompt[s: s + 1], lens[s: s + 1], ids[s: s + 1], lora, 2.0)[0]
        assert rel(got, want) < TOL
        for j, picked in enumerate(out["topk"]):  # the biased router's choice, slot by slot
            mine = np.asarray(rows["topk"][s, :, j])
            assert np.array_equal(mine[cfg.max_prompt_len:], np.sort(np.asarray(picked), -1)[L:])
    # the gauges: 2 sub-layers x 3 layers a sampled position, the prompt's rows at all but the last layer's FFN side
    assert float(rows["hc_row"].max()) < 3e-6 and float(rows["hc_row"].max()) <= float(rows["hc_err"].max()) < 1e-2
    assert np.array_equal(np.asarray(rows["hc_n"]), 6 * 16 + 4 * np.asarray(lens))
    assert 0.1 < float(rows["hc_off"].sum() / rows["hc_n"].sum()) < 0.9


def _decode_logits(params, cfg, prompt, lens, ids, lora, scale):
    """Logits of every decode step, teacher-forced on ``ids``: the family's
    own ``prefill_state`` / ``decode_layers`` hooks a position at a time."""
    fam = cfg.family()
    factors = lm.expert_factors(lora, cfg, cfg.compute_dtype)
    state, _, _ = fam.prefill_state(params, cfg, prompt, lens, lora, scale, factors)
    last, lo, out = jnp.full((prompt.shape[0],), cfg.boi_id), cfg.image_id_offset, []
    for i in range(cfg.image_tokens):
        x, state, _ = fam.decode_layers(params, cfg, params["embed"][last], state, jnp.int32(i), lens,
                                        lora, scale, factors)
        out.append(fam.head(params, cfg, x)[:, lo: lo + cfg.image_vocab])
        last = ids[:, i] + lo
    return jnp.stack(out, axis=1)


# (e) five omissions, each of which has to fail --------------------------------

def _without_dynamic_term(cfg, raw, params, monkeypatch):
    zero = lambda hc: dict(hc, alpha=jnp.zeros_like(hc["alpha"]))
    layers = [dict(p, hc_attn=zero(p["hc_attn"]), hc_ffn=zero(p["hc_ffn"])) for p in params["layers"]]
    return cfg, dict(params, layers=layers)


def _five_sinkhorn_iterations(cfg, raw, params, monkeypatch):
    return dataclasses.replace(cfg, hc_sinkhorn_iters=5), params


def _without_selection_bias(cfg, raw, params, monkeypatch):
    return dataclasses.replace(cfg, topk_method="greedy"), params


def _plain_rope(cfg, raw, params, monkeypatch):
    return dataclasses.replace(cfg, rope_scaling_factor=1.0), params


def _h_post_without_its_factor(cfg, raw, params, monkeypatch):
    monkeypatch.setattr(lm, "HC_POST_GAIN", 1.0)
    return cfg, params


@pytest.mark.parametrize("omit", [_without_dynamic_term, _five_sinkhorn_iterations, _without_selection_bias,
                                  _plain_rope, _h_post_without_its_factor], ids=lambda f: f.__name__.strip("_"))
def test_an_omission_fails_the_reference_by_ten_times_the_tolerance(toy, omit, monkeypatch):
    """On a tree whose ``α_res`` is 2 (the mixing logits twice as sharp as
    seeded, so that five Sinkhorn iterations are visibly too few); the
    unchanged program passes there, each omission fails."""
    cfg, raw, params = toy
    sharp = lambda hc: dict(hc, alpha=jnp.array([1.0, 1.0, 2.0]))
    params = dict(params, layers=[dict(p, hc_attn=sharp(p["hc_attn"]), hc_ffn=sharp(p["hc_ffn"]))
                                  for p in params["layers"]])
    assert _generate_against_reference(params, cfg, raw)[0] < TOL
    less_cfg, less_params = omit(cfg, raw, params, monkeypatch)
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0, cfg.vocab_rows_held)
    got = lm.forward_logits(less_params, less_cfg, ids, jnp.array([6]))
    assert rel(got[0], ref_forward(params, raw, ids[0])["logits"]) > 10 * TOL   # teacher-forced
    # through the cache; the reference reads the unchanged tree: only the program left something out
    assert _generate_against_reference(less_params, less_cfg, raw, params)[0] > 10 * TOL


def test_sinkhorn_needs_its_twenty_iterations(toy):
    """What five iterations leave at the seeded φ: the median token's column
    sums a hundred times further from 1 than twenty leave them (the rows are
    normalized last: 1 − hc_eps either way)."""
    cfg, _, params = toy
    res = lambda c: lm.hc_coefficients(params["layers"][0]["hc_ffn"], c, _streams(jax.random.PRNGKey(9), cfg, T=256))[2]
    col = lambda c: float(jnp.median(jnp.abs(res(c).sum(-2) - 1).max(-1)))
    assert col(dataclasses.replace(cfg, hc_sinkhorn_iters=5)) > 100 * col(cfg)
    assert float(jnp.abs(res(cfg).sum(-1) - 1).max()) < 3e-6


# (f) yarn, by hand ------------------------------------------------------------

def test_yarn_frequencies_by_hand(toy):
    """8 rotary numbers = 4 pairs, θ 1e4, factor 64 over 4096 positions: the
    pair that turns 32 times lies at 8 ln(4096 / 64π) / (2 ln 1e4) = 1.31 → 1,
    the one that turns once at 2.81 → 3: pairs 0, 1 keep their frequency, pair
    2 is half way, pair 3 is ÷ 64."""
    cfg, raw, _ = toy
    keep = np.asarray(lm._yarn_blend(cfg, 4))
    assert np.allclose(keep, [1.0, 1.0, 0.5, 0.0])
    inv, amp = ref.inverse_frequencies(raw, 8)
    plain = 1e4 ** (-np.arange(4) / 4)
    assert amp == 1.0 and np.allclose(inv, plain * keep + plain / 64 * (1 - keep), rtol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8))
    with jax.default_matmul_precision("highest"):
        assert rel(lm._rope(x, jnp.arange(3), cfg.rope_theta, cfg), ref.rope(x, jnp.arange(3), raw)) < 1e-6


# (g) the other model_types come out of the one block as they went in ----------

def _parent_sandwich_block(p, cfg, li, x, attn, row_valid, lora, factors, scale, prefix="layers", hc_seen=None):
    """``models/lm.block`` as it stood before the residual path became a
    property of the configuration (commit 7cf61c8), its body kept literally
    (``hc_seen`` is the one argument its callers have gained)."""
    path = f"{prefix}/{li}"
    with jax.named_scope("lm_mla"):
        a, extra = attn(lm._rms(x, p["n1"], cfg))
        h = x + lm._rms(a, p["n2"], cfg)
    u = lm._rms(h, p["n3"], cfg)
    if "moe" in p:
        with jax.named_scope("lm_moe"):
            flat = u.reshape(-1, u.shape[-1])
            f, stats = lm.moe(p["moe"], cfg, flat, row_valid.reshape(-1), lora,
                              factors, f"{path}/moe", scale)
            f = f.reshape(u.shape)
    else:
        with jax.named_scope("lm_dense_ffn"):
            f, stats = lm._swiglu(p["ffn"], u, lora, f"{path}/ffn", scale), None
    return h + lm._rms(f, p["n4"], cfg), extra, stats


def test_pangu_through_the_one_block_is_the_parents_program(tmp_path, monkeypatch):
    from tests.test_lm import TOY as PANGU_TOY, toy_cfg as pangu_cfg

    cfg, _ = pangu_cfg(tmp_path, model_type="pangu_ultra_moe")
    assert (cfg.sandwich_norm, cfg.hc_mult, cfg.topk_method, cfg.rope_scaling_factor) == (True, 0, "greedy", 1.0)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    paths = {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(params["layers"])[0]}
    mla = {f"mla/{m}" for m in ("wdq/kernel", "q_norm/scale", "wuq/kernel", "wdkv/kernel", "kv_norm/scale",
                                "wukv/kernel", "wo/kernel")} | {f"n{i}/scale" for i in (1, 2, 3, 4)}
    swiglu = lambda at: {f"{at}/{m}/kernel" for m in ("gate", "up", "down")}
    want = {f"0/{k}" for k in mla | swiglu("ffn")} | {
        f"{i}/{k}" for i in (1, 2) for k in mla | swiglu("moe/experts") | swiglu("moe/shared") | {"moe/router/weight"}}
    assert paths == want and PANGU_TOY["num_hidden_layers"] == 3
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0, cfg.vocab_rows_held)
    lens = jnp.array([6, 4])
    lora = random_lora(jax.random.PRNGKey(4), params, cfg)
    got = lm.forward_logits(params, cfg, ids, lens, lora, 2.0)
    gen = lm.generate(params, cfg, ids, lens, jax.random.PRNGKey(5), lora=lora, lora_scale=2.0, decode=False)[1]
    monkeypatch.setattr(lm, "block", _parent_sandwich_block)
    assert np.array_equal(np.asarray(got), np.asarray(lm.forward_logits(params, cfg, ids, lens, lora, 2.0)))
    old = lm.generate(params, cfg, ids, lens, jax.random.PRNGKey(5), lora=lora, lora_scale=2.0, decode=False)[1]
    assert set(gen) == set(old) and all(np.array_equal(np.asarray(gen[k]), np.asarray(old[k])) for k in gen)


# (h) the backend on train.cli's normal path -----------------------------------

def test_train_cli_lm_ar_xing_two_epochs(tmp_path, monkeypatch):
    """``train.cli --backend lm_ar`` on a ``model_type: xing4_0`` file: the
    same trainer, member loop, quantizer and reward path; one compile; every
    counter of the family and the two of the residual path."""
    from hyperscalees_t2i_tpu.train import cli

    monkeypatch.setenv("HSES_BASE_QUANT_MIN_SIZE", "1")
    (tmp_path / "config.json").write_text(json.dumps({**TOY, "num_nextn_predict_layers": 0}))
    prompts = tmp_path / "p.txt"
    prompts.write_text("a red square on a table\na blue circle\nthree green triangles in a row\n")
    cli.main([
        "--backend", "lm_ar", "--lm_config", str(tmp_path / "config.json"), "--model_scale", "tiny",
        "--prompts_txt", str(prompts), "--base_quant", "int8",
        "--noise_dtype", "bfloat16", "--sigma", "0.5", "--lora_r", "2", "--lora_alpha", "4",
        "--pop_size", "4", "--prompts_per_gen", "2", "--member_batch", "2",
        "--num_epochs", "2", "--allow_random_rewards", "true",
        "--run_dir", str(tmp_path / "runs"), "--run_name", "run", "--resume", "false", "--save_every", "0",
    ])
    run = tmp_path / "runs" / "run"
    rows = [json.loads(l) for l in (run / "metrics.jsonl").read_text().splitlines()]
    assert [r["epoch"] for r in rows] == [0, 1]
    for r in rows:
        assert r["obs/compiles"] == 1 and r["obs/pop_eval_traces"] == 1
        assert np.isfinite(r["reward/combined_mean"]) and r["delta_norm"] > 0
        assert r["moe/local_assignments"] > 4 * 2 * 16 * 2 * 2  # every pair is computed here: all 8 experts held
        assert 0.0 < r["lm/hc_row_err"] < 3e-6 and r["lm/hc_row_err"] <= r["lm/hc_marginal_err"] < 0.1
        assert 0.1 < r["lm/hc_offdiag_mass"] < 0.9
        assert all(isinstance(r[k], float) for k in ("lm/hc_marginal_err", "lm/hc_row_err", "lm/hc_offdiag_mass"))
        assert not any(k.startswith(("probe/", "gen/")) for k in r)
    steps = [json.loads(l) for l in (run / "programs.jsonl").read_text().splitlines()]
    (step,) = [p for p in steps if p["label"].startswith("es_step_")]
    # generate notes the head it cuts its image-id columns from; only a traced run counts the ops of that size
    assert step["geometry"]["lm_head_shape"] == [32, 64] and "lm_head_whole_ops" not in step
    probe = np.load(run / "probe_epoch0.npz")
    assert probe["topk"].shape == (2, 22, 2, 2) and probe["logits"].shape == (2, 1, 16)


def test_reference_batch_form_hooks_and_the_benchmarks_copy(toy):
    """``forward_batch`` is ``forward`` a sequence at a time; forcing the
    reference's own routing changes nothing; each hook moves the logits; and
    the benchmark's copy of the reference is the program's file."""
    cfg, raw, params = toy
    ids = jax.random.randint(jax.random.PRNGKey(13), (2, 7), 0, cfg.vocab_rows_held)
    lw = lambda i: ref.block_weights(params["layers"][i], f"layers/{i}")
    top = ref.top_weights(params)
    both = ref.forward_batch(lw, 3, top, raw, ids)
    for s in range(2):
        one = ref.forward(lw, 3, top, raw, ids[s, :5])
        assert rel(both["logits"][s, :5], one["logits"]) < 1e-6
        assert np.array_equal(both["topk"][s, :5, 0], one["topk"][0])
    forced = ref.forward_batch(lw, 3, top, raw, ids, both["topk"])
    assert rel(forced["logits"], both["logits"]) < 1e-6
    bf16 = lambda t: jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)
    for hooks in ({"act": bf16}, {"coeff_round": bf16}):
        moved = ref.forward_batch(lw, 3, top, raw, ids, both["topk"], **hooks)
        assert 1e-4 < rel(moved["logits"], both["logits"]) < 0.2
    root = Path(ref.__file__).resolve().parents[2]
    assert (root / "benchmarks/reference/mhc_moe_reference.py").read_text() == Path(ref.__file__).read_text()
    # an int8 head is read a block of columns at a time and gives the dequantized head's logits
    from hyperscalees_t2i_tpu.ops.quant import quantize_tree

    node = quantize_tree({"head": params["head"]}, min_size=0)["head"]
    h = jax.random.normal(jax.random.PRNGKey(1), (3, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        assert rel(ref.head_logits(h, node, columns=24), h @ ref.kernel_f32(node)) < 1e-6

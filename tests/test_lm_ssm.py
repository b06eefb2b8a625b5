"""The dense ``granitemoehybrid`` decoder (models/lm_ssm.py, ops/ssd.py: Mamba-2
mixers beside grouped-query attention without position embedding, a SwiGLU in
every layer, a tied head) against its plain float32 reference
(reference/mamba2_gqa_reference.py), at toy widths on the CPU.

Tolerances, as tests/test_lm.py argues them: both sides compute in float32 at
``highest`` (conftest; the toy states ``torch_dtype: float32``), so what is
left is the order of float32 sums — the chunked recurrence's masked products
against the position-by-position one, the decode step's output taken from the
old state, ``W + dW`` materialized or not. 1e-5 for the recurrence and one
mixer, 1e-4 relative for logits through the stack. A state touched by
padding, a window taken from padded positions, a dropped multiplier or an
untied head is orders above them.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperscalees_t2i_tpu.es import EggRollConfig, factored_member_theta, perturb_member, sample_noise
from hyperscalees_t2i_tpu.models import lm, lm_hybrid, lm_ssm
from hyperscalees_t2i_tpu.ops import ssd
from hyperscalees_t2i_tpu.reference import mamba2_gqa_reference as ref
from tests.test_lm import random_lora, rel

TOL = 1e-4
MIXER_TOL = 1e-5

PERIOD = ["mamba", "attention", "mamba"]
TOY = {
    "model_type": "granitemoehybrid",
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2, "attention_multiplier": 0.125,
    "embedding_multiplier": 12, "residual_multiplier": 0.22, "logits_scaling": 8, "intermediate_size": 48,
    "shared_intermediate_size": 48, "layer_types": PERIOD * 2, "mamba_chunk_size": 4, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 16, "mamba_d_state": 8, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 4, "mamba_proj_bias": False, "rms_norm_eps": 1e-5, "num_hidden_layers": 6, "vocab_size": 64,
    "position_embedding_type": "nope", "tie_word_embeddings": True, "attention_bias": False,
    "num_local_experts": 0, "num_experts_per_tok": 0,
    "image_tokens": {"image_vocab": 16, "image_id_offset": 48, "boi_id": 1, "grid": 4,
                     "max_prompt_len": 6, "top_k": 0, "top_p": 0.0},
    "vq": {"c_vae": 8, "phi_partial": 2, "ch": 8, "ch_mult": [1, 1], "num_res_blocks": 1},
    "torch_dtype": "float32",
}
STATE_ELEMENTS = 4 * 16 * 8    # a Mamba-2 layer's state, a sequence: heads x head width x state size
CONV_CHANNELS = 4 * 16 + 2 * 8


def toy_cfg(tmp_path, **over):
    raw = {**TOY, **over}
    path = tmp_path / "config.json"
    path.write_text(json.dumps(raw))
    return lm.config_from_json(str(path)), raw


def randomized(params, key):
    """Norm weights and ``D`` away from their initial values, so that a norm
    applied with the wrong weight or a dropped skip shows (the conv's bias is
    drawn at init)."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(key, len(leaves))
    names = lambda path: {getattr(p, "key", None) for p in path}
    out = [leaf + 0.2 * jax.random.normal(k, leaf.shape) if names(path) & {"scale", "d"} and "kernel_q8" not in
           names(path) else leaf for k, (path, leaf) in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture()
def toy(tmp_path):
    cfg, raw = toy_cfg(tmp_path)
    return cfg, raw, randomized(lm.init_lm(jax.random.PRNGKey(0), cfg), jax.random.PRNGKey(99))


def ref_forward(params, raw, ids, lora=None, scale=1.0):
    return ref.forward(lambda i: ref.block_weights(params, i, lora, scale), raw["num_hidden_layers"],
                       ref.top_weights(params), raw, ids)


def ref_layer(params, i):
    return ref.block_weights(params, i)


# (a) the recurrence: chunked = one-position steps = a plain loop --------------

def ssd_inputs(key, Bt=2, T=10, H=3, P=4, N=5):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (Bt, T, H, P))
    dt = jax.random.uniform(ks[1], (Bt, T, H), minval=0.01, maxval=0.5)
    A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    B, C = jax.random.normal(ks[3], (Bt, T, N)), jax.random.normal(ks[4], (Bt, T, N))
    D = jax.random.normal(ks[5], (H,))
    return x, dt, A, B, C, D, jax.random.normal(jax.random.fold_in(key, 7), (Bt, H, P, N))


def loop_ssd(x, dt, A, B, C, D, S):
    x, dt, A, B, C, D, S = (np.asarray(a, np.float64) for a in (x, dt, A, B, C, D, S))
    y = np.zeros(x.shape)
    for t in range(x.shape[1]):
        S = np.exp(dt[:, t] * A)[..., None, None] * S + (dt[:, t, :, None] * x[:, t])[..., None] * B[:, t, None, None]
        y[:, t] = np.einsum("bhpn,bn->bhp", S, C[:, t]) + D[:, None] * x[:, t]
    return y, S


@pytest.mark.parametrize("chunk", [4, 5, 64], ids=["three-chunks-padded", "two-chunks", "one-chunk"])
def test_chunked_ssd_is_the_recurrence(chunk):
    args = ssd_inputs(jax.random.PRNGKey(0))
    want_y, want_s = loop_ssd(*args)
    y_r, s_r = ssd.recurrent_ssd(*args)
    y_c, s_c = ssd.chunk_ssd(*args, chunk=chunk)
    assert rel(y_r, want_y) < MIXER_TOL and rel(s_r, want_s) < MIXER_TOL
    assert rel(y_c, want_y) < MIXER_TOL and rel(s_c, want_s) < MIXER_TOL


def test_positions_with_dt_0_leave_the_state_alone():
    x, dt, A, B, C, D, S = ssd_inputs(jax.random.PRNGKey(1))
    real = jnp.arange(10)[None, :] < jnp.array([10, 6])[:, None]
    _, s = ssd.chunk_ssd(x, jnp.where(real[..., None], dt, 0.0), A, B, C, D, S, chunk=4)
    _, s6 = ssd.recurrent_ssd(x[1:, :6], dt[1:, :6], A, B[1:, :6], C[1:, :6], D, S[1:])
    assert rel(s[1], s6[0]) < MIXER_TOL
    y, new = ssd.ssd_step(x[:, 0], jnp.zeros_like(dt[:, 0]), A, B[:, 0], C[:, 0], D, S)
    assert np.array_equal(np.asarray(new), np.asarray(S))  # bit for bit


@pytest.mark.parametrize("chunk", [4, 5, 256], ids=["two-chunks", "one-chunk-padded", "published-chunk"])
def test_mamba_prefill_mixer_against_reference(toy, chunk):
    """The chunked prefill of a whole mixer (projection, conv, SSD, gated
    norm, output projection) against the reference's position-by-position
    recurrence, and its final state against the recurrence run alone."""
    cfg, raw, params = toy
    p = lm_ssm._at(params["layers"][0], 1)
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 6, cfg.hidden_size))
    out, (state, _) = lm_ssm.mamba_prefill(p["mamba"], cfg, u, jnp.array([6]), None, "x", 1.0, chunk)
    with jax.default_matmul_precision("highest"):
        want = ref.mamba2(ref_layer(params, 3), raw, u[0])
    assert rel(out[0], want) < MIXER_TOL
    z, xbc, dt = lm_ssm._mamba_project(p["mamba"], cfg, u, None, "x", 1.0)
    xbc, _ = lm_hybrid.causal_conv(xbc, p["mamba"]["conv"]["weight"], jnp.array([6]), p["mamba"]["conv"]["bias"])
    x, delta, B, C, A = lm_ssm._ssd_inputs(p["mamba"], cfg, xbc, dt)
    _, s_r = ssd.recurrent_ssd(x, delta, A, B, C, p["mamba"]["d"], jnp.zeros_like(state))
    assert rel(state, s_r) < MIXER_TOL


# (b) each block kind, the padded prefill, and the whole stack -----------------

@pytest.mark.parametrize("layer", [0, 4], ids=["mamba", "attention"])
def test_one_block_against_reference(toy, layer):
    cfg, raw, params = toy
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, cfg.hidden_size))
    lens, valid = jnp.array([6]), jnp.ones((1, 6), bool)
    p = lm_ssm._at(params["layers"][layer % 3], layer // 3)
    if "attn" in p:
        mixer = lambda u: lm_ssm.attn_prefill(p["attn"], cfg, u, valid, None, "x", 1.0)
    else:
        mixer = lambda u: lm_ssm.mamba_prefill(p["mamba"], cfg, u, lens, None, "x", 1.0)
    y, _ = lm_ssm.block(p, cfg, x, mixer, None, "x", 1.0)
    with jax.default_matmul_precision("highest"):
        want = ref.block(ref_layer(params, layer), raw, x[0])
    assert rel(y[0], want) < TOL


@pytest.mark.parametrize("chunk", [4, 256], ids=["two-chunks", "one-chunk"])
def test_padded_prefill_leaves_state_and_conv_window_untouched(toy, chunk):
    """Right-padded prompts of unequal length: each sequence's output before
    its padding, its state and its conv window are those of the sequence run
    alone at its own length — and its output the reference's."""
    cfg, raw, params = toy
    p = lm_ssm._at(params["layers"][2], 0)["mamba"]
    u = jax.random.normal(jax.random.PRNGKey(2), (3, 6, cfg.hidden_size))
    lens = jnp.array([6, 2, 4])
    out, (state, window) = lm_ssm.mamba_prefill(p, cfg, u, lens, None, "x", 1.0, chunk)
    K = cfg.mamba_d_conv
    for s in range(3):
        L = int(lens[s])
        alone, (s_alone, w_alone) = lm_ssm.mamba_prefill(p, cfg, u[s: s + 1, :L], lens[s: s + 1], None, "x", 1.0,
                                                         chunk)
        assert rel(out[s, :L], alone[0]) < MIXER_TOL and rel(state[s], s_alone[0]) < MIXER_TOL
        assert np.array_equal(np.asarray(window[s]), np.asarray(w_alone[0]))
        with jax.default_matmul_precision("highest"):
            assert rel(out[s, :L], ref.mamba2(ref_layer(params, 2), raw, u[s, :L])) < TOL
        xbc = np.asarray(lm_ssm._mamba_project(p, cfg, u[s, :L], None, "x", 1.0)[1])   # the conv's real inputs
        want = np.concatenate([np.zeros((max(K - 1 - L, 0), xbc.shape[1])), xbc[-(K - 1):]])
        assert np.allclose(np.asarray(window[s]), want, atol=1e-6)


def test_whole_stack_logits_against_reference(toy):
    cfg, raw, params = toy
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0, cfg.vocab_rows_held)
    lens = jnp.array([6, 4])
    got = lm_ssm.forward_logits(params, cfg, ids, lens)
    for s in range(2):
        n = int(lens[s])
        assert rel(got[s, :n], ref_forward(params, raw, ids[s, :n])["logits"]) < TOL


# (c) prefill, then decode through the carried state ---------------------------

def decode_logits(params, cfg, prompt, lens, ids, lora, scale):
    """Logits of every decode step, teacher-forced on ``ids``, through the two
    hooks ``lm.generate`` runs and the tied head's image-id rows."""
    state, stats, _ = lm_ssm.prefill_state(params, cfg, prompt, lens, lora, scale, None)
    assert stats == []
    image_head, whole = lm_ssm.cut_head(params, cfg.image_id_offset, cfg.image_id_offset + cfg.image_vocab)
    assert whole == tuple(params["embed"].shape) and "head" not in image_head
    last, out = jnp.full((prompt.shape[0],), cfg.boi_id), []
    for i in range(cfg.image_tokens):
        x, state, _ = lm_ssm.decode_layers(params, cfg, params["embed"][last], state, jnp.int32(i), lens, lora,
                                           scale, None)
        out.append(lm_ssm.head(image_head, cfg, x))
        last = ids[:, i] + cfg.image_id_offset
    return jnp.stack(out, axis=1)


def test_prefill_then_cached_decode_against_full_forward(toy):
    cfg, raw, params = toy
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, cfg.max_prompt_len), 2, 48)
    lens = jnp.array([6, 3])
    lora = random_lora(jax.random.PRNGKey(4), params, cfg)
    ids, rows = lm.generate(params, cfg, prompt, lens, jax.random.PRNGKey(5), lora=lora, lora_scale=2.0,
                            decode=False)
    assert set(rows) == {"ids", "logits", "carried/state", "carried/kv_cache"}   # no router: no routing rows
    assert rows["logits"].shape == (2, cfg.image_tokens // lm.PROBE_EVERY, cfg.image_vocab)
    assert float(rows["carried/state"][0]) == 4 * (STATE_ELEMENTS * 4 + 3 * CONV_CHANNELS * 4)
    assert float(rows["carried/kv_cache"][0]) == 2 * 2 * cfg.cache_len * 2 * 8 * 4
    got = decode_logits(params, cfg, prompt, lens, ids, lora, 2.0)
    lo = cfg.image_id_offset
    for s in range(2):
        L = int(lens[s])
        seq = jnp.concatenate([prompt[s, :L], jnp.array([cfg.boi_id]), ids[s, :-1] + lo])
        want = ref_forward(params, raw, seq, lora, 2.0)["logits"][L:, lo: lo + cfg.image_vocab]
        assert rel(got[s], want) < TOL
        assert rel(rows["logits"][s, 0], want[0]) < TOL


def test_generate_does_not_depend_on_how_the_batch_is_chunked(toy):
    cfg, _, params = toy
    prompt = jax.random.randint(jax.random.PRNGKey(6), (4, cfg.max_prompt_len), 2, 48)
    lens = jnp.array([6, 3, 5, 1])
    key = jax.random.PRNGKey(7)
    ids, rows = lm.generate(params, cfg, prompt, lens, key, decode=False)
    for lo in (0, 2):
        part, prow = lm.generate(params, cfg, prompt[lo: lo + 2], lens[lo: lo + 2], key, decode=False,
                                 item_index=jnp.arange(lo, lo + 2))
        assert np.array_equal(part, ids[lo: lo + 2])
        assert rel(prow["logits"], rows["logits"][lo: lo + 2]) < MIXER_TOL


@pytest.mark.parametrize("dtype,itemsize", [(jnp.float32, 4), (jnp.bfloat16, 2)], ids=["float32", "bfloat16-control"])
def test_carried_bytes_follow_the_dtype_the_state_is_carried_in(toy, monkeypatch, dtype, itemsize):
    """``lm/state_bytes`` is counted from the carried arrays themselves, and
    the family reads the one ``lm_hybrid.STATE_DTYPE``: the benchmark's
    control reaches it there. The control carries half and is further from
    the float32 program than the order of its sums."""
    cfg, raw, params = toy
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, cfg.max_prompt_len), 2, 48)
    lens = jnp.array([6, 3])
    ids, _ = lm.generate(params, cfg, prompt, lens, jax.random.PRNGKey(5), decode=False)
    stated = decode_logits(params, cfg, prompt, lens, ids, None, 1.0)
    monkeypatch.setattr(lm_hybrid, "STATE_DTYPE", dtype)
    _, rows = lm.generate(params, cfg, prompt, lens, jax.random.PRNGKey(5), decode=False)
    assert float(rows["carried/state"][0]) == 4 * (STATE_ELEMENTS * itemsize + 3 * CONV_CHANNELS * 4)
    state, _, _ = lm_ssm.prefill_state(params, cfg, prompt, lens, None, 1.0, None)
    assert [c[0].dtype for c, kind in zip(state, PERIOD) if kind == "mamba"] == [dtype] * 2
    moved = rel(decode_logits(params, cfg, prompt, lens, ids, None, 1.0), stated)
    assert moved == 0.0 if dtype == jnp.float32 else 1e-6 < moved < 1e-1


# (d) what the configuration states reaches both sides --------------------------

@pytest.mark.parametrize("change", ["embedding_multiplier", "residual_multiplier", "attention_multiplier",
                                    "logits_scaling", "tied_head"])
def test_each_multiplier_and_the_tied_head_move_program_and_reference_alike(tmp_path, toy, change):
    """Each of the four published multipliers, changed in the file, moves the
    program's logits and the reference's by more than a hundred tolerances
    — and the two still agree; so does a change of the embedding's image-id
    rows, which only a head tied to the embedding reads."""
    cfg, raw, params = toy
    ids = jax.random.randint(jax.random.PRNGKey(8), (1, 6), 0, 48)
    lens = jnp.array([6])
    if change == "tied_head":
        cfg2, raw2 = cfg, raw
        params2 = dict(params, embed=params["embed"].at[48:].multiply(-2.0))   # no prompt id reads these rows
    else:
        cfg2, raw2 = toy_cfg(tmp_path, **{change: TOY[change] * 1.7})
        params2 = params
    base, other = lm_ssm.forward_logits(params, cfg, ids, lens)[0], lm_ssm.forward_logits(params2, cfg2, ids, lens)[0]
    want, want2 = ref_forward(params, raw, ids[0])["logits"], ref_forward(params2, raw2, ids[0])["logits"]
    assert rel(other, base) > 100 * TOL and rel(want2, want) > 100 * TOL
    assert rel(other, want2) < TOL and rel(base, want) < TOL


def test_config_json_gives_the_family_its_layer_types_and_period(toy):
    cfg, _, params = toy
    assert isinstance(cfg, lm_ssm.SSMLMConfig) and cfg.family() is lm_ssm.FAMILY
    assert cfg.model_type == "granitemoehybrid" and cfg.layer_types == tuple(PERIOD * 2)
    assert (cfg.period, cfg.n_periods, cfg.period_types) == (3, 2, tuple(PERIOD))
    assert len(params["layers"]) == 3 and "head" not in params                      # tied: one matrix
    assert params["layers"][1]["attn"]["wq"]["kernel"].shape == (2, 32, 32)         # stacked by period
    published = lm_ssm.SSMLMConfig()
    assert (published.period, published.n_periods, published.period_types.index("attention")) == (10, 4, 5)
    assert (published.conv_channels, published.head_dim) == (4352, 64)


@pytest.mark.parametrize("over,match", [
    ({"num_local_experts": 8, "num_experts_per_tok": 2}, r"^num_local_experts 8: .*the routed variant"),
    ({"position_embedding_type": "rope"}, r"position_embedding_type.*'rope'"),
    ({"layer_types": PERIOD * 2 + ["mamba"]}, "layer_types must name 6 layers"),
    ({"vocab_rows_held": 32}, "a tied head is the whole embedding"),
], ids=["routed-variant", "rope", "layer-count", "vocab-share"])
def test_config_from_json_refuses_what_it_does_not_write_down_with_one_line(tmp_path, over, match):
    with pytest.raises(ValueError, match=match) as e:
        toy_cfg(tmp_path, **over)
    assert "\n" not in str(e.value)


def test_config_from_json_names_the_family_among_the_known(tmp_path):
    path = tmp_path / "c.json"
    path.write_text(json.dumps({**TOY, "model_type": "mamba2"}))
    with pytest.raises(ValueError, match="model_type 'mamba2'.*granitemoehybrid"):
        lm.config_from_json(str(path))
    path.write_text(json.dumps({k: v for k, v in TOY.items() if k != "mamba_d_state"}))
    with pytest.raises(ValueError, match=r"granitemoehybrid config.json needs the keys \['mamba_d_state'\]"):
        lm.config_from_json(str(path))


# (e) factored per-member deltas against materialized weights ------------------

def test_factored_member_path_against_materialized_weights(toy):
    """Two members of an antithetic pair through both Mamba-2 projections,
    the attention projections and the FFN, stacked by period: the fused path
    (``FactoredDelta`` leaves sliced at the period index inside the scan)
    agrees with each member's materialized ``W + dW`` in the reference."""
    cfg, raw, params = toy
    theta = random_lora(jax.random.PRNGKey(8), params, cfg)
    assert set(theta) == {f"layers/{j}/{site}" for j, kind in enumerate(PERIOD) for site in
                          ((["mamba/in_proj", "mamba/out_proj"] if kind == "mamba" else
                            ["attn/wq", "attn/wk", "attn/wv", "attn/wo"]) + ["ffn/gate", "ffn/up", "ffn/down"])}
    assert theta["layers/0/mamba/in_proj"]["a"].shape == (2, 32, 2)
    es = EggRollConfig(sigma=0.05, rank=2, antithetic=True)
    noise = sample_noise(jax.random.PRNGKey(9), theta, 2, es)
    ids = jax.random.randint(jax.random.PRNGKey(10), (1, 6), 0, cfg.vocab_rows_held)
    lens = jnp.array([6])

    def fused(k):
        return lm_ssm.forward_logits(params, cfg, ids, lens, factored_member_theta(theta, noise, k, 2, es), 2.0)

    got = jax.jit(jax.vmap(fused))(jnp.arange(2))
    wants = [ref_forward(params, raw, ids[0], perturb_member(theta, noise, k, 2, es), 2.0)["logits"] for k in range(2)]
    for k in range(2):
        assert rel(got[k, 0], wants[k]) < TOL
    assert rel(wants[0], wants[1]) > 1e-3


def test_reference_batch_form_and_the_benchmarks_copy(toy):
    cfg, raw, params = toy
    ids = jax.random.randint(jax.random.PRNGKey(13), (2, 7), 0, cfg.vocab_rows_held)
    lw = lambda i: ref.block_weights(params, i)
    top = ref.top_weights(params)
    both = ref.forward_batch(lw, 6, top, raw, ids)
    for s in range(2):
        one = ref.forward(lw, 6, top, raw, ids[s, :5])   # a shorter prefix: padding behind it is not seen
        assert rel(both["logits"][s, :5], one["logits"]) < 1e-5
    # the two hooks of the chip comparison move the logits, each by more than the tolerance
    f8 = ref.forward_batch(lw, 6, top, raw, ids, act=lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype))
    b16 = ref.forward_batch(lw, 6, top, raw, ids, state_round=lambda s: s.astype(jnp.bfloat16).astype(s.dtype))
    assert rel(f8["logits"], both["logits"]) > 1e-3 and rel(b16["logits"], both["logits"]) > 1e-5
    root = Path(ref.__file__).resolve().parents[2]
    assert (root / "benchmarks/reference/mamba2_gqa_reference.py").read_text() == Path(ref.__file__).read_text()


# (f) the backend on train.cli's normal path -----------------------------------

def test_train_cli_lm_ar_two_epochs_on_a_granitemoehybrid_config(tmp_path, monkeypatch):
    """``train.cli --backend lm_ar --lm_config <granitemoehybrid file>`` through
    the same trainer, member loop, quantizer and reward path as every backend:
    one compile, one ``pop_eval`` trace, an applied update, members that score
    differently, the carried-state counters — and no ``moe/*`` counter and no
    routing in the probe, for a model without a router."""
    from hyperscalees_t2i_tpu.train import cli

    monkeypatch.setenv("HSES_BASE_QUANT_MIN_SIZE", "1")  # toy kernels still go int8
    (tmp_path / "config.json").write_text(json.dumps({**TOY, "vocab_size": 77}))  # 77: no other axis of the toy
    prompts = tmp_path / "p.txt"
    prompts.write_text("a red square on a table\na blue circle\nthree green triangles in a row\n")
    cli.main([
        "--backend", "lm_ar", "--lm_config", str(tmp_path / "config.json"), "--model_scale", "tiny",
        "--prompts_txt", str(prompts), "--base_quant", "int8",
        "--noise_dtype", "bfloat16", "--sigma", "0.25", "--lora_r", "2", "--lora_alpha", "4",
        "--pop_size", "4", "--prompts_per_gen", "2", "--member_batch", "2",
        "--num_epochs", "2", "--allow_random_rewards", "true",
        "--run_dir", str(tmp_path / "runs"), "--run_name", "run", "--resume", "false", "--save_every", "0",
        "--trace", "true",
    ])
    run = tmp_path / "runs" / "run"
    rows = [json.loads(l) for l in (run / "metrics.jsonl").read_text().splitlines()]
    assert [r["epoch"] for r in rows] == [0, 1]
    state = 4 * (STATE_ELEMENTS * 4 + 3 * CONV_CHANNELS * 4)   # a sequence: four Mamba-2 layers' f32 state + window
    kv = 2 * 2 * 22 * 2 * 8 * 4                                # two attention layers' K and V over 22 slots
    for r in rows:
        assert r["obs/compiles"] == 1 and r["obs/pop_eval_traces"] == 1
        assert np.isfinite(r["reward/combined_mean"]) and r["delta_norm"] > 0
        assert r["lm/state_bytes"] == 8 * state and r["lm/kv_cache_bytes"] == 8 * kv
        assert not any(k.startswith(("moe/", "probe/", "gen/")) for k in r)
    assert len(set(rows[0]["es/member_reward"])) > 1   # the perturbation reaches the model: members score differently
    steps = [json.loads(l) for l in (run / "programs.jsonl").read_text().splitlines()]
    (step,) = [p for p in steps if p["label"].startswith("es_step_")]
    # the ops as large as a position's state stack (2 periods x a member's one sequence on each of the mesh's two
    # data shards x 4 heads of 16 x 8) or one layer's: at least the decode scan that carries them
    assert step["geometry"]["recurrent_state_shape"] == [2, 1, 4, 16, 8]
    whole = step["recurrent_state_whole_ops"]
    assert whole.get("while", 0) >= 1 and all(isinstance(v, int) and v > 0 for v in whole.values())
    # the tied head is the embedding [rows, hidden]: the decode scan carries it (a `while`) to look up each sampled
    # id's row, and its head reads the image-id rows; nothing converts, copies or cuts it whole
    assert step["geometry"]["lm_head_shape"] == [77, 32]
    assert step["lm_head_whole_ops"] == {"while": 1}
    probe = np.load(run / "probe_epoch0.npz")
    assert sorted(probe.files) == ["ids", "logits", "prompt_ids", "prompt_len"] and probe["ids"].shape == (2, 16)
    q = lm.init_lm(jax.random.PRNGKey(0), lm.config_from_json(str(tmp_path / "config.json")), "int8")
    node = q["layers"][0]["mamba"]["in_proj"]["kernel_q8"]
    assert node["q8"].dtype == jnp.int8 and node["scale"].shape == (2, 1, 2 * 64 + 16 + 4)   # per period and column
    assert q["layers"][0]["mamba"]["conv"]["weight"].dtype == jnp.float32 and q["embed"].dtype == jnp.float32

"""The hybrid decoder (models/lm_hybrid.py, ops/gated_delta.py: Gated DeltaNet
layers beside gated attention, ``model_type: qwen3_next``) against its plain
float32 reference (reference/gdn_moe_reference.py), at toy widths on the CPU.

Tolerance 1e-4 relative (of the reference's largest magnitude), as
tests/test_lm.py argues it: both sides compute in float32 at ``highest``, so
what is left is the order of float32 sums — the chunked delta rule's WY
transform against the recurrence, the decode step's ``alpha q^T S + (q.k) D``
against ``q^T S_new``, grouped against looped experts, ``W + dW`` materialized
or not. A state touched by padding, a conv window taken from padded positions,
a non-zero-centred norm or a dropped gate is orders above it.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperscalees_t2i_tpu.es import EggRollConfig, factored_member_theta, perturb_member, sample_noise
from hyperscalees_t2i_tpu.lora import init_lora
from hyperscalees_t2i_tpu.models import lm, lm_hybrid as hy
from hyperscalees_t2i_tpu.ops import gated_delta
from hyperscalees_t2i_tpu.reference import gdn_moe_reference as ref

TOL = 1e-4

TOY = {
    "model_type": "qwen3_next",
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "partial_rotary_factor": 0.5, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "full_attention_interval": 4, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 8,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_value_head_dim": 8,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16, "num_experts": 16,
    "num_experts_per_tok": 4, "norm_topk_prob": True, "num_hidden_layers": 4, "vocab_size": 64,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "experts_held": 16, "expert_offset": 0, "vocab_rows_held": 64,
    "image_tokens": {"image_vocab": 16, "image_id_offset": 48, "boi_id": 1, "grid": 4,
                     "max_prompt_len": 6, "top_k": 0, "top_p": 0.0},
    "vq": {"c_vae": 8, "phi_partial": 2, "ch": 8, "ch_mult": [1, 1], "num_res_blocks": 1},
    "torch_dtype": "float32",
}


def toy_cfg(tmp_path, **over):
    raw = {**TOY, **over}
    path = tmp_path / "config.json"
    path.write_text(json.dumps(raw))
    return lm.config_from_json(str(path)), raw


def randomized_norms(params, key):
    """Norm weights away from their initial 0 / 1, so that a norm applied with
    the wrong centring shows."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(key, len(leaves))
    out = [leaf + 0.2 * jax.random.normal(k, leaf.shape) if any(getattr(p, "key", None) == "scale" for p in path)
           else leaf for k, (path, leaf) in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture(params=[4, 5], ids=["ends-in-attention", "ends-in-deltanet"])
def toy(request, tmp_path):
    cfg, raw = toy_cfg(tmp_path, num_hidden_layers=request.param)
    assert isinstance(cfg, hy.HybridLMConfig) and cfg.layer_types[:4] == ("linear_attention",) * 3 + ("full_attention",)
    return cfg, raw, randomized_norms(lm.init_lm(jax.random.PRNGKey(0), cfg), jax.random.PRNGKey(99))


@pytest.fixture()
def toy4(tmp_path):
    cfg, raw = toy_cfg(tmp_path)
    return cfg, raw, randomized_norms(lm.init_lm(jax.random.PRNGKey(0), cfg), jax.random.PRNGKey(99))


@pytest.fixture(params=["grouped", "dense"])
def form(request, monkeypatch):
    monkeypatch.setattr(lm, "DENSE_ROWS", 0 if request.param == "grouped" else 1 << 30)
    return request.param


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def ref_forward(params, raw, ids, lora=None, scale=1.0, forced=None):
    n = len(params["layers"])
    return ref.forward(lambda i: ref.block_weights(params["layers"][i], f"layers/{i}", lora, scale),
                       n, ref.top_weights(params), raw, ids, forced)


def random_lora(key, params, cfg):
    theta = init_lora(key, params, cfg.lora_spec(rank=2, alpha=4.0))
    ks = jax.random.split(key, len(theta))
    return {p: {"a": l["a"], "b": jax.random.normal(k, l["b"].shape) * 0.1}
            for k, (p, l) in zip(ks, sorted(theta.items()))}


# (a) the gated delta rule: chunked = one-position steps = a plain loop --------

def delta_rule_inputs(key, B=2, T=10, H=3, dk=4, dv=5):
    ks = jax.random.split(key, 6)
    l2 = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q, k = l2(jax.random.normal(ks[0], (B, T, H, dk))), l2(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jax.random.uniform(ks[3], (B, T, H), minval=0.01, maxval=3.0)
    beta = jax.random.uniform(ks[4], (B, T, H))
    return q, k, v, g, beta, jax.random.normal(ks[5], (B, H, dk, dv))


def loop_delta_rule(q, k, v, g, beta, S):
    q, k, v, g, beta, S = (np.asarray(a, np.float64) for a in (q, k, v, g, beta, S))
    out = np.zeros(v.shape)
    for t in range(q.shape[1]):
        S = np.exp(g[:, t])[..., None, None] * S
        delta = beta[:, t][..., None] * (v[:, t] - np.einsum("bhk,bhkv->bhv", k[:, t], S))
        S = S + k[:, t][..., :, None] * delta[..., None, :]
        out[:, t] = np.einsum("bhk,bhkv->bhv", q[:, t], S)
    return out, S


@pytest.mark.parametrize("chunk", [4, 5, 64], ids=["three-chunks-padded", "two-chunks", "one-chunk"])
def test_chunked_delta_rule_is_the_recurrence(chunk):
    args = delta_rule_inputs(jax.random.PRNGKey(0))
    want_o, want_s = loop_delta_rule(*args)
    o_r, s_r = gated_delta.recurrent_gated_delta_rule(*args)
    o_c, s_c = gated_delta.chunk_gated_delta_rule(*args, chunk=chunk)
    assert rel(o_r, want_o) < 1e-5 and rel(s_r, want_s) < 1e-5
    assert rel(o_c, want_o) < 1e-5 and rel(s_c, want_s) < 1e-5


def test_positions_with_beta_0_and_g_0_leave_the_state_alone():
    q, k, v, g, beta, S = delta_rule_inputs(jax.random.PRNGKey(1))
    real = (jnp.arange(10)[None, :] < jnp.array([10, 6])[:, None])[..., None]
    o, s = gated_delta.chunk_gated_delta_rule(q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0), S, chunk=4)
    _, s6 = gated_delta.recurrent_gated_delta_rule(q[1:, :6], k[1:, :6], v[1:, :6], g[1:, :6], beta[1:, :6], S[1:])
    assert rel(s[1], s6[0]) < 1e-5


# (b) each block kind, the padded prefill, and the whole stack -----------------

@pytest.mark.parametrize("layer", [0, 3], ids=["deltanet", "attention"])
def test_one_block_against_reference(toy4, layer, form):
    cfg, raw, params = toy4
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, cfg.hidden_size))
    pos, lens = jnp.arange(6)[None], jnp.array([6])
    valid = jnp.ones((1, 6), bool)
    p = params["layers"][layer]
    if layer == 3:
        mixer = lambda u: hy.attn_prefill(p["attn"], cfg, u, pos, valid, None, "x", 1.0)
    else:
        mixer = lambda u: hy.gdn_prefill(p["gdn"], cfg, u, lens, None, "x", 1.0, chunk=4)
    y, _, stats = hy.block(p, cfg, layer, x, mixer, valid, None, None, 1.0)
    with jax.default_matmul_precision("highest"):
        want, ids = ref.block(ref.block_weights(p, "x"), raw, x[0])
    assert rel(y[0], want) < TOL
    assert np.array_equal(np.sort(np.asarray(ids), -1), np.asarray(stats["topk"]))


@pytest.mark.parametrize("chunk", [4, 64], ids=["two-chunks", "one-chunk"])
def test_padded_prefill_leaves_state_and_conv_window_untouched(toy4, chunk):
    """Right-padded prompts of unequal length: each sequence's output before
    its padding, its recurrent state and its conv window are those of the
    sequence run alone at its own length — and the reference's."""
    cfg, raw, params = toy4
    p = params["layers"][0]["gdn"]
    u = jax.random.normal(jax.random.PRNGKey(2), (3, 6, cfg.hidden_size))
    lens = jnp.array([6, 2, 4])
    out, (state, window) = hy.gdn_prefill(p, cfg, u, lens, None, "x", 1.0, chunk=chunk)
    K = cfg.linear_conv_kernel_dim
    w = ref.block_weights(params["layers"][0], "x")
    for s in range(3):
        L = int(lens[s])
        alone, (s_alone, w_alone) = hy.gdn_prefill(p, cfg, u[s: s + 1, :L], lens[s: s + 1], None, "x", 1.0, chunk=chunk)
        assert rel(out[s, :L], alone[0]) < 1e-5 and rel(state[s], s_alone[0]) < 1e-5
        assert np.array_equal(np.asarray(window[s]), np.asarray(w_alone[0]))
        with jax.default_matmul_precision("highest"):
            assert rel(out[s, :L], ref.gated_deltanet(w, raw, u[s, :L])) < TOL
        mixed = hy._gdn_project(p, cfg, u[s, :L], None, "x", 1.0)[0]     # the conv's real inputs
        want = np.concatenate([np.zeros((max(K - 1 - L, 0), mixed.shape[1])), np.asarray(mixed)[-(K - 1):]])
        assert np.allclose(np.asarray(window[s]), want, atol=1e-6)


def test_whole_stack_logits_against_reference(toy, form):
    cfg, raw, params = toy
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0, cfg.vocab_rows_held)
    lens = jnp.array([6, 4])
    got = hy.forward_logits(params, cfg, ids, lens)
    for s in range(2):
        n = int(lens[s])
        want = ref_forward(params, raw, ids[s, :n])["logits"]
        assert rel(got[s, :n], want) < TOL  # padded positions change nothing before them


# (c) prefill, then cached decode through both kinds of state ------------------

def decode_logits(params, cfg, prompt, lens, ids, lora, scale):
    """Logits of every decode step, teacher-forced on ``ids``, through the two
    hooks ``lm.generate`` runs: ``prefill_state``, then ``decode_layers`` over
    the carried recurrent states, conv windows and KV caches."""
    factors = lm.expert_factors(lora, cfg, cfg.compute_dtype)
    state, _, _ = hy.prefill_state(params, cfg, prompt, lens, lora, scale, factors)
    last = jnp.full((prompt.shape[0],), cfg.boi_id)
    lo, out = cfg.image_id_offset, []
    for i in range(cfg.image_tokens):
        x, state, _ = hy.decode_layers(params, cfg, params["embed"][last], state, jnp.int32(i), lens, lora, scale,
                                       factors)
        out.append(hy.head(params, cfg, x)[:, lo: lo + cfg.image_vocab])
        last = ids[:, i] + lo
    return jnp.stack(out, axis=1)


def force_kernel(monkeypatch):
    """What a TPU selects, made to run here: ``gdn_decode`` calls
    ``gated_delta.gated_delta_step`` with no arguments of its own, so the test
    steers the call through the interpreted Pallas kernel. Returns the list
    the fit check's verdict of every such call is appended to."""
    real, verdicts = gated_delta.gated_delta_step, []

    def forced(q, k, v, g, beta, state):
        verdicts.append(gated_delta.kernel_head_block(q, v, state))
        return real(q, k, v, g, beta, state, interpret=True)

    monkeypatch.setattr(gated_delta, "gated_delta_step", forced)
    return verdicts


@pytest.fixture(params=[(4, 8), (5, 8), (4, 128)],
                ids=["ends-in-attention", "ends-in-deltanet", "published-head-through-the-kernel"])
def toy_decoded(request, tmp_path, monkeypatch):
    """The two toys, and one at the published 128 x 128 DeltaNet head whose
    decode steps go through the kernel (:func:`force_kernel`)."""
    layers, head = request.param
    cfg, raw = toy_cfg(tmp_path, num_hidden_layers=layers, linear_key_head_dim=head, linear_value_head_dim=head)
    verdicts = force_kernel(monkeypatch) if head == 128 else []
    yield cfg, raw, randomized_norms(lm.init_lm(jax.random.PRNGKey(0), cfg), jax.random.PRNGKey(99))
    assert all(h == cfg.linear_num_value_heads for h in verdicts) and bool(verdicts) == (head == 128)


def test_prefill_then_cached_decode_against_full_forward(toy_decoded, form):
    cfg, raw, params = toy_decoded
    n, L_all = cfg.image_tokens, cfg.num_hidden_layers
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, cfg.max_prompt_len), 2, 48)
    lens = jnp.array([6, 3])
    lora = random_lora(jax.random.PRNGKey(4), params, cfg)
    ids, rows = lm.generate(params, cfg, prompt, lens, jax.random.PRNGKey(5), lora=lora, lora_scale=2.0,
                            decode=False)
    assert ids.shape == (2, n) and rows["logits"].shape == (2, n // lm.PROBE_EVERY, cfg.image_vocab)
    assert rows["topk"].shape == (2, cfg.cache_len, L_all, cfg.num_experts_per_tok)
    # what a sequence carries: 3 (or 4) DeltaNet layers' float32 state + conv window, one attention layer's K and V
    n_g = sum(k == "linear_attention" for k in cfg.layer_types)
    assert float(rows["carried/state"][0]) == n_g * (4 * dk * dv * 4 + 3 * cfg.conv_channels * 4)
    assert float(rows["carried/kv_cache"][0]) == (L_all - n_g) * 2 * cfg.cache_len * 2 * 8 * 4
    got = decode_logits(params, cfg, prompt, lens, ids, lora, 2.0)
    for s in range(2):
        L = int(lens[s])
        seq = jnp.concatenate([prompt[s, :L], jnp.array([cfg.boi_id]), ids[s, :-1] + cfg.image_id_offset])
        out = ref_forward(params, raw, seq, lora, 2.0)
        want = out["logits"][L:, cfg.image_id_offset: cfg.image_id_offset + cfg.image_vocab]
        assert rel(got[s], want) < TOL
        assert rel(rows["logits"][s, 0], want[0]) < TOL
        for j, picked in enumerate(out["topk"]):   # the cache slots' routing is the reference's, layer by layer
            mine = np.asarray(rows["topk"][s, :, j])
            picked = np.sort(np.asarray(picked), -1)
            assert np.array_equal(mine[cfg.max_prompt_len:], picked[L:])
            assert (mine[L: cfg.max_prompt_len] == -1).all()
            if j + 1 < L_all:
                assert np.array_equal(mine[:L], picked[:L])
            else:  # generation stops the last layer's prefill at what it carries on: no prompt row is routed there
                assert (mine[:L] == -1).all()


def test_generate_does_not_depend_on_how_the_batch_is_chunked(toy4):
    cfg, _, params = toy4
    prompt = jax.random.randint(jax.random.PRNGKey(6), (4, cfg.max_prompt_len), 2, 48)
    lens = jnp.array([6, 3, 5, 1])
    key = jax.random.PRNGKey(7)
    ids, rows = lm.generate(params, cfg, prompt, lens, key, decode=False)
    for lo in (0, 2):
        part, prow = lm.generate(params, cfg, prompt[lo: lo + 2], lens[lo: lo + 2], key, decode=False,
                                 item_index=jnp.arange(lo, lo + 2))
        assert np.array_equal(part, ids[lo: lo + 2])
        assert rel(prow["logits"], rows["logits"][lo: lo + 2]) < 1e-5


@pytest.mark.parametrize("dtype,itemsize", [(jnp.float32, 4), (jnp.bfloat16, 2)], ids=["float32", "bfloat16-control"])
def test_carried_bytes_follow_the_dtype_the_state_is_carried_in(toy4, monkeypatch, dtype, itemsize):
    """``lm/state_bytes`` is counted from the carried arrays themselves: the
    benchmark's ``correct`` holds a step to the float32 state by that count,
    because the reference comparison cannot (a bfloat16 state moves the logits
    less than bf16 activations do). The control carries half and is further
    from the float32 program than the order of its sums."""
    cfg, raw, params = toy4
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, cfg.max_prompt_len), 2, 48)
    lens = jnp.array([6, 3])
    ids, rows = lm.generate(params, cfg, prompt, lens, jax.random.PRNGKey(5), decode=False)
    stated = decode_logits(params, cfg, prompt, lens, ids, None, 1.0)
    monkeypatch.setattr(hy, "STATE_DTYPE", dtype)
    again, rows = lm.generate(params, cfg, prompt, lens, jax.random.PRNGKey(5), decode=False)
    assert float(rows["carried/state"][0]) == 3 * (4 * 8 * 8 * itemsize + 3 * cfg.conv_channels * 4)
    state, _, _ = hy.prefill_state(params, cfg, prompt, lens, None, 1.0, None)
    assert [c[0].dtype for c in state[:3]] == [dtype] * 3
    moved = rel(decode_logits(params, cfg, prompt, lens, ids, None, 1.0), stated)
    assert moved == 0.0 if dtype == jnp.float32 else 1e-5 < moved < 1e-1


# (d) the share adds up --------------------------------------------------------

def test_four_shares_and_the_gated_shared_expert_add_up_to_the_uncut_layer(tmp_path, toy4, form):
    """16 experts over 4 shares: the routed parts of the four shares plus the
    sigmoid-gated shared expert counted once equal the uncut reference's MoE."""
    cfg, raw, params = toy4
    p = params["layers"][1]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(7), (10, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.moe(ref.block_weights(params["layers"][1], "x"), raw, u)
    top_i, top_w = hy.route(p, cfg, u)
    assert np.allclose(top_w.sum(-1), 1.0, atol=1e-6)  # normalized over all k chosen, held or not
    total = jax.nn.sigmoid(u @ p["shared_gate"]["weight"]) * lm._swiglu(p["shared"], u, None, "x", 1.0)
    for share in range(4):
        cfg_s, _ = toy_cfg(tmp_path, experts_held=4, expert_offset=4 * share)
        mine = {k: {"kernel": v["kernel"][4 * share: 4 * share + 4]} for k, v in p["experts"].items()}
        routed, e = lm.routed_experts(mine, cfg_s, u, top_i, top_w, jnp.ones((10,), bool), None, 1.0)
        assert int((e < 4).sum()) == int(((top_i >= 4 * share) & (top_i < 4 * share + 4)).sum())
        total = total + routed
    assert rel(total, whole) < TOL


# (e) factored per-member deltas against materialized weights ------------------

def test_factored_member_path_against_materialized_weights(toy4, form):
    """Two members, an antithetic pair, through ``Wqkvz`` / ``Wout``, the
    attention projections and the expert axis: the fused path (``FactoredDelta``
    leaves, members vmapped so that their rows share one expert call) agrees
    with each member's materialized ``W + dW`` in the plain reference."""
    cfg, raw, params = toy4
    theta = random_lora(jax.random.PRNGKey(8), params, cfg)
    assert {"layers/0/gdn/wqkvz", "layers/0/gdn/wout", "layers/3/attn/wq", "layers/3/attn/wo",
            "layers/2/moe/experts/down", "layers/2/moe/shared/up"} <= set(theta)
    assert not any(k in path for path in theta for k in ("wba", "conv", "router", "shared_gate", "head"))
    assert theta["layers/1/moe/experts/gate"]["a"].shape == (16, 32, 2)
    es = EggRollConfig(sigma=0.05, rank=2, antithetic=True)
    noise = sample_noise(jax.random.PRNGKey(9), theta, 2, es)
    ids = jax.random.randint(jax.random.PRNGKey(10), (1, 6), 0, cfg.vocab_rows_held)
    lens = jnp.array([6])

    def fused(k):
        return hy.forward_logits(params, cfg, ids, lens, factored_member_theta(theta, noise, k, 2, es), 2.0)

    got = jax.jit(jax.vmap(fused))(jnp.arange(2))
    wants = []
    for k in range(2):
        wants.append(ref_forward(params, raw, ids[0], perturb_member(theta, noise, k, 2, es), 2.0)["logits"])
        assert rel(got[k, 0], wants[k]) < TOL
    assert rel(wants[0], wants[1]) > 1e-3  # the pair's halves do differ


# (f) the configuration file ---------------------------------------------------

def test_config_from_json_names_what_it_cannot_read(tmp_path):
    path = tmp_path / "c.json"
    path.write_text(json.dumps({**TOY, "model_type": "gpt_neox"}))
    with pytest.raises(ValueError, match="model_type 'gpt_neox'.*qwen3_next"):
        lm.config_from_json(str(path))
    path.write_text(json.dumps({k: v for k, v in TOY.items() if k not in ("head_dim", "num_experts")}))
    with pytest.raises(ValueError, match=r"qwen3_next config.json needs the keys \['head_dim', 'num_experts'\]"):
        lm.config_from_json(str(path))
    path.write_text(json.dumps({"hidden_size": 32}))  # no model_type: read as the MLA family, whose keys are missing
    with pytest.raises(ValueError, match="MLA-family config.json needs the keys .*q_lora_rank"):
        lm.config_from_json(str(path))
    with pytest.raises(ValueError, match="n_shared_experts 2: the MLA family"):
        lm.LMConfig(n_shared_experts=2)
    path.write_text(json.dumps({**TOY, "mlp_only_layers": [1]}))
    with pytest.raises(ValueError, match="every layer has routed experts"):
        lm.config_from_json(str(path))


def test_reference_batch_form_forced_routing_and_the_benchmarks_copy(toy4):
    cfg, raw, params = toy4
    ids = jax.random.randint(jax.random.PRNGKey(13), (2, 7), 0, cfg.vocab_rows_held)
    lw = lambda i: ref.block_weights(params["layers"][i], f"layers/{i}")
    top = ref.top_weights(params)
    both = ref.forward_batch(lw, 4, top, raw, ids)
    for s in range(2):
        one = ref.forward(lw, 4, top, raw, ids[s, :5])  # a shorter prefix: padding behind it is not seen
        assert rel(both["logits"][s, :5], one["logits"]) < 1e-5
        assert np.array_equal(both["topk"][s, :5, 0], one["topk"][0])
    forced = ref.forward_batch(lw, 4, top, raw, ids, forced_topk=both["topk"])
    assert rel(forced["logits"], both["logits"]) < 1e-5
    other = ref.forward_batch(lw, 4, top, raw, ids, forced_topk=(both["topk"] + 1) % 16)
    assert rel(other["logits"], both["logits"]) > 1e-3
    assert np.array_equal(other["topk"][:, :, 0], both["topk"][:, :, 0])  # its own choice is still reported
    # the two hooks of the chip comparison move the logits, each by more than the tolerance
    f8 = ref.forward_batch(lw, 4, top, raw, ids, act=lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype))
    b16 = ref.forward_batch(lw, 4, top, raw, ids, state_round=lambda s: s.astype(jnp.bfloat16).astype(s.dtype))
    assert rel(f8["logits"], both["logits"]) > 1e-3 and rel(b16["logits"], both["logits"]) > 1e-4
    root = Path(ref.__file__).resolve().parents[2]
    assert (root / "benchmarks/reference/gdn_moe_reference.py").read_text() == Path(ref.__file__).read_text()


# (g) the backend on train.cli's normal path -----------------------------------

def test_train_cli_lm_ar_two_epochs_on_a_qwen3_next_config(tmp_path, monkeypatch):
    """``train.cli --backend lm_ar --lm_config <qwen3_next file>`` through the
    same trainer, member loop, quantizer and reward path as every backend: one
    compile, one ``pop_eval`` trace, an applied update, members that score
    differently, every counter — the carried-state ones among them."""
    from hyperscalees_t2i_tpu.train import cli

    monkeypatch.setenv("HSES_BASE_QUANT_MIN_SIZE", "1")  # toy kernels still go int8
    (tmp_path / "config.json").write_text(json.dumps({**TOY, "vocab_size": 77, "vocab_rows_held": 77}))  # 77: no other axis of the toy
    prompts = tmp_path / "p.txt"
    prompts.write_text("a red square on a table\na blue circle\nthree green triangles in a row\n")
    cli.main([
        "--backend", "lm_ar", "--lm_config", str(tmp_path / "config.json"), "--model_scale", "tiny",
        "--prompts_txt", str(prompts), "--base_quant", "int8",
        "--noise_dtype", "bfloat16", "--sigma", "0.5", "--lora_r", "2", "--lora_alpha", "4",
        "--pop_size", "4", "--prompts_per_gen", "2", "--member_batch", "2",
        "--num_epochs", "2", "--allow_random_rewards", "true",
        "--run_dir", str(tmp_path / "runs"), "--run_name", "run", "--resume", "false", "--save_every", "0",
        "--trace", "true",
    ])
    run = tmp_path / "runs" / "run"
    rows = [json.loads(l) for l in (run / "metrics.jsonl").read_text().splitlines()]
    assert [r["epoch"] for r in rows] == [0, 1]
    state = 3 * (4 * 8 * 8 * 4 + 3 * 64 * 4)    # a sequence: three DeltaNet layers' float32 state + conv window
    kv = 2 * 22 * 2 * 8 * 4                     # and one attention layer's K and V over 22 slots
    for r in rows:
        assert r["obs/compiles"] == 1 and r["obs/pop_eval_traces"] == 1
        assert np.isfinite(r["reward/combined_mean"]) and r["delta_norm"] > 0
        # 4 members x 2 prompts x 16 sampled positions x 4 layers x top-4, all experts held, + the prompts' at three
        assert r["moe/local_assignments"] > 4 * 2 * 16 * 4 * 4
        assert r["moe/max_expert_load"] >= 1.0 and 0.0 <= r["moe/pair_route_flip"] <= 1.0
        assert r["lm/state_bytes"] == 8 * state and r["lm/kv_cache_bytes"] == 8 * kv
        assert not any(k.startswith(("probe/", "gen/")) for k in r)
    assert len(set(rows[0]["es/member_reward"])) > 1   # the perturbation reaches the model: members score differently
    assert rows[-1]["obs/dispatches"] == 2
    steps = [json.loads(l) for l in (run / "programs.jsonl").read_text().splitlines()]
    (step,) = [p for p in steps if p["label"].startswith("es_step_")]
    # a traced run counts the compiled step's ops as large as a DeltaNet layer's state (three layers; a member's
    # two sequences lie on the mesh's two data shards, one each x 4 heads of 8 x 8): at least the decode scan
    assert step["geometry"]["recurrent_state_shape"] == [3, 1, 4, 8, 8]
    whole = step["recurrent_state_whole_ops"]
    assert whole.get("while", 0) >= 1 and all(isinstance(v, int) and v > 0 for v in whole.values())
    # and the ops as large as the whole head, noted as its [hidden, rows held] kernel: the decode scan is
    # handed the image-id columns, so nothing dequantizes, converts, copies, cuts or carries the int8 head whole
    assert step["geometry"]["lm_head_shape"] == [32, 77]
    head_whole = step["lm_head_whole_ops"]
    assert not any(op.startswith(("fusion", "convert", "copy", "slice", "while")) for op in head_whole), head_whole
    assert not any("lm_head" in k for r in rows for k in r)   # geometry is the program's record, never an epoch's row
    probe = np.load(run / "probe_epoch0.npz")
    assert probe["ids"].shape == (2, 16) and probe["topk"].shape == (2, 22, 4, 4)
    q = lm.init_lm(jax.random.PRNGKey(0), lm.config_from_json(str(tmp_path / "config.json")), "int8")
    assert q["layers"][0]["gdn"]["wqkvz"]["kernel_q8"]["q8"].dtype == jnp.int8
    assert q["layers"][0]["gdn"]["conv"]["weight"].dtype == jnp.float32        # the conv, A_log, dt_bias stay float
    assert q["layers"][1]["moe"]["experts"]["gate"]["kernel_q8"]["scale"].shape == (16, 1, 16)

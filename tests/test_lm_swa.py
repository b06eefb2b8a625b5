"""The ``mimo_v2_flash`` decoder (models/lm_swa.py: sliding-window attention
with a learned sink beside full grouped-query attention, query-key heads wider
than value heads, two RoPE bases, a sigmoid router over routed experts with no
shared expert, a dense first layer) against its plain float32 reference
(reference/gqa_swa_moe_reference.py), at toy widths on the CPU.

Tolerance 1e-4 relative, as tests/test_lm.py argues it: both sides compute in
float32 at ``highest`` (conftest; the toy states ``torch_dtype: float32``), so
what is left is the order of float32 sums — the decode step's ring and cache
against the reference's whole masked sequence, the sink as a term of the
denominator against a column of the softmax, ``W + dW`` materialized or not.
A key seen outside its window, a dropped sink, an unscaled V or the wrong RoPE
base is orders above it. The toy's window (4) is shorter than its prompts (up
to 6) and than its sequences (up to 23 positions), so both masks bind in the
prefill and the ring wraps many times in the decode.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperscalees_t2i_tpu.es import EggRollConfig, factored_member_theta, perturb_member, sample_noise
from hyperscalees_t2i_tpu.models import lm, lm_swa
from hyperscalees_t2i_tpu.reference import gqa_swa_moe_reference as ref
from tests.test_lm import random_lora, rel

TOL = 1e-4

PATTERN = [0, 1, 1, 0, 1]
TOY = {
    "model_type": "mimo_v2_flash",
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 12, "v_head_dim": 8,
    "swa_num_attention_heads": 4, "swa_num_key_value_heads": 2, "swa_head_dim": 12, "swa_v_head_dim": 8,
    "partial_rotary_factor": 0.334, "rope_theta": 5000000, "swa_rope_theta": 10000, "sliding_window": 4,
    "attention_value_scale": 0.707, "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
    "layernorm_epsilon": 1e-5, "intermediate_size": 48, "moe_intermediate_size": 16, "n_routed_experts": 16,
    "num_experts_per_tok": 4, "n_shared_experts": None, "norm_topk_prob": True, "routed_scaling_factor": None,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
    "hybrid_layer_pattern": PATTERN, "moe_layer_freq": [0, 1, 1, 1, 1], "num_hidden_layers": 5,
    "vocab_size": 64, "tie_word_embeddings": False, "attention_bias": False,
    "experts_held": 16, "expert_offset": 0, "vocab_rows_held": 64,
    "image_tokens": {"image_vocab": 16, "image_id_offset": 48, "boi_id": 1, "grid": 4,
                     "max_prompt_len": 6, "top_k": 0, "top_p": 0.0},
    "vq": {"c_vae": 8, "phi_partial": 2, "ch": 8, "ch_mult": [1, 1], "num_res_blocks": 1},
    "torch_dtype": "float32",
}
# bytes a sequence carries, float32: a full layer's K (1 head x 12) and V (1 x 8) over cache_len = 6 + 16 slots;
# a window layer's K (2 x 12) and V (2 x 8) over its ring of 4 slots
FULL_BYTES = 2 * (6 + 16) * (12 + 8) * 4
WINDOW_BYTES = 3 * 4 * 2 * (12 + 8) * 4


def toy_cfg(tmp_path, **over):
    raw = {**TOY, **over}
    path = tmp_path / "config.json"
    path.write_text(json.dumps(raw))
    return lm.config_from_json(str(path)), raw


def randomized(params, key):
    """Norm weights away from 1, so that a norm applied with the wrong weight shows."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(key, len(leaves))
    names = lambda path: {getattr(p, "key", None) for p in path}
    out = [leaf + 0.2 * jax.random.normal(k, leaf.shape) if "scale" in names(path) and "kernel_q8" not in
           names(path) else leaf for k, (path, leaf) in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture()
def toy(tmp_path):
    cfg, raw = toy_cfg(tmp_path)
    return cfg, raw, randomized(lm.init_lm(jax.random.PRNGKey(0), cfg), jax.random.PRNGKey(99))


def ref_scalars(cfg):
    """The configuration's scalar fields, as the benchmark hands them to the reference."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if isinstance(getattr(cfg, f.name), (int, float, bool))}


def ref_forward(params, cfg, ids, lora=None, scale=1.0):
    return ref.forward(lambda i: ref.block_weights(params["layers"][i], f"layers/{i}", lora, scale),
                       cfg.num_hidden_layers, ref.top_weights(params), ref_scalars(cfg), ids)


# (a) prefill, then decode through both caches, against the full forward -------

def decode_logits(params, cfg, prompt, lens, ids, lora, scale):
    """Logits of every decode step, teacher-forced on ``ids``, through the two
    hooks ``lm.generate`` runs, the experts' factors built once as it builds them."""
    factors = lm.expert_factors(lora, cfg, cfg.compute_dtype)
    state, _, _ = lm_swa.prefill_state(params, cfg, prompt, lens, lora, scale, factors)

    @jax.jit
    def step(last, state, i):
        x, state, _ = lm_swa.decode_layers(params, cfg, lm._embed(params, cfg, last), state, i, lens, lora, scale,
                                           factors)
        return lm_swa.head(params, cfg, x), state

    last, out = jnp.full((prompt.shape[0],), cfg.boi_id), []
    for i in range(cfg.image_tokens):
        logits, state = step(last, state, jnp.int32(i))
        out.append(logits)
        last = ids[:, i] + cfg.image_id_offset
    return jnp.stack(out, axis=1)


def test_prefill_then_cached_decode_against_full_forward(toy):
    cfg, _, params = toy
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, cfg.max_prompt_len), 2, 48)
    lens = jnp.array([6, 3])                                   # right-padded, of unequal length
    lora = random_lora(jax.random.PRNGKey(4), params, cfg)
    ids, rows = lm.generate(params, cfg, prompt, lens, jax.random.PRNGKey(5), lora=lora, lora_scale=2.0,
                            decode=False)
    assert {"carried/kv_cache", "carried/window_cache", "topk", "assign", "load"} <= set(rows)
    assert float(rows["carried/kv_cache"][0]) == FULL_BYTES and float(rows["carried/window_cache"][0]) == WINDOW_BYTES
    assert rows["topk"].shape == (2, cfg.cache_len, 4, 4)      # the four MoE layers of five
    got = decode_logits(params, cfg, prompt, lens, ids, lora, 2.0)
    lo = cfg.image_id_offset
    for s in range(2):
        L = int(lens[s])
        seq = jnp.concatenate([prompt[s, :L], jnp.array([cfg.boi_id]), ids[s, :-1] + lo])
        want = ref_forward(params, cfg, seq, lora, 2.0)["logits"][L:]
        assert rel(got[s], want) < TOL
        assert rel(rows["logits"][s, 0], want[0, lo: lo + cfg.image_vocab]) < TOL


def test_window_cache_does_not_grow_with_cache_len(tmp_path, toy):
    """A window layer's ring is ``sliding_window`` slots at any prompt length
    and image size; a full layer's cache grows with both."""
    cfg, _, params = toy
    counted = []
    for grid, prompt_len in ((4, 6), (8, 30)):
        img = {**TOY["image_tokens"], "grid": grid, "max_prompt_len": prompt_len}
        c, _ = toy_cfg(tmp_path, image_tokens=img)
        ids, lens = jnp.zeros((2, prompt_len), jnp.int32), jnp.array([prompt_len, 1])
        nbytes = {}

        def prefill(p, c=c, ids=ids, lens=lens, nbytes=nbytes):
            state, _, n = lm_swa.prefill_state(p, c, ids, lens, None, 1.0, None)
            nbytes.update(n)
            return state

        state = jax.eval_shape(prefill, params)
        assert [s[0].shape[1] for s in state] == [c.cache_len if k == 0 else 4 for k in PATTERN]
        counted.append(nbytes)
    assert counted[0] == {"kv_cache": FULL_BYTES, "window_cache": WINDOW_BYTES}
    assert counted[1]["window_cache"] == WINDOW_BYTES and counted[1]["kv_cache"] == 2 * (30 + 64) * 20 * 4


# (b) what the configuration states reaches both sides --------------------------

@pytest.mark.parametrize("change", ["window", "sink", "attention_value_scale", "swa_rope_theta"])
def test_each_mechanism_moves_program_and_reference_alike(tmp_path, toy, change):
    """The window (4 -> wider than the sequence), the sink (removed), the value
    scale (0.707 -> 1) and the window layers' RoPE base (10^4 -> 10^6) each
    move the program's logits and the reference's by more than a hundred
    tolerances, and the two still agree."""
    cfg, _, params = toy
    ids = jax.random.randint(jax.random.PRNGKey(8), (1, 12), 0, 48)
    lens = jnp.array([12])
    cfg2, params2 = cfg, params
    if change == "sink":
        params2 = dict(params, layers=[dict(p, attn={**p["attn"], "sink": jnp.full_like(p["attn"]["sink"], -jnp.inf)})
                                       if "sink" in p["attn"] else p for p in params["layers"]])
    else:
        cfg2, _ = toy_cfg(tmp_path, **{"window": {"sliding_window": 64}, "attention_value_scale": {
            "attention_value_scale": 1.0}, "swa_rope_theta": {"swa_rope_theta": 1e6}}[change])
    base, other = lm_swa.forward_logits(params, cfg, ids, lens)[0], lm_swa.forward_logits(params2, cfg2, ids, lens)[0]
    want, want2 = ref_forward(params, cfg, ids[0])["logits"], ref_forward(params2, cfg2, ids[0])["logits"]
    assert rel(other, base) > 100 * TOL and rel(want2, want) > 100 * TOL
    assert rel(other, want2) < TOL and rel(base, want) < TOL


def test_config_json_gives_the_family_and_its_per_layer_kinds(toy):
    cfg, _, params = toy
    assert isinstance(cfg, lm_swa.SWALMConfig) and cfg.family() is lm_swa.FAMILY
    assert cfg.model_type == "mimo_v2_flash" and cfg.routed_scaling_factor == 1.0 and cfg.n_moe_layers == 4
    assert cfg.layer_types == ("full_attention", "sliding_attention", "sliding_attention", "full_attention",
                               "sliding_attention")
    assert cfg.ffn_types == ("dense", "moe", "moe", "moe", "moe")
    assert "ffn" in params["layers"][0] and "moe" in params["layers"][1] and "shared" not in params["layers"][1]["moe"]
    assert params["layers"][1]["attn"]["sink"].shape == (4,) and "sink" not in params["layers"][0]["attn"]
    assert params["layers"][0]["attn"]["wk"]["kernel"].shape == (32, 12) and \
        params["layers"][1]["attn"]["wv"]["kernel"].shape == (32, 16)
    published = lm_swa.SWALMConfig()
    assert published.layer_types.count("full_attention") == 9 and published.layer_types[:6] == (
        "full_attention",) + ("sliding_attention",) * 4 + ("full_attention",)
    assert [i for i, t in enumerate(published.layer_types) if t == "full_attention"] == [0, 5, 11, 17, 23, 29, 35,
                                                                                          41, 47]
    assert (published.rope_dim("full_attention"), published.rope_dim("sliding_attention")) == (64, 64)


@pytest.mark.parametrize("over,match", [
    ({"add_full_attention_sink_bias": True}, r"^add_full_attention_sink_bias true: "),
    ({"n_shared_experts": 1}, r"^n_shared_experts 1: "),
    ({"n_group": 8, "topk_group": 4}, r"one expert group .*n_group 8"),
], ids=["full-layer-sink", "shared-expert", "expert-groups"])
def test_config_from_json_refuses_what_it_does_not_write_down_with_one_line(tmp_path, over, match):
    with pytest.raises(ValueError, match=match) as e:
        toy_cfg(tmp_path, **over)
    assert "\n" not in str(e.value)


# (c) factored per-member deltas against materialized weights ------------------

def test_factored_member_path_against_materialized_weights(toy):
    """Two members of an antithetic pair through the attention projections
    of both kinds, the dense FFN and the held experts: the fused path
    (``FactoredDelta`` leaves, members vmapped) agrees with each member's
    materialized ``W + dW`` in the reference."""
    cfg, _, params = toy
    theta = random_lora(jax.random.PRNGKey(8), params, cfg)
    assert set(theta) == ({f"layers/{i}/attn/{m}" for i in range(5) for m in ("wq", "wk", "wv", "wo")}
                          | {f"layers/0/ffn/{m}" for m in ("gate", "up", "down")}
                          | {f"layers/{i}/moe/experts/{m}" for i in range(1, 5) for m in ("gate", "up", "down")})
    es = EggRollConfig(sigma=0.05, rank=2, antithetic=True)
    noise = sample_noise(jax.random.PRNGKey(9), theta, 2, es)
    ids = jax.random.randint(jax.random.PRNGKey(10), (1, 6), 0, cfg.vocab_rows_held)
    lens = jnp.array([6])

    def fused(k):
        return lm_swa.forward_logits(params, cfg, ids, lens, factored_member_theta(theta, noise, k, 2, es), 2.0)

    got = jax.jit(jax.vmap(fused))(jnp.arange(2))
    wants = [ref_forward(params, cfg, ids[0], perturb_member(theta, noise, k, 2, es), 2.0)["logits"] for k in range(2)]
    for k in range(2):
        assert rel(got[k, 0], wants[k]) < TOL
    assert rel(wants[0], wants[1]) > 1e-3


def test_reference_batch_form_and_the_benchmarks_copy(toy):
    cfg, _, params = toy
    raw = ref_scalars(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(13), (2, 9), 0, cfg.vocab_rows_held)
    lw = lambda i: ref.block_weights(params["layers"][i], f"layers/{i}")
    top = ref.top_weights(params)
    both = ref.forward_batch(lw, 5, top, raw, ids)
    for s in range(2):
        one = ref.forward(lw, 5, top, raw, ids[s, :7])   # a shorter prefix: padding behind it is not seen
        assert rel(both["logits"][s, :7], one["logits"]) < 1e-5
        assert np.array_equal(both["topk"][s, :7, 0], one["topk"][0])
    forced = ref.forward_batch(lw, 5, top, raw, ids, forced_topk=both["topk"])
    assert rel(forced["logits"], both["logits"]) < 1e-6
    # the two hooks of the chip comparison move the logits, each by more than the tolerance
    f8 = ref.forward_batch(lw, 5, top, raw, ids, act=lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype))
    b16 = ref.forward_batch(lw, 5, top, raw, ids, kv_round=lambda t: t.astype(jnp.bfloat16).astype(t.dtype))
    assert rel(f8["logits"], both["logits"]) > 1e-3 and rel(b16["logits"], both["logits"]) > 1e-5
    root = Path(ref.__file__).resolve().parents[2]
    assert (root / "benchmarks/reference/gqa_swa_moe_reference.py").read_text() == Path(ref.__file__).read_text()

"""The sparse-expert MLA decoder (models/lm.py, ops/grouped.py) against its
plain float32 reference (reference/lm_reference.py), at toy widths on the CPU.

Tolerance 1e-4 relative (of the reference's largest magnitude): both sides
compute in float32 — conftest pins ``jax_default_matmul_precision`` to
``highest`` and the toy config states ``torch_dtype: float32`` — so what is
left is the order of float32 sums (the absorbed decode contracts ``Wukv``
before the cache, the grouped product sums an expert's rows in another order,
the reference materializes ``W + dW``), a few 1e-6 a layer. A dropped expert,
a wrong norm or an 8-bit activation path is three orders above it.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperscalees_t2i_tpu.es import EggRollConfig, factored_member_theta, perturb_member, sample_noise
from hyperscalees_t2i_tpu.lora import init_lora
from hyperscalees_t2i_tpu.models import lm
from hyperscalees_t2i_tpu.ops import grouped
from hyperscalees_t2i_tpu.ops.quant import dequantize_kernel, quantize_tree
from hyperscalees_t2i_tpu.reference import lm_reference as ref

TOL = 1e-4

TOY = {
    "hidden_size": 32, "num_attention_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 8,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "intermediate_size": 48, "moe_intermediate_size": 16,
    "n_routed_experts": 16, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_nextn_predict_layers": 1, "vocab_size": 64,
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
    return lm.LMConfig.from_json(str(path)), raw


@pytest.fixture()
def toy(tmp_path):
    cfg, raw = toy_cfg(tmp_path)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, raw, params


@pytest.fixture(params=["grouped", "dense"])
def form(request, monkeypatch):
    """Both forms of the routed experts' products at the toy's row counts:
    the grouped (ragged) products of many rows and the dense form of a few."""
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
    """An adapter with both factors non-zero (init_lora's ``b`` is zero)."""
    theta = init_lora(key, params, cfg.lora_spec(rank=2, alpha=4.0))
    ks = jax.random.split(key, len(theta))
    return {p: {"a": l["a"], "b": jax.random.normal(k, l["b"].shape) * 0.1}
            for k, (p, l) in zip(ks, sorted(theta.items()))}


# (a) one block of each kind and the whole stack -------------------------------

@pytest.mark.parametrize("layer", [0, 1], ids=["dense", "moe"])
def test_one_block_against_reference(toy, layer, form):
    cfg, raw, params = toy
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, cfg.hidden_size))
    pos = jnp.arange(6)[None]
    valid = jnp.ones((1, 6), bool)
    p = params["layers"][layer]
    attn = lambda u: lm.mla_prefill(p["mla"], cfg, u, pos, valid, None, "x", 1.0)
    y, _, stats = lm.block(p, cfg, layer, x, attn, valid, None, None, 1.0)
    with jax.default_matmul_precision("highest"):
        want, ids = ref.block(ref.block_weights(p, "x"), raw, x[0])
    assert rel(y[0], want) < TOL
    if layer == 1:
        assert np.array_equal(np.sort(np.asarray(ids), -1), np.asarray(stats["topk"]))


def test_whole_stack_logits_against_reference(toy, form):
    cfg, raw, params = toy
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0, cfg.vocab_rows_held)
    lens = jnp.array([6, 4])
    got = lm.forward_logits(params, cfg, ids, lens)
    for s in range(2):
        n = int(lens[s])
        want = ref_forward(params, raw, ids[s, :n])["logits"]
        assert rel(got[s, :n], want) < TOL  # padded positions change nothing before them


# (b) prefill, then cached absorbed decode, against the full forward -----------

def test_prefill_then_cached_decode_against_full_forward(toy, form):
    cfg, raw, params = toy
    n = cfg.image_tokens  # 16 decode steps
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, cfg.max_prompt_len), 2, 48)
    lens = jnp.array([6, 3])
    lora = random_lora(jax.random.PRNGKey(4), params, cfg)
    ids, rows = lm.generate(params, cfg, prompt, lens, jax.random.PRNGKey(5), lora=lora, lora_scale=2.0,
                            decode=False)
    assert ids.shape == (2, n) and rows["logits"].shape == (2, n // lm.PROBE_EVERY, cfg.image_vocab)
    # the step keeps logits at every PROBE_EVERY-th position; to see every
    # position, decode teacher-forced on the ids it sampled, one position a step
    for s in range(2):
        L = int(lens[s])
        seq = jnp.concatenate([prompt[s, :L], jnp.array([cfg.boi_id]), ids[s, :-1] + cfg.image_id_offset])
        out = ref_forward(params, raw, seq, lora, 2.0)
        want = out["logits"][L:, cfg.image_id_offset: cfg.image_id_offset + cfg.image_vocab]
        got = decode_logits(params, cfg, prompt[s: s + 1], lens[s: s + 1], ids[s: s + 1], lora, 2.0)[0]
        assert rel(got, want) < TOL
        assert rel(rows["logits"][s, 0], want[0]) < TOL
        # the cache slots' routing is the reference's, layer by layer
        for j, picked in enumerate(out["topk"]):
            mine = np.asarray(rows["topk"][s, :, j])
            picked = np.sort(np.asarray(picked), -1)
            assert np.array_equal(mine[cfg.max_prompt_len:], picked[L:])
            assert (mine[L: cfg.max_prompt_len] == -1).all()
            if j + 1 < len(out["topk"]):
                assert np.array_equal(mine[:L], picked[:L])
            else:  # generation stops the last layer's prefill at its cache entry: no prompt row is routed there
                assert (mine[:L] == -1).all()


def decode_logits(params, cfg, prompt, lens, ids, lora, scale):
    """Logits of every decode step, teacher-forced on ``ids``: prefill into the
    latent cache, then ``mla_decode`` over it a position at a time."""
    B, P = prompt.shape
    factors = lm.expert_factors(lora, cfg, cfg.compute_dtype)
    _, entries, _ = lm.prefill(params, cfg, prompt, lens, lora, scale, factors)
    caches = [jnp.zeros((B, cfg.cache_len, cfg.cache_width)).at[:, :P].set(e) for e in entries]
    slots = jnp.arange(cfg.cache_len)
    last = jnp.full((B,), cfg.boi_id)
    out = []
    for i in range(cfg.image_tokens):
        slot, pos = P + i, lens + i
        valid = (slots[None] < lens[:, None]) | ((slots[None] >= P) & (slots[None] <= slot))
        x = params["embed"][last]
        for li, p in enumerate(params["layers"]):
            attn = lambda u, p=p, li=li: lm.mla_decode(p["mla"], cfg, u, pos, caches[li], slot, valid,
                                                       lora, f"layers/{li}/mla", scale)
            x, caches[li], _ = lm.block(p, cfg, li, x, attn, jnp.ones((B,), bool), lora,
                                        factors[li], scale)
        lo = cfg.image_id_offset
        out.append(lm._head(params, cfg, x)[:, lo: lo + cfg.image_vocab])
        last = ids[:, i] + lo
    return jnp.stack(out, axis=1)


# (c) the MTP module -----------------------------------------------------------

def test_mtp_module_against_reference(toy):
    cfg, raw, params = toy
    ids = jax.random.randint(jax.random.PRNGKey(6), (1, 6), 0, cfg.vocab_rows_held)
    lens = jnp.array([5])
    hidden, _, _ = lm.prefill(params, cfg, ids[:, :5], lens)
    got = lm.mtp_logits(params, cfg, hidden, ids[:, 1:6], lens)
    main = ref_forward(params, raw, ids[0, :5])
    want = ref.mtp(ref.mtp_weights(params["mtp"][0]), ref.top_weights(params), raw, main["hidden"], ids[0, 1:6])
    assert rel(got[0], want) < TOL


# (d) the share adds up --------------------------------------------------------

@pytest.mark.parametrize("family", ["sandwich", "xing4_0", "mimo_v2_flash"])
def test_the_shares_and_the_shared_expert_add_up_to_the_uncut_layer(tmp_path, family, form):
    """16 experts over 4 shares (the sandwich-norm toy, top-k by the score; the
    ``mimo_v2_flash`` toy, by the score plus a selection bias and with no
    shared expert) or 8 experts over 2 shares under the selection-bias router
    (the ``xing4_0`` toy): the routed parts of the shares plus the shared
    expert counted once, where there is one, equal the uncut reference's MoE
    output."""
    if family == "xing4_0":
        from hyperscalees_t2i_tpu.reference import mhc_moe_reference as reference
        from tests.test_lm_mhc import toy_cfg as make_cfg
    elif family == "mimo_v2_flash":
        from hyperscalees_t2i_tpu.reference import gqa_swa_moe_reference as reference
        from tests.test_lm_swa import ref_scalars, toy_cfg as make_cfg
    else:
        reference, make_cfg = ref, toy_cfg
    cfg, raw = make_cfg(tmp_path)
    if family == "mimo_v2_flash":  # the scalars the benchmark hands its reference (``routed_scaling_factor`` 1)
        raw = ref_scalars(cfg)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    p = params["layers"][1]["moe"]
    held, shares = 4, cfg.n_routed_experts // 4
    u = jax.random.normal(jax.random.PRNGKey(7), (10, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole, picked = reference.moe(reference.block_weights(params["layers"][1], "x"), raw, u)
    top_i, top_w = lm.route(p, cfg, u)
    assert np.array_equal(np.sort(np.asarray(top_i), -1), np.sort(np.asarray(picked), -1))
    if family != "sandwich":  # the bias changes the choice: by the score alone some token chooses otherwise
        by_score = jax.lax.top_k(jax.nn.sigmoid(u @ p["router"]["weight"].T), cfg.num_experts_per_tok)[1]
        assert not np.array_equal(np.sort(np.asarray(by_score), -1), np.sort(np.asarray(top_i), -1))
    total = lm._swiglu(p["shared"], u, None, "x", 1.0) if "shared" in p else jnp.zeros_like(u)
    for share in range(shares):
        cfg_s, _ = make_cfg(tmp_path, experts_held=held, expert_offset=held * share)
        mine = {k: {"kernel": v["kernel"][held * share: held * (share + 1)]} for k, v in p["experts"].items()}
        routed, e = lm.routed_experts(mine, cfg_s, u, top_i, top_w, jnp.ones((10,), bool), None, 1.0)
        assert int((e < held).sum()) == int(((top_i >= held * share) & (top_i < held * (share + 1))).sum())
        total = total + routed
    assert rel(total, whole) < TOL


# (e) factored per-(member, expert) deltas against materialized weights --------

def member_thetas(params, cfg, pop=2, rank=2):
    theta = random_lora(jax.random.PRNGKey(8), params, cfg)
    es = EggRollConfig(sigma=0.05, rank=rank, antithetic=True)
    noise = sample_noise(jax.random.PRNGKey(9), theta, pop, es)
    return theta, noise, es


def test_factored_member_path_against_materialized_weights(toy, form):
    """Two members, an antithetic pair, through the experts and the MLA
    sites: the fused path (``FactoredDelta`` leaves with the expert axis in
    front, members vmapped so that their rows share one grouped call) agrees
    with each member's materialized ``W + dW`` in the plain reference."""
    cfg, raw, params = toy
    theta, noise, es = member_thetas(params, cfg)
    ids = jax.random.randint(jax.random.PRNGKey(10), (1, 6), 0, cfg.vocab_rows_held)
    lens = jnp.array([6])

    def fused(k):
        th = factored_member_theta(theta, noise, k, 2, es)
        return lm.forward_logits(params, cfg, ids, lens, th, 2.0)

    got = jax.jit(jax.vmap(fused))(jnp.arange(2))
    wants = []
    for k in range(2):
        th = perturb_member(theta, noise, k, 2, es)
        wants.append(ref_forward(params, raw, ids[0], th, 2.0)["logits"])
        assert rel(got[k, 0], wants[k]) < TOL
    assert rel(wants[0], wants[1]) > 1e-3  # the pair's halves do differ


def test_members_rows_share_one_grouped_call():
    """Under vmap the members' rows are flattened into one ragged product and
    the load ratio is the whole call's."""
    E, R, M = 4, 6, 3
    w = jax.random.normal(jax.random.PRNGKey(0), (E, 5, 7))
    xp = jax.random.normal(jax.random.PRNGKey(1), (M, R, 5))
    e = jax.random.randint(jax.random.PRNGKey(2), (M, R), 0, E + 1)
    got = jax.vmap(lambda x, ee: grouped.grouped_matmul(x, ee, {"kernel": w}))(xp, e)
    want = jnp.where((e < E)[..., None], jnp.einsum("mrd,mrdf->mrf", xp, w[jnp.minimum(e, E - 1)]), 0.0)
    assert rel(got, want) < 1e-6
    text = jax.make_jaxpr(jax.vmap(lambda x, ee: grouped.grouped_matmul(x, ee, {"kernel": w})))(xp, e)
    assert str(text).count("= ragged_dot_general[") == 1 and f"f32[{M * R},7] = ragged_dot_general[" in str(text)
    load = jax.vmap(lambda ee: grouped.expert_load_ratio(ee, E), out_axes=None)(e)
    counts = np.bincount(np.asarray(e).ravel(), minlength=E + 1)[:E]
    assert abs(float(load) - counts.max() / counts.mean()) < 1e-6


# (f) int8 expert kernels ------------------------------------------------------

def test_int8_expert_kernels_scale_per_expert_and_channel(toy):
    """``_scale_axes`` reads an odd-rank kernel's leading axis as a stack of
    independent matrices: for ``[experts, din, dout]`` that is one scale per
    expert and output channel."""
    cfg, raw, params = toy
    experts = params["layers"][1]["moe"]["experts"]
    w = experts["gate"]["kernel"] * jnp.arange(1, 17)[:, None, None]  # experts of very different size
    q = quantize_tree({"gate": {"kernel": w}}, min_size=0)["gate"]["kernel_q8"]
    assert q["q8"].dtype == jnp.int8 and q["scale"].shape == (16, 1, w.shape[-1])
    want = np.abs(np.asarray(w)).max(axis=1, keepdims=True) / 127.0
    assert np.allclose(q["scale"], want, rtol=1e-6)
    err = np.abs(np.asarray(dequantize_kernel(q, jnp.float32)) - np.asarray(w))
    assert (err <= 0.5 * np.asarray(q["scale"]) * (1 + 1e-6)).all()  # half a step, per expert and channel
    # the grouped product on the int8 node is the product on its dequantized form
    xp = jax.random.normal(jax.random.PRNGKey(11), (12, cfg.hidden_size))
    e = jax.random.randint(jax.random.PRNGKey(12), (12,), 0, 17)
    got = grouped.grouped_matmul(xp, e, {"kernel_q8": q})
    want = grouped.grouped_matmul(xp, e, {"kernel": dequantize_kernel(q, jnp.float32)})
    assert rel(got, want) < 1e-5


def test_int8_init_is_the_quantized_float_init(toy, monkeypatch):
    cfg, _, params = toy
    monkeypatch.setenv("HSES_BASE_QUANT_MIN_SIZE", "0")
    q = lm.init_lm(jax.random.PRNGKey(0), cfg, base_quant="int8")
    node = q["layers"][1]["moe"]["experts"]["down"]
    assert "kernel_q8" in node
    want = quantize_tree(params, min_size=0)["layers"][1]["moe"]["experts"]["down"]["kernel_q8"]
    assert np.array_equal(node["kernel_q8"]["q8"], want["q8"])
    assert q["layers"][1]["moe"]["router"]["weight"].dtype == jnp.float32


# (g) the backend on train.cli's normal path -----------------------------------

def test_train_cli_lm_ar_two_epochs(tmp_path, monkeypatch):
    """``train.cli --backend lm_ar`` at toy widths through the same trainer,
    member loop, quantizer and reward path as the other backends: one compile,
    one ``pop_eval`` trace, finite rewards, an applied update, every counter."""
    from hyperscalees_t2i_tpu.train import cli

    monkeypatch.setenv("HSES_BASE_QUANT_MIN_SIZE", "1")  # toy kernels still go int8
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
        # 4 members x 2 prompts x 16 sampled positions x 2 MoE layers x top-4, all experts held, + the prompts' at one layer
        assert r["moe/local_assignments"] > 4 * 2 * 16 * 2 * 4
        assert r["moe/max_expert_load"] >= 1.0 and 0.0 <= r["moe/pair_route_flip"] <= 1.0
        assert not any(k.startswith(("probe/", "gen/")) for k in r)
    assert rows[-1]["obs/dispatches"] == 2
    steps = [json.loads(l) for l in (run / "programs.jsonl").read_text().splitlines()]
    (step,) = [p for p in steps if p["label"].startswith("es_step_")]
    # generate notes the head it cuts its image-id columns from; only a traced run counts the ops of that size
    assert step["geometry"]["lm_head_shape"] == [32, 64] and "lm_head_whole_ops" not in step
    probe = np.load(run / "probe_epoch0.npz")
    assert probe["ids"].shape == (2, 16) and probe["topk"].shape == (2, 22, 2, 4)
    assert probe["logits"].shape == (2, 1, 16) and (probe["prompt_len"] > 0).all()
    assert not (run / "probe_epoch1.npz").exists()
    # int8 went through the experts' [E, din, dout] kernels too
    from hyperscalees_t2i_tpu.models import lm as lm_mod

    q = lm_mod.init_lm(jax.random.PRNGKey(0), lm_mod.LMConfig.from_json(str(tmp_path / "config.json")), "int8")
    assert q["layers"][1]["moe"]["experts"]["gate"]["kernel_q8"]["scale"].shape == (16, 1, 16)


def test_reference_batch_form_and_forced_routing(toy):
    """``forward_batch`` (layers outermost, sequences vmapped, right-padded) is
    ``forward`` a sequence at a time; forcing the reference's own routing
    changes nothing, forcing another changes the logits; and the benchmark's
    copy of the reference is the program's file."""
    from pathlib import Path

    cfg, raw, params = toy
    ids = jax.random.randint(jax.random.PRNGKey(13), (2, 7), 0, cfg.vocab_rows_held)
    lw = lambda i: ref.block_weights(params["layers"][i], f"layers/{i}")
    top = ref.top_weights(params)
    both = ref.forward_batch(lw, 3, top, raw, ids)
    for s in range(2):
        one = ref.forward(lw, 3, top, raw, ids[s, :5])  # a shorter prefix: padding behind it is not seen
        assert rel(both["logits"][s, :5], one["logits"]) < 1e-6
        assert np.array_equal(both["topk"][s, :5, 0], one["topk"][0])
    forced = ref.forward_batch(lw, 3, top, raw, ids, forced_topk=both["topk"])
    assert rel(forced["logits"], both["logits"]) < 1e-6
    other = ref.forward_batch(lw, 3, top, raw, ids, forced_topk=(both["topk"] + 1) % 16)
    assert rel(other["logits"], both["logits"]) > 1e-3
    assert np.array_equal(other["topk"][:, :, 0], both["topk"][:, :, 0])  # its own choice is still reported
    root = Path(ref.__file__).resolve().parents[2]
    assert (root / "benchmarks/reference/lm_reference.py").read_text() == Path(ref.__file__).read_text()


def test_adapter_and_noise_carry_the_expert_axis(toy):
    """``init_lora`` / ``sample_noise`` / ``factored_member_theta`` read a
    ``[E, din, dout]`` kernel's leading axis as a stack of matrices: every
    held expert has its own factors and its own low-rank noise."""
    from hyperscalees_t2i_tpu.lora import FactoredDelta, effective_factor

    cfg, _, params = toy
    theta = init_lora(jax.random.PRNGKey(0), params, cfg.lora_spec(rank=2, alpha=4.0))
    assert set(theta) == (
        {f"layers/{i}/mla/{m}" for i in range(3) for m in ("wdq", "wuq", "wdkv", "wukv", "wo")}
        | {f"layers/0/ffn/{m}" for m in ("gate", "up", "down")}
        | {f"layers/{i}/moe/{part}/{m}" for i in (1, 2) for part in ("shared", "experts") for m in ("gate", "up", "down")}
    )  # router, norms, embedding, head and the MTP module stay frozen
    leaf = theta["layers/1/moe/experts/gate"]
    assert leaf["a"].shape == (16, 32, 2) and leaf["b"].shape == (16, 2, 16)
    es = EggRollConfig(sigma=0.1, rank=3)
    noise = sample_noise(jax.random.PRNGKey(1), theta, 4, es)
    assert noise["layers/1/moe/experts/gate"]["a"].U.shape == (2, 16, 32, 3)
    fd = factored_member_theta(theta, noise, 1, 4, es)["layers/1/moe/experts/gate"]["a"]
    assert isinstance(fd, FactoredDelta) and fd.u.shape == (16, 32, 3) and fd.c.shape == ()
    want = perturb_member(theta, noise, 1, 4, es)["layers/1/moe/experts/gate"]["a"]
    assert rel(effective_factor(fd, jnp.float32), want) < 1e-6
    assert rel(want[0] - leaf["a"][0], want[1] - leaf["a"][1]) > 0.1  # each expert its own noise

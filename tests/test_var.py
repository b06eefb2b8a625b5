"""VAR family tests (SURVEY.md §4 plan: golden-value pyramid math, KV-cache
vs teacher-forced parity, sampling ops, backend integration)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperscalees_t2i_tpu.backends.var_backend import VarBackend, VarBackendConfig
from hyperscalees_t2i_tpu.models import msvq, var as var_mod, nn
from hyperscalees_t2i_tpu.ops.sampling import filter_top_k, filter_top_p, sample_top_k_top_p


def tiny_vq():
    return msvq.MSVQConfig(
        vocab_size=32, c_vae=4, patch_nums=(1, 2, 4), phi_partial=2,
        ch=8, ch_mult=(1, 1), num_res_blocks=1, compute_dtype=jnp.float32,
    )


def tiny_cfg(**kw):
    return var_mod.VARConfig(
        num_classes=5, depth=2, d_model=16, n_heads=2, ff_ratio=2.0,
        patch_nums=(1, 2, 4), vq=tiny_vq(), compute_dtype=jnp.float32,
        top_k=0, top_p=0.0, **kw,
    )


# ---------------------------------------------------------------------------
# sampling ops
# ---------------------------------------------------------------------------

def test_filter_top_k():
    logits = jnp.asarray([[1.0, 5.0, 3.0, 2.0]])
    out = filter_top_k(logits, 2)
    np.testing.assert_array_equal(np.asarray(out[0] > -1e29), [False, True, True, False])
    # k=0 / k>=V are no-ops
    np.testing.assert_array_equal(np.asarray(filter_top_k(logits, 0)), np.asarray(logits))


def test_filter_top_p():
    # one dominant token: tiny p keeps only it
    logits = jnp.asarray([[10.0, 0.0, 0.0, 0.0]])
    out = filter_top_p(logits, 0.5)
    np.testing.assert_array_equal(np.asarray(out[0] > -1e29), [True, False, False, False])
    # p→1 keeps everything
    out = filter_top_p(jnp.asarray([[1.0, 1.0, 1.0, 1.0]]), 0.999)
    assert int(np.sum(np.asarray(out) > -1e29)) == 4


def test_sample_respects_filter():
    key = jax.random.PRNGKey(0)
    logits = jnp.tile(jnp.asarray([[0.0, 0.1, 0.2, 5.0]]), (64, 1))
    ids = sample_top_k_top_p(key, logits, top_k=1)
    assert np.all(np.asarray(ids) == 3)


# ---------------------------------------------------------------------------
# multi-scale VQ pyramid
# ---------------------------------------------------------------------------

def test_msvq_encode_generate_parity_and_residual():
    """The encode-side pyramid and the generate-side ``accumulate_scale``
    replay must agree exactly (the two halves of quant.py:135-196), and on an
    in-range target (one the pyramid can represent) the residual must shrink."""
    cfg = tiny_vq()
    params = msvq.init_msvq(jax.random.PRNGKey(0), cfg)
    # in-range target: decode a random id pyramid through the generate path
    f = jnp.zeros((2, cfg.grid, cfg.grid, cfg.c_vae))
    for si, pn in enumerate(cfg.patch_nums):
        ids = jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(9), si), (2, pn * pn), 0, cfg.vocab_size)
        f, _ = msvq.accumulate_scale(params, cfg, f, ids, si)

    ids_list, f_hat_enc = msvq.encode_to_scales(params, cfg, f)
    assert [i.shape[1] for i in ids_list] == [p * p for p in cfg.patch_nums]

    # generation-side accumulation with the encoded ids reproduces encode-side f̂
    f_hat = jnp.zeros_like(f)
    errs = [float(jnp.mean(f**2))]
    for si, ids in enumerate(ids_list):
        f_hat, _ = msvq.accumulate_scale(params, cfg, f_hat, ids, si)
        errs.append(float(jnp.mean((f - f_hat) ** 2)))
    np.testing.assert_allclose(np.asarray(f_hat), np.asarray(f_hat_enc), rtol=1e-5, atol=1e-6)
    assert errs[-1] < errs[0], f"residual did not shrink: {errs}"


def test_msvq_decode_shape_and_range():
    cfg = tiny_vq()
    params = msvq.init_msvq(jax.random.PRNGKey(0), cfg)
    f_hat = jax.random.normal(jax.random.PRNGKey(2), (2, cfg.grid, cfg.grid, cfg.c_vae))
    img = msvq.decode_img(params, cfg, f_hat)
    factor = 2 ** (len(cfg.ch_mult) - 1)
    assert img.shape == (2, cfg.grid * factor, cfg.grid * factor, 3)
    assert float(img.min()) >= 0.0 and float(img.max()) <= 1.0


def test_phi_index_static_selection():
    cfg = tiny_vq()  # 3 scales, 2 φ convs
    assert msvq.phi_index(cfg, 0) == 0
    assert msvq.phi_index(cfg, 2) == 1


# ---------------------------------------------------------------------------
# transformer: KV-cached incremental path == teacher-forced full path
# ---------------------------------------------------------------------------

def _incremental_logits(params, cfg, labels, scale_inputs):
    """Drive _blocks_step scale-by-scale with teacher inputs (no sampling)."""
    B = labels.shape[0]
    d, H, dh = cfg.d_model, cfg.n_heads, cfg.head_dim
    L, dt = cfg.seq_len, cfg.compute_dtype
    cond = params["class_emb"][labels]
    ada = params["blocks"]["ada_lin"]
    c = jax.nn.silu(cond.astype(jnp.float32))
    cond6_all = (
        jnp.einsum("bd,lde->lbe", c, ada["kernel"]) + ada["bias"][:, None, :]
    ).reshape(cfg.depth, B, 6, d)
    hs, hb = jnp.split(nn.dense(params["head_ada"], jax.nn.silu(cond)), 2, axis=-1)
    kC = jnp.zeros((cfg.depth, B, L, H, dh), dt)
    vC = jnp.zeros((cfg.depth, B, L, H, dh), dt)

    emb = nn.dense(params["word_embed"], scale_inputs.astype(jnp.float32))
    lvl = np.concatenate([np.full(p * p, i) for i, p in enumerate(cfg.patch_nums)])
    outs = []
    pos = 0
    for si, pn in enumerate(cfg.patch_nums):
        n = pn * pn
        if si == 0:
            x = cond[:, None, :] + params["pos_start"]
        else:
            x = emb[:, pos : pos + n]
        x = (x + params["lvl_emb"][si][None, None, :] + params["pos_emb"][None, pos : pos + n, :]).astype(dt)
        h, (kC, vC) = var_mod._blocks_step(params, cfg, x, cond6_all, (kC, vC), pos, None, 1.0)
        h = nn.layer_norm(h) * (1.0 + hs[:, None, :].astype(dt)) + hb[:, None, :].astype(dt)
        outs.append(nn.dense(params["head"], h).astype(jnp.float32))
        pos += n
    return jnp.concatenate(outs, axis=1)


def test_kv_cache_matches_teacher_forcing():
    cfg = tiny_cfg()
    params = var_mod.init_var(jax.random.PRNGKey(0), cfg)
    labels = jnp.asarray([1, 3], jnp.int32)
    scale_inputs = jax.random.normal(jax.random.PRNGKey(5), (2, cfg.seq_len, cfg.vq.c_vae)) * 0.3

    full = var_mod.forward_teacher(params, cfg, labels, scale_inputs)
    inc = _incremental_logits(params, cfg, labels, scale_inputs)
    np.testing.assert_allclose(np.asarray(full), np.asarray(inc), rtol=2e-4, atol=2e-4)


def test_generate_shapes_and_determinism():
    cfg = tiny_cfg()
    params = var_mod.init_var(jax.random.PRNGKey(0), cfg)
    labels = jnp.asarray([0, 2], jnp.int32)
    g = jax.jit(lambda p, l, k: var_mod.generate(p, cfg, l, k))
    img1 = g(params, labels, jax.random.PRNGKey(7))
    img2 = g(params, labels, jax.random.PRNGKey(7))
    factor = 2 ** (len(cfg.vq.ch_mult) - 1)
    assert img1.shape == (2, cfg.vq.grid * factor, cfg.vq.grid * factor, 3)
    np.testing.assert_array_equal(np.asarray(img1), np.asarray(img2))
    img3 = g(params, labels, jax.random.PRNGKey(8))
    assert float(jnp.abs(img1 - img3).max()) > 0.0  # different seed → different sample


# ---------------------------------------------------------------------------
# ISSUE 30: a scale writes its own rows and the cache is never filled or
# copied whole again. The form before it, kept here as the oracle.
# ---------------------------------------------------------------------------

def _blocks_step_through_scan(params, cfg, x, cond6_all, caches, pos, lora, lora_scale):
    """``_blocks_step`` as it stood before ISSUE 30: the whole K and V stacks
    go through the scan as inputs and come back as its outputs, each layer
    writing its rows into a copy of that layer's whole cache."""
    import math

    from hyperscalees_t2i_tpu.lora import lookup, slice_layer
    from hyperscalees_t2i_tpu.ops.attention import decode_attention

    d, H, dh = cfg.d_model, cfg.n_heads, cfg.head_dim
    B2, n, _ = x.shape
    dt = cfg.compute_dtype
    blk = params["blocks"]

    def layer(carry, inp):
        x, = carry
        li, kC, vC, cond6 = inp
        g1, s1, b1, g2, s2, b2 = (cond6[:, i][:, None, :] for i in range(6))
        h = nn.layer_norm(x) * (1.0 + s1.astype(dt)) + b1.astype(dt)
        qkv = nn.dense(nn.slice_stacked(blk["qkv"], li), h,
                       slice_layer(lookup(lora, "blocks/qkv"), li), lora_scale)
        q, k, v = (t.reshape(B2, n, H, dh) for t in jnp.split(qkv, 3, axis=-1))
        if cfg.attn_l2_norm:
            q, k = nn.qk_l2(q, k, blk["scale_mul"][li])
            sm_scale = 1.0
        else:
            sm_scale = 0.25 / math.sqrt(dh)
        kC = jax.lax.dynamic_update_slice(kC, k.astype(kC.dtype), (0, pos, 0, 0))
        vC = jax.lax.dynamic_update_slice(vC, v.astype(vC.dtype), (0, pos, 0, 0))
        out = decode_attention(q, kC, vC, kv_len=pos + n, sm_scale=sm_scale).astype(dt).reshape(B2, n, d)
        out = nn.dense(nn.slice_stacked(blk["attn_proj"], li), out,
                       slice_layer(lookup(lora, "blocks/attn_proj"), li), lora_scale)
        x = x + g1.astype(dt) * out
        h2 = nn.layer_norm(x) * (1.0 + s2.astype(dt)) + b2.astype(dt)
        h2 = nn.dense(nn.slice_stacked(blk["fc1"], li), h2,
                      slice_layer(lookup(lora, "blocks/fc1"), li), lora_scale)
        h2 = jax.nn.gelu(h2, approximate=True)
        h2 = nn.dense(nn.slice_stacked(blk["fc2"], li), h2,
                      slice_layer(lookup(lora, "blocks/fc2"), li), lora_scale)
        x = x + g2.astype(dt) * h2.astype(dt)
        return (x,), (kC, vC)

    kAll, vAll = caches
    (x,), (kAll, vAll) = jax.lax.scan(
        layer, (x.astype(dt),), (jnp.arange(cfg.depth), kAll, vAll, cond6_all)
    )
    return x, (kAll, vAll)


def _through_scan_preallocated(params, cfg, x, cond6_all, caches, pos, lora, lora_scale):
    """The form before ISSUE 30 as ``generate`` ran it: over a zero-filled
    cache of the whole sequence's length, allocated before the first scale
    (``generate`` now starts from no rows at all)."""
    if pos == 0:
        shape = (cfg.depth, x.shape[0], cfg.seq_len, cfg.n_heads, cfg.head_dim)
        caches = (jnp.zeros(shape, cfg.compute_dtype), jnp.zeros(shape, cfg.compute_dtype))
    return _blocks_step_through_scan(params, cfg, x, cond6_all, caches, pos, lora, lora_scale)


def _perturbed_theta(params, cfg, members):
    """``members`` adapters with every factor non-zero, stacked on axis 0."""
    from hyperscalees_t2i_tpu.lora import init_lora

    theta = init_lora(jax.random.PRNGKey(1), params, cfg.lora_spec(rank=2, alpha=4.0))
    return jax.tree_util.tree_map(
        lambda t: jnp.stack([t + 0.1 * (m + 1) for m in range(members)]), theta
    )


@pytest.mark.parametrize("vmapped", [False, True], ids=["one_member", "vmap_two_members"])
@pytest.mark.parametrize("with_lora", [False, True], ids=["no_lora", "lora"])
@pytest.mark.parametrize("si", [0, 1, 2])
def test_blocks_step_equals_the_form_that_carried_the_stack_through_the_scan(si, with_lora, vmapped):
    cfg = tiny_cfg()
    params = var_mod.init_var(jax.random.PRNGKey(0), cfg)
    B2, d, L = 4, cfg.d_model, cfg.seq_len
    cond6_all = jax.random.normal(jax.random.PRNGKey(3), (cfg.depth, B2, 6, d)) * 0.1
    theta = _perturbed_theta(params, cfg, 2)
    scale = cfg.lora_spec(rank=2, alpha=4.0).scale

    def through_scales(step, lora):
        """The scales up to ``si`` with ``step``, each on its own input, the
        cache handed from one to the next; returns the last ``x`` and cache."""
        caches = tuple(jnp.zeros((cfg.depth, B2, L, cfg.n_heads, cfg.head_dim)) for _ in "kv")
        for s, (pos, n) in enumerate(var_mod._scale_slices(cfg)[: si + 1]):
            x = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(4), s), (B2, n, d))
            x, caches = step(params, cfg, x, cond6_all, caches, pos, lora, scale)
        return x, caches

    def run(step):
        if not vmapped:
            lora = jax.tree_util.tree_map(lambda t: t[0], theta) if with_lora else None
            return through_scales(step, lora)
        if not with_lora:  # members that differ by nothing: the axis alone
            return jax.vmap(lambda _: through_scales(step, None))(jnp.arange(2))
        return jax.vmap(lambda lora: through_scales(step, lora))(theta)

    x, (k_blocks, v_blocks) = run(var_mod._blocks_step)
    x0, (kAll0, vAll0) = run(_blocks_step_through_scan)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x0))
    pos, n = var_mod._scale_slices(cfg)[si]
    # the cache that comes back holds the positions written so far, all of
    # them, a block of rows a scale — a row as the qkv product wrote it, its
    # heads side by side (PR 36: the block decode_attention reads)
    assert [b.shape[-2:] for b in k_blocks] == [
        (m, d) for m in [0] + [m for _, m in var_mod._scale_slices(cfg)[: si + 1]]]
    kAll, vAll = (
        jnp.concatenate(blocks, axis=-2).reshape(*blocks[0].shape[:-2], pos + n, cfg.n_heads, cfg.head_dim)
        for blocks in (k_blocks, v_blocks))
    assert float(jnp.abs(kAll0[..., : pos + n, :, :]).min()) > 0.0
    assert not np.asarray(kAll0[..., pos + n :, :, :]).any()
    np.testing.assert_array_equal(np.asarray(kAll), np.asarray(kAll0[..., : pos + n, :, :]))
    np.testing.assert_array_equal(np.asarray(vAll), np.asarray(vAll0[..., : pos + n, :, :]))


@pytest.mark.parametrize("seed", [7, 8])
def test_generate_samples_the_ids_of_the_form_that_carried_the_stack(seed, monkeypatch):
    cfg = tiny_cfg()
    params = var_mod.init_var(jax.random.PRNGKey(0), cfg)
    labels = jnp.asarray([0, 2], jnp.int32)
    real = msvq.accumulate_scale

    def sampled_ids(step):
        ids = []
        monkeypatch.setattr(var_mod, "_blocks_step", step)
        monkeypatch.setattr(msvq, "accumulate_scale",
                            lambda p, c, f, i, si: (ids.append(np.asarray(i)), real(p, c, f, i, si))[1])
        # not jitted: the scale loop is Python's, so each scale's ids are values
        f_hat = var_mod.generate(params, cfg, labels, jax.random.PRNGKey(seed), decode=False)
        return ids, np.asarray(f_hat)

    new_step = var_mod._blocks_step
    ids0, f0 = sampled_ids(_through_scan_preallocated)
    ids1, f1 = sampled_ids(new_step)
    assert [i.shape for i in ids1] == [(2, pn * pn) for pn in cfg.patch_nums]
    for a, b in zip(ids0, ids1):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(f0, f1)


@pytest.mark.parametrize("patch_nums", [(1, 2, 4), (1, 2, 3, 4)], ids=["three_scales", "four_scales"])
def test_generate_fills_no_cache_stack_a_scale(patch_nums, monkeypatch):
    """A scan that takes the whole stack in and gives it back zero-fills a
    new one every scale (6 fills at three scales before ISSUE 30). However
    many scales, at most the two fills of a preallocated K and V are left —
    none, since the cache grows from no rows."""
    import dataclasses
    import re

    vq = dataclasses.replace(tiny_vq(), patch_nums=patch_nums)
    cfg = dataclasses.replace(tiny_cfg(), patch_nums=patch_nums, vq=vq)
    params = var_mod.init_var(jax.random.PRNGKey(0), cfg)

    def fills(step):
        monkeypatch.setattr(var_mod, "_blocks_step", step)
        text = jax.jit(lambda p, l, k: var_mod.generate(p, cfg, l, k, decode=False)).lower(
            params, jnp.asarray([0, 2], jnp.int32), jax.random.PRNGKey(7)
        ).as_text()
        stack = "x".join(str(d) for d in (cfg.depth, 4, cfg.seq_len, cfg.n_heads, cfg.head_dim))
        assert text.count("stablehlo.while") >= len(patch_nums)  # a scan a scale, either way
        return len(re.findall(rf"broadcast_in_dim[^\n]*-> tensor<{stack}xf32>", text))

    assert fills(var_mod._blocks_step) <= 2
    assert fills(_through_scan_preallocated) >= len(patch_nums)  # what the count is of


def _count_whole(text, shape):
    """The total ``kv_cache_whole_ops`` should reach, counted another way:
    lines outside fused computations whose result type holds the stack's
    dims or a layer's, exactly or with one more axis anywhere."""
    import re

    wanted = (list(shape), list(shape[1:]))
    fused = set(re.findall(r"\bfusion\(.*?\bcalls=%?([\w.\-]+)", text))
    total, comp = 0, None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
            comp = m.group(1) if m else comp
            continue
        if comp in fused or " = " not in line:
            continue
        result = " " + line.split(" = ", 1)[1]
        opcode = re.search(r"\s([a-z][\w\-]*)\(", result)
        if opcode is None or opcode.group(1).endswith("-start") \
                or opcode.group(1) in ("parameter", "tuple", "get-tuple-element", "bitcast"):
            continue
        for dims in re.findall(r"\w\[([\d,]+)\]", result[: opcode.start()]):
            dims = [int(d) for d in dims.split(",")]
            if dims in wanted or any(dims[:i] + dims[i + 1:] in wanted for i in range(len(dims))):
                total += 1
                break
    return total


def test_whole_cache_ops_of_a_member_chunk_before_and_after(monkeypatch):
    """Two members a chunk, compiled: the form that carried the stack through
    the scan fills and copies it in every scale; the count falls with the
    change, and both are what the text holds."""
    from hyperscalees_t2i_tpu.obs.xla_cost import kv_cache_whole_ops

    cfg = tiny_cfg()
    params = var_mod.init_var(jax.random.PRNGKey(0), cfg)
    theta = _perturbed_theta(params, cfg, 2)
    labels = jnp.asarray([0, 2], jnp.int32)
    by_head = (cfg.depth, 4, cfg.seq_len, cfg.n_heads, cfg.head_dim)  # the older form's stack
    flat = (cfg.depth, 4, cfg.seq_len, cfg.d_model)  # a row's heads side by side, as generate notes it

    def counts(step, shape):
        monkeypatch.setattr(var_mod, "_blocks_step", step)
        compiled = jax.jit(jax.vmap(
            lambda lora, p, l, k: var_mod.generate(p, cfg, l, k, lora=lora, decode=False),
            in_axes=(0, None, None, None),
        )).lower(theta, params, labels, jax.random.PRNGKey(7)).compile()
        got = kv_cache_whole_ops(compiled, shape)
        assert sum(got.values()) == _count_whole(compiled.as_text(), shape)
        return got

    after = counts(var_mod._blocks_step, flat)
    assert not counts(var_mod._blocks_step, by_head)  # and nothing holds the cache by head
    before = counts(_through_scan_preallocated, by_head)
    assert before.get("while", 0) == len(cfg.patch_nums)  # each scale's scan returned the stack
    assert "while" not in after and sum(after.values()) < sum(before.values()), (before, after)


def test_traced_run_counts_the_ops_as_large_as_the_cache(tmp_path, monkeypatch):
    """``--trace true``: the step's program record carries
    ``kv_cache_whole_ops``, the count ``obs/xla_cost`` makes of the compiled
    text, for the cache shape ``generate`` noted."""
    import json

    from hyperscalees_t2i_tpu.obs import xla_cost
    from hyperscalees_t2i_tpu.train import cli

    seen = []
    real = xla_cost.kv_cache_whole_ops
    monkeypatch.setattr(xla_cost, "kv_cache_whole_ops",
                        lambda compiled, shape: (seen.append((compiled.as_text(), tuple(shape))),
                                                 real(compiled, shape))[1])
    labels = tmp_path / "labels.txt"
    labels.write_text("\n".join(f"class_{i}" for i in range(10)))
    cli.main([
        "--backend", "var", "--model_scale", "tiny", "--labels_path", str(labels),
        "--pop_size", "4", "--prompts_per_gen", "2", "--member_batch", "2",
        "--num_epochs", "1", "--allow_random_rewards", "true",
        "--run_dir", str(tmp_path), "--run_name", "run", "--resume", "false",
        "--save_every", "0", "--trace", "true",
    ])
    programs = [json.loads(l) for l in (tmp_path / "run" / "programs.jsonl").read_text().splitlines()]
    (step,) = [p for p in programs if p["label"].startswith("es_step_")]
    (text, shape), = seen
    assert list(shape) == step["geometry"]["kv_cache_shape"] and len(shape) == 4  # [depth, 2B, L, H·dh]
    # sites by heads a grid step (PR 36): the key is always there; on the CPU the step runs
    # the XLA path and has no site (tests/test_attention.py lowers the toy for a TPU: {"2": 3})
    assert step["pallas_heads_per_block"] == {} == step["pallas_kernels"]
    counts = step["kv_cache_whole_ops"]  # {} where XLA:CPU never builds the stack whole
    assert all(isinstance(v, int) and v > 0 for v in counts.values())
    assert sum(counts.values()) == _count_whole(text, shape)


def test_lora_changes_output():
    from hyperscalees_t2i_tpu.lora import init_lora

    cfg = tiny_cfg()
    params = var_mod.init_var(jax.random.PRNGKey(0), cfg)
    spec = cfg.lora_spec(rank=2, alpha=4.0)
    theta = init_lora(jax.random.PRNGKey(1), params, spec)
    assert set(theta) == {
        "blocks/qkv", "blocks/attn_proj", "blocks/fc1", "blocks/fc2",
    }
    labels = jnp.asarray([1], jnp.int32)
    base = var_mod.generate(params, cfg, labels, jax.random.PRNGKey(2), decode=False)
    same = var_mod.generate(params, cfg, labels, jax.random.PRNGKey(2), lora=theta, lora_scale=spec.scale, decode=False)
    np.testing.assert_allclose(np.asarray(base), np.asarray(same), atol=1e-6)  # b=0 init → identity
    # continuous check (sampling can absorb small logit shifts): teacher-forced
    # logits must move under a perturbed adapter
    theta_p = jax.tree_util.tree_map(lambda x: x + 0.3, theta)
    si = jax.random.normal(jax.random.PRNGKey(4), (1, cfg.seq_len, cfg.vq.c_vae)) * 0.3
    lg0 = var_mod.forward_teacher(params, cfg, labels, si)
    lg1 = var_mod.forward_teacher(params, cfg, labels, si, lora=theta_p, lora_scale=spec.scale)
    assert float(jnp.abs(lg0 - lg1).max()) > 1e-3


# ---------------------------------------------------------------------------
# backend
# ---------------------------------------------------------------------------

def test_var_backend_protocol(tmp_path):
    labels = tmp_path / "labels.txt"
    labels.write_text("\n".join(f"name{i}" for i in range(5)))
    bcfg = VarBackendConfig(
        model=tiny_cfg(), class_pool=(0, 2, 4), labels_path=str(labels),
        lora_r=2, lora_alpha=4.0, cfg_scale=1.5,
    )
    b = VarBackend(bcfg)
    b.setup()
    assert b.num_items == 3
    assert b.texts[1] == "a photo of name2"
    info = b.step_info(0, 2, 2)
    assert len(info.flat_ids) == 4 and info.repeats == 2

    theta = b.init_theta(jax.random.PRNGKey(0))
    imgs = jax.jit(b.generate)(theta, jnp.asarray(info.flat_ids, jnp.int32), jax.random.PRNGKey(1))
    assert imgs.shape[0] == 4 and imgs.shape[-1] == 3

    # ES trains over it end-to-end (tiny): one sharded step on the CPU mesh
    from hyperscalees_t2i_tpu.parallel import make_mesh
    from hyperscalees_t2i_tpu.train.config import TrainConfig
    from hyperscalees_t2i_tpu.train.trainer import make_es_step

    def reward_fn(images, flat_ids):
        r = -jnp.mean((images - 0.6) ** 2, axis=(1, 2, 3))
        return {"combined": r}

    from hyperscalees_t2i_tpu.backends.base import make_frozen

    tc = TrainConfig(pop_size=8, sigma=0.05, egg_rank=2, member_batch=4)
    step = make_es_step(b, reward_fn, tc, 2, 2, make_mesh())
    step_args = (make_frozen(b, reward_fn), theta, jnp.asarray(info.flat_ids, jnp.int32), jax.random.PRNGKey(3))
    theta2, metrics, scores = step(*step_args)
    assert np.isfinite(float(metrics["opt_score_mean"]))
    assert scores.shape == (8,)

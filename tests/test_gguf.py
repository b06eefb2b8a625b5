"""weights/gguf.py: synthetic GGUF round trips.

Writes tiny GGUF files with the minimal writer, reads them back with the
parser, and checks: metadata/tensor fidelity, exact Q8_0 dequantization
(ggml block semantics), the bit-preserving ``q8_kernel_node`` →
``ops/quant.dequantize_kernel`` path, the ``weights/io.load_state_dict``
``.gguf`` routing, and the wired ``weights/zimage.py`` converter consuming a
GGUF checkpoint end-to-end (forward parity vs the f32 original within the
Q8_0 rounding budget).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperscalees_t2i_tpu.weights.gguf import (
    GGML_F16,
    GGML_F32,
    GGML_Q8_0,
    load_gguf_state_dict,
    q8_kernel_node,
    quantize_q8_0,
    read_gguf,
    write_gguf,
)


def _rng(seed=0):
    return np.random.RandomState(seed)


def test_roundtrip_f32_f16_q8_0(tmp_path):
    rng = _rng(1)
    tensors = {
        "a.weight": rng.randn(8, 64).astype(np.float32),   # q8_0 (64 % 32 == 0)
        "a.bias": rng.randn(8).astype(np.float32),         # f32
        "b.weight": rng.randn(4, 32).astype(np.float32),   # f16
    }
    path = tmp_path / "tiny.gguf"
    write_gguf(path, tensors, metadata={"general.architecture": "test"},
               tensor_types={"a.weight": "q8_0", "b.weight": "f16"})

    meta, parsed = read_gguf(path)
    assert meta["general.architecture"] == "test"
    assert meta["general.alignment"] == 32
    assert parsed["a.weight"].ggml_type == GGML_Q8_0
    assert parsed["a.bias"].ggml_type == GGML_F32
    assert parsed["b.weight"].ggml_type == GGML_F16
    # ne is reversed torch shape; .shape restores torch layout
    assert parsed["a.weight"].ne == (64, 8)
    assert parsed["a.weight"].shape == (8, 64)

    sd = load_gguf_state_dict(path)
    np.testing.assert_array_equal(sd["a.bias"], tensors["a.bias"])
    np.testing.assert_array_equal(
        sd["b.weight"], tensors["b.weight"].astype(np.float16).astype(np.float32)
    )
    # Q8_0: exact vs a reference ggml dequant of the written payload
    q = np.frombuffer(quantize_q8_0(tensors["a.weight"]),
                      dtype=np.dtype([("d", "<f2"), ("qs", "i1", (32,))]))
    ref = (q["qs"].astype(np.float32)
           * q["d"].astype(np.float32)[:, None]).reshape(8, 64)
    np.testing.assert_array_equal(sd["a.weight"], ref)
    # and the dequant error vs the original is bounded by the block scales
    err = np.abs(sd["a.weight"] - tensors["a.weight"])
    bound = np.repeat(q["d"].astype(np.float32).reshape(8, 2), 32, axis=1) * 0.5 + 1e-6
    assert np.all(err <= bound)


def test_q8_kernel_node_bit_preserving(tmp_path):
    """The exact-int8 path: GGUF Q8_0 payload → ops/quant block-scale node,
    consumed by nn.dense — values identical to the f32 dequant route."""
    from hyperscalees_t2i_tpu.models import nn

    rng = _rng(2)
    w_torch = rng.randn(24, 64).astype(np.float32)  # Linear [out, in]
    path = tmp_path / "lin.gguf"
    write_gguf(path, {"w": w_torch}, tensor_types={"w": "q8_0"})
    _, parsed = read_gguf(path)
    node = q8_kernel_node(parsed["w"])
    assert node["q8"].shape == (64, 24)       # [din, dout]
    assert node["q8"].dtype == np.int8
    assert node["scale"].shape == (2, 24)     # [din/32, dout] block scales
    sd = load_gguf_state_dict(path)
    x = jnp.asarray(rng.randn(3, 64).astype(np.float32))
    y_node = nn.dense({"kernel_q8": {k: jnp.asarray(v) for k, v in node.items()}}, x)
    y_f32 = nn.dense({"kernel": jnp.asarray(sd["w"].T)}, x)
    np.testing.assert_allclose(np.asarray(y_node), np.asarray(y_f32),
                               rtol=1e-6, atol=1e-6)
    import dataclasses

    with pytest.raises(ValueError, match="Q8_0"):
        q8_kernel_node(dataclasses.replace(parsed["w"], ggml_type=GGML_F32))


def test_io_routing_and_error_paths(tmp_path):
    from hyperscalees_t2i_tpu.weights import load_state_dict

    rng = _rng(3)
    tensors = {"x": rng.randn(4, 32).astype(np.float32)}
    path = tmp_path / "route.gguf"
    write_gguf(path, tensors, tensor_types={"x": "q8_0"})
    sd = load_state_dict(path)  # .gguf suffix routes to weights/gguf.py
    assert set(sd) == {"x"} and sd["x"].shape == (4, 32)

    bad = tmp_path / "bad.gguf"
    bad.write_bytes(b"NOTG" + b"\x00" * 64)
    with pytest.raises(ValueError, match="magic"):
        load_state_dict(bad)
    trunc = tmp_path / "trunc.gguf"
    trunc.write_bytes(path.read_bytes()[:40])
    with pytest.raises(ValueError, match="truncated"):
        load_state_dict(trunc)


def _tiny_zimage_sd(rng, cfg):
    """Synthetic torch-layout Z-Image state dict at a tiny geometry —
    numpy only (no torch), keys as the public checkpoints name them."""
    d, L, cap = cfg.d_model, cfg.n_layers, cfg.caption_dim
    dh = cfg.head_dim
    hid = round(d * cfg.ff_ratio)
    pp = cfg.patch_size ** 2 * cfg.in_channels
    sd = {
        "x_embedder.weight": rng.randn(d, pp), "x_embedder.bias": rng.randn(d),
        "cap_embedder.0.weight": rng.randn(cap) * 0.1 + 1.0,
        "cap_embedder.1.weight": rng.randn(d, cap), "cap_embedder.1.bias": rng.randn(d),
        "t_embedder.mlp.0.weight": rng.randn(d, cfg.time_freq_dim),
        "t_embedder.mlp.0.bias": rng.randn(d),
        "t_embedder.mlp.2.weight": rng.randn(d, d), "t_embedder.mlp.2.bias": rng.randn(d),
        "final_layer.adaLN_modulation.1.weight": rng.randn(2 * d, d),
        "final_layer.adaLN_modulation.1.bias": rng.randn(2 * d),
        "final_layer.linear.weight": rng.randn(pp, d),
        "final_layer.linear.bias": rng.randn(pp),
    }
    for i in range(L):
        b = f"layers.{i}."
        sd[b + "adaLN_modulation.1.weight"] = rng.randn(6 * d, d)
        sd[b + "adaLN_modulation.1.bias"] = rng.randn(6 * d)
        for nm in ("to_q", "to_k", "to_v"):
            sd[b + f"attention.{nm}.weight"] = rng.randn(d, d)
        sd[b + "attention.norm_q.weight"] = rng.randn(dh) * 0.1 + 1.0
        sd[b + "attention.norm_k.weight"] = rng.randn(dh) * 0.1 + 1.0
        sd[b + "attention.to_out.0.weight"] = rng.randn(d, d)
        sd[b + "feed_forward.w1.weight"] = rng.randn(hid, d)
        sd[b + "feed_forward.w3.weight"] = rng.randn(hid, d)
        sd[b + "feed_forward.w2.weight"] = rng.randn(d, hid)
    return {k: (v * 0.05).astype(np.float32) if v.ndim else v for k, v in sd.items()}


def test_zimage_gguf_end_to_end(tmp_path):
    """The wired weights/zimage.py punt: a Q8_0-quantized GGUF Z-Image
    checkpoint loads through load_zimage_params and generates latents that
    track the f32 original within the Q8_0 rounding budget."""
    from hyperscalees_t2i_tpu.models import zimage
    from hyperscalees_t2i_tpu.weights.zimage import (
        convert_zimage_transformer,
        infer_zimage_config,
        load_zimage_params,
    )

    cfg = zimage.ZImageConfig(
        in_channels=4, patch_size=2, d_model=16, n_layers=2, n_heads=2,
        caption_dim=12, ff_ratio=2.0, time_freq_dim=32, num_steps=2,
        compute_dtype=jnp.float32,
    )
    rng = _rng(4)
    sd = _tiny_zimage_sd(rng, cfg)
    path = tmp_path / "zimage.gguf"
    # quantize the big Linears (all dims here are multiples of 32 where it
    # matters: d=16 rows but inner dims 16... use q8_0 only where the
    # innermost (torch last) dim is a multiple of 32 — like real exports,
    # which keep norms/small tensors f32/f16)
    ttypes = {
        k: "q8_0" for k, v in sd.items()
        if v.ndim == 2 and (v.size % 32 == 0) and v.shape[-1] % 32 == 0
    }
    write_gguf(path, sd, tensor_types=ttypes)
    assert ttypes, "expected at least one Q8_0 tensor in the synthetic export"

    # geometry inference works off the GGUF-loaded dict too
    icfg = infer_zimage_config(load_gguf_state_dict(path), patch_size=2)
    assert (icfg.n_layers, icfg.d_model, icfg.caption_dim) == (2, 16, 12)

    params_gguf = load_zimage_params(str(path), cfg)
    params_f32 = convert_zimage_transformer(dict(sd), cfg)
    emb = jax.random.normal(jax.random.PRNGKey(5), (2, 5, 12))
    mask = jnp.ones((2, 5), bool)
    out_g = zimage.generate_latents(
        params_gguf, cfg, emb, mask, jax.random.PRNGKey(6), latent_hw=(4, 4))
    out_f = zimage.generate_latents(
        params_f32, cfg, emb, mask, jax.random.PRNGKey(6), latent_hw=(4, 4))
    assert out_g.shape == out_f.shape
    diff = float(jnp.max(jnp.abs(out_g - out_f)))
    assert diff < 0.1, diff         # Q8_0 rounding only
    assert diff > 0.0               # the quantized tensors really differ

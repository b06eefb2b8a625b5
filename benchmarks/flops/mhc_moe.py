"""FLOPs one image of the ``lm_ar`` generator requires when the model is the
``xing4_0`` decoder (``model_type: xing4_0``: the MLA / routed-expert block of
``flops/mla_moe.py`` inside manifold-constrained hyper-connections, ``hc_mult``
residual streams a token) — and the least work of its distinctive layers for
their roofline shares: the routed experts (here a layer held whole), attention
over the latent cache, and the hyper-connection chain around every sub-layer.

Shapes from the configuration file's ``model`` group (``lm``: the model's
``config.json`` keys plus the share, which is the whole here). The block's own
counts are ``mla_moe``'s, imported: yarn changes RoPE's frequencies and the
selection bias the router's choice, neither a count. What this file adds is
the chain: for each token and sub-layer a norm over ``n x C`` numbers, an
``[nC, n (n + 2)]`` product, ``hc_sinkhorn_iters`` column and row
normalisations of an ``n x n`` matrix, and three small mixes (``H_pre X``,
``H_res X``, ``H_post^T y``).
"""

from __future__ import annotations

from typing import Dict, Tuple

from . import common as c
from .mla_moe import (attend_work, dense_ffn_params, expert_calls_per_step, expert_params,  # noqa: F401 (the
                      experts_work, mla_params, positions, vq_decoder)                      # readers look here)
from . import mla_moe

COEFF_BYTES = 4  # φ, b, α and the coefficient path are float32
STREAM_BYTES = {"bfloat16": 2, "float32": 4}  # by the model's ``torch_dtype``


def hc_params(lm: Dict) -> int:
    """Parameters of one sub-layer's hyper-connection: φ, b and the three α."""
    n = lm["hc_mult"]
    return n * lm["hidden_size"] * n * (n + 2) + n * (n + 2) + 3


def hc_macs_per_token(lm: Dict) -> int:
    """Multiply-accumulates of one sub-layer's chain for one token."""
    n, C = lm["hc_mult"], lm["hidden_size"]
    norm, product = n * C, n * C * n * (n + 2)
    sinkhorn = lm["hc_sinkhorn_iters"] * 2 * n * n            # a sum and a division an entry, columns then rows
    mixes = n * C + n * n * C + n * C                          # H_pre X, H_res X, H_post^T y
    return norm + product + sinkhorn + mixes


def sublayers(lm: Dict) -> int:
    return 2 * lm["num_hidden_layers"]


def transformer(model: Dict) -> float:
    prompt, sampled = positions(model)
    return mla_moe.transformer(model) + (prompt + sampled) * sublayers(model["lm"]) * hc_macs_per_token(model["lm"])


def flops_per_image(model: Dict) -> Dict[str, float]:
    parts = {"generator": 2.0 * transformer(model), "decoder": 2.0 * vq_decoder(model),
             "rewards": 2.0 * c.reward_towers(model["reward_towers"])}
    parts["total"] = sum(parts.values())
    return parts


def hc_calls_per_step(model: Dict, sequences_per_call: int, sequences: int) -> float:
    """One call a sub-layer for the prefill and one for every sampled position,
    for every chunk of ``sequences_per_call`` sequences."""
    return (sequences / sequences_per_call) * sublayers(model["lm"]) * (1 + positions(model)[1])


def hc_work(model: Dict, sequences: int, sequences_per_call: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) a step of the hyper-connection chain: every token of
    every sequence (prompt and sampled positions) through every sub-layer's
    chain; bytes: a token's streams read once and written once, the
    sub-layer's input out and its output in, φ (float32) once a call. The
    floor of a chain that is one pass over the streams — what it takes above
    that is launches and passes, which ``lm_hc_ops_per_call`` counts."""
    lm = model["lm"]
    n, C = lm["hc_mult"], lm["hidden_size"]
    prompt, sampled = positions(model)
    tokens = sequences * (prompt + sampled) * sublayers(lm)
    width = STREAM_BYTES[lm.get("torch_dtype", "bfloat16")]
    bytes_ = tokens * (2 * n * C + 2 * C) * width \
        + hc_calls_per_step(model, sequences_per_call, sequences) * hc_params(lm) * COEFF_BYTES
    return 2.0 * tokens * hc_macs_per_token(lm), float(bytes_)


def weight_bytes(model: Dict) -> Dict[str, float]:
    """Bytes of the weights the chip holds, by the program's storage: the
    frozen base int8 (1 B a parameter), the embedding in bf16, routers, their
    selection bias and the hyper-connection parameters in float32."""
    lm = model["lm"]
    L, dense = lm["num_hidden_layers"], lm["first_k_dense_replace"]
    moe = L - dense
    d, rows = lm["hidden_size"], lm["vocab_rows_held"]
    int8 = L * mla_params(lm) + dense * dense_ffn_params(lm) \
        + moe * (1 + lm["experts_held"]) * expert_params(lm) + d * rows
    f32 = moe * (d * lm["n_routed_experts"] + lm["n_routed_experts"]) + sublayers(lm) * hc_params(lm)
    parts = {"int8_base": float(int8), "embedding_bf16": 2.0 * d * rows, "float32_parts": 4.0 * f32}
    parts["total"] = sum(parts.values())
    return parts

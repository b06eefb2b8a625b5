"""FLOPs one image of the ``lm_ar`` generator requires when the model is the
dense ``granitemoehybrid`` decoder (Mamba-2 mixers beside grouped-query
attention without position embedding, a SwiGLU in every layer, a head tied to
the embedding) — and the least work of its distinctive layer for its roofline
share: the SSD state update.

Shapes from the configuration file's ``model`` group (``lm``: the model's
``config.json`` keys). One image is one sequence: a prompt of
``prompt_tokens_mean`` positions, a begin-of-image position and the sampled
positions, ``grid x grid`` in all; the head runs at the sampled positions over
the image-id rows only (``generate`` hands its scan those rows of the tied
embedding). Needed work only: the recurrence's products a position a state
element (``exp(dt A) S``, ``(dt x) B``, their sum, ``S C`` and its sum),
whatever form computes them — the chunked prefill multiplies more and touches
the state less.
"""

from __future__ import annotations

from typing import Dict, Tuple

from . import common as c
from .mla_moe import positions, vq_decoder

STATE_BYTES = 4  # the SSM state is float32
ACTIVATION_BYTES = {"bfloat16": 2, "float32": 4}  # by the model's ``torch_dtype``
STATE_FLOPS = 5  # a position, a state element: decay multiply, input multiply, add; output multiply and add


def layer_kinds(lm: Dict) -> Tuple[int, int]:
    """(Mamba-2 layers, attention layers) of the layers run."""
    n_m = sum(t == "mamba" for t in lm["layer_types"])
    return n_m, len(lm["layer_types"]) - n_m


def ssm_width(lm: Dict) -> int:
    return lm["mamba_n_heads"] * lm["mamba_d_head"]


def conv_channels(lm: Dict) -> int:
    return ssm_width(lm) + 2 * lm["mamba_n_groups"] * lm["mamba_d_state"]


def mamba_params(lm: Dict) -> int:
    """``W_in`` (``[z | xBC | dt]``), ``W_out`` and the conv's taps and bias."""
    d, I = lm["hidden_size"], ssm_width(lm)
    return d * (I + conv_channels(lm) + lm["mamba_n_heads"]) + I * d + (lm["mamba_d_conv"] + 1) * conv_channels(lm)


def attn_params(lm: Dict) -> int:
    d, H, Hkv = lm["hidden_size"], lm["num_attention_heads"], lm["num_key_value_heads"]
    dh = d // H
    return 2 * d * H * dh + 2 * d * Hkv * dh


def ffn_params(lm: Dict) -> int:
    return 3 * lm["hidden_size"] * lm["shared_intermediate_size"]


def state_elements(lm: Dict) -> int:
    """Numbers in one sequence's state of one Mamba-2 layer."""
    return lm["mamba_n_heads"] * lm["mamba_d_head"] * lm["mamba_d_state"]


def weight_bytes(lm: Dict) -> int:
    """The base at 1 B a parameter (int8) and the tied embedding in the
    activations' dtype; norms, ``A_log``, ``dt_bias``, ``D`` left out (under
    a megabyte)."""
    n_m, n_a = layer_kinds(lm)
    base = n_m * mamba_params(lm) + n_a * attn_params(lm) + len(lm["layer_types"]) * ffn_params(lm)
    return base + lm["vocab_size"] * lm["hidden_size"] * ACTIVATION_BYTES[lm["torch_dtype"]]


def carried_state_bytes(lm: Dict, sequences: int) -> int:
    """Bytes ``sequences`` sequences carry through a decode scan in the
    Mamba-2 layers, as the configuration states them: the float32 state and
    the conv's last ``K - 1`` inputs in the activations' dtype. What the
    program's ``lm/state_bytes`` has to read, to the byte."""
    n_m, _ = layer_kinds(lm)
    window = (lm["mamba_d_conv"] - 1) * conv_channels(lm) * ACTIVATION_BYTES[lm["torch_dtype"]]
    return sequences * n_m * (state_elements(lm) * STATE_BYTES + window)


def transformer(model: Dict) -> float:
    lm = model["lm"]
    d, H = lm["hidden_size"], lm["num_attention_heads"]
    prompt, sampled = positions(model)
    T = prompt + sampled                      # the begin-of-image id takes the first sampled slot's input
    n_m, n_a = layer_kinds(lm)
    per_token = n_m * (mamba_params(lm) + STATE_FLOPS * state_elements(lm) / 2) + n_a * attn_params(lm) \
        + len(lm["layer_types"]) * ffn_params(lm)
    attn = n_a * H * 2 * (d // H) * (T * (T + 1) // 2)   # causal: a query at position i sees i + 1 keys and values
    head = sampled * d * lm["image_tokens"]["image_vocab"]
    return T * per_token + attn + head


def flops_per_image(model: Dict) -> Dict[str, float]:
    """Multiply-accumulates doubled, as ``common`` counts them (the state
    update's FLOPs are halved into MACs above and doubled here)."""
    parts = {"generator": 2.0 * transformer(model), "decoder": 2.0 * vq_decoder(model),
             "rewards": 2.0 * c.reward_towers(model["reward_towers"])}
    parts["total"] = sum(parts.values())
    return parts


# ---------------------------------------------------------------------------
# least work of the distinctive layer, a step
# ---------------------------------------------------------------------------

def state_update_work(model: Dict, sequences: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) a step of the SSD state update in the Mamba-2
    layers, the same whatever computes it. Every sampled position reads each
    sequence's float32 state once and writes it once a layer; the prefill
    (chunked, so the state is not touched a position) writes it once a
    sequence a layer. FLOPs: :data:`STATE_FLOPS` a state element a position,
    prompt and sampled."""
    lm = model["lm"]
    n_m, _ = layer_kinds(lm)
    prompt, sampled = positions(model)
    S = state_elements(lm)
    flops = (prompt + sampled) * STATE_FLOPS * S
    bytes_ = sampled * 2 * S * STATE_BYTES + S * STATE_BYTES
    return float(n_m * sequences * flops), float(n_m * sequences * bytes_)

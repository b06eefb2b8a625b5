"""FLOPs one image of the ``lm_ar`` generator requires when the model is the
hybrid decoder (``model_type: qwen3_next``: Gated DeltaNet layers beside gated
softmax attention, many small routed experts of which the chip holds a share)
— and the least work of its distinctive layers for their roofline shares: the
gated delta rule's state update, the grouped expert products, attention over
the KV cache of the few attention layers.

Shapes from the configuration file's ``model`` group (``lm``: the model's
``config.json`` keys plus the share). One image is one sequence: a prompt of
``prompt_tokens_mean`` positions, a begin-of-image position and the sampled
positions, ``grid x grid`` in all; the head runs at the sampled positions
only. A token's routed work is counted at the held share: of its
``num_experts_per_tok`` experts, ``experts_held / num_experts`` are here in the
mean. Needed work only: the recurrence's three products a position a value
head (``k^T S``, ``k (x) delta``, ``q^T S``), whatever form computes them — the
chunked prefill multiplies more and touches the state less.
"""

from __future__ import annotations

from typing import Dict, Tuple

from .mla_moe import positions, vq_decoder
from . import common as c

STATE_BYTES = 4  # the recurrent state is float32
ACTIVATION_BYTES = {"bfloat16": 2, "float32": 4}  # by the model's ``torch_dtype``


def layer_kinds(lm: Dict) -> Tuple[int, int]:
    """(Gated DeltaNet layers, gated attention layers) of the layers run."""
    L, every = lm["num_hidden_layers"], lm["full_attention_interval"]
    return L - L // every, L // every


def gdn_params(lm: Dict) -> int:
    d = lm["hidden_size"]
    nq, nv = lm["linear_num_key_heads"] * lm["linear_key_head_dim"], lm["linear_num_value_heads"] * lm["linear_value_head_dim"]
    return d * (2 * nq + 2 * nv) + d * 2 * lm["linear_num_value_heads"] + nv * d \
        + lm["linear_conv_kernel_dim"] * (2 * nq + nv)


def attn_params(lm: Dict) -> int:
    d, H, Hkv, dh = lm["hidden_size"], lm["num_attention_heads"], lm["num_key_value_heads"], lm["head_dim"]
    return d * H * 2 * dh + 2 * d * Hkv * dh + H * dh * d


def expert_params(lm: Dict) -> int:
    return 3 * lm["hidden_size"] * lm["moe_intermediate_size"]


def shared_params(lm: Dict) -> int:
    return 3 * lm["hidden_size"] * lm["shared_expert_intermediate_size"] + lm["hidden_size"]


def held_experts_per_token(lm: Dict) -> float:
    return lm["num_experts_per_tok"] * lm["experts_held"] / lm["num_experts"]


def state_elements(lm: Dict) -> int:
    """Numbers in one sequence's recurrent state of one DeltaNet layer."""
    return lm["linear_num_value_heads"] * lm["linear_key_head_dim"] * lm["linear_value_head_dim"]


def carried_state_bytes(lm: Dict, sequences: int) -> int:
    """Bytes ``sequences`` sequences carry through a decode scan in the
    DeltaNet layers, as the configuration states them: the float32 recurrent
    state and the conv's last ``K - 1`` inputs (its ``2 Hk dk + Hv dv``
    channels) in the activations' dtype. What the program's ``lm/state_bytes``
    has to read, to the byte."""
    n_g, _ = layer_kinds(lm)
    channels = 2 * lm["linear_num_key_heads"] * lm["linear_key_head_dim"] \
        + lm["linear_num_value_heads"] * lm["linear_value_head_dim"]
    window = (lm["linear_conv_kernel_dim"] - 1) * channels * ACTIVATION_BYTES[lm["torch_dtype"]]
    return sequences * n_g * (state_elements(lm) * STATE_BYTES + window)


def transformer(model: Dict) -> float:
    lm = model["lm"]
    d, H, dh = lm["hidden_size"], lm["num_attention_heads"], lm["head_dim"]
    prompt, sampled = positions(model)
    T = prompt + sampled                      # the begin-of-image id takes the first sampled slot's input
    n_g, n_a = layer_kinds(lm)
    per_token = n_g * (gdn_params(lm) + 3 * state_elements(lm)) + n_a * attn_params(lm) \
        + lm["num_hidden_layers"] * (d * lm["num_experts"] + shared_params(lm)
                                     + held_experts_per_token(lm) * expert_params(lm))
    attn = n_a * H * 2 * dh * (T * (T + 1) // 2)   # causal: a query at position i sees i + 1 keys and values
    head = sampled * d * lm["vocab_rows_held"]
    return T * per_token + attn + head


def flops_per_image(model: Dict) -> Dict[str, float]:
    parts = {"generator": 2.0 * transformer(model), "decoder": 2.0 * vq_decoder(model),
             "rewards": 2.0 * c.reward_towers(model["reward_towers"])}
    parts["total"] = sum(parts.values())
    return parts


# ---------------------------------------------------------------------------
# least work of the distinctive layers, a step
# ---------------------------------------------------------------------------

def state_update_work(model: Dict, sequences: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) a step of the gated delta rule in the DeltaNet
    layers, the same whether XLA or a kernel computes it. Every sampled
    position reads each sequence's float32 state once and writes it once a
    layer, and multiplies it three times (``k^T S``, ``k (x) delta``, ``q^T
    S``) and decays it. The prefill (chunked, so the state is not touched a
    position) writes the state once a sequence a layer and owes the
    recurrence's products at the prompt's positions. ``q``, ``k``, ``v`` and
    the output in float32 a position."""
    lm = model["lm"]
    n_g, _ = layer_kinds(lm)
    prompt, sampled = positions(model)
    S = state_elements(lm)
    Hv, dk, dv = lm["linear_num_value_heads"], lm["linear_key_head_dim"], lm["linear_value_head_dim"]
    io = Hv * (2 * dk + 2 * dv) * 4
    flops = (prompt + sampled) * (2 * 3 + 1) * S
    bytes_ = sampled * (2 * S * STATE_BYTES + io) + S * STATE_BYTES + prompt * io
    return float(n_g * sequences * flops), float(n_g * sequences * bytes_)


def experts_work(model: Dict, assignments: float, calls: float) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of the routed experts' products a step:
    ``assignments`` token-expert pairs computed here (the program's
    ``moe/local_assignments``), three matrices a pair; each held expert's int8
    base read once a call (``calls``: expert-layer invocations a step), a
    pair's activations in and out in bf16."""
    lm = model["lm"]
    d, f = lm["hidden_size"], lm["moe_intermediate_size"]
    flops = 2.0 * assignments * expert_params(lm)
    bytes_ = calls * lm["experts_held"] * expert_params(lm) + assignments * 2 * (2 * d + 3 * f)
    return flops, bytes_


def expert_calls_per_step(model: Dict, sequences_per_call: int, sequences: int) -> float:
    """One call a layer for every sampled position and one for the prefill —
    but for the last layer, whose prefill stops at what it carries on — for
    every chunk of ``sequences_per_call`` sequences."""
    L = model["lm"]["num_hidden_layers"]
    return (sequences / sequences_per_call) * (L * positions(model)[1] + L - 1)


def attend_work(model: Dict, sequences: int, sequences_per_call: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) a step of attention proper in the gated attention
    layers: scores and weighted sum over the grouped K and V, causal in the
    prefill, over the cache's seen slots at every sampled position. Bytes: K
    and V a query sees (bf16, once for the query heads that share them),
    queries and outputs. ``sequences_per_call`` is not used: nothing is read
    once a call."""
    lm = model["lm"]
    H, Hkv, dh = lm["num_attention_heads"], lm["num_key_value_heads"], lm["head_dim"]
    _, n_a = layer_kinds(lm)
    prompt, sampled = positions(model)
    seen = sum(prompt + 1 + i for i in range(sampled))                                # slots a sampled query sees
    macs = n_a * H * 2 * dh * (prompt * (prompt + 1) // 2 + seen)
    bytes_ = n_a * ((prompt + seen) * 2 * Hkv * dh * 2 + (prompt + sampled) * 2 * H * dh * 2)
    return 2.0 * macs * sequences, float(bytes_ * sequences)

"""FLOPs one image of the ``lm_ar`` generator requires when the model is the
``mimo_v2_flash`` decoder (sliding-window attention with a learned sink beside
full grouped-query attention, query-key heads of ``head_dim`` and value heads
of ``v_head_dim``, a dense first layer, then routed experts of which the chip
holds a share and no shared expert) — the bytes its caches and weights come
to, and the least work of attention over each kind of cache for their
roofline shares.

Shapes from the configuration file's ``model`` group (``lm``: the model's
``config.json`` keys plus the share). One image is one sequence: a prompt of
``prompt_tokens_mean`` positions, a begin-of-image position and the sampled
positions, ``grid x grid`` in all; the head runs at the sampled positions over
the image-id columns only (``generate`` hands its scan that cut). A token's
routed work is counted at the held share: of its ``num_experts_per_tok``
experts, ``experts_held / n_routed_experts`` are here in the mean. Needed work
only: a window layer's query attends to the ``sliding_window`` positions
ending at itself, or fewer where the sequence is shorter, whatever the
program reads.
"""

from __future__ import annotations

from typing import Dict, Tuple

from . import common as c
from .mla_moe import positions, vq_decoder

ACTIVATION_BYTES = {"bfloat16": 2, "float32": 4}  # K, V and the embedding rows, by the model's ``torch_dtype``
ROUTER_BYTES = 4  # the router's weight is float32; every other kernel int8


def kinds(lm: Dict) -> Tuple[int, int]:
    """(full attention layers, window layers) of the layers run."""
    n_w = sum(lm["hybrid_layer_pattern"][: lm["num_hidden_layers"]])
    return lm["num_hidden_layers"] - n_w, n_w


def heads(lm: Dict, window: bool) -> Tuple[int, int, int, int]:
    """(query heads, key/value heads, query-key width, value width) of a kind of layer."""
    p = "swa_" if window else ""
    return lm[f"{p}num_attention_heads"], lm[f"{p}num_key_value_heads"], lm[f"{p}head_dim"], lm[f"{p}v_head_dim"]


def attn_params(lm: Dict, window: bool) -> int:
    d = lm["hidden_size"]
    H, Hkv, dqk, dv = heads(lm, window)
    return d * H * dqk + d * Hkv * (dqk + dv) + H * dv * d


def expert_params(lm: Dict) -> int:
    return 3 * lm["hidden_size"] * lm["moe_intermediate_size"]


def dense_ffn_params(lm: Dict) -> int:
    return 3 * lm["hidden_size"] * lm["intermediate_size"]


def moe_layers(lm: Dict) -> int:
    return sum(lm["moe_layer_freq"][: lm["num_hidden_layers"]])


def held_experts_per_token(lm: Dict) -> float:
    return lm["num_experts_per_tok"] * lm["experts_held"] / lm["n_routed_experts"]


def cache_len(lm: Dict) -> int:
    img = lm["image_tokens"]
    return img["max_prompt_len"] + img["grid"] ** 2


def slot_bytes(lm: Dict, window: bool) -> int:
    """One position's K and V in one layer of a kind, in the activations' dtype."""
    _, Hkv, dqk, dv = heads(lm, window)
    return Hkv * (dqk + dv) * ACTIVATION_BYTES[lm["torch_dtype"]]


def full_cache_bytes(lm: Dict, sequences: int) -> int:
    """K and V of the full layers over ``cache_len`` slots, what ``sequences``
    sequences carry through a decode scan: what the program's
    ``lm/kv_cache_bytes`` has to read, to the byte."""
    n_f, _ = kinds(lm)
    return n_f * sequences * cache_len(lm) * slot_bytes(lm, False)


def window_cache_bytes_max(lm: Dict, sequences: int) -> int:
    """The most a window layer may carry a sequence, over the window layers:
    the prompt's slots and a window's, ``max_prompt_len + sliding_window``,
    whatever ``cache_len`` is. ``lm/window_cache_bytes`` has to be above 0 and
    at or under it."""
    _, n_w = kinds(lm)
    slots = lm["image_tokens"]["max_prompt_len"] + lm["sliding_window"]
    return n_w * sequences * slots * slot_bytes(lm, True)


def window_cache_bytes_ring(lm: Dict, sequences: int) -> int:
    """What a ring of ``sliding_window`` slots a window layer comes to."""
    _, n_w = kinds(lm)
    return n_w * sequences * lm["sliding_window"] * slot_bytes(lm, True)


def weight_bytes(lm: Dict) -> int:
    """The base at 1 B a parameter (int8): attention, the dense FFN, the held
    experts and the head; routers in float32, the embedding rows in bf16;
    norms, sinks and correction biases left out (kilobytes)."""
    n_f, n_w = kinds(lm)
    L, E, d, V = lm["num_hidden_layers"], moe_layers(lm), lm["hidden_size"], lm["vocab_rows_held"]
    return (n_f * attn_params(lm, False) + n_w * attn_params(lm, True) + (L - E) * dense_ffn_params(lm)
            + E * (lm["experts_held"] * expert_params(lm) + ROUTER_BYTES * lm["n_routed_experts"] * d)
            + V * d * (1 + ACTIVATION_BYTES[lm["torch_dtype"]]))


def read_bytes_per_position(model: Dict, sequences: int) -> Dict[str, float]:
    """What a sampled position reads at least, for ``sequences`` sequences,
    part by part: every held expert (the dense form reads all of them), the
    dense FFN, the attention projections, the full layers' whole caches, the
    window layers' rings, routers and the image-id columns of the head."""
    lm = model["lm"]
    n_f, n_w = kinds(lm)
    E, d = moe_layers(lm), lm["hidden_size"]
    parts = {"experts": float(E * lm["experts_held"] * expert_params(lm)),
             "dense_ffn": float((lm["num_hidden_layers"] - E) * dense_ffn_params(lm)),
             "attention_projections": float(n_f * attn_params(lm, False) + n_w * attn_params(lm, True)),
             "full_kv": float(full_cache_bytes(lm, sequences)),
             "window_kv": float(window_cache_bytes_ring(lm, sequences)),
             "routers_and_head": float(E * ROUTER_BYTES * lm["n_routed_experts"] * d
                                       + d * lm["image_tokens"]["image_vocab"])}
    parts["total"] = sum(parts.values())
    return parts


def _attend_macs(lm: Dict, window: bool, T: int) -> int:
    """Scores and weighted sum of one layer over a sequence of ``T``
    positions, all heads: a query sees ``i + 1`` keys, at most the window."""
    H, _, dqk, dv = heads(lm, window)
    seen = sum(min(i + 1, lm["sliding_window"]) if window else i + 1 for i in range(T))
    return H * (dqk + dv) * seen


def transformer(model: Dict) -> float:
    lm = model["lm"]
    d = lm["hidden_size"]
    prompt, sampled = positions(model)
    T = prompt + sampled                      # the begin-of-image id takes the first sampled slot's input
    n_f, n_w = kinds(lm)
    E = moe_layers(lm)
    per_token = n_f * attn_params(lm, False) + n_w * attn_params(lm, True) \
        + (lm["num_hidden_layers"] - E) * dense_ffn_params(lm) \
        + E * (d * lm["n_routed_experts"] + held_experts_per_token(lm) * expert_params(lm))
    attn = n_f * _attend_macs(lm, False, T) + n_w * _attend_macs(lm, True, T)
    head = sampled * d * lm["image_tokens"]["image_vocab"]
    return T * per_token + attn + head


def flops_per_image(model: Dict) -> Dict[str, float]:
    parts = {"generator": 2.0 * transformer(model), "decoder": 2.0 * vq_decoder(model),
             "rewards": 2.0 * c.reward_towers(model["reward_towers"])}
    parts["total"] = sum(parts.values())
    return parts


# ---------------------------------------------------------------------------
# least work of attention over each kind of cache, a step
# ---------------------------------------------------------------------------

def _attend_work(model: Dict, sequences: int, window: bool) -> Tuple[float, float]:
    lm = model["lm"]
    n_f, n_w = kinds(lm)
    layers = n_w if window else n_f
    H, _, dqk, dv = heads(lm, window)
    prompt, sampled = positions(model)
    see = (lambda n: min(n, lm["sliding_window"])) if window else (lambda n: n)
    seen = sum(see(prompt + 1 + i) for i in range(sampled))                 # slots a sampled query sees
    macs = H * (dqk + dv) * (sum(see(p + 1) for p in range(prompt)) + seen)
    # K and V a query sees (once for the query heads that share them), the prompt's once; queries and outputs
    bytes_ = (prompt + seen) * slot_bytes(lm, window) \
        + (prompt + sampled) * H * (dqk + dv) * ACTIVATION_BYTES[lm["torch_dtype"]]
    return 2.0 * macs * layers * sequences, float(bytes_ * layers * sequences)


def window_attend_work(model: Dict, sequences: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) a step of attention proper in the window layers:
    scores and weighted sum over at most ``sliding_window`` slots a query —
    ``min(window, seen)`` at each sampled position, the band at the prompt's —
    K and V read once in bf16 at the layer's key/value heads, queries and
    outputs in and out, whatever computes it."""
    return _attend_work(model, sequences, True)


def full_attend_work(model: Dict, sequences: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) a step of attention proper in the full layers: the
    same over every slot a query sees (causal in the prefill, the seen slots
    at every sampled position)."""
    return _attend_work(model, sequences, False)

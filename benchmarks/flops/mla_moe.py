"""FLOPs one image of the ``lm_ar`` generator requires — a decoder with
multi-head latent attention and routed experts of which the chip holds a
share — and the least work of its two distinctive layers (the grouped expert
products, attention over the latent cache) for their roofline shares.

Shapes from the configuration file's ``model`` group (``lm``: the model's
``config.json`` keys plus the share). One image is one sequence: a prompt of
``prompt_tokens_mean`` positions, a begin-of-image position and the sampled
positions, ``grid x grid`` in all; the head runs at the sampled positions
only. A token's routed work is counted at the held share: of its
``num_experts_per_tok`` experts, ``experts_held / n_routed_experts`` are here
in the mean. Needed work only: the expanded attention form (the absorbed
decode form multiplies more and reads less: :func:`attend_work` counts that
one, because it is what the latent cache is for).
"""

from __future__ import annotations

from typing import Dict, Tuple

from . import common as c


def mla_params(lm: Dict) -> int:
    d, H = lm["hidden_size"], lm["num_attention_heads"]
    return (d * lm["q_lora_rank"] + lm["q_lora_rank"] * H * (lm["qk_nope_head_dim"] + lm["qk_rope_head_dim"])
            + d * (lm["kv_lora_rank"] + lm["qk_rope_head_dim"])
            + lm["kv_lora_rank"] * H * (lm["qk_nope_head_dim"] + lm["v_head_dim"])
            + H * lm["v_head_dim"] * d)


def expert_params(lm: Dict) -> int:
    return 3 * lm["hidden_size"] * lm["moe_intermediate_size"]


def dense_ffn_params(lm: Dict) -> int:
    return 3 * lm["hidden_size"] * lm["intermediate_size"]


def held_experts_per_token(lm: Dict) -> float:
    return lm["num_experts_per_tok"] * lm["experts_held"] / lm["n_routed_experts"]


def positions(model: Dict) -> Tuple[int, int]:
    """(prompt positions, sampled positions) of one image's sequence."""
    g = model["lm"]["image_tokens"]["grid"]
    return int(model["prompt_tokens_mean"]), g * g


def transformer(model: Dict) -> float:
    lm = model["lm"]
    d, H = lm["hidden_size"], lm["num_attention_heads"]
    prompt, sampled = positions(model)
    T = prompt + sampled                      # the begin-of-image id takes the first sampled slot's input
    dense_layers = lm["first_k_dense_replace"]
    moe_layers = lm["num_hidden_layers"] - dense_layers
    per_token = lm["num_hidden_layers"] * mla_params(lm) + dense_layers * dense_ffn_params(lm) \
        + moe_layers * (d * lm["n_routed_experts"] + (1 + held_experts_per_token(lm)) * expert_params(lm))
    # causal attention, expanded form: a query at position i sees i + 1 keys of width qk, values of width v
    qk, v = lm["qk_nope_head_dim"] + lm["qk_rope_head_dim"], lm["v_head_dim"]
    attn = lm["num_hidden_layers"] * H * (qk + v) * (T * (T + 1) // 2)
    head = sampled * d * lm["vocab_rows_held"]
    return T * per_token + attn + head


def vq_decoder(model: Dict) -> int:
    """The CompVis decoder of ``models/msvq.py`` on one ``grid x grid`` map of
    codes (no scale pyramid: one scale, no phi convs)."""
    vq, g = model["vq"], model["lm"]["image_tokens"]["grid"]
    cv, block_in = vq["c_vae"], vq["ch"] * vq["ch_mult"][-1]
    macs = c.conv(g, g, 3, 3, cv, cv) + c.conv(g, g, 3, 3, cv, block_in)

    def res(side, cin, cout):
        extra = c.conv(side, side, 1, 1, cin, cout) if cin != cout else 0
        return c.conv(side, side, 3, 3, cin, cout) + c.conv(side, side, 3, 3, cout, cout) + extra

    def attn(side, ch):
        n = side * side
        return c.dense(n, ch, 3 * ch) + c.softmax_attention(n, n, ch) + c.dense(n, ch, ch)

    macs += 2 * res(g, block_in, block_in) + (attn(g, block_in) if vq["using_mid_sa"] else 0)
    side, cin, levels = g, block_in, len(vq["ch_mult"])
    for level in reversed(range(levels)):
        cout = vq["ch"] * vq["ch_mult"][level]
        for _ in range(vq["num_res_blocks"] + 1):
            macs += res(side, cin, cout)
            cin = cout
            if level == levels - 1 and vq["using_sa"]:
                macs += attn(side, cout)
        if level != 0:
            side *= 2
            macs += c.conv(side, side, 3, 3, cout, cout)
    return macs + c.conv(side, side, 3, 3, cin, 3)


def flops_per_image(model: Dict) -> Dict[str, float]:
    parts = {"generator": 2.0 * transformer(model), "decoder": 2.0 * vq_decoder(model),
             "rewards": 2.0 * c.reward_towers(model["reward_towers"])}
    parts["total"] = sum(parts.values())
    return parts


# ---------------------------------------------------------------------------
# least work of the two distinctive layers, a step
# ---------------------------------------------------------------------------

def experts_work(model: Dict, assignments: float, calls: float) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of the grouped expert products a step: ``assignments``
    token-expert pairs computed here (the program's ``moe/local_assignments``),
    three matrices a pair; each held expert's int8 base read once a call
    (``calls``: expert-layer invocations a step), a pair's activations in and
    out in bf16."""
    lm = model["lm"]
    d, f = lm["hidden_size"], lm["moe_intermediate_size"]
    flops = 2.0 * assignments * expert_params(lm)
    bytes_ = calls * lm["experts_held"] * expert_params(lm) + assignments * 2 * (2 * d + 3 * f)
    return flops, bytes_


def expert_calls_per_step(model: Dict, sequences_per_call: int, sequences: int) -> float:
    """One call a MoE layer for the prefill and one for every sampled
    position, for every chunk of ``sequences_per_call`` sequences."""
    lm = model["lm"]
    moe_layers = lm["num_hidden_layers"] - lm["first_k_dense_replace"]
    return (sequences / sequences_per_call) * moe_layers * (1 + positions(model)[1])


def attend_work(model: Dict, sequences: int, sequences_per_call: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) a step of attention proper over the latent cache, in
    the forms the program uses: the prefill expanded (scores and weighted sum
    over per-head K and V), every sampled position absorbed — the K half of the
    up-projection into the query, scores and weighted sum over the ``c + dr``
    wide latent entries, the V half after. Bytes: the cache entries a query
    sees (bf16), the int8 up-projection once a call, queries and outputs."""
    lm = model["lm"]
    H, dn, dr, dv, cl = (lm["num_attention_heads"], lm["qk_nope_head_dim"], lm["qk_rope_head_dim"],
                         lm["v_head_dim"], lm["kv_lora_rank"])
    L = lm["num_hidden_layers"]
    prompt, sampled = positions(model)
    macs = L * H * (dn + dr + dv) * (prompt * (prompt + 1) // 2)                       # prefill, expanded
    bytes_ = L * prompt * H * (2 * (dn + dr) + 2 * dv) * 2
    seen = sum(prompt + 1 + i for i in range(sampled))                                # slots a sampled query sees
    macs += L * H * (sampled * (dn * cl + cl * dv) + seen * ((cl + dr) + cl))
    bytes_ += L * (seen * (cl + dr) * 2 + sampled * H * ((dn + dr) + dv) * 2)
    up_proj = (sequences / sequences_per_call) * L * sampled * cl * H * (dn + dv)     # int8, once a call
    return 2.0 * macs * sequences, float(bytes_ * sequences + up_proj)

"""FLOPs one Sana-Sprint image requires: one DiT forward (one-step SCM), the
DC-AE decode, both reward towers. Shapes from the configuration file's
``model`` group; formulas follow ``models/sana.py`` and ``models/dcae.py``."""

from __future__ import annotations

from typing import Dict

from . import common as c


def dit(m: Dict) -> int:
    t = m["transformer"]
    d, tokens, cap = t["d_model"], (m["latent_size"] // t["patch_size"]) ** 2, m["caption_tokens"]
    hidden2 = 2 * int(round(d * t["ff_ratio"]))
    side = m["latent_size"] // t["patch_size"]
    block = (
        4 * c.dense(tokens, d, d)                       # attn1 q, k, v, out
        + c.linear_attention(tokens, d, t["head_dim"])
        + 2 * c.dense(tokens, d, d)                     # attn2 q, out
        + 2 * c.dense(cap, d, d)                        # attn2 k, v over the caption
        + c.softmax_attention(tokens, cap, d)
        + c.conv(side, side, 1, 1, d, hidden2)          # GLUMBConv: inverted,
        + c.conv(side, side, 3, 3, hidden2, hidden2, groups=hidden2)  # depthwise,
        + c.conv(side, side, 1, 1, hidden2 // 2, d)     # point
    )
    embed = (
        c.dense(tokens, t["patch_size"] ** 2 * t["in_channels"], d)
        + c.dense(cap, t["caption_dim"], d) + c.dense(cap, d, d)
        + (2 if t["guidance_embeds"] else 1) * (c.dense(1, t["time_freq_dim"], d) + c.dense(1, d, d))
        + c.dense(1, d, 6 * d)
    )
    head = c.dense(tokens, d, t["patch_size"] ** 2 * t["out_channels"])
    return embed + t["n_layers"] * block + head


def dcae_decoder(m: Dict) -> int:
    v = m["vae_decoder"]
    chs, side = v["channels"], m["latent_size"]
    macs = c.conv(side, side, 3, 3, v["latent_channels"], chs[0])
    for si, ch in enumerate(chs):
        if si > 0:
            macs += c.conv(side, side, 3, 3, chs[si - 1], 4 * ch)  # then pixel-shuffle x2
            side *= 2
        tokens = side * side
        for _ in range(v["blocks_per_stage"][si]):
            if si in v["attn_stages"]:
                heads = min(v["attn_heads"], ch)
                hidden2 = 2 * int(round(ch * v["glumb_ratio"]))
                macs += (
                    c.dense(tokens, ch, 3 * ch) + c.linear_attention(tokens, ch, ch // heads)
                    + c.dense(tokens, ch, ch)
                    + c.conv(side, side, 1, 1, ch, hidden2)
                    + c.conv(side, side, 3, 3, hidden2, hidden2, groups=hidden2)
                    + c.conv(side, side, 1, 1, hidden2 // 2, ch)
                )
            else:
                macs += 2 * c.conv(side, side, 3, 3, ch, ch)
    return macs + c.conv(side, side, 3, 3, chs[-1], 3)


def flops_per_image(model: Dict) -> Dict[str, float]:
    parts = {"generator": 2.0 * dit(model), "decoder": 2.0 * dcae_decoder(model),
             "rewards": 2.0 * c.reward_towers(model["reward_towers"])}
    parts["total"] = sum(parts.values())
    return parts

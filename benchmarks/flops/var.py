"""FLOPs one VAR image requires: ten next-scale steps of the AR transformer
over a KV cache for two CFG sequences, the MSVQ residual accumulation, the
CompVis decoder to pixels, both reward towers. Shapes from the configuration
file's ``model`` group; formulas follow ``models/var.py`` and ``models/msvq.py``."""

from __future__ import annotations

from typing import Dict

from . import common as c


def transformer(m: Dict) -> int:
    t, vq = m["transformer"], m["vq"]
    d, hid = t["d_model"], int(t["d_model"] * t["ff_ratio"])
    macs, seen = 0, 0
    for pn in t["patch_nums"]:
        n = pn * pn
        seen += n                                   # this scale attends to itself and all before
        per_layer = (
            c.dense(n, d, 3 * d) + c.softmax_attention(n, seen, d) + c.dense(n, d, d)
            + c.dense(n, d, hid) + c.dense(n, hid, d)
        )
        macs += t["depth"] * per_layer + c.dense(n, d, vq["vocab_size"]) + c.dense(n, vq["c_vae"], d)
    cond = t["depth"] * c.dense(1, d, 6 * d) + c.dense(1, d, 2 * d)
    return 2 * (macs + cond)                        # conditional + unconditional sequence


def msvq(m: Dict) -> int:
    """phi convs on the full grid once a scale, then the decoder."""
    t, vq = m["transformer"], m["vq"]
    g, cv = t["patch_nums"][-1], vq["c_vae"]
    macs = len(t["patch_nums"]) * c.conv(g, g, 3, 3, cv, cv)
    macs += c.conv(g, g, 3, 3, cv, cv)              # post_quant_conv
    block_in = vq["ch"] * vq["ch_mult"][-1]
    macs += c.conv(g, g, 3, 3, cv, block_in)

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
    parts = {"generator": 2.0 * transformer(model), "decoder": 2.0 * msvq(model),
             "rewards": 2.0 * c.reward_towers(model["reward_towers"])}
    parts["total"] = sum(parts.values())
    return parts

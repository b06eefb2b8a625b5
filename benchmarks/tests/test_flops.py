"""The FLOP and byte functions against counts made by hand at one tiny shape each."""

import pytest

from benchmarks.flops import common as c
from benchmarks.flops import kernels, sana, var

TOWER = {"d_model": 4, "n_layers": 1, "n_heads": 1, "d_mlp": 8, "image_size": 4,
         "patch_size": 2, "projection_dim": 2}


def test_building_blocks():
    assert c.dense(3, 4, 5) == 60
    assert c.conv(2, 2, 3, 3, 4, 6) == 2 * 2 * 9 * 4 * 6
    assert c.conv(2, 2, 3, 3, 6, 6, groups=6) == 2 * 2 * 9 * 6
    assert c.softmax_attention(3, 5, 4) == 2 * 3 * 5 * 4
    assert c.linear_attention(3, 4, 2) == 2 * 3 * 4 * 2


def test_vit_tower_by_hand():
    # 4 patches + class token = 5 tokens of width 4
    patch = 4 * (3 * 2 * 2) * 4
    layer = 5 * 4 * 12 + 2 * 5 * 5 * 4 + 5 * 4 * 4 + 2 * 5 * 4 * 8
    assert c.vit_image_tower(TOWER) == patch + layer + 4 * 2


def test_sana_dit_by_hand():
    m = {"transformer": {"in_channels": 2, "out_channels": 2, "patch_size": 1, "d_model": 4,
                         "n_layers": 2, "n_heads": 2, "head_dim": 2, "caption_dim": 3,
                         "ff_ratio": 2.0, "time_freq_dim": 2, "guidance_embeds": False},
         "latent_size": 2, "caption_tokens": 3}
    tokens, d, cap, h2 = 4, 4, 3, 16
    block = (4 * tokens * d * d + 2 * tokens * d * 2 + 2 * tokens * d * d + 2 * cap * d * d
             + 2 * tokens * cap * d + tokens * d * h2 + tokens * 9 * h2 + tokens * (h2 // 2) * d)
    embed = tokens * 2 * d + cap * 3 * d + cap * d * d + (2 * d + d * d) + d * 6 * d
    assert sana.dit(m) == embed + 2 * block + tokens * d * 2


def test_dcae_decoder_by_hand():
    m = {"latent_size": 2, "vae_decoder": {"latent_channels": 2, "channels": [4, 2],
                                           "blocks_per_stage": [1, 1], "attn_stages": [],
                                           "attn_heads": 1, "glumb_ratio": 2.0}}
    want = (4 * 9 * 2 * 4            # conv_in on 2x2
            + 2 * 4 * 9 * 4 * 4      # stage 0: one res block
            + 4 * 9 * 4 * 8          # up conv on 2x2 to 4*2 channels, then shuffle to 4x4
            + 2 * 16 * 9 * 2 * 2     # stage 1: one res block on 4x4
            + 16 * 9 * 2 * 3)        # conv_out
    assert sana.dcae_decoder(m) == want


def test_var_transformer_by_hand():
    m = {"transformer": {"depth": 1, "d_model": 4, "ff_ratio": 2.0, "patch_nums": [1, 2]},
         "vq": {"vocab_size": 8, "c_vae": 2}}

    def scale(n, seen):
        return (n * 4 * 12 + 2 * n * seen * 4 + n * 4 * 4 + 2 * n * 4 * 8) + n * 4 * 8 + n * 2 * 4

    cond = 4 * 24 + 4 * 8
    assert var.transformer(m) == 2 * (scale(1, 1) + scale(4, 5) + cond)


def test_flops_per_image_is_twice_the_macs():
    m = {"transformer": {"depth": 1, "d_model": 4, "ff_ratio": 2.0, "patch_nums": [1, 2]},
         "vq": {"vocab_size": 8, "c_vae": 2, "ch": 2, "ch_mult": [1, 1], "num_res_blocks": 1,
                "using_sa": False, "using_mid_sa": False},
         "reward_towers": {"t": TOWER}}
    parts = var.flops_per_image(m)
    assert parts["generator"] == 2 * var.transformer(m)
    assert parts["total"] == parts["generator"] + parts["decoder"] + parts["rewards"]


def test_fused_qlora_call_by_hand():
    flops, bytes_ = kernels.fused_qlora_call(rows=8, din=4, dout=6, lora_rank=2, es_rank=1)
    assert flops == 2 * (8 * 4 * 6 + 8 * 4 * 2 + 8 * 2 * 6 + 2 * (4 + 6) * 1 * 2)
    assert bytes_ == 2 * 8 * 4 + 4 * 6 + 4 * 6 + 2 * 8 * 6 + 4 * ((4 + 6) * 2 + (4 + 6 + 4) * 1)


def test_sites_to_calls_and_roofline_floor():
    sites = [{"rows_per_image": [1, 4], "din": 4, "dout": 4, "calls_per_image": 6}]
    calls = kernels.fused_qlora(sites, images_per_call=2, model={"lora": {"rank": 2, "es_rank": 1}})
    assert [c_[2] for c_ in calls] == [3.0, 3.0]          # 6 calls an image, 2 images a call
    floor = kernels.least_seconds([(100.0, 10.0, 2.0), (10.0, 100.0, 1.0)], images=3,
                                  peak_flops=10.0, peak_bytes=10.0)
    assert floor["calls"] == 9 and floor["seconds"] == pytest.approx(6 * 10 + 3 * 10)
    assert floor["bound"] == "compute"


def test_decode_attention_calls_grow_with_the_cache():
    sites = [{"heads": 2, "head_dim": 4, "sequences_per_image": 2, "patch_nums": [1, 2], "layers": 3}]
    calls = kernels.decode_attention(sites, images_per_call=1, model={})
    assert calls[0][0] == 2.0 * 2 * 2 * 2 * 1 * 1 * 4 and calls[1][0] == 2.0 * 2 * 2 * 2 * 4 * 5 * 4
    assert calls[1][1] == 2 * 2 * 2 * 4 * (2 * 4 + 2 * 5) and calls[0][2] == 3

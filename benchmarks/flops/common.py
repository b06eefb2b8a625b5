"""Multiply-accumulates of the building blocks both families share, from
shapes alone. A FLOP count is 2 x MACs of every matmul and convolution the
forward pass *requires*: no recomputation (remat), no padding a kernel adds,
nothing from XLA's cost analysis (which counts a loop body once)."""

from __future__ import annotations

from typing import Dict


def dense(rows: int, din: int, dout: int) -> int:
    return rows * din * dout


def conv(h: int, w: int, kh: int, kw: int, cin: int, cout: int, groups: int = 1) -> int:
    """Stride-1 'same' convolution on an h x w output grid."""
    return h * w * kh * kw * (cin // groups) * cout


def softmax_attention(q_tokens: int, kv_tokens: int, width: int) -> int:
    """QK^T and AV over all heads: 2 x q x kv x width."""
    return 2 * q_tokens * kv_tokens * width


def linear_attention(tokens: int, width: int, head_dim: int) -> int:
    """ReLU linear attention: K^T V then Q (K^T V), each tokens x width x head_dim."""
    return 2 * tokens * width * head_dim


def vit_image_tower(t: Dict[str, int]) -> int:
    """CLIP vision tower on one image: patch embed, n_layers pre-LN blocks, projection."""
    grid = t["image_size"] // t["patch_size"]
    n, d = grid * grid + 1, t["d_model"]
    macs = dense(grid * grid, 3 * t["patch_size"] ** 2, d)
    per_layer = dense(n, d, 3 * d) + softmax_attention(n, n, d) + dense(n, d, d) \
        + dense(n, d, t["d_mlp"]) + dense(n, t["d_mlp"], d)
    return macs + t["n_layers"] * per_layer + dense(1, d, t["projection_dim"])


def reward_towers(towers: Dict[str, Dict[str, int]]) -> int:
    """Every tower sees every image once; the text sides are tables built at set-up."""
    return sum(vit_image_tower(t) for t in towers.values())

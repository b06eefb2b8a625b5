"""The least work each Pallas kernel call needs, from its call shape: FLOPs
(2 x MACs) and HBM bytes (every operand and result crosses once). Call shapes
are data in the configuration file (``kernel_sites``); one call covers the
images of one member chunk's sub-batch. Every ``<kernel>(sites, images_per_call,
model)`` returns the calls one image needs."""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

Call = Tuple[float, float, float]  # (flops, bytes, calls per image)


def fused_qlora_call(rows: int, din: int, dout: int, lora_rank: int, es_rank: int) -> Tuple[float, float]:
    """y = x @ dequant(q8) + scale * (x @ a_k) @ b_k, a_k/b_k perturbed by
    rank-``es_rank`` factors. x, y bf16; base int8 + f32 per-channel scale;
    thin factors f32."""
    macs = rows * din * dout + rows * din * lora_rank + rows * lora_rank * dout \
        + 2 * (din + dout) * es_rank * lora_rank
    thin = 4 * ((din + dout) * lora_rank + (din + dout + 2 * lora_rank) * es_rank)
    bytes_ = 2 * rows * din + din * dout + 4 * dout + 2 * rows * dout + thin
    return 2.0 * macs, float(bytes_)


def fused_qlora(sites: Iterable[Dict], images_per_call: int, model: Dict) -> List[Call]:
    lora = model["lora"]
    out = []
    for s in sites:
        rows = s["rows_per_image"] if isinstance(s["rows_per_image"], list) else [s["rows_per_image"]]
        for r in rows:
            f, b = fused_qlora_call(r * images_per_call, s["din"], s["dout"], lora["rank"], lora["es_rank"])
            out.append((f, b, s["calls_per_image"] / images_per_call))
    return out


def decode_attention_call(seqs: int, heads: int, head_dim: int, q: int, kv: int) -> Tuple[float, float]:
    """softmax(q k^T) v over ``kv`` cached positions; q, k, v, out bf16."""
    macs = 2 * seqs * heads * q * kv * head_dim
    bytes_ = 2 * seqs * heads * head_dim * (2 * q + 2 * kv)
    return 2.0 * macs, float(bytes_)


def decode_attention(sites: Iterable[Dict], images_per_call: int, model: Dict) -> List[Call]:
    out = []
    for s in sites:
        seen = 0
        for pn in s["patch_nums"]:
            n = pn * pn
            seen += n
            f, b = decode_attention_call(s["sequences_per_image"] * images_per_call,
                                         s["heads"], s["head_dim"], n, seen)
            out.append((f, b, s["layers"] / images_per_call))
    return out


def least_seconds(calls: Iterable[Call], images: float, peak_flops: float, peak_bytes: float) -> Dict[str, float]:
    """Roofline floor of ``images`` images' worth of calls, and which bound sets it."""
    t = t_flops = t_bytes = n = 0.0
    for flops, bytes_, per_image in calls:
        k = per_image * images
        t += k * max(flops / peak_flops, bytes_ / peak_bytes)
        t_flops += k * flops / peak_flops
        t_bytes += k * bytes_ / peak_bytes
        n += k
    return {"seconds": t, "calls": n, "bound": "compute" if t_flops >= t_bytes else "memory"}

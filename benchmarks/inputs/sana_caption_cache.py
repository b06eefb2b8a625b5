"""Sana's encoded-caption cache at the published shape, drawn from the seed.

A real run hands the trainer ``--encoded_prompts <file>``: Gemma-2 embeddings
of its prompts, ``[P, 300, 2304]`` with an attention mask. No text encoder is
on the machine, so the embeddings are unit normals from the workload seed and
each prompt's mask covers ``words x tokens_per_word`` positions, capped at the
padded length. The file has the keys ``utils/prompt_cache.load_sana_cache``
reads (``prompts``, ``prompt_embeds``, ``prompt_attention_mask``).
"""

from __future__ import annotations

from pathlib import Path
from typing import List

import numpy as np


def mask_lengths(prompts: List[str], tokens: int, tokens_per_word: float) -> List[int]:
    return [min(tokens, max(1, round(len(p.split()) * tokens_per_word))) for p in prompts]


def make(spec: dict, model: dict, seed: int, out_dir: Path, bench_dir: Path) -> List[str]:
    prompts = [ln.strip() for ln in (bench_dir / spec["prompts_file"]).read_text().splitlines()
               if ln.strip()]
    tokens = int(spec["tokens"])
    dim = int(spec.get("caption_dim", model["transformer"]["caption_dim"]))
    rng = np.random.default_rng(seed)
    embeds = rng.standard_normal((len(prompts), tokens, dim), dtype=np.float32)
    mask = np.zeros((len(prompts), tokens), bool)
    for i, n in enumerate(mask_lengths(prompts, tokens, float(spec["tokens_per_word"]))):
        mask[i, :n] = True
    path = out_dir / "captions.npz"
    np.savez(path, prompts=np.asarray(prompts, dtype=object), prompt_embeds=embeds,
             prompt_attention_mask=mask)
    return [spec["flag"], str(path)]

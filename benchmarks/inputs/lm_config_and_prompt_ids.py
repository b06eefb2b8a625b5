"""The two files a user of ``--backend lm_ar`` brings: the model's
``config.json`` (published keys plus this chip's share) and the prompts as
token ids.

The ``config.json``-shaped file is the configuration's ``model.lm`` group with
the VQ decoder's sizes beside it, written out as it stands. openPangu's
tokenizer is not on the machine, so the ids are synthetic: ``words x
tokens_per_word`` ids a prompt (capped at ``max_prompt_len``), drawn from the
workload seed below the image-id range (ids 0 and 1 pad and begin the image).
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import List

import numpy as np

FIRST_TEXT_ID = 2


def make(spec: dict, model: dict, seed: int, out_dir: Path, bench_dir: Path) -> List[str]:
    lm = dict(spec.get("lm", model["lm"]))
    vq = spec.get("vq", {k: v for k, v in model["vq"].items() if k not in ("vocab_size", "image_px")})
    lm["vq"] = vq
    config_path = out_dir / "config.json"
    config_path.write_text(json.dumps(lm, indent=1))

    prompts = [ln.strip() for ln in (bench_dir / spec["prompts_file"]).read_text().splitlines() if ln.strip()]
    img = lm["image_tokens"]
    rng = np.random.default_rng(seed)
    ids = []
    for p in prompts:
        n = min(int(img["max_prompt_len"]), max(1, round(len(p.split()) * float(spec["tokens_per_word"]))))
        ids.append(rng.integers(FIRST_TEXT_ID, int(img["image_id_offset"]), size=n).tolist())
    ids_path = out_dir / "prompt_ids.json"
    ids_path.write_text(json.dumps({"prompts": prompts, "ids": ids}))
    return ["--lm_config", str(config_path), "--prompt_token_ids", str(ids_path)]

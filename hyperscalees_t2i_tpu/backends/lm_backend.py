"""``lm_ar``: a decoder language model as an autoregressive image-token
generator under ES (models/lm.py; the family is the one the ``model_type`` of
``--lm_config`` names: MLA over a latent cache with routed experts,
``qwen3_next``'s Gated DeltaNet layers beside gated attention,
models/lm_hybrid.py, ``granitemoehybrid``'s Mamba-2 mixers beside
attention with a dense FFN, models/lm_ssm.py, or ``mimo_v2_flash``'s
sliding-window attention beside full attention, models/lm_swa.py).

A mechanism's backend, not a model's: sizes come from the ``config.json``-shaped
file ``--lm_config`` names (the model's published keys plus the share of the
deployment this chip holds), prompts are token ids (``--prompt_token_ids``: a
JSON file ``{"prompts": [text], "ids": [[int]]}`` from the model's own
tokenizer; without it ids are synthesized from each prompt's text, which is
enough for seeded weights), and the text is what the CLIP rewards score
against. Per member: prefill the prompt ids into what the family carries (a
latent cache; or recurrent states and a KV cache side by side), sample the
image ids one position a step, decode them through the VQ decoder.

Besides the images, ``generate_p`` returns per-image rows (sampled ids, the
router's choices, counters); ``pop_eval`` carries them beside the reward rows
and :meth:`LMArBackend.step_metrics` reduces them to the step's metrics:

- ``moe/local_assignments`` (a family with a router, as the next two):
  token–expert pairs computed here, all layers;
- ``moe/max_expert_load``: largest count one held expert saw in one call over
  the mean of that call;
- ``moe/pair_route_flip``: share of (cache slot, layer) top-k sets that differ
  between the two halves of an antithetic pair;
- ``lm/state_bytes``, ``lm/kv_cache_bytes``, ``lm/window_cache_bytes`` (a
  family that says what a sequence carries; ``qwen3_next``,
  ``granitemoehybrid`` and ``mimo_v2_flash`` do): bytes of recurrent state +
  conv window, of KV cache over every position, and of a window layer's ring
  of ``sliding_window`` slots, that the step's sequences carry through their
  decode scans — what
  grows with ``pop_size x prompts_per_gen`` whatever the prompt length, and of
  which ``member_batch / pop_size`` is resident at a time;
- ``lm/hc_marginal_err``, ``lm/hc_row_err``, ``lm/hc_offdiag_mass`` (a model
  with hyper-connection streams; ``xing4_0``): the largest distance of a row
  or column sum of any ``H_res`` of the step from 1 (mostly the columns': what
  the Sinkhorn iterations have not converged away at the step's sharpest
  matrix); the same of the row sums alone (rows are normalized last, so this
  is ``hc_eps`` and one rounding in float32 — a coefficient path in lower
  precision shows here); and the mean off-diagonal mass of ``H_res`` over the
  step's tokens and sub-layers — 0 is ``n`` plain residuals side by side,
  ``1 − 1/n`` is uniform mixing of the streams;
- ``probe/*``: what member 0 produced for its first sequences (ids, routing,
  logits at every 16th position, the prompt ids) — the trainer writes them to
  ``probe_epoch<k>.npz`` once and keeps them out of ``metrics.jsonl``.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..lora import init_lora
from ..models import lm
from ..obs import block_if_tracing, span as obs_span
from ..utils.seeding import stable_text_seed
from .base import StepInfo, default_step_info

Pytree = Any
PROBE_SEQUENCES = 4
TOKENS_PER_WORD = 1.3
FIRST_TEXT_ID = 2  # 0 pads, 1 begins the image


@dataclasses.dataclass
class LMBackendConfig:
    model: lm.GeneratorUse  # either family's configuration (lm.config_from_json)
    prompts_txt_path: Optional[str] = None
    prompt_token_ids_path: Optional[str] = None
    base_quant: str = "off"
    decode_images: bool = True
    lora_r: int = 8
    lora_alpha: float = 16.0
    seed_params: int = 0


def synthetic_token_ids(text: str, cfg: lm.GeneratorUse) -> List[int]:
    """Stand-in for a tokenizer that is not on the machine: ``words x 1.3``
    ids (capped at ``max_prompt_len``) below the image-id range, drawn from a
    hash of the text so that they are stable across processes."""
    n = min(cfg.max_prompt_len, max(1, round(len(text.split()) * TOKENS_PER_WORD)))
    rng = np.random.default_rng(stable_text_seed(text))
    return rng.integers(FIRST_TEXT_ID, cfg.image_id_offset, size=n).tolist()


class LMArBackend:
    def __init__(self, cfg: LMBackendConfig, params: Optional[Pytree] = None):
        self.cfg = cfg
        self.name = "lm_ar"
        self.params = params
        self._spec = cfg.model.lora_spec(rank=cfg.lora_r, alpha=cfg.lora_alpha)
        with obs_span("load_prompts"):
            self.prompts, ids = self._load_prompts()
        m = cfg.model
        if not all(0 < len(row) <= m.max_prompt_len and all(0 <= t < m.vocab_rows_held for t in row) for row in ids):
            raise ValueError(f"prompt ids must be 1..{m.max_prompt_len} ids of the {m.vocab_rows_held} rows held")
        padded = np.zeros((len(ids), m.max_prompt_len), np.int32)
        for i, row in enumerate(ids):
            padded[i, : len(row)] = row
        self._ids = jnp.asarray(padded)
        self._lens = jnp.asarray([len(row) for row in ids], jnp.int32)

    def _load_prompts(self) -> Tuple[List[str], List[List[int]]]:
        cfg = self.cfg
        if cfg.prompt_token_ids_path:
            data = json.loads(Path(cfg.prompt_token_ids_path).read_text())
            return list(data["prompts"]), [list(map(int, row)) for row in data["ids"]]
        prompts = ["a photo of a cat"]
        if cfg.prompts_txt_path and Path(cfg.prompts_txt_path).exists():
            lines = Path(cfg.prompts_txt_path).read_text().splitlines()
            prompts = [l.strip() for l in lines if l.strip() and not l.strip().startswith("#")] or prompts
        return prompts, [synthetic_token_ids(p, cfg.model) for p in prompts]

    def setup(self) -> None:
        if self.params is None:
            # ONE seeded-init program (as Sana's); with --base_quant int8 each
            # kernel is quantized inside it (models/lm.init_lm says why)
            with obs_span("init_params"):
                self.params = block_if_tracing(jax.jit(
                    lambda key: lm.init_lm(key, self.cfg.model, self.cfg.base_quant)
                )(jax.random.PRNGKey(self.cfg.seed_params)))

    def init_theta(self, key: jax.Array) -> Pytree:
        return init_lora(key, self.params, self._spec)

    @property
    def lora_scale(self) -> float:
        return self._spec.scale

    @property
    def num_items(self) -> int:
        return len(self.prompts)

    @property
    def texts(self) -> List[str]:
        return self.prompts

    def step_info(self, seed: int, num_unique: int, repeats: int) -> StepInfo:
        return default_step_info(seed, self.num_items, num_unique, repeats, self.prompts)

    @property
    def frozen(self) -> Pytree:
        return {"params": self.params, "prompt_ids": self._ids, "prompt_len": self._lens}

    def generate_p(
        self,
        frozen: Pytree,
        theta: Pytree,
        flat_ids: jax.Array,
        key: jax.Array,
        item_index: Optional[jax.Array] = None,
    ):
        """[B] prompt indices → (images [B, H, W, 3] in [0, 1], per-image rows)."""
        images, rows = lm.generate(
            frozen["params"], self.cfg.model, frozen["prompt_ids"][flat_ids], frozen["prompt_len"][flat_ids],
            key, lora=theta, lora_scale=self.lora_scale, decode=self.cfg.decode_images, item_index=item_index,
        )
        rows["prompt_ids"] = frozen["prompt_ids"][flat_ids]
        rows["prompt_len"] = frozen["prompt_len"][flat_ids]
        return images, rows

    def generate(self, theta: Pytree, flat_ids: jax.Array, key: jax.Array) -> jax.Array:
        return self.generate_p(self.frozen, theta, flat_ids, key)[0]

    def step_metrics(self, rows: Dict[str, jax.Array], pop: int, antithetic: bool) -> Dict[str, jax.Array]:
        """The generator's rows of a step (each ``[pop, B, ...]``) → metrics.
        A family without a router (``granitemoehybrid`` with no experts)
        returns no routing rows, and its step has no ``moe/*`` metrics and no
        ``probe/topk``."""
        out = {}
        if "topk" in rows:
            topk = rows["topk"]
            out["moe/local_assignments"] = rows["assign"].sum().astype(jnp.float32)
            out["moe/max_expert_load"] = rows["load"].max()
            half = pop // 2
            if antithetic and half and topk.shape[3]:
                seen = (topk[:half] >= 0).all(-1)                              # slots that hold a token
                differ = (topk[:half] != topk[half: 2 * half]).any(-1) & seen
                out["moe/pair_route_flip"] = differ.sum() / jnp.maximum(seen.sum(), 1)
            else:
                out["moe/pair_route_flip"] = jnp.float32(0.0)
        for kind in ("state", "kv_cache", "window_cache"):
            if f"carried/{kind}" in rows:
                out[f"lm/{kind}_bytes"] = rows[f"carried/{kind}"].sum()
        if "hc_err" in rows:
            out["lm/hc_marginal_err"] = rows["hc_err"].max()
            out["lm/hc_row_err"] = rows["hc_row"].max()
            out["lm/hc_offdiag_mass"] = rows["hc_off"].sum() / jnp.maximum(rows["hc_n"].sum(), 1.0)
        n = min(PROBE_SEQUENCES, rows["ids"].shape[1])
        for k in ("ids", "topk", "logits", "prompt_ids", "prompt_len"):
            if k in rows:
                out[f"probe/{k}"] = rows[k][0, :n]
        return out

"""Jit-able reward computation over image arrays.

Reward semantics (exact contract from ``/root/reference/rewards.py:66-268``):

- CLIP-B/32 cosine sims against three texts — the aesthetic text, the image's
  own prompt, and the negative/artifact text — each mapped ``(s+1)/2`` into
  [0,1]; ``no_artifacts = 1 − sim(negative)``.
- PickScore v1: ``exp(logit_scale) · dot(text̂, imĝ)`` with the CLIP-H towers.
- ``combined = w_aes·aes + w_align·align + w_noart·noart + w_pick·pick`` with
  default weights (0.3, 0.3, 0.2, 0.2) (``rewards.py:171``).

Unlike the reference (one reward-model call per image), everything here is
batched: ``compute_rewards_batch`` scores ``[B]`` images against per-image
prompt indices in one pass and is safe to call inside the jitted ES step.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..models import clip as clip_mod
from ..obs import scope as obs_scope

Params = Dict[str, Any]

# Default reward texts (rewards.py:23-27).
AESTHETIC_TEXT = "a high quality, professional, beautiful, aesthetically pleasing image"
NEGATIVE_TEXT = (
    "blurry, low resolution, noisy, pixelated, washed out colors, oversaturated "
)


@dataclasses.dataclass(frozen=True)
class RewardWeights:
    aesthetic: float = 0.3
    align: float = 0.3
    no_artifacts: float = 0.2
    pickscore: float = 0.2


def _normalize(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    n = jnp.linalg.norm(x.astype(jnp.float32), axis=-1, keepdims=True)
    return x / jnp.maximum(n, eps)


def clip_text_embed_table(
    params: Params,
    cfg: clip_mod.CLIPConfig,
    input_ids: jax.Array,  # [M+2, L] — rows: prompts..., aesthetic, negative
    eot_index: Optional[jax.Array] = None,
    attention_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Precompute the normalized CLIP text table once per run → [M+2, P]."""
    emb = clip_mod.text_features(params, cfg, input_ids, eot_index, attention_mask)
    return _normalize(emb)


def pickscore_text_embeds(
    params: Params,
    cfg: clip_mod.CLIPConfig,
    input_ids: jax.Array,  # [M, L]
    eot_index: Optional[jax.Array] = None,
    attention_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Normalized PickScore text embeddings per prompt → [M, P]."""
    emb = clip_mod.text_features(params, cfg, input_ids, eot_index, attention_mask)
    return _normalize(emb)


def compute_rewards_batch(
    clip_params: Params,
    clip_cfg: clip_mod.CLIPConfig,
    images: jax.Array,  # [B, H, W, 3] in [0, 1]
    clip_text_table: jax.Array,  # [M+2, P] normalized (prompts, aesthetic, negative)
    prompt_ids: jax.Array,  # [B] int — index of each image's prompt in the table
    weights: RewardWeights = RewardWeights(),
    pick_params: Optional[Params] = None,
    pick_cfg: Optional[clip_mod.CLIPConfig] = None,
    pick_text_embeds: Optional[jax.Array] = None,  # [M, P2] normalized
) -> Dict[str, jax.Array]:
    """Per-image rewards — every value is a ``[B]`` float32 array.

    When the PickScore tower is omitted, ``pickscore`` is zeros (same
    degradation as ``rewards.py:239-241``).
    """
    M = clip_text_table.shape[0] - 2
    # device-time scopes (obs/xla_cost.TOP_SCOPES / INNER_SCOPES): names only
    with obs_scope("reward"):
        with jax.named_scope("preprocess"):
            pixels = clip_mod.preprocess_images(images, clip_cfg)
        with jax.named_scope("clip_b"):
            img = _normalize(clip_mod.image_features(clip_params, clip_cfg, pixels))  # [B, P]

        with jax.named_scope("score"):
            aes_t = clip_text_table[M]
            neg_t = clip_text_table[M + 1]
            own_t = clip_text_table[prompt_ids]  # [B, P]

            to01 = lambda s: (s + 1.0) / 2.0
            clip_aesthetic = to01(img @ aes_t)
            clip_text = to01(jnp.sum(img * own_t, axis=-1))
            no_artifacts = 1.0 - to01(img @ neg_t)

        if pick_params is not None and pick_text_embeds is not None and pick_cfg is not None:
            with jax.named_scope("preprocess"):
                ppix = clip_mod.preprocess_images(images, pick_cfg)
            with jax.named_scope("clip_h"):
                pimg = _normalize(clip_mod.image_features(pick_params, pick_cfg, ppix))
            with jax.named_scope("score"):
                pown = pick_text_embeds[prompt_ids]
                pickscore = jnp.exp(pick_params["logit_scale"].astype(jnp.float32)) * jnp.sum(
                    pimg * pown, axis=-1
                )
        else:
            pickscore = jnp.zeros(images.shape[0], jnp.float32)

        with jax.named_scope("score"):
            combined = (
                weights.aesthetic * clip_aesthetic
                + weights.align * clip_text
                + weights.no_artifacts * no_artifacts
                + weights.pickscore * pickscore
            )
            return {
                "clip_aesthetic": clip_aesthetic.astype(jnp.float32),
                "clip_text": clip_text.astype(jnp.float32),
                "no_artifacts": no_artifacts.astype(jnp.float32),
                "pickscore": pickscore.astype(jnp.float32),
                "combined": combined.astype(jnp.float32),
            }


class RewardSuite:
    """The trainer-facing reward object.

    Callable as ``suite(images, prompt_ids)`` for eval/one-off use, but the
    trainer uses the pure form ``suite.apply(frozen, images, prompt_ids)``
    with ``suite.frozen`` threaded through the jitted step as an argument —
    multi-GB CLIP towers must never be captured as HLO constants
    (backends/base.py rationale).
    """

    def __init__(
        self,
        clip_params: Params,
        clip_cfg: clip_mod.CLIPConfig,
        clip_text_table: jax.Array,
        weights: RewardWeights = RewardWeights(),
        pick_params: Optional[Params] = None,
        pick_cfg: Optional[clip_mod.CLIPConfig] = None,
        pick_text_embeds: Optional[jax.Array] = None,
    ):
        self.clip_cfg = clip_cfg
        self.pick_cfg = pick_cfg
        self.weights = weights
        self.frozen: Dict[str, Any] = {
            "clip_params": clip_params,
            "clip_text_table": clip_text_table,
        }
        if pick_params is not None and pick_text_embeds is not None and pick_cfg is not None:
            self.frozen["pick_params"] = pick_params
            self.frozen["pick_text_embeds"] = pick_text_embeds

    def apply(self, frozen: Dict[str, Any], images: jax.Array, prompt_ids: jax.Array) -> Dict[str, jax.Array]:
        return compute_rewards_batch(
            frozen["clip_params"], self.clip_cfg, images, frozen["clip_text_table"],
            prompt_ids, weights=self.weights,
            pick_params=frozen.get("pick_params"), pick_cfg=self.pick_cfg,
            pick_text_embeds=frozen.get("pick_text_embeds"),
        )

    def __call__(self, images: jax.Array, prompt_ids: jax.Array) -> Dict[str, jax.Array]:
        return self.apply(self.frozen, images, prompt_ids)


def make_clip_reward_fn(
    clip_params: Params,
    clip_cfg: clip_mod.CLIPConfig,
    clip_text_table: jax.Array,
    weights: RewardWeights = RewardWeights(),
    pick_params: Optional[Params] = None,
    pick_cfg: Optional[clip_mod.CLIPConfig] = None,
    pick_text_embeds: Optional[jax.Array] = None,
) -> RewardSuite:
    """Bind the reward towers into the trainer's ``RewardFn`` contract."""
    return RewardSuite(
        clip_params, clip_cfg, clip_text_table, weights=weights,
        pick_params=pick_params, pick_cfg=pick_cfg, pick_text_embeds=pick_text_embeds,
    )


def tokenize_with_hf(prompts: Sequence[str], name: str = "openai/clip-vit-base-patch32") -> Tuple[Any, Any, Any]:
    """Host-side tokenization via transformers when available/cached.

    Returns (input_ids [N, L] int32, eot_index [N], attention_mask [N, L]).
    Falls back to a deterministic hash tokenizer when the HF tokenizer can't
    be loaded (e.g. zero-egress environments without a cache) — fine for
    smoke tests, NOT for scoring parity with the reference.
    """
    import numpy as np

    try:  # pragma: no cover - environment dependent
        from transformers import AutoTokenizer

        tok = AutoTokenizer.from_pretrained(name)
        out = tok(list(prompts), padding="max_length", truncation=True, max_length=77, return_tensors="np")
        ids = out["input_ids"].astype(np.int32)
        mask = out["attention_mask"].astype(bool)
        eot = ids.argmax(axis=-1).astype(np.int32)
        return jnp.asarray(ids), jnp.asarray(eot), jnp.asarray(mask)
    except Exception:
        from ..utils.seeding import stable_text_seed

        L = 77
        ids = np.ones((len(prompts), L), np.int32)
        for i, p in enumerate(prompts):
            # stable across interpreters (hash() is salted; multi-host desync)
            toks = [
                (stable_text_seed(f"{p}\x00{j}") % 40000) + 2
                for j in range(min(len(p.split()), L - 2))
            ]
            ids[i, 1 : 1 + len(toks)] = toks
            ids[i, 1 + len(toks)] = 49407  # EOT = max id in CLIP vocab
        eot = ids.argmax(axis=-1).astype(np.int32)
        mask = np.ones((len(prompts), L), bool)
        return jnp.asarray(ids), jnp.asarray(eot), jnp.asarray(mask)

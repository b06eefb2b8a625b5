"""Sana backend: one-step (TrigFlow) and multi-step (pipeline) generation.

Role parity with the reference ``SanaBackend`` (``es_backend.py:96-292``):
prompt-cache load/encode, LoRA spec on the transformer, flat batched
generation. TPU-native differences: params are frozen pytrees, generation +
decode is one pure function, and the prompt-embedding cache is an array table
indexed *inside* jit (no per-epoch host transfers).
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..lora import LoRASpec, init_lora
from ..models import dcae, sana
from ..obs import block_if_tracing, scope as obs_scope, span as obs_span
from .base import StepInfo, default_step_info

Pytree = Any


@dataclasses.dataclass
class SanaBackendConfig:
    """Mirror of the reference's ``SanaConfig`` dataclass (es_backend.py:64-93),
    minus torch-isms (compile flags → jit is always on; device strings → mesh)."""

    backend_mode: str = "one_step"  # "one_step" | "pipeline"
    model: sana.SanaConfig = dataclasses.field(default_factory=sana.SanaConfig)
    vae: dcae.DCAEConfig = dataclasses.field(default_factory=dcae.DCAEConfig)
    prompts_txt_path: Optional[str] = None
    encoded_prompt_path: Optional[str] = None
    guidance_scale: float = 1.0
    num_inference_steps: int = 2  # pipeline mode
    width_latent: int = 32
    height_latent: int = 32
    decode_images: bool = True
    lora_r: int = 8
    lora_alpha: float = 16.0
    lora_targets: Tuple[str, ...] = sana.SANA_LORA_TARGETS
    seed_params: int = 0


class SanaBackend:
    def __init__(self, cfg: SanaBackendConfig, params: Optional[Pytree] = None, vae_params: Optional[Pytree] = None):
        self.cfg = cfg
        self.name = f"sana_{cfg.backend_mode}"
        self.params = params
        self.vae_params = vae_params
        self.prompts: List[str] = []
        self.prompt_embeds: Optional[jax.Array] = None  # [P, Ltxt, cap_dim]
        self.prompt_mask: Optional[jax.Array] = None  # [P, Ltxt]
        self._spec = LoRASpec(rank=cfg.lora_r, alpha=cfg.lora_alpha, targets=cfg.lora_targets)

    # -- setup ---------------------------------------------------------------
    def setup(self) -> None:
        need_vae = self.vae_params is None and self.cfg.decode_images
        if self.params is None or need_vae:
            # random weights come from ONE compiled program: at the 1.6B
            # geometry an op-by-op eager init is ~40 separately compiled
            # programs, and this one lands in the persistent compile cache
            need_params = self.params is None

            def init(key):
                kt, kv = jax.random.split(key)
                out = {}
                if need_params:
                    out["params"] = sana.init_sana(kt, self.cfg.model)
                if need_vae:
                    out["vae"] = dcae.init_decoder(kv, self.cfg.vae)
                return out

            # one program, so one span: the DiT's and the decoder's weights
            with obs_span("init_params"):
                out = block_if_tracing(jax.jit(init)(jax.random.PRNGKey(self.cfg.seed_params)))
            self.params = out.get("params", self.params)
            self.vae_params = out.get("vae", self.vae_params)
        if self.prompt_embeds is None:
            with obs_span("load_prompts"):
                self._load_prompts()
                block_if_tracing((self.prompt_embeds, self.prompt_mask))

    def _load_prompts(self) -> None:
        """Load an encoded-prompt cache (reference ``_load_or_encode_prompts``,
        es_backend.py:112-171). Supports the reference's torch ``.pt`` payload
        {"prompts", "prompt_embeds", "prompt_attention_mask"} and our ``.npz``."""
        from ..utils.prompt_cache import load_cache

        path = self.cfg.encoded_prompt_path
        if path and Path(path).exists():
            # unified content-stamped loader (serving tier): byte-identical
            # caches share one warm in-process entry across engines/backends
            data = load_cache(path, "sana")
            self.prompt_cache_sha = data["content_sha256"]
            self.prompts = data["prompts"]
            self.prompt_embeds = jnp.asarray(data["prompt_embeds"])
            self.prompt_mask = jnp.asarray(data["prompt_attention_mask"]).astype(bool)
            return
        # No cache: synthesize deterministic placeholder embeddings from the
        # prompt list (smoke/bench mode — a real run supplies the cache, same
        # as the reference requires a text encoder only at cache-build time).
        prompts = ["a photo of a cat"]
        if self.cfg.prompts_txt_path and Path(self.cfg.prompts_txt_path).exists():
            lines = Path(self.cfg.prompts_txt_path).read_text().splitlines()
            prompts = [l.strip() for l in lines if l.strip() and not l.strip().startswith("#")] or prompts
        self.prompts = prompts
        L = 32
        embeds = []
        from ..utils.seeding import stable_text_seed

        for i, p in enumerate(prompts):
            # stable across processes/restarts (hash() is salted per interpreter)
            k = jax.random.fold_in(jax.random.PRNGKey(1234), stable_text_seed(p))
            embeds.append(jax.random.normal(k, (L, self.cfg.model.caption_dim), jnp.float32))
        self.prompt_embeds = jnp.stack(embeds)
        self.prompt_mask = jnp.ones((len(prompts), L), bool)

    # -- protocol ------------------------------------------------------------
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
        fz: Dict[str, Any] = {
            "params": self.params,
            "prompt_embeds": self.prompt_embeds,
            "prompt_mask": self.prompt_mask,
        }
        if self.vae_params is not None:
            fz["vae"] = self.vae_params
        return fz

    def generate_p(
        self,
        frozen: Pytree,
        theta: Pytree,
        flat_ids: jax.Array,
        key: jax.Array,
        item_index: Optional[jax.Array] = None,
    ) -> jax.Array:
        """[B] prompt indices → images [B, H, W, 3] (or raw latents when
        ``decode_images=False``, for latent-space reward experiments).

        Pure in ``frozen``/``theta``; ``item_index`` carries each image's
        *global* batch position so per-image noise keys are invariant to how
        the batch is chunked or sharded over the ``data`` mesh axis."""
        cfg = self.cfg
        embeds = frozen["prompt_embeds"][flat_ids]
        mask = frozen["prompt_mask"][flat_ids]
        hw = (cfg.height_latent, cfg.width_latent)
        with obs_scope("generate"):
            if cfg.backend_mode == "pipeline":
                latents = sana.multistep_generate(
                    frozen["params"], cfg.model, embeds, mask, key,
                    guidance_scale=cfg.guidance_scale, num_steps=cfg.num_inference_steps,
                    latent_hw=hw, lora=theta, lora_scale=self.lora_scale,
                    item_index=item_index,
                )
            else:
                latents = sana.one_step_generate(
                    frozen["params"], cfg.model, embeds, mask, key,
                    guidance_scale=cfg.guidance_scale, latent_hw=hw,
                    lora=theta, lora_scale=self.lora_scale,
                    item_index=item_index,
                )
        if not cfg.decode_images:
            return latents
        with obs_scope("decode"):
            return dcae.decode(frozen["vae"], cfg.vae, latents / cfg.vae.scaling_factor)

    def generate(self, theta: Pytree, flat_ids: jax.Array, key: jax.Array) -> jax.Array:
        return self.generate_p(self.frozen, theta, flat_ids, key)

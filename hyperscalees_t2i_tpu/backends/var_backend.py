"""VAR backend: class-conditional ES over the next-scale AR generator.

Role parity with the reference ``VarBackend`` (``/root/reference/
es_backend.py:319-450``): a class *pool* is the catalog (instead of prompts),
per-epoch unique class sampling, grouped repeats, LoRA on the transformer.
The reference's ``es_model.var = transformer`` aliasing dance
(es_backend.py:344-368) disappears entirely — params are pytrees and the
adapter is an input.

Class names come from a labels file (one name per line, the reference
downloads the same list at ``utills.py:219-266``) or fall back to ``class_{i}``
so zero-egress environments still run; prompts for text-reward lookup are
"a photo of {name}" (utills.py:267-275).
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..lora import LoRASpec, init_lora
from ..models import var as var_mod
from ..obs import block_if_tracing, span as obs_span
from .base import StepInfo, default_step_info

Pytree = Any


@dataclasses.dataclass
class VarBackendConfig:
    """Mirror of the reference ``VarConfig`` dataclass (es_backend.py:299-316)."""

    model: var_mod.VARConfig = dataclasses.field(default_factory=var_mod.VARConfig)
    class_pool: Optional[Tuple[int, ...]] = None  # None → all classes
    labels_path: Optional[str] = None
    cfg_scale: float = 4.0
    top_k: int = 900
    top_p: float = 0.96
    decode_images: bool = True
    lora_r: int = 8
    lora_alpha: float = 16.0
    lora_targets: Tuple[str, ...] = var_mod.VAR_LORA_TARGETS
    seed_params: int = 0


def load_class_names(num_classes: int, labels_path: Optional[str]) -> List[str]:
    """Class names for reward prompts. An explicit ``labels_path`` wins; the
    full-ImageNet geometry otherwise tries the shared download-and-cache
    helper (reference ``get_imagenet_labels``, utills.py:219-267) and falls
    back to ``class_{i}`` placeholders only for toy class counts or offline
    hosts (loudly — wrong names would silently train against wrong text)."""
    if labels_path and Path(labels_path).exists():
        names = [l.strip() for l in Path(labels_path).read_text().splitlines() if l.strip()]
        if len(names) >= num_classes:
            return names[:num_classes]
    if num_classes == 1000:
        from ..utils.imagenet_labels import get_imagenet_labels

        try:
            return get_imagenet_labels(labels_path=None)[:num_classes]
        except (RuntimeError, FileNotFoundError) as e:
            print(f"[var] WARNING: {e}; using class_<i> placeholder names", flush=True)
    return [f"class_{i}" for i in range(num_classes)]


class VarBackend:
    def __init__(self, cfg: VarBackendConfig, params: Optional[Pytree] = None):
        self.cfg = cfg
        self.name = "var"
        self.params = params
        self._spec = LoRASpec(rank=cfg.lora_r, alpha=cfg.lora_alpha, targets=cfg.lora_targets)
        pool = cfg.class_pool or tuple(range(cfg.model.num_classes))
        self.class_pool: Tuple[int, ...] = tuple(int(c) for c in pool)
        with obs_span("load_prompts"):
            names = load_class_names(cfg.model.num_classes, cfg.labels_path)
        # catalog item i ↔ class self.class_pool[i]; prompt text for rewards
        self.prompts = [f"a photo of {names[c]}" for c in self.class_pool]
        self._pool_arr = jnp.asarray(self.class_pool, jnp.int32)

    def setup(self) -> None:
        if self.params is None:
            with obs_span("init_params"):
                self.params = block_if_tracing(var_mod.init_var(
                    jax.random.PRNGKey(self.cfg.seed_params), self.cfg.model
                ))

    def init_theta(self, key: jax.Array) -> Pytree:
        return init_lora(key, self.params, self._spec)

    @property
    def lora_scale(self) -> float:
        return self._spec.scale

    @property
    def num_items(self) -> int:
        return len(self.class_pool)

    @property
    def texts(self) -> List[str]:
        return self.prompts

    def step_info(self, seed: int, num_unique: int, repeats: int) -> StepInfo:
        """Per-epoch unique class sampling (reference ``_sample_classes_unique``,
        es_backend.py:377-396) over catalog indices."""
        return default_step_info(seed, self.num_items, num_unique, repeats, self.prompts)

    @property
    def frozen(self) -> Pytree:
        return {"params": self.params, "pool": self._pool_arr}

    def generate_p(
        self,
        frozen: Pytree,
        theta: Pytree,
        flat_ids: jax.Array,
        key: jax.Array,
        item_index: Optional[jax.Array] = None,
    ) -> jax.Array:
        labels = frozen["pool"][flat_ids]
        return var_mod.generate(
            frozen["params"],
            self.cfg.model,
            labels,
            key,
            cfg_scale=self.cfg.cfg_scale,
            top_k=self.cfg.top_k,
            top_p=self.cfg.top_p,
            lora=theta,
            lora_scale=self.lora_scale,
            decode=self.cfg.decode_images,
            item_index=item_index,
        )

    def generate(self, theta: Pytree, flat_ids: jax.Array, key: jax.Array) -> jax.Array:
        return self.generate_p(self.frozen, theta, flat_ids, key)

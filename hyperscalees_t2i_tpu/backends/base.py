"""The backend protocol: "same ES training across generators".

Reference contract: ``ESBackend`` with ``init_and_attach_lora``,
``collect_lora_params``, ``step_sampling_info``, ``generate_flat``,
``save_lora`` (``/root/reference/es_backend.py:16-57``). The TPU-native
protocol reshapes that around functional purity:

- ``setup()`` loads/initializes frozen model params and the prompt catalog
  (the reference's prompt-cache load/encode step);
- ``init_theta(key)`` returns the LoRA adapter pytree (the evolved θ);
- ``step_info(seed)`` does the host-side prompt/class subset sampling
  (``step_sampling_info``, es_backend.py:234-263);
- ``frozen`` exposes every non-evolved device array (model params, VAE
  params, prompt-embedding tables) as one pytree;
- ``generate_p(frozen, theta, flat_ids, key)`` is a *pure jit-able function*:
  LoRA-adapted generation for one population member over the epoch's flat
  prompt batch → images ``[B, H, W, 3]`` in [0, 1]. The trainer vmaps/maps it
  over the population inside one compiled program — the reference instead
  mutates live module weights per candidate in Python (unifed_es.py:159-163).

Why ``frozen`` is an explicit argument rather than captured state: a jitted
closure over multi-GB frozen params bakes them into the HLO as *constants*
(XLA "large amount of constants captured during lowering"), exploding
lowering/compile time at flagship geometry. Threading them as arguments keeps
the program small and the params device-resident exactly once.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Protocol, runtime_checkable

import jax

Pytree = Any


@dataclasses.dataclass(frozen=True)
class StepInfo:
    """One epoch's sampling plan (host-side, static per step).

    ``unique_ids``: the m sampled prompt/class indices.
    ``flat_ids``: grouped repeats — ``repeats`` copies of ``unique_ids`` in
    order (reference ``repeat_batches``, utills.py:376-379).
    ``texts``: display/prompt strings for logging and reward text lookup.
    """

    unique_ids: List[int]
    flat_ids: List[int]
    repeats: int
    texts: List[str]


@runtime_checkable
class ESBackend(Protocol):
    name: str

    def setup(self) -> None:
        ...

    def init_theta(self, key: jax.Array) -> Pytree:
        ...

    @property
    def lora_scale(self) -> float:
        ...

    @property
    def num_items(self) -> int:
        """Size of the prompt/class catalog."""
        ...

    @property
    def texts(self) -> List[str]:
        """Prompt text per catalog item (class names for class-conditional)."""
        ...

    def step_info(self, seed: int, num_unique: int, repeats: int) -> StepInfo:
        ...

    @property
    def frozen(self) -> Pytree:
        """All non-evolved device arrays, threaded through the jitted step as
        an explicit argument (never captured as HLO constants)."""
        ...

    def generate_p(
        self,
        frozen: Pytree,
        theta: Pytree,
        flat_ids: jax.Array,
        key: jax.Array,
        item_index: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Pure function: [B] catalog indices → images [B, H, W, 3] in [0,1].
        Reads arrays only from ``frozen``/``theta`` args (static config aside).
        In a step program ``theta`` is a population member's: its matrix leaves
        arrive as ``lora.FactoredDelta`` (``nn.dense``/``conv2d`` consume them;
        code that reads a leaf itself builds it with ``lora.effective_factor``).
        ``item_index`` is each image's *global* batch position (default
        ``arange(B)``): per-image noise keys must fold it in so outputs are
        invariant to batch chunking and data-axis sharding."""
        ...

    def generate(self, theta: Pytree, flat_ids: jax.Array, key: jax.Array) -> jax.Array:
        """Convenience: ``generate_p(self.frozen, ...)`` for eval/one-off use."""
        ...


RewardFn = Callable[[jax.Array, jax.Array], Dict[str, jax.Array]]
"""(images [B,H,W,3], prompt_ids [B]) → dict of per-image reward arrays [B];
must contain key 'combined'. Pure/jit-able. Reward objects may additionally
expose ``.frozen`` (param pytree) and ``.apply(frozen, images, ids)`` so the
trainer can thread their params as jit arguments too."""


def generate_parts(backend: Any):
    """(pure_fn, frozen) for any backend — adapts plain closures (toy/test
    backends) into the frozen-argument calling convention. ``item_index`` is
    forwarded when the plain ``generate`` accepts it; otherwise the backend
    cannot honor the data-sharding invariance contract and only 1-device
    data layouts are safe."""
    if hasattr(backend, "generate_p") and hasattr(backend, "frozen"):
        return backend.generate_p, backend.frozen
    import inspect

    if "item_index" in inspect.signature(backend.generate).parameters:
        return (
            lambda fz, theta, ids, key, item_index=None: backend.generate(
                theta, ids, key, item_index=item_index
            )
        ), {}
    fn = lambda fz, theta, ids, key, item_index=None: backend.generate(theta, ids, key)
    # pop_eval refuses to shard this backend's batch over the data axis —
    # per-image noise would depend on the shard-local position.
    fn.ignores_item_index = True
    return fn, {}


def reward_parts(reward_fn: Any):
    """(pure_fn, frozen) for any reward callable — same adaptation."""
    if hasattr(reward_fn, "apply") and hasattr(reward_fn, "frozen"):
        return reward_fn.apply, reward_fn.frozen
    return (lambda fz, images, ids: reward_fn(images, ids)), {}


def make_frozen(backend: Any, reward_fn: Any) -> Dict[str, Pytree]:
    """The jit-argument pytree of every frozen array the step reads."""
    return {"gen": generate_parts(backend)[1], "reward": reward_parts(reward_fn)[1]}


def default_step_info(
    seed: int, total: int, num_unique: int, repeats: int, texts: Optional[List[str]] = None
) -> StepInfo:
    """Shared sampling logic used by the concrete backends."""
    from ..es.sampling import repeat_batches, sample_indices_unique

    unique = sample_indices_unique(seed, total, min(num_unique, total))
    flat = repeat_batches(unique, repeats)
    t = [texts[i] for i in unique] if texts else [str(i) for i in unique]
    return StepInfo(unique_ids=unique, flat_ids=flat, repeats=repeats, texts=t)

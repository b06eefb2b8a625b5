"""EGGROLL low-rank ES noise engine — pure JAX, factored, population-batched.

Behavioral contract comes from the reference's ``EggRollNoiser``
(``/root/reference/utills.py:14-136``):

- every *matrix-shaped* (2D) trainable parameter of shape ``(m, n)`` receives a
  low-rank perturbation ``E = (1/sqrt(r)) * A @ B^T`` with ``A ~ N(0,1)^{m×r}``,
  ``B ~ N(0,1)^{n×r}``;
- parameters of any other rank receive dense Gaussian noise;
- antithetic sampling builds the population ``[e_0..e_{h-1}, -e_0..-e_{h-1}]``
  for even pop sizes and appends one extra unpaired *positive* sample for odd
  pop sizes (``utills.py:88-104``);
- the update is ``θ' = θ + (lr_scale · σ) · mean_k(f_k · ε_k)`` — note the
  *code* behavior is ``lr = lr_scale * sigma`` (``utills.py:131``), which we
  reproduce (SURVEY.md §7.4).

TPU-first redesign (NOT a port):

- parameters live in a *pytree* ``theta`` (the LoRA adapter tree), never a flat
  torch vector; flattening only happens for diagnostics.
- noise is kept in **factored form** — per 2D leaf we store only
  ``U: [base, m, r]`` and ``V: [base, n, r]`` where ``base ≈ pop/2`` under
  antithetic pairing. A full materialized population of perturbations is never
  allocated. This is the actual point of EGGROLL: factors cost ``r(m+n)`` per
  member instead of ``m·n``.
- a member's perturbed parameters ``θ_k = θ + σ·s_k·U_b V_bᵀ/√r`` are built
  *inside* the (vmapped / shard_mapped) evaluation, one member per lane, and
  stay factored up to the dense that consumes them
  (:func:`factored_member_theta`); :func:`perturb_member` is the plain
  reference that materializes them.
- the ES update contracts fitness into the factors with one batched einsum per
  leaf: ``Δ = Σ_b c_b · U_b V_bᵀ / (n·√r)`` with ``c_b = Σ_{k: base(k)=b} f_k s_k``
  (a segment-sum). No ``[pop, D]`` matrix ever exists.

All functions are jit-safe; population size / antithetic flag / rank are static.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


@dataclasses.dataclass(frozen=True)
class EggRollConfig:
    """Static ES hyperparameters (mirror of reference ``EggRollNoiser.__init__``)."""

    sigma: float = 0.01
    lr_scale: float = 1.0
    rank: int = 1
    antithetic: bool = True
    # Storage dtype of the factored noise (``U``/``V``/``E`` — the largest
    # ES-state arrays). "bfloat16" halves their bytes; every contraction that
    # consumes them upcasts to f32 first, so only the *stored* factors lose
    # precision (one rounding of N(0,1) draws), never the accumulation.
    noise_dtype: str = "float32"

    def __post_init__(self) -> None:
        if self.noise_dtype not in ("float32", "f32", "bfloat16", "bf16"):
            raise ValueError(
                f"noise_dtype must be float32 or bfloat16, got {self.noise_dtype!r}"
            )

    @property
    def lr(self) -> float:
        # Reference code behavior: lr = lr_scale * sigma (utills.py:131),
        # even though the adjacent comment claims lr_scale / sigma.
        return self.lr_scale * self.sigma

    @property
    def noise_jnp_dtype(self):
        from ..utils.pytree import resolve_float_dtype

        return resolve_float_dtype(self.noise_dtype)


class LowRankNoise(NamedTuple):
    """Factored noise for one 2D leaf: eps_b = U[b] @ V[b]^T / sqrt(r)."""

    U: jax.Array  # [base, m, r]
    V: jax.Array  # [base, n, r]


class DenseNoise(NamedTuple):
    """Dense noise for one non-2D leaf: eps_b = E[b]."""

    E: jax.Array  # [base, *leaf.shape]


def base_pop_size(pop_size: int, antithetic: bool) -> int:
    """Number of independently sampled base perturbations.

    Antithetic pairing shares one base sample between members ``k`` and
    ``k + pop//2``; an odd population gets one extra unpaired positive sample
    (reference ``utills.py:88-104``).
    """
    if not antithetic:
        return pop_size
    half = pop_size // 2
    return half + (pop_size % 2)


def member_signs_and_bases(pop_size: int, antithetic: bool) -> Tuple[np.ndarray, np.ndarray]:
    """Static maps: member index k → (sign s_k, base sample index b_k).

    Layout matches the reference population ordering
    ``[e_0..e_{h-1}, -e_0..-e_{h-1}, (+e_h if odd)]`` (utills.py:98-103).

    Deliberately *uncached*: returning one shared ndarray object would let
    jax deduplicate the resulting jnp constants across call sites, which
    changes the lowered text of every program built on the update
    (:func:`fitness_coeffs`, :func:`es_update`). The member loop goes through
    :func:`member_maps`, which IS cached and threads one device-side table
    pair through the whole loop.
    """
    if not antithetic:
        return np.ones(pop_size, np.float32), np.arange(pop_size, dtype=np.int32)
    half = pop_size // 2
    signs = np.ones(pop_size, np.float32)
    signs[half : 2 * half] = -1.0
    bases = np.concatenate(
        [
            np.arange(half, dtype=np.int32),
            np.arange(half, dtype=np.int32),
            np.full(pop_size % 2, half, dtype=np.int32),
        ]
    )
    return signs, bases


@functools.lru_cache(maxsize=64)
def _cached_member_tables(pop_size: int, antithetic: bool) -> Tuple[np.ndarray, np.ndarray]:
    signs, bases = member_signs_and_bases(pop_size, antithetic)
    signs.setflags(write=False)
    bases.setflags(write=False)
    return signs, bases


def member_maps(pop_size: int, antithetic: bool) -> Tuple[jax.Array, jax.Array]:
    """Device-side ``(signs, bases)`` lookup tables for the member loop: the
    numpy tables are built once per (pop, antithetic) geometry (lru-cached)
    and wrapped once per trace, threaded through the loop as explicit
    arguments instead of re-wrapped per member."""
    signs, bases = _cached_member_tables(pop_size, antithetic)
    return jnp.asarray(signs), jnp.asarray(bases)


def sample_noise(key: jax.Array, theta: Pytree, pop_size: int, cfg: EggRollConfig) -> Pytree:
    """Sample factored population noise matching the structure of ``theta``.

    Returns a pytree with the same *outer* structure as ``theta`` whose leaves
    are replaced by :class:`LowRankNoise` (2D leaves) or :class:`DenseNoise`
    nodes. The result is itself a valid pytree (NamedTuples), so it flows
    through jit/scan/shard_map untouched.

    Mirrors ``EggRollNoiser._sample_low_rank_block`` + ``sample_eps``
    (utills.py:43-106) without ever concatenating into a ``[pop, D]`` matrix.
    """
    base = base_pop_size(pop_size, cfg.antithetic)
    leaves, treedef = jax.tree_util.tree_flatten(theta)
    keys = jax.random.split(key, max(len(leaves), 1))
    # Draws are always f32 then cast to the store dtype, so the bf16 stream is
    # exactly round(f32 stream) — bitstream-compatible across noise_dtype.
    ndt = cfg.noise_jnp_dtype
    factors: List[Any] = []
    for leaf_key, leaf in zip(keys, leaves):
        if leaf.ndim in (2, 3):
            # 2D: one matrix. 3D [L, m, n]: a scan-over-layers stack — each of
            # the L matrices gets its own independent low-rank perturbation,
            # matching the reference's per-matrix semantics (utills.py:53-62).
            *stack, m, n = leaf.shape
            stack = tuple(stack)
            ku, kv = jax.random.split(leaf_key)
            factors.append(
                LowRankNoise(
                    U=jax.random.normal(ku, (base, *stack, m, cfg.rank), jnp.float32).astype(ndt),
                    V=jax.random.normal(kv, (base, *stack, n, cfg.rank), jnp.float32).astype(ndt),
                )
            )
        else:
            factors.append(
                DenseNoise(
                    E=jax.random.normal(leaf_key, (base,) + leaf.shape, jnp.float32).astype(ndt)
                )
            )
    return jax.tree_util.tree_unflatten(treedef, factors)


def _noise_leaves(theta: Pytree, noise: Pytree) -> Tuple[List[jax.Array], List[Any], Any]:
    """Align theta leaves with their factored-noise nodes.

    Raises ``ValueError`` naming the mismatch when ``noise`` was not sampled
    from a theta of this structure — the treedefs must be identical once the
    factored-noise nodes are treated as leaves, and every such leaf must be a
    :class:`LowRankNoise`/:class:`DenseNoise` node (a raw array in a
    structurally-matching position would otherwise corrupt the update
    silently).
    """
    theta_leaves, treedef = jax.tree_util.tree_flatten(theta)
    is_node = lambda x: isinstance(x, (LowRankNoise, DenseNoise))
    noise_leaves, noise_def = jax.tree_util.tree_flatten(noise, is_leaf=is_node)
    if noise_def != treedef:
        raise ValueError(
            "noise tree structure does not match theta (was the noise sampled "
            f"from a different adapter tree?):\n  theta: {treedef}\n  noise: {noise_def}"
        )
    bad = [type(x).__name__ for x in noise_leaves if not is_node(x)]
    if bad:
        raise ValueError(
            "noise leaves must be LowRankNoise/DenseNoise nodes; got "
            f"{bad} — pass the pytree returned by sample_noise, not raw arrays"
        )
    return theta_leaves, noise_leaves, treedef


def materialize_member_eps(theta: Pytree, noise: Pytree, k: jax.Array, pop_size: int, cfg: EggRollConfig) -> Pytree:
    """Materialize member ``k``'s full-rank perturbation ε_k as a theta-shaped pytree.

    ``k`` may be a traced scalar (e.g. inside ``vmap``/``lax.map``). No step
    program calls this: it is the reference of :func:`perturb_member`.
    """
    signs, bases = member_signs_and_bases(pop_size, cfg.antithetic)
    s = jnp.asarray(signs)[k]
    b = jnp.asarray(bases)[k]
    inv_sqrt_r = 1.0 / math.sqrt(cfg.rank)
    theta_leaves, noise_leaves, treedef = _noise_leaves(theta, noise)
    out = []
    for fac in noise_leaves:
        if isinstance(fac, LowRankNoise):
            # [..., m, r] @ [..., n, r]^T → [..., m, n]; works for 2D and
            # stacked. Factors upcast to f32 at the point of use — under
            # noise_dtype=bfloat16 the HBM-resident store stays half-size
            # (the convert fuses into the read) while the contraction
            # accumulates in f32.
            eps = jnp.einsum(
                "...mr,...nr->...mn",
                fac.U[b].astype(jnp.float32), fac.V[b].astype(jnp.float32),
                precision="highest",
            ) * inv_sqrt_r
        else:
            eps = fac.E[b].astype(jnp.float32)
        out.append(s * eps)
    return jax.tree_util.tree_unflatten(treedef, out)


def perturb_member(
    theta: Pytree,
    noise: Pytree,
    k: jax.Array,
    pop_size: int,
    cfg: EggRollConfig,
) -> Pytree:
    """θ_k = θ + σ · ε_k, materialized for one population member (jit/vmap-safe).

    The plain reference of the member path: the step programs hand members to
    the forward factored (:func:`factored_member_theta`) and are held to this
    within float rounding (tests/test_fused.py; the benchmark's
    ``drivers/es_train_ref.py``). Its one use outside a comparison is the
    single-member regeneration of ``trainer.regenerate_member_images``, which
    runs a backend's plain ``generate`` on raw leaves.
    """
    eps = materialize_member_eps(theta, noise, k, pop_size, cfg)
    return jax.tree_util.tree_map(lambda t, e: t + cfg.sigma * e.astype(t.dtype), theta, eps)


def factored_member_theta(
    theta: Pytree,
    noise: Pytree,
    k: jax.Array,
    pop_size: int,
    cfg: EggRollConfig,
    maps: Optional[Tuple[jax.Array, jax.Array]] = None,
    sigma: Optional[jax.Array] = None,
    c_scale: Optional[jax.Array] = None,
) -> Pytree:
    """Member ``k``'s perturbed adapter with the perturbation kept *factored*.

    What every step program hands the forward: every low-rank-noised leaf
    becomes a ``lora.FactoredDelta(w=θ_leaf, u=U[b], v=V[b], c=σ·s_k/√r)`` node — the dense ``U@Vᵀ`` product is never built;
    consumers (models/nn.py ``dense``/``conv2d`` via lora.matmul_factored)
    apply it as chained thin contractions with f32 accumulation over the
    (possibly bf16) noise store. Dense-noised leaves (conv-4D ``a`` factors,
    biases) have no factored form and are materialized exactly as before:
    ``θ + σ·s·E[b]``.

    ``maps`` threads precomputed device-side ``(signs, bases)`` tables from
    :func:`member_maps` so a member loop builds them once, not per member.

    ``sigma``/``c_scale`` (optional traced f32 scalars) are the fleet path's
    lane-indexed per-job σ_j and σ_j/√r (ISSUE 20): ``c_scale`` replaces the
    baked ``σ/√r`` constant in the factored coefficient and ``sigma`` the
    dense-leaf σ. Both must be passed together, precomputed host-side with
    one rounding each (``np.float32(σ_j / sqrt(r))``) so a fleet lane whose
    σ_j equals ``cfg.sigma`` computes the bitwise-identical member theta.
    ``None`` keeps the static-constant trace (the solo program).
    """
    from ..lora import FactoredDelta

    signs_j, bases_j = maps if maps is not None else member_maps(pop_size, cfg.antithetic)
    s = signs_j[k]
    b = bases_j[k]
    if (sigma is None) != (c_scale is None):
        raise ValueError(
            "factored_member_theta: sigma and c_scale override together "
            f"(got sigma={'set' if sigma is not None else None}, "
            f"c_scale={'set' if c_scale is not None else None}) — precompute "
            "c_scale = float32(sigma / sqrt(rank)) host-side"
        )
    if c_scale is None:
        c = jnp.asarray(cfg.sigma / math.sqrt(cfg.rank), jnp.float32) * s
        sig = cfg.sigma
    else:
        c = c_scale * s
        sig = sigma
    theta_leaves, noise_leaves, treedef = _noise_leaves(theta, noise)
    out = []
    for t, fac in zip(theta_leaves, noise_leaves):
        if isinstance(fac, LowRankNoise):
            out.append(FactoredDelta(w=t, u=fac.U[b], v=fac.V[b], c=c))
        else:
            e = fac.E[b].astype(jnp.float32)
            out.append(t + (sig * s * e).astype(t.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def lane_slice(stacked: Pytree, k: jax.Array, what: str = "stacked adapter") -> Pytree:
    """Slot ``k`` of a leading-axis-stacked pytree — THE member-axis slicing
    seam, shared by every consumer of the "lane index selects a slab" contract
    (:func:`stacked_adapter_theta` for serving, the fleet evaluator's per-job
    θ/noise slabs for training — ISSUE 20's dedup satellite: one helper, not a
    third copy-paste).

    ``stacked`` is any pytree whose every array leaf carries an extra leading
    axis (adapters via ``lora.stack_adapters``; job-stacked noise trees keep
    their ``LowRankNoise``/``DenseNoise`` nodes — NamedTuples are pytrees, so
    their ``U``/``V``/``E`` arrays are sliced in place and the node types
    survive). ``k`` may be traced (a ``lax.map`` lane index). ``what`` names
    the caller's contract in the scalar-leaf refusal.
    """
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    bad = [i for i, l in enumerate(leaves) if getattr(l, "ndim", 0) < 1]
    if bad:
        raise ValueError(
            f"{what} leaves need a leading adapter axis; leaf "
            f"index(es) {bad} are scalars — build the batch with "
            "lora.stack_adapters"
        )
    return jax.tree_util.tree_unflatten(treedef, [l[k] for l in leaves])


def stacked_adapter_theta(stacked: Pytree, k: jax.Array) -> Pytree:
    """Adapter ``k`` from a leading-axis adapter batch — the *serving* twin of
    :func:`factored_member_theta`.

    Training batches one shared θ plus per-member factored noise over the
    member axis; serving batches N fully-trained adapter trees over the same
    axis (``serve/``: "member" re-read as "user request"). ``stacked`` is a
    theta-structured pytree whose every leaf carries an extra leading ``[A]``
    adapter axis (build with ``lora.stack_adapters``); ``k`` may be traced
    (the slot index inside the serve program's ``lax.map``). Kept beside the
    member-theta builders so the member-axis contracts — what the lane
    index selects — live in one file; the slicing itself is
    :func:`lane_slice`, shared with the fleet training path.
    """
    return lane_slice(stacked, k)


def fitness_coeffs(fitness: jax.Array, pop_size: int, cfg: EggRollConfig) -> jax.Array:
    """Per-base-sample fitness coefficients ``c_b = Σ_{k: base(k)=b} f_k s_k``
    — the segment-sum at the head of :func:`es_update`, exposed standalone so
    the pop-sharded update (``parallel/pop_update.py``) can compute the tiny
    ``[base]`` vector once (replicated) and hand each pop shard its slice.
    Deliberately NOT called from :func:`es_update` itself: the replicated
    update's lowered program is the bit-for-bit parity anchor of the sharded
    one and stays textually untouched."""
    signs, bases = member_signs_and_bases(pop_size, cfg.antithetic)
    base = base_pop_size(pop_size, cfg.antithetic)
    w = fitness.astype(jnp.float32) * jnp.asarray(signs)  # [pop]
    return jax.ops.segment_sum(w, jnp.asarray(bases), num_segments=base)  # [base]


def es_partial_delta(
    theta: Pytree,
    noise: Pytree,
    coeffs: jax.Array,
    lo: jax.Array,
    n_slice: int,
    pop_size: int,
    cfg: EggRollConfig,
) -> Pytree:
    """One shard's UNnormalized contribution to the EGGROLL update: the
    fitness-weighted noise sum over base samples ``[lo, lo+n_slice)`` only.

    ``lo`` may be traced (``lax.axis_index`` inside a shard_map body);
    ``n_slice`` is static. Returns a theta-shaped pytree of f32 partial sums
    — low-rank leaves carry ``Σ_{b∈slice} c_b U_b V_bᵀ`` (NOT yet divided by
    ``pop·√r``), dense leaves ``Σ_{b∈slice} c_b E_b`` (NOT yet ``/pop``).
    Summing the partials of a disjoint cover of ``[0, base)`` — one ``psum``
    over the pop axis — reproduces :func:`es_update`'s per-leaf contraction
    up to f32 summation order (parity is rounding-tight, not bitwise).
    """
    theta_leaves, noise_leaves, treedef = _noise_leaves(theta, noise)
    cs = jax.lax.dynamic_slice_in_dim(coeffs, lo, n_slice, axis=0)
    out = []
    for fac in noise_leaves:
        if isinstance(fac, LowRankNoise):
            U = jax.lax.dynamic_slice_in_dim(fac.U, lo, n_slice, axis=0)
            V = jax.lax.dynamic_slice_in_dim(fac.V, lo, n_slice, axis=0)
            part = jnp.einsum(
                "b,b...mr,b...nr->...mn",
                cs, U.astype(jnp.float32), V.astype(jnp.float32),
                precision="highest", preferred_element_type=jnp.float32,
            )
        else:
            E = jax.lax.dynamic_slice_in_dim(fac.E, lo, n_slice, axis=0)
            part = jnp.einsum(
                "b,b...->...", cs, E.astype(jnp.float32),
                precision="highest", preferred_element_type=jnp.float32,
            )
        out.append(part)
    return jax.tree_util.tree_unflatten(treedef, out)


def apply_es_delta(
    theta: Pytree, delta_sums: Pytree, noise: Pytree, pop_size: int, cfg: EggRollConfig
) -> Pytree:
    """``θ' = θ + lr · delta`` from the *summed* partial contributions of
    :func:`es_partial_delta` (post-``psum``): low-rank leaves are scaled by
    ``1/(pop·√r)``, dense leaves by ``1/pop`` — the same normalizations
    :func:`es_update` applies inline. The low-rank-vs-dense verdict is read
    from the ``noise`` tree's node types (the one authority — re-deriving it
    from leaf ranks here would silently fork if ``sample_noise``'s
    classification rule ever changes)."""
    lr = cfg.lr
    inv = 1.0 / (pop_size * math.sqrt(cfg.rank))
    theta_leaves, noise_leaves, treedef = _noise_leaves(theta, noise)
    out = []
    for t, fac, d in zip(
        theta_leaves, noise_leaves, jax.tree_util.tree_leaves(delta_sums)
    ):
        scale = inv if isinstance(fac, LowRankNoise) else 1.0 / pop_size
        out.append(t + lr * (d * scale).astype(t.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def es_update(
    theta: Pytree,
    noise: Pytree,
    fitness: jax.Array,
    pop_size: int,
    cfg: EggRollConfig,
    lr: Optional[jax.Array] = None,
) -> Pytree:
    """EGGROLL ES update: θ' = θ + (lr_scale·σ) · mean_k(f_k · ε_k).

    Computed entirely in factored form: for each 2D leaf,
    ``mean_k f_k ε_k = (1/(n√r)) Σ_b c_b U_b V_bᵀ`` with
    ``c_b = Σ_{k: base(k)=b} f_k s_k`` — one segment-sum plus one batched
    einsum per leaf. Mirrors ``EggRollNoiser.do_update`` (utills.py:115-136)
    exactly in expectation and (given identical noise) in value.

    Args:
        fitness: ``[pop_size]`` standardized fitness; non-finite members must
            already be zeroed (see ``scoring.standardize_fitness_masked``).
        lr: optional traced f32 scalar overriding ``cfg.lr`` — the fleet
            path's per-job learning rate (precompute host-side as
            ``float32(lr_scale_j * sigma_j)``, one rounding, so a job whose
            hyperparameters equal the config's applies the bitwise-identical
            update). ``None`` keeps the static constant — the bit-for-bit
            parity anchor's trace is untouched.
    """
    signs, bases = member_signs_and_bases(pop_size, cfg.antithetic)
    base = base_pop_size(pop_size, cfg.antithetic)
    w = fitness.astype(jnp.float32) * jnp.asarray(signs)  # [pop]
    c = jax.ops.segment_sum(w, jnp.asarray(bases), num_segments=base)  # [base]
    if lr is None:
        lr = cfg.lr
    inv = 1.0 / (pop_size * math.sqrt(cfg.rank))
    theta_leaves, noise_leaves, treedef = _noise_leaves(theta, noise)
    out = []
    for t, fac in zip(theta_leaves, noise_leaves):
        if isinstance(fac, LowRankNoise):
            # f32 upcast at use + f32 accumulation: the bf16 noise store never
            # degrades the update contraction (preferred_element_type pins the
            # accumulator even if a backend would otherwise accumulate low).
            delta = jnp.einsum(
                "b,b...mr,b...nr->...mn",
                c, fac.U.astype(jnp.float32), fac.V.astype(jnp.float32),
                precision="highest", preferred_element_type=jnp.float32,
            ) * inv
        else:
            delta = jnp.einsum(
                "b,b...->...", c, fac.E.astype(jnp.float32),
                precision="highest", preferred_element_type=jnp.float32,
            ) / pop_size
        out.append(t + lr * delta.astype(t.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)

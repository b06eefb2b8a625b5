"""Pop-sharded EGGROLL update: each pop shard sums only its own base slice.

The replicated update (``es/noiser.es_update``) is a handful of
``[base, m+n, r]`` einsums per LoRA leaf, computed identically on every
device — cheap at small populations, but at popscale geometry (pop 128,
base 64) it is ~``n_pop``× redundant work on a pop mesh, and it reads the
ENTIRE factored-noise store from every device's HBM. EGGROLL's structure
makes the distributed form trivial (the same property PR 6 exploited at host
level): the update is a *sum over base samples* of fitness-weighted rank-r
factors, so a contiguous slice per pop shard plus ONE ``psum`` of the
adapter-tree-sized partial sums reproduces the full Δθ —

    Δ = Σ_b c_b U_b V_bᵀ = Σ_shard ( Σ_{b ∈ shard's slice} c_b U_b V_bᵀ )

Per-device update FLOPs (and noise-store bytes read) drop ~``n_pop``×, paid
for with one adapter-sized all-reduce over the pop axis — kilobytes-to-MB of
LoRA factors, per *epoch*, on the same axis whose per-member score rows
already cross ICI (``pop_eval.py``).

Parity is rounding-tight, not bitwise: the psum changes f32 summation order
(tests/test_pop_shard.py pins the tolerance). The replicated path stays the
bit-for-bit parity anchor (``--pop_shard_update off`` and every mesh-less
program lower it).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P

from ..es import EggRollConfig, base_pop_size, es_partial_delta, fitness_coeffs
from ..es.noiser import apply_es_delta
from .mesh import POP_AXIS, shard_map

Pytree = Any


def pop_shard_update_plan(
    mode: str,
    pop_size: int,
    antithetic: bool,
    mesh: Optional[Mesh],
) -> Tuple[bool, str]:
    """Resolve ``--pop_shard_update {auto,on,off}`` against a mesh.

    Returns ``(enabled, reason)``. Rules:

    - ``off`` (or no mesh / no pop axis / pop axis of 1) → replicated. With
      ``on`` and no usable pop axis, raise — the user asked for a sharding
      that cannot exist.
    - the base-sample count must tile the pop axis (contiguous slices, no
      padding: padding the noise store would materialize a second copy of
      the largest ES-state arrays, the exact thing the factored form
      avoids). ``auto`` falls back to replicated when it doesn't; ``on``
      raises naming both numbers.
    """
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"pop_shard_update must be auto/on/off, got {mode!r}")
    if mode == "off":
        return False, "off"
    n_pop = mesh.shape.get(POP_AXIS, 1) if mesh is not None else 1
    if n_pop <= 1:
        if mode == "on":
            raise ValueError(
                "pop_shard_update=on needs a mesh with a pop axis of size > 1 "
                f"(mesh: {dict(mesh.shape) if mesh is not None else None})"
            )
        return False, "no pop axis"
    base = base_pop_size(pop_size, antithetic)
    if base % n_pop:
        if mode == "on":
            raise ValueError(
                f"pop_shard_update=on needs the base-sample count ({base}, "
                f"from pop_size={pop_size}, antithetic={antithetic}) divisible "
                f"by the pop-axis size ({n_pop}) — contiguous slices only"
            )
        return False, f"base {base} % pop axis {n_pop} != 0"
    return True, f"{n_pop}-way"


def make_sharded_es_update(
    mesh: Mesh,
    pop_size: int,
    cfg: EggRollConfig,
) -> Callable[[Pytree, Pytree, jax.Array], Pytree]:
    """Build ``update(theta, noise, fitness) → θ'`` with the fitness-weighted
    noise contraction sharded over the mesh's pop axis.

    All inputs enter replicated (θ and the noise store are already
    replicated in the epoch step; fitness is the post-all-gather ``[pop]``
    vector) — each shard *reads* only its base slice of the store and
    contracts ``base/n_pop`` factors, then one ``psum`` of the partial-delta
    pytree over ``POP_AXIS`` replicates the full Δθ everywhere. Output spec
    is replicated (`P()`): the psum makes it so on the pop axis, and no
    other axis is read, so every device leaves with the identical θ'.
    """
    n_pop = mesh.shape[POP_AXIS]
    base = base_pop_size(pop_size, cfg.antithetic)
    if base % n_pop:
        raise ValueError(
            f"base sample count {base} does not tile the pop axis ({n_pop})"
        )
    lslice = base // n_pop

    def body(theta, noise, coeffs):
        lo = jax.lax.axis_index(POP_AXIS) * lslice
        partial = es_partial_delta(theta, noise, coeffs, lo, lslice, pop_size, cfg)
        # ONE collective: the whole adapter-shaped partial tree rides a
        # single psum over the pop axis (XLA emits/combines the per-leaf
        # all-reduces; the ledger's collective_bytes field publishes what
        # actually crossed — obs/xla_cost.collective_stats)
        delta = jax.lax.psum(partial, POP_AXIS)
        return apply_es_delta(theta, delta, noise, pop_size, cfg)

    sharded = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(), P()),
        out_specs=P(),
        check_vma=False,
    )

    def update(theta: Pytree, noise: Pytree, fitness: jax.Array) -> Pytree:
        coeffs = fitness_coeffs(fitness, pop_size, cfg)  # tiny [base], replicated
        return sharded(theta, noise, coeffs)

    return update

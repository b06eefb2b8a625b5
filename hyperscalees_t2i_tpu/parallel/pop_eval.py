"""Population-parallel evaluation: the TPU payoff of ES training.

The reference evaluates its population *sequentially in Python*, mutating live
module weights per candidate (``unifed_es.py:159-163``, HOT LOOP 1). Here the
population axis is a first-class mesh axis: ``shard_map`` places a contiguous
slice of the population on each device, every device runs its slice through
the same compiled generate→reward program (chunked by ``member_batch`` via
``lax.map`` for memory control), and one tiny ``all_gather`` of the per-member
score rows brings the full score matrix everywhere for fitness shaping and
the factored EGGROLL update — which is then computed redundantly-replicated
(it is a handful of [base, m+n, r] einsums on LoRA-sized tensors, far cheaper
than any cross-device scheme).

Two mesh axes are honored (parallel/mesh.py conventions):

- ``"pop"`` — population members, padded up to the axis size so any pop_size
  works (padded slots recompute an existing member and are sliced away);
- ``"data"`` — the intra-member image batch (prompts × repeats), so a small
  population still saturates a full slice. Per-image generation keys fold in
  the *global* batch position (``item_index``), making results bit-identical
  to the unsharded program regardless of the data-axis layout.

Communication cost per epoch over ICI: one all-gather of ``[pop, B] ×
n_reward_keys`` floats — kilobytes. The generation FLOPs (billions) stay
entirely device-local. This is the design SURVEY.md §2.2 calls "population
parallelism = the natural DP of ES".

All frozen params flow through as *arguments* (``frozen`` pytree), never as
jit-captured constants — see backends/base.py for the rationale.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..es import (
    EggRollConfig,
    factored_member_theta,
    lane_slice,
    member_maps,
    stacked_adapter_theta,
)
from ..obs import get_registry, note_program_geometry, scope as obs_scope, span as obs_span
from .collectives import all_gather_tree
from .mesh import DATA_AXIS, POP_AXIS, shard_map

Pytree = Any
# (frozen_gen, theta, flat_ids, key, item_index) -> images
GenerateFn = Callable[..., jax.Array]
# (frozen_reward, images, flat_ids) -> {name: [B]}
RewardFn = Callable[[Pytree, jax.Array, jax.Array], Dict[str, jax.Array]]


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


GEN_ROWS = "gen/"  # prefix of a generator's own rows in the evaluator's result


def _generate_and_reward(generate_p, reward_apply, frozen, theta_k, flat_ids, gen_key, item_index):
    """One member's image batch: generate, reward. A generator may return
    ``(images, rows)`` — per-image rows of its own (a sampler's ids, a router's
    counters; each ``[B, ...]``): they ride beside the reward rows under
    ``gen/<name>``, through the same member loop and gathers, and the step
    hands them to the backend's ``step_metrics``."""
    out = generate_p(frozen["gen"], theta_k, flat_ids, gen_key, item_index)
    images, rows = out if isinstance(out, tuple) else (out, {})
    rewards = dict(reward_apply(frozen["reward"], images, flat_ids))
    rewards.update({GEN_ROWS + k: v for k, v in rows.items()})
    return rewards


def effective_reward_tile(batch: int, reward_tile: int) -> int:
    """Largest divisor of ``batch`` that is ≤ ``reward_tile`` (0 = untiled).

    ``lax.map`` tiles must divide the batch exactly; rounding the knob down
    to a divisor keeps every geometry legal without padding (reward rows are
    per-image, so any exact split is value-identical)."""
    if reward_tile <= 0 or reward_tile >= batch:
        return 0
    tile = reward_tile
    while batch % tile:
        tile -= 1
    return tile


def _note_effective_tile(batch: int, reward_tile: int) -> int:
    """Resolve the tile actually used for a ``batch``, warn loudly (trace
    time, stderr) when the divisor rounding degraded it — e.g. tile 2 on a
    prime batch of 7 serializes to 1-image tiles, a silent severalfold
    step-time cliff otherwise — and return it for the ledger geometry."""
    eff = effective_reward_tile(batch, reward_tile)
    if 0 < eff < reward_tile < batch:
        import sys

        print(
            f"[pop_eval] WARNING: reward_tile={reward_tile} does not divide "
            f"the per-member batch B={batch}; degraded to tile={eff} "
            "(pick a divisor of prompts_per_gen*batches_per_gen to avoid "
            "over-serializing the decode→reward pipeline)",
            file=sys.stderr, flush=True,
        )
    return eff


def _eval_member(generate_p, reward_apply, reward_tile, frozen, theta_k, flat_ids, item_index, gen_key):
    """One member's rows for its whole image batch — in one piece, or through
    ``lax.map`` over sub-batches of ``reward_tile`` images (rounded down to a
    divisor of the batch, :func:`effective_reward_tile`)."""
    B = flat_ids.shape[0]
    tile = effective_reward_tile(B, reward_tile)
    if tile == 0:
        return _generate_and_reward(generate_p, reward_apply, frozen, theta_k, flat_ids, gen_key, item_index)
    n_tiles = B // tile
    tiled = jax.lax.map(
        lambda args: _generate_and_reward(
            generate_p, reward_apply, frozen, theta_k, args[0], gen_key, args[1]
        ),
        (flat_ids.reshape(n_tiles, tile), item_index.reshape(n_tiles, tile)),
    )  # dict of [n_tiles, tile]
    return jax.tree_util.tree_map(lambda a: a.reshape(B, *a.shape[2:]), tiled)


def make_adapter_batch_generator(
    generate_p: GenerateFn,
    adapter_batch: int,
    images_per_request: int,
    member_batch: int = 0,
):
    """Build the multi-tenant *serving* program: ``gen_batch(frozen,
    stacked_theta, flat_ids [A, B], keys [A, ...]) → images [A, B, H, W, C]``.

    The training hot path's member loop re-read for inference (ISSUE 12 /
    ROADMAP item 1: "member" = "user request"): ``stacked_theta`` is an
    adapter *batch* — N fully-trained LoRA trees stacked on a leading axis
    (``lora.stack_adapters``) entering the compiled program as an ordinary
    argument, so serving a new adapter is a new argument value, never a new
    compile. Each ``lax.map`` lane selects its slot
    (``es.stacked_adapter_theta``), generates its own ``[B]`` prompt batch
    under its own key, and ``member_batch`` chunks the lane axis exactly
    like population evaluation (0 = one vmapped chunk). Per-lane
    ``item_index`` is ``arange(B)`` — each request is its own global batch,
    bitwise-identical to a single-request dispatch of the same adapter
    (generation keys fold only request-local positions; asserted by
    tests/test_serve.py).

    Trace-time obs mirrors ``make_population_evaluator``: a ``serve_traces``
    counter exposes silent retrace storms (the hot-swap test asserts it FLAT
    across adapter swaps) and the geometry note lands in the enclosing
    compile's ledger record (site="serve").
    """
    A, B = adapter_batch, images_per_request
    if A < 1 or B < 1:
        raise ValueError(
            f"adapter_batch and images_per_request must be >= 1, got "
            f"({adapter_batch}, {images_per_request})"
        )

    def gen_batch(frozen, stacked_theta, flat_ids, keys):
        get_registry().inc("serve_traces")
        note_program_geometry(
            adapter_batch=A, images_per_request=B,
            member_batch=member_batch,
        )
        with obs_span("trace/serve_batch", adapter_batch=A, images=B):
            item_index = jnp.arange(B)

            def one(k):
                theta_k = stacked_adapter_theta(stacked_theta, k)
                return generate_p(frozen, theta_k, flat_ids[k], keys[k], item_index)

            return jax.lax.map(
                one, jnp.arange(A),
                batch_size=min(member_batch, A) if member_batch > 0 else A,
            )

    return gen_batch


def make_fleet_evaluator(
    generate_p: GenerateFn,
    reward_apply: RewardFn,
    width: int,
    pop_size: int,
    es_cfg: EggRollConfig,
    member_batch: int,
    reward_tile: int = 0,
) -> Callable[..., Dict[str, jax.Array]]:
    """Build the *fleet* evaluator: ``eval_fleet(frozen, stacked_theta,
    stacked_noise, flat_ids [W, B], gen_keys [W, ...], sigmas [W],
    c_scales [W]) → rewards`` with every reward leaf ``[W, pop_size, B]``.

    The member axis generalized to a flat (job, member) lane axis (ISSUE 20):
    ``W`` independent ES jobs — each with its own adapter slab in the
    job-stacked ``stacked_theta`` (``lora.stack_adapters`` of W solo trees),
    its own job-stacked noise slab, its own prompt row ``flat_ids[j]``, its
    own generation key ``gen_keys[j]``, and its own σ entering the factored
    perturbation as the lane-indexed scalars ``sigmas[j]`` /
    ``c_scales[j] = f32(σ_j/√r)`` — advance through ONE ``lax.map`` over the
    ``W*pop_size`` concatenated lane axis, against one resident frozen base.
    Lane ``i`` is job ``i // pop_size``, member ``i % pop_size``: jobs are
    contiguous lane spans, so ``mesh.host_slices(W*pop, W)`` is exactly the
    job→lane packing map (tested cover identity, tests/test_fleet.py).

    Parity contract: each job's lane runs the *same ops in the same
    association* as the solo ``make_population_evaluator`` member lane —
    ``lane_slice`` is the very gather the serve twin uses, and the σ scalars
    are host-precomputed f32 (one rounding, like the solo program's baked
    constants) — so per-job reward rows agree with W solo runs to rounding:
    two XLA programs, a written ulp bound, not a hash
    (``train/fleet.reward_rows_close``; bench --fleet / CI fleet_smoke hold
    it). Fitness shaping stays OUT of this program; the trainer standardizes
    per job (``es.jobwise_prompt_normalized_scores``), never across jobs.

    All jobs in one step share compile-relevant geometry (pop_size, rank,
    antithetic, dtypes, B) — that is the admission cohort contract
    (train/fleet.py); per-job σ/lr vary as argument *values*, so any job mix
    at a given width reuses one compiled program (the PR-12 serve
    discipline; ``fleet_traces`` stays flat across job swaps).
    """
    W = width
    if W < 1 or pop_size < 1:
        raise ValueError(
            f"width and pop_size must be >= 1, got ({width}, {pop_size})"
        )
    n_lanes = W * pop_size

    def eval_fleet(frozen, stacked_theta, stacked_noise, flat_ids, gen_keys,
                   sigmas, c_scales):
        get_registry().inc("fleet_traces")
        note_program_geometry(
            fleet_width=W, pop=pop_size, member_batch=member_batch,
            n_pop=1, n_data=1, reward_tile=reward_tile,
            reward_tile_effective=_note_effective_tile(
                flat_ids.shape[1], reward_tile
            ),
        )
        with obs_span(
            "trace/fleet_eval", fleet_width=W, pop=pop_size,
            member_batch=member_batch,
        ):
            B = flat_ids.shape[1]
            item_index = jnp.arange(B)
            maps = member_maps(pop_size, es_cfg.antithetic)

            def eval_lane(i):
                j = i // pop_size
                k = i % pop_size
                theta_j = lane_slice(stacked_theta, j, what="job-stacked adapter")
                noise_j = lane_slice(stacked_noise, j, what="job-stacked noise")
                theta_k = factored_member_theta(
                    theta_j, noise_j, k, pop_size, es_cfg, maps,
                    sigma=sigmas[j], c_scale=c_scales[j],
                )
                return _eval_member(
                    generate_p, reward_apply, reward_tile,
                    frozen, theta_k, flat_ids[j], item_index, gen_keys[j],
                )

            flat = jax.lax.map(
                eval_lane, jnp.arange(n_lanes),
                batch_size=min(member_batch, n_lanes) if member_batch > 0 else n_lanes,
            )  # dict of [W*pop, B]
            return jax.tree_util.tree_map(
                lambda a: a.reshape(W, pop_size, *a.shape[1:]), flat
            )

    return eval_fleet


def make_population_evaluator(
    generate_p: GenerateFn,
    reward_apply: RewardFn,
    pop_size: int,
    es_cfg: EggRollConfig,
    member_batch: int,
    mesh: Optional[Mesh] = None,
    reward_tile: int = 0,
    host_slice: Optional[Tuple[int, int]] = None,
) -> Callable[[Pytree, Pytree, Pytree, jax.Array, jax.Array], Dict[str, jax.Array]]:
    """Build ``eval_pop(frozen, theta, noise, flat_ids, gen_key) → rewards``
    where ``frozen = {"gen": ..., "reward": ...}`` and each reward leaf is
    ``[pop_size, B]``, identical on every device.

    ``host_slice=(lo, n_local)`` builds the *host-sharded* variant for pod
    training: this process evaluates only global members ``[lo, lo+n_local)``
    and the returned leaves are ``[n_local, B]`` — the full matrix is then
    reassembled at host level (``collectives.host_allgather_rows``), so only
    fitness rows ever cross hosts (the EGGROLL pod contract) and the compiled
    program never spans processes (XLA:CPU cannot build one; TPU pods avoid
    per-epoch DCN latency inside the step). Perturbations still index the
    *global* member id against the *global* ``pop_size``, so each member's
    reward is bit-identical to the single-process program's. ``mesh`` must be
    a local-devices mesh in this mode; it further shards the slice.

    Common-random-numbers discipline: all members share ``gen_key`` (reference
    "SAME seed for all indiv", runES.py:103-107), so reward differences are
    attributable to the LoRA perturbation alone.

    ``member_batch`` members run together (``lax.map`` ``batch_size``), and a
    chunk holds whatever each of its members' generations carries: a KV or
    latent cache, and for a generator with recurrent layers a state per
    sequence that is full-sized from the first position (``lm_ar`` on a
    ``qwen3_next`` file: 19 MB a sequence, ``lm/state_bytes`` over the step).
    Nothing here sizes the chunk from that; ``member_batch`` is the knob.

    ``reward_tile`` (0 = off) bounds *member-interior* memory: each member's
    generate→decode→preprocess→reward pipeline runs through ``lax.map`` over
    image sub-batches of that size, so the 1024px decode + CLIP tower temps
    scale with one tile instead of the full [B] batch. Value-identical to the
    untiled program: per-image generation keys fold the *global* item_index
    (the chunk-invariance contract) and every reward row is per-image.

    Member ``k``'s adapter reaches the forward as ``lora.FactoredDelta``
    leaves (``es.factored_member_theta``): the dense ``σ·s·U_bV_bᵀ/√r``
    products are never materialized, every adapted dense builds its perturbed
    factor at the point of use (f32 accumulation over the bf16 noise store;
    ``models/nn.dense`` has the table of lowerings), and the sign/base lookup
    tables are built once per trace and threaded through the member loop.
    ``es.perturb_member`` is the plain reference this is held to, within
    float rounding (tests/test_fused.py).
    """

    def eval_one(frozen, theta, noise, flat_ids, item_index, gen_key, k, maps):
        # device-time scope (obs/xla_cost.INNER_SCOPES): a name only
        with obs_scope("es_noise"), jax.named_scope("perturb"):
            theta_k = factored_member_theta(theta, noise, k, pop_size, es_cfg, maps)
        return _eval_member(
            generate_p, reward_apply, reward_tile,
            frozen, theta_k, flat_ids, item_index, gen_key,
        )

    def make_maps():
        # device-side (signs, bases) built ONCE per trace and threaded into
        # every member lane
        with obs_scope("es_noise"), jax.named_scope("perturb"):
            return member_maps(pop_size, es_cfg.antithetic)

    # iteration domain: the whole population, or this host's member slice
    slice_lo, slice_n = host_slice if host_slice is not None else (0, pop_size)
    if not (0 <= slice_lo and slice_lo + slice_n <= pop_size and slice_n >= 1):
        raise ValueError(
            f"host_slice={host_slice} out of range for pop_size={pop_size}"
        )

    n_pop = mesh.shape.get(POP_AXIS, 1) if mesh is not None else 1
    n_data = mesh.shape.get(DATA_AXIS, 1) if mesh is not None else 1
    if n_data > 1 and getattr(generate_p, "ignores_item_index", False):
        raise ValueError(
            "data-axis sharding needs a generator that folds item_index into "
            "its per-image noise keys; this backend's generate() does not "
            "accept item_index, so shard-local positions would silently "
            "change the noise. Use a pop-only mesh for it."
        )
    if reward_tile > 0 and getattr(generate_p, "ignores_item_index", False):
        raise ValueError(
            "reward_tile needs a generator that folds item_index into its "
            "per-image noise keys; this backend's generate() does not accept "
            "item_index, so tile-local positions would silently change the "
            "noise. Run it untiled (reward_tile=0)."
        )

    if n_pop == 1 and n_data == 1:

        def eval_pop(frozen, theta, noise, flat_ids, gen_key):
            # This body runs at jax *trace* time: the counter/span fire once
            # per (re)trace of the enclosing step, making silent retrace storms
            # visible in metrics.jsonl / trace.jsonl (obs/).
            get_registry().inc("pop_eval_traces")
            # geometry only this layer knows, published for the XLA ledger
            # record the enclosing compile site writes (obs/xla_cost.py)
            note_program_geometry(
                pop=pop_size, member_batch=member_batch, n_pop=1, n_data=1,
                reward_tile=reward_tile, host_slice=host_slice,
                    reward_tile_effective=_note_effective_tile(
                    flat_ids.shape[0], reward_tile
                ),
            )
            with obs_span("trace/pop_eval", pop=pop_size, member_batch=member_batch):
                item_index = jnp.arange(flat_ids.shape[0])
                maps = make_maps()
                return jax.lax.map(
                    lambda k: eval_one(frozen, theta, noise, flat_ids, item_index, gen_key, k, maps),
                    slice_lo + jnp.arange(slice_n),
                    batch_size=min(member_batch, slice_n),
                )

        return eval_pop

    pop_pad = _ceil_to(slice_n, n_pop)
    lpop = pop_pad // n_pop

    def local_eval(frozen, theta, noise, gen_key, member_ids, flat_ids_l, item_index_l):
        # member_ids: this shard's [lpop] member indices; flat_ids_l /
        # item_index_l: this shard's [B/n_data] slice of the image batch.
        maps = make_maps()
        local = jax.lax.map(
            lambda k: eval_one(frozen, theta, noise, flat_ids_l, item_index_l, gen_key, k, maps),
            member_ids,
            batch_size=min(member_batch, lpop),
        )  # dict of [lpop, B_local]
        if n_data > 1:
            local = all_gather_tree(local, DATA_AXIS, axis=1)  # [lpop, B_pad]
        if n_pop > 1:
            local = all_gather_tree(local, POP_AXIS)  # [pop_pad, B_pad]
        return local

    pop_spec = P(POP_AXIS) if POP_AXIS in mesh.axis_names else P()
    data_spec = P(DATA_AXIS) if DATA_AXIS in mesh.axis_names else P()
    sharded = shard_map(
        local_eval,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), pop_spec, data_spec, data_spec),
        out_specs=P(),
        check_vma=False,
    )

    def eval_pop(frozen, theta, noise, flat_ids, gen_key):
        # Trace-time observability — see the unsharded variant above.
        get_registry().inc("pop_eval_traces")
        # effective tile resolved against the SHARD-local batch (that is the
        # slice each member's lax.map actually tiles)
        note_program_geometry(
            pop=pop_size, member_batch=member_batch, n_pop=n_pop, n_data=n_data,
            reward_tile=reward_tile, host_slice=host_slice,
            reward_tile_effective=_note_effective_tile(
                _ceil_to(flat_ids.shape[0], n_data) // n_data, reward_tile
            ),
        )
        with obs_span(
            "trace/pop_eval", pop=pop_size, member_batch=member_batch,
            n_pop=n_pop, n_data=n_data,
        ):
            B = flat_ids.shape[0]
            B_pad = _ceil_to(B, n_data)
            # Padded members re-evaluate an existing member (wrapping within
            # this host's slice); padded batch slots re-generate item 0. Both
            # are sliced away below — the cost is idle work on the last
            # shard, never wrong results.
            member_ids = slice_lo + (jnp.arange(pop_pad) % slice_n)
            ids_p = jnp.pad(flat_ids, (0, B_pad - B))
            item_index = jnp.arange(B_pad)
            out = sharded(frozen, theta, noise, gen_key, member_ids, ids_p, item_index)
            return {k: v[:slice_n, :B] for k, v in out.items()}

    return eval_pop

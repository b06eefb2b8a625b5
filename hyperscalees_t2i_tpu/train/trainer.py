"""The unified ES training loop — one jitted program per epoch.

Reference call stack being re-designed (SURVEY.md §3.1, ``unifed_es.py:89-314``):
the reference loops Python-side over the population, mutates live module
weights, generates, then calls the reward models once *per image*. Here the
entire epoch step — noise sampling, per-member LoRA perturbation, generation,
batched rewards, promptnorm, the EGGROLL update, and the norm caps — is ONE
compiled XLA program. The population axis is evaluated by ``lax.map`` with a
configurable ``batch_size`` (vmap chunks), so memory scales with
``member_batch``, not ``pop_size``, and the MXU stays busy.

Common-random-numbers discipline: every member shares one generation key per
epoch (reference "SAME seed for all indiv", runES.py:103-107); the prompt
subset, generation noise and ES noise all derive from (seed, epoch)
(unifed_es.py:752-767) via key folding.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..backends.base import ESBackend, RewardFn, StepInfo
from ..obs import (
    CompileProvenance,
    MetricsRegistry,
    ProgramLedger,
    Tracer,
    compile_cache_entries,
    get_tracer,
    maybe_heartbeat,
    record_compile,
    record_device_memory,
    roofline,
    scope as obs_scope,
    set_ledger,
    set_registry,
    set_tracer,
)
from ..es import (
    cap_step_norm,
    cap_theta_norm,
    epoch_key,
    es_update,
    lane_slice,
    perturb_member,
    prompt_normalized_scores,
    sample_noise,
    standardize_fitness_masked,
)
from ..es.caps import global_norm
from .config import TrainConfig

Pytree = Any

REWARD_KEYS = ("clip_aesthetic", "clip_text", "no_artifacts", "pickscore", "combined")


def _combine_and_update(
    theta: Pytree,
    prev_delta: Pytree,
    noise: Pytree,
    rewards: Dict[str, jax.Array],
    *,
    tc: TrainConfig,
    es_cfg,
    pop: int,
    num_unique: int,
    repeats: int,
    update_fn: Optional[Callable] = None,
    lr: Optional[jax.Array] = None,
    gen_metrics_fn: Optional[Callable] = None,
):
    """Rewards → scores → fitness → EGGROLL update → metrics: the back half
    of the epoch step, shared verbatim between the fused single-program step
    (``make_es_step``) and the host-sharded pod variant
    (``make_host_sharded_programs``) so both paths apply bit-identical math
    to the same ``[pop, B]`` reward matrix.

    ``update_fn`` (``(theta, noise, fitness) → θ'``) substitutes the EGGROLL
    contraction itself — the pop-sharded update (``parallel/pop_update.py``)
    passes its shard_map/psum variant here; ``None`` keeps the replicated
    ``es_update``, whose traced program is the bit-for-bit parity anchor.

    ``lr`` (fleet path, ISSUE 20) overrides the learning rate entering
    ``es_update`` as a traced scalar — the fleet step passes each job's
    host-precomputed ``f32(lr_scale_j·σ_j)`` so one compiled program serves
    any per-job hyperparameter mix. ``None`` (every solo caller) resolves to
    ``es_cfg.lr`` inside ``es_update`` — the solo trace is the same with or
    without the fleet path.

    ``gen_metrics_fn`` (a backend's ``step_metrics``) reduces the generator's
    own rows (``gen/<name>``, ``[pop, B, ...]``, parallel/pop_eval.py) to
    metrics of the step; without it such rows are dropped."""
    from ..obs.es_health import es_health_metrics
    from ..parallel.pop_eval import GEN_ROWS

    gen_rows = {k[len(GEN_ROWS):]: v for k, v in rewards.items() if k.startswith(GEN_ROWS)}
    rewards = {k: v for k, v in rewards.items() if not k.startswith(GEN_ROWS)}

    # device-time scopes (obs/xla_cost.TOP_SCOPES / INNER_SCOPES): names only,
    # the traced program is the same
    with obs_scope("es_update"):
        with jax.named_scope("fitness"):
            # S_comb[k, j]: mean over repeats (grouped layout [r][m],
            # unifed_es.py:208-215).
            S = rewards["combined"].reshape(pop, repeats, num_unique).mean(axis=1)
            if tc.promptnorm:
                opt_scores, _, sigma_bar = prompt_normalized_scores(S)
            else:
                opt_scores = S.mean(axis=1)
                sigma_bar = jnp.float32(0.0)
            fitness, n_finite = standardize_fitness_masked(opt_scores)

        with jax.named_scope("update"):
            if update_fn is not None:
                theta_new = update_fn(theta, noise, fitness)
            else:
                theta_new = es_update(theta, noise, fitness, pop, es_cfg, lr=lr)
            theta_new, step_scale = cap_step_norm(theta, theta_new, tc.max_step_norm)
            theta_new, theta_scale = cap_theta_norm(theta_new, tc.theta_max_norm)
            delta = jax.tree_util.tree_map(lambda a, b: a - b, theta_new, theta)

        with jax.named_scope("health"):
            metrics = {
                "opt_score_mean": opt_scores.mean(),
                "opt_score_best": opt_scores.max(),
                "opt_score_worst": opt_scores.min(),
                "sigma_bar": sigma_bar,
                "n_finite": n_finite,
                "theta_norm": global_norm(theta_new),
                "delta_norm": global_norm(delta),
            }
            # ES-semantic health diagnostics (es/ prefix) ride along in the same
            # metrics pytree — no extra dispatches (obs/es_health.py contract).
            metrics.update(
                es_health_metrics(
                    opt_scores=opt_scores,
                    fitness=fitness,
                    delta=delta,
                    prev_delta=prev_delta,
                    cap_theta_scale=theta_scale,
                    cap_step_scale=step_scale,
                    pop_size=pop,
                    antithetic=es_cfg.antithetic,
                )
            )
            for k in REWARD_KEYS:
                if k in rewards:
                    metrics[f"reward/{k}_mean"] = rewards[k].mean()
            # per-prompt raw means (reference per-prompt W&B panels,
            # unifed_es.py:307-310)
            metrics["per_prompt_mean"] = S.mean(axis=0)  # [m]
            # per-member raw combined reward, before promptnorm: the one row
            # of metrics.jsonl that depends on each member's perturbation
            metrics["es/member_reward"] = S.mean(axis=1)  # [pop]
            # per-prompt × per-term quality attribution (quality/ prefix) rides the
            # same pytree — zero extra dispatches (obs/quality.py, the es_health
            # contract; CI asserts the obs/dispatches counter is identical on/off)
            if getattr(tc, "quality", True):
                from ..obs.quality import quality_metrics

                metrics.update(
                    quality_metrics(
                        rewards, pop=pop, num_unique=num_unique, repeats=repeats,
                        reward_keys=REWARD_KEYS,
                    )
                )
            if gen_rows and gen_metrics_fn is not None:
                metrics.update(gen_metrics_fn(gen_rows, pop=pop, antithetic=es_cfg.antithetic))
    return theta_new, delta, metrics, opt_scores


def _resolve_update_fn(tc: TrainConfig, es_cfg, mesh):
    """Resolve ``tc.pop_shard_update`` → ``(update_fn, enabled, n_shards)``.

    ``update_fn`` is ``None`` for the replicated path (off / no mesh / pop
    axis of 1 / base not tiling the axis under "auto") — in which case
    ``_combine_and_update`` traces exactly the pre-PR program. "on" raises
    from the plan when the sharding can't exist (pop_update.py names why).
    """
    from ..parallel.mesh import POP_AXIS
    from ..parallel.pop_update import make_sharded_es_update, pop_shard_update_plan

    mode = getattr(tc, "pop_shard_update", "auto")
    enabled, _reason = pop_shard_update_plan(
        mode, tc.pop_size, es_cfg.antithetic, mesh
    )
    if not enabled:
        return None, False, 1
    return (
        make_sharded_es_update(mesh, tc.pop_size, es_cfg),
        True,
        int(mesh.shape[POP_AXIS]),
    )


PROBE = "probe/"  # prefix of a step's probe arrays (a backend's step_metrics)


def _write_probe_once(metrics: Dict[str, Any], run_dir: Optional[Path], epoch: int) -> Dict[str, Any]:
    """``probe/*`` arrays are what one member produced, kept so that it can be
    checked against a reference (sampled ids, routing, logits): megabytes, not
    a row of ``metrics.jsonl``. The first epoch a run executes writes them to
    ``probe_epoch<k>.npz`` in its run directory; they are dropped from every
    epoch's metrics."""
    probe = {k[len(PROBE):]: v for k, v in metrics.items() if k.startswith(PROBE)}
    if not probe:
        return metrics
    if run_dir is not None and not list(run_dir.glob("probe_epoch*.npz")):
        np.savez(run_dir / f"probe_epoch{epoch}.npz", **probe)
    return {k: v for k, v in metrics.items() if not k.startswith(PROBE)}


def host_reduce_keys(scalars: Dict[str, Any]) -> List[str]:
    """Keys of one epoch's ``scalars`` that a pod averages across hosts in the
    per-epoch ``host_scalar_allgather``: the host-local clocks and the scalar
    ``es/`` health figures. Vector rows (``es/member_reward``, ``[pop]``) stay
    out — the gather carries one float a key, and reward rows are already
    replicated-global (pop_eval all-gathers scores in-graph)."""
    return [
        k for k, v in scalars.items()
        if not isinstance(v, (list, tuple))
        and (k in ("step_time_s", "images_per_sec", "mfu")
             or (k.startswith("es/") and not k.startswith("es/leaf_")))
    ]


def make_host_sharded_programs(
    backend: ESBackend,
    reward_fn: RewardFn,
    tc: TrainConfig,
    num_unique: int,
    repeats: int,
    mesh: Optional["jax.sharding.Mesh"],
    host_slice: Tuple[int, int],
):
    """The pod-scale step split at the EGGROLL seam: two *process-local*
    compiled programs with a host-level fitness gather between them.

    - ``eval_slice(frozen, theta, flat_ids, key) → rewards [lpop, B]`` —
      this host's contiguous member slice, generated and rewarded locally
      (``mesh`` is a local-devices mesh that may shard the slice further).
    - ``update(theta, prev_delta, rewards_full, key) → (θ', Δθ, metrics,
      opt_scores)`` — the identical replicated update every host computes
      from the reassembled ``[pop, B]`` matrix. Noise is *resampled* from
      the same ``key`` split (CRN: bitwise the same draw as eval's, and a
      few low-rank einsum inputs — negligible next to generation FLOPs).

    Why not one spanning-mesh program: XLA:CPU cannot compile cross-process
    programs at all (so none of the distributed recovery paths would be
    testable on the 2-proc CPU rig), and on TPU pods this split is the
    paper's own scaling argument — fitness evaluation is embarrassingly
    parallel, so only ``pop·B`` float32 reward rows cross DCN per epoch,
    never activations or θ.

    Parity contract (asserted by the 2-proc chaos tests): within a topology
    everything is bit-exact — every host computes the identical θ' (same
    update program, same gathered fitness bytes), and an interrupted+resumed
    run matches an uninterrupted one bit-for-bit. ACROSS topologies (1-proc
    fused vs N-proc split) values agree only to XLA program-boundary ulp
    drift: re-chunking the member ``lax.map`` changes fusion and therefore
    float rounding (measured ≤1e-5 on standardized scores, ≤1e-6 on θ after
    2 tiny-rung epochs) — the same boundary PERF.md documents for
    ``reward_tile``. CRN makes the *noise* draws bitwise identical
    everywhere; the drift is purely reward-side rounding.
    """
    from ..backends.base import generate_parts, reward_parts
    from ..parallel.pop_eval import GEN_ROWS, make_population_evaluator

    es_cfg = tc.es_config()
    pop = tc.pop_size
    gen_p, _ = generate_parts(backend)
    rew_p, _ = reward_parts(reward_fn)
    eval_slice_pop = make_population_evaluator(
        gen_p, rew_p, pop, es_cfg, tc.member_batch, mesh,
        reward_tile=tc.reward_tile, host_slice=host_slice,
    )

    def eval_slice(frozen: Pytree, theta: Pytree, flat_ids: jax.Array, key: jax.Array):
        k_noise, k_gen = jax.random.split(key)
        with obs_scope("es_noise"):
            noise = sample_noise(k_noise, theta, pop, es_cfg)
        rewards = eval_slice_pop(frozen, theta, noise, flat_ids, k_gen)
        # a generator's own rows stay on their host: only reward rows cross
        return {k: v for k, v in rewards.items() if not k.startswith(GEN_ROWS)}

    # The pod's replicated update composes with the pop-sharded contraction:
    # the LOCAL mesh's pop axis splits the fitness-weighted noise sum, one
    # intra-host psum rebuilds Δθ — every host still computes the identical
    # θ' from the identical gathered fitness bytes.
    update_fn, _shard_on, _n_upd = _resolve_update_fn(tc, es_cfg, mesh)

    def update(theta: Pytree, prev_delta: Pytree,
               rewards: Dict[str, jax.Array], key: jax.Array):
        k_noise, _ = jax.random.split(key)
        with obs_scope("es_noise"):
            noise = sample_noise(k_noise, theta, pop, es_cfg)
        return _combine_and_update(
            theta, prev_delta, noise, rewards, tc=tc, es_cfg=es_cfg,
            pop=pop, num_unique=num_unique, repeats=repeats,
            update_fn=update_fn,
        )

    return jax.jit(eval_slice), jax.jit(update, donate_argnums=(0, 1))


def make_es_step(
    backend: ESBackend,
    reward_fn: RewardFn,
    tc: TrainConfig,
    num_unique: int,
    repeats: int,
    mesh: Optional["jax.sharding.Mesh"] = None,
    *,
    stateful_delta: bool = False,
    donate: bool = True,
):
    """Build the jitted epoch step for a fixed (m, r) batch plan.

    When ``mesh`` (with ``"pop"``/``"data"`` axes) is given, the population
    and intra-member batch are sharded across devices via shard_map and only
    per-member score rows cross the interconnect (parallel/pop_eval.py).

    Returns ``step(frozen, theta, flat_ids [m·r], key) → (theta', metrics,
    opt_scores)``. ``frozen`` (build with ``make_frozen(backend, reward_fn)``)
    carries every frozen param pytree as an explicit jit *argument* — capturing
    them as closure constants bakes multi-GB weights into the HLO and explodes
    lowering time at flagship geometry.

    ``stateful_delta=True`` (the trainer's variant) instead returns
    ``step(frozen, theta, prev_delta, flat_ids, key) → (theta', delta,
    metrics, opt_scores)``: the applied update Δθ is threaded through so
    ``es/update_cosine`` (obs/es_health.py) can compare consecutive update
    directions *in-graph* — one dispatch per generation either way. The
    default 4-arg form feeds a zero ``prev_delta`` (cosine reads 0) and keeps
    every existing call site (bench.py, __graft_entry__.py, parity tests)
    working unchanged.
    """
    from ..backends.base import generate_parts, reward_parts
    from ..parallel.pop_eval import make_population_evaluator

    es_cfg = tc.es_config()
    pop = tc.pop_size
    gen_p, _ = generate_parts(backend)
    rew_p, _ = reward_parts(reward_fn)
    eval_pop = make_population_evaluator(
        gen_p, rew_p, pop, es_cfg, tc.member_batch, mesh,
        reward_tile=tc.reward_tile,
    )
    update_fn, shard_update_on, n_update_shards = _resolve_update_fn(tc, es_cfg, mesh)

    def core(
        frozen: Pytree,
        theta: Pytree,
        prev_delta: Pytree,
        flat_ids: jax.Array,
        key: jax.Array,
    ):
        k_noise, k_gen = jax.random.split(key)
        with obs_scope("es_noise"):
            noise = sample_noise(k_noise, theta, pop, es_cfg)

        rewards = eval_pop(frozen, theta, noise, flat_ids, k_gen)  # dict of [pop, B]
        # trace-time geometry for the enclosing compile's ledger record
        # (merges with pop_eval's notes — obs/xla_cost.note_program_geometry)
        from ..obs import note_program_geometry

        note_program_geometry(
            pop_shard_update=shard_update_on, update_shards=n_update_shards
        )
        return _combine_and_update(
            theta, prev_delta, noise, rewards, tc=tc, es_cfg=es_cfg,
            pop=pop, num_unique=num_unique, repeats=repeats,
            update_fn=update_fn,
            gen_metrics_fn=getattr(backend, "step_metrics", None),
        )

    # ``donate=False`` (bench.py --fleet): repeated in-process executions of
    # donated programs on XLA:CPU have shown input-aliasing misbehavior
    # (heap corruption / silently clobbered inputs) — a measurement harness
    # re-executing many programs opts out; real training keeps donation
    # (θ/Δ buffers must alias at flagship geometry).
    if stateful_delta:
        return jax.jit(core, donate_argnums=(1, 2) if donate else ())

    def step(frozen: Pytree, theta: Pytree, flat_ids: jax.Array, key: jax.Array):
        zeros = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, x.dtype), theta)
        theta_new, _delta, metrics, opt_scores = core(frozen, theta, zeros, flat_ids, key)
        return theta_new, metrics, opt_scores

    return jax.jit(step, donate_argnums=(1,) if donate else ())


class CompiledProgram(NamedTuple):
    """What :func:`lower_and_compile` hands back: the two stages and the
    program's ``programs.jsonl`` record."""

    lowered: Any
    compiled: Any
    record: Dict[str, Any]


def lower_and_compile(jitted: Callable, args: Tuple[Any, ...], *, label: str,
                      geometry: Dict[str, Any], chain: int = 1) -> CompiledProgram:
    """Lower ``jitted`` on ``args``, compile it, write its ledger record —
    what every compile site of ``run_training`` does inside its ``compile``
    span, under one span each:

    - ``lower`` (``lowering_s``): ``jitted.lower``; jax's own timing of its two
      halves is written beneath it as ``jaxpr_trace`` and ``to_stablehlo``,
      beside the ``trace/<scope>`` spans the step's body opens as it is traced;
    - ``backend_compile`` (``compile_s``): ``lowered.compile()``, a compile or
      a read of the persistent cache — the span's attrs say which, and under
      which key;
    - ``record``: :func:`obs.record_compile` — cost and memory analysis,
      StableHLO stats, the donation audit and, on a traced run, the scope table.

    The provenance (``cache``, ``cache_key``, ``cache_read_s``,
    ``backend_compile_s``, ``jaxpr_trace_s``, ``to_stablehlo_s``; traced also
    ``cache_key_parts``) goes into the record whether or not a tracer is on."""
    tracer = get_tracer()
    with CompileProvenance(key_parts=tracer.enabled) as prov:
        with tracer.span("lower"):
            t0 = time.perf_counter()
            lowered = jitted.lower(*args)
            lowering_s = time.perf_counter() - t0
            for name, t_a, t_b in prov.lower_spans():
                tracer.event(name, t_a, t_b, parent="lower", depth=tracer.depth())
        with tracer.span("backend_compile") as attrs:
            t0 = time.perf_counter()
            compiled = lowered.compile()
            compile_s = time.perf_counter() - t0
            attrs.update(cache=prov.cache, cache_read_s=prov.cache_read_s,
                         cache_key=prov.cache_key)
    with tracer.span("record"):
        record = record_compile(
            site="train", label=label, lowered=lowered, compiled=compiled,
            chain=chain, lowering_s=lowering_s, compile_s=compile_s,
            geometry=geometry, extra=prov.fields(),
        )
    return CompiledProgram(lowered, compiled, record)


def fleet_scalar_args(tc_list) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-job hyperparameter rows for the fleet step, precomputed HOST-side
    with ONE f32 rounding each — identical inputs to the solo program's.

    The solo program bakes ``f32(σ/√r)`` and ``f32(lr_scale·σ)`` as traced
    constants (rounded once from float64 by the Python frontend). The fleet
    program receives the SAME quantities as lane-indexed argument values, so
    they must be rounded the same single time here — computing σ/√r on-device
    from an f32 σ would round twice and feed every member a different
    perturbation scale than its solo run for any σ/rank whose intermediate
    is not exactly representable.

    Returns ``(sigmas [W], c_scales [W], lrs [W])`` as float32 numpy rows,
    where job j contributes ``σ_j``, ``σ_j/√r_j`` and ``lr_scale_j·σ_j``
    from its own TrainConfig.
    """
    import math

    sigmas, c_scales, lrs = [], [], []
    for tcj in tc_list:
        cfg = tcj.es_config()
        sigmas.append(np.float32(cfg.sigma))
        c_scales.append(np.float32(cfg.sigma / math.sqrt(cfg.rank)))
        lrs.append(np.float32(cfg.lr))
    return (
        np.asarray(sigmas, np.float32),
        np.asarray(c_scales, np.float32),
        np.asarray(lrs, np.float32),
    )


def make_fleet_step(
    backend: ESBackend,
    reward_fn: RewardFn,
    tc: TrainConfig,
    num_unique: int,
    repeats: int,
    width: int,
    *,
    donate: bool = True,
):
    """Build the fused W-job epoch step (ISSUE 20 tentpole): ONE compiled
    program advances ``width`` independent ES jobs against one resident
    frozen base.

    Returns ``fleet_step(frozen, stacked_theta, stacked_prev_delta,
    flat_ids [W, m·r], keys [W, ...], sigmas [W], c_scales [W], lrs [W]) →
    (stacked_theta', stacked_delta, metrics, opt_scores [W, pop])`` where
    ``stacked_theta`` is a job-stacked adapter tree (``lora.stack_adapters``
    of W solo trees) and every metrics leaf gains a leading job axis — the
    scheduler (train/fleet.py) unstacks them into ``job<j>/…`` streams.

    Design contracts:

    - **Per-job CRN**: job j's key splits into (noise, gen) exactly as the
      solo step's (``jax.random.split`` per row), and its noise slab is
      ``sample_noise`` under its own ``k_noise`` — counter-based draws with
      no cross-job reduction, so each job's noise is bitwise the solo draw.
    - **Per-job math**: evaluation runs the flat (job, member) lane axis
      (``parallel.pop_eval.make_fleet_evaluator``); fitness shaping and the
      EGGROLL update run per job via ``vmap`` of the SAME
      ``_combine_and_update`` body the solo step traces — the job axis is
      batched, never reduced, so promptnorm standardizes within each job's
      ``[pop, B]`` block, NEVER across jobs (semantically
      ``es.jobwise_prompt_normalized_scores``).
    - **Per-job hypers as argument values**: σ_j/lr_j enter as the
      host-precomputed f32 rows from :func:`fleet_scalar_args`; any job mix
      at a given width reuses one compiled program (the PR-12 serve
      discipline — ``fleet_traces`` stays flat across join/leave).
    - ``tc`` supplies the *cohort* geometry (pop_size, rank, member_batch,
      dtypes, promptnorm, caps) every admitted job must share
      (train/fleet.py enforces); per-job σ/lr are free.

    The fleet path is opt-in (J>1 callers only) — nothing here is reachable
    from the solo ``make_es_step`` trace.
    """
    from ..backends.base import generate_parts, reward_parts
    from ..parallel.pop_eval import make_fleet_evaluator

    es_cfg = tc.es_config()
    pop = tc.pop_size
    W = width
    if W < 1:
        raise ValueError(f"fleet width must be >= 1, got {width}")
    gen_p, _ = generate_parts(backend)
    rew_p, _ = reward_parts(reward_fn)
    eval_fleet = make_fleet_evaluator(
        gen_p, rew_p, W, pop, es_cfg, tc.member_batch,
        reward_tile=tc.reward_tile,
    )

    def fleet_core(
        frozen: Pytree,
        stacked_theta: Pytree,
        stacked_prev_delta: Pytree,
        flat_ids: jax.Array,
        keys: jax.Array,
        sigmas: jax.Array,
        c_scales: jax.Array,
        lrs: jax.Array,
    ):
        # per-job key split — row j bitwise matches the solo step's split
        split = jax.vmap(jax.random.split)(keys)  # [W, 2, key]
        k_noise, k_gen = split[:, 0], split[:, 1]

        # Per-job noise slabs: vmap of the solo sample_noise over the
        # per-job noise keys. Shapes come from job 0's slab — the admission
        # cohort guarantees every job shares adapter geometry, and the draw
        # depends only on (key, shapes). Counter-based RNG batches over keys
        # without cross-key reductions, so slab j is bitwise job j's solo
        # draw; vmap (not lax.map) batches the W slabs' elementwise bit-gen
        # into single ops instead of a serial W-trip loop of tiny ones. The
        # full [W, ...] slab is the output either way — only sampling-time
        # temporaries differ, and those are low-rank factors by design.
        theta0 = lane_slice(stacked_theta, 0, what="job-stacked adapter")
        stacked_noise = jax.vmap(
            lambda kn: sample_noise(kn, theta0, pop, es_cfg)
        )(k_noise)

        rewards = eval_fleet(
            frozen, stacked_theta, stacked_noise, flat_ids, k_gen,
            sigmas, c_scales,
        )  # dict of [W, pop, B]

        def combine_job(theta_j, prev_j, noise_j, rewards_j, lr_j):
            return _combine_and_update(
                theta_j, prev_j, noise_j, rewards_j, tc=tc, es_cfg=es_cfg,
                pop=pop, num_unique=num_unique, repeats=repeats,
                lr=lr_j,
            )

        # vmap (not lax.map): the per-job update math is rank-r adapter ops
        # — tiny tensors whose per-op overhead dominates a serial W-trip
        # loop; batching the job axis turns W trips of small ops into one
        # set of W-wide ops. Reductions stay within each job's block (the
        # batch axis is never reduced), so promptnorm/standardization remain
        # per-job by construction.
        theta_new, delta, metrics, opt_scores = jax.vmap(
            combine_job
        )(stacked_theta, stacked_prev_delta, stacked_noise, rewards, lrs)
        # Raw per-job reward rows [W, pop, B] ride the metrics pytree out:
        # the parity surface against solo runs (bench --fleet / CI
        # fleet_smoke hold them to train/fleet.reward_rows_close; the
        # scheduler pops them before logging). Rows and the *update* outputs
        # above are rounding-tight, not bitwise — the fused and the solo step
        # are different XLA programs, and XLA pins neither fusion nor
        # reduction association across programs (the same documented
        # boundary as reward_tile / the pod eval split; README runbook).
        metrics["fleet_reward_rows"] = rewards["combined"]
        return theta_new, delta, metrics, opt_scores

    # donate=False: same XLA:CPU aliasing caveat as make_es_step — the bench
    # harness re-executes many programs in-process and opts out
    return jax.jit(fleet_core, donate_argnums=(1, 2) if donate else ())


@dataclasses.dataclass
class TrainState:
    theta: Pytree
    epoch: int = 0
    # resilience outcomes (resilience/): the CLI maps these to exit status
    preempted: bool = False  # SIGTERM/SIGINT honored — checkpointed + marker
    halted: bool = False  # rollback policy gave up (halted.json has why)
    rollbacks: int = 0
    # a hard host failure shrank the membership and the survivors took
    # --elastic_action checkpoint_exit: survivor slot committed, clean exit
    # for a relaunch at the new topology (resilience/elastic.py)
    elastic_exit: bool = False
    # THIS rank was voted out by roll-call (its liveness key arrived past a
    # peer's deadline): it committed nothing and must not invite a relaunch
    # that would collide with survivors continuing in the same run dir
    elastic_evicted: bool = False


def run_training(
    backend: ESBackend,
    reward_fn: RewardFn,
    tc: TrainConfig,
    on_epoch_end: Optional[Callable[[int, Dict[str, Any]], None]] = None,
    mesh: Optional["jax.sharding.Mesh"] = None,
) -> TrainState:
    """Full training driver (reference ``unifed_es.main``, unifed_es.py:497-839):
    setup → θ init (or RESUME — a capability the reference lacks, SURVEY.md
    §5.4) → epoch loop → metrics/checkpoints."""
    t_entered = time.perf_counter()  # → the ``trainer_init`` span, back-dated
    from ..obs.es_health import DegeneracyWatchdog
    from ..obs.heartbeat import emit_heartbeat
    from ..obs.multihost import trace_segment_path
    from ..parallel.collectives import (
        GatherTimeout,
        host_allgather_rows,
        host_flag_any,
        host_scalar_allgather,
        is_master,
        process_count,
    )
    from ..parallel.mesh import (
        POP_AXIS,
        initialize_multihost,
        mesh_spans_processes,
        replicate_to_mesh,
    )
    from ..resilience import (
        HALT_MARKER,
        PREEMPT_MARKER,
        PreemptionHandler,
        RollbackController,
        SimulatedCrash,
        fault_epoch,
        get_fault_plan,
        install_fault_plan,
        set_fault_plan,
        set_resilience_registry,
        write_host_snapshot,
        write_marker,
    )
    from ..resilience.checkpoints import CheckpointStore, TopologyMismatch
    from ..resilience.coord import (
        CoordinatedCheckpoint,
        fingerprint_payload,
        fingerprints_agree,
    )
    from .checkpoints import load_legacy_checkpoint
    from .logging import MetricsLogger

    # Idempotent; no-op unless coordinator env vars are set. Must run before
    # backend.setup() touches any device so multi-host pods get a correct
    # process_index for the master-only write discipline below.
    initialize_multihost()
    backend.setup()
    run_dir = Path(tc.run_dir) / tc.auto_run_name(backend.name)
    # Multi-process runs share run_dir on a common filesystem: process 0 owns
    # all writes (metrics JSONL, checkpoints) — the reference's master_only
    # discipline (VAR_models/dist.py:171-194). Every process still *reads*
    # checkpoints on resume (theta is replicated).
    master = is_master()
    pc = process_count()
    logger = MetricsLogger(run_dir) if master else MetricsLogger(None)
    # Launch topology, recorded in every slot manifest and enforced on
    # resume: a slot written by a 4-process pop-split must never silently
    # resume as a 2-process run (resilience/checkpoints.py TopologyMismatch).
    n_pop_axis = mesh.shape.get(POP_AXIS, 1) if mesh is not None else 1
    # Host-sharded population mode (the pod default, "auto"): each process
    # evaluates members [rank·lpop, (rank+1)·lpop) in a LOCAL program and
    # only the [pop, B] fitness rows cross hosts (host_allgather_rows) —
    # the EGGROLL pod contract, and the only distributed form XLA:CPU can
    # run (it cannot compile cross-process programs, see
    # make_host_sharded_programs). "off" keeps the single spanning-mesh
    # SPMD program (TPU pods with cross-host tp/data meshes).
    # "--pop_host_shard on" forces the split eval/update program form even
    # at pc == 1 (the gather degrades to identity): elastic fleets run the
    # SAME per-slice programs at every size, which is what makes a
    # reshard-on-restore trajectory bit-identical to an uninterrupted run
    # at the destination topology (tests/test_multihost_resilience.py).
    host_shard = tc.pop_host_shard == "on" or (
        pc > 1 and tc.pop_host_shard != "off"
    )
    if host_shard:
        from ..parallel.mesh import host_slices

        try:
            slices = host_slices(tc.pop_size, pc)
        except ValueError as e:
            raise ValueError(
                f"{e} (pass --pop_host_shard off for a spanning-mesh launch)"
            ) from None
        host_lo, host_lpop = slices[jax.process_index()]
    else:
        host_lpop, host_lo = tc.pop_size, 0
    topology = {
        "process_count": pc, "pop_shards": int(n_pop_axis),
        "pop_size": tc.pop_size,
        "pop_host_shard": bool(host_shard),
    }
    if host_shard:
        for r in range(pc):
            logger.info(
                f"host pop slices: process {r} -> members "
                f"[{r * host_lpop}..{(r + 1) * host_lpop - 1}]"
                + (f" (local mesh {dict(mesh.shape)})" if mesh is not None else "")
            )
    elif mesh is not None and pc > 1:
        from ..parallel.mesh import pop_slice_plan

        # XLA:CPU cannot compile a cross-process program, so no test or CI
        # chaos job can drive this branch — it is TPU-pod-only and has never
        # run end-to-end on the rigs this repo tests on. Say so at launch
        # rather than letting the first production pod discover it.
        print(
            "[train] WARNING: --pop_host_shard off with a process-spanning "
            "mesh is EXPERIMENTAL — this path cannot be exercised on the "
            "CPU test rig (XLA:CPU has no cross-process programs); the "
            "tested pod mode is the host-sharded default",
            file=sys.stderr, flush=True,
        )
        plan_desc = pop_slice_plan(mesh, tc.pop_size)
        for sh in plan_desc["shards"]:
            lo, hi = sh["members"]
            logger.info(
                f"pop slice plan: shard {sh['shard']} -> members "
                f"[{lo % tc.pop_size}..{(hi - 1) % tc.pop_size}] on "
                f"process(es) {sh['processes']}"
            )

    # Observability (obs/): with tc.trace, EVERY process traces — into its
    # own segment (master: trace.jsonl; process i: trace.<i>.jsonl via
    # obs/multihost.py), so a pod's hosts never clobber one shared timeline.
    # Installed globally so layers without a tracer handle
    # (parallel/pop_eval.py) emit into the same file. The registry is fresh
    # per run — a second same-process run's counters must not include the
    # first run's activity.
    # A tracer that train.cli.main made on entry (enabled, no file yet: it
    # holds the build's spans) is adopted and given this run's file; any
    # other caller gets a fresh one, as before.
    tracer = get_tracer()
    if tc.trace and tracer.enabled and tracer.path is None:
        tracer.attach(trace_segment_path(run_dir))
    else:
        tracer = set_tracer(Tracer(trace_segment_path(run_dir)) if tc.trace else None)
    registry = set_registry(MetricsRegistry())
    # Per-compiled-program XLA ledger (obs/xla_cost.py): one JSON record per
    # AOT compile → run_dir/programs.jsonl. Master-only like metrics.jsonl —
    # every process compiles the same programs, one record suffices.
    ledger = set_ledger(ProgramLedger(run_dir / "programs.jsonl") if master else None)

    # Streaming phase histograms (obs/metrics.Histogram): every completed
    # tracer span of the named trainer phases lands one sample in a
    # phase_<name>_seconds histogram — live on /metrics whether or not a
    # trace FILE is being written (the observer fires on disabled tracers).
    from ..obs.trace import set_span_observer

    _HIST_PHASES = frozenset(
        ("compile", "dispatch", "plan", "log", "checkpoint", "hist", "strip",
         "snapshot")
    )

    def _observe_phase(name: str, dur_s: float) -> None:
        if name in _HIST_PHASES:
            registry.observe(f"phase_{name}_seconds", dur_s)

    set_span_observer(_observe_phase)

    # Resilience (resilience/): fresh per-run counters under resilience/*,
    # the fault plan (config > env > a plan a test pre-installed), the
    # SIGTERM/SIGINT → checkpoint-at-boundary handler, the non-finite
    # rollback policy, and the versioned slot store. Guard decisions key off
    # in-graph replicated scalars (theta_norm), so every host of a pod takes
    # the same action at the same epoch.
    res_registry = set_resilience_registry(None)
    # elastic membership view (resilience/elastic.py): fresh per run, every
    # rank initially live; /healthz serves it and roll-call verdicts /
    # reshard restores append transitions. The incarnation id is stamped
    # after resume resolves the start epoch (all processes agree on it —
    # that agreement is what makes stale liveness keys detectable).
    from ..resilience import elastic as _elastic

    _elastic.reset_membership("pending", list(range(pc)))

    # ---- live telemetry (obs/exporter.py + obs/slo.py) --------------------
    # /metrics + /healthz served from a stdlib daemon thread, per-process
    # port offset in pods (host i → tc.metrics_port + i) so every host
    # exports its own slice. The exporter is pull-only and reads registry
    # snapshots under their own locks — nothing rides the compiled graph.
    from ..obs.exporter import maybe_exporter, note_health, reset_health
    from ..obs.multihost import exporter_port
    from ..resilience.telemetry import host_snapshot_payload

    reset_health()
    # last epoch's numeric scalars (es/*), published to the exporter thread
    # by REFERENCE SWAP: the train loop builds a fresh dict and assigns it
    # into this one-element holder (atomic under the GIL); mutating a dict
    # the HTTP daemon thread is concurrently iterating would intermittently
    # RuntimeError and silently drop the whole es_* section from a scrape
    latest_scalars_ref: Dict[str, Dict[str, Any]] = {"scalars": {}}

    slo_eval = None
    if tc.slo:
        from ..obs.slo import build_trainer_evaluator

        slo_eval = build_trainer_evaluator(tc.slo, registry, res_registry)

    # ES-health anomaly watchdog (obs/anomaly.py): one host-side tick per
    # logged dispatch over the already-fetched scalars — rolling robust-z /
    # changepoint detection on the es/* streams. Master owns the
    # anomalies.jsonl file; every process keeps its own gauges + stderr
    # alerts (a straggling host's anomaly must be visible in its own slice).
    anomaly_watchdog = None
    if tc.anomaly_detect:
        from ..obs.anomaly import AnomalyWatchdog

        anomaly_watchdog = AnomalyWatchdog(
            run_dir=run_dir if master else None,
            window=tc.anomaly_window,
            min_history=tc.anomaly_min_epochs,
            z_thresh=tc.anomaly_z,
        )

    # model-quality ledger (obs/quality.py): one host-side tick per logged
    # dispatch over the same already-fetched scalars — quality.jsonl stream
    # (master-only file, like metrics.jsonl), hardest-prompt ranking, the
    # reward-hacking detector, and the scalar quality/* exporter gauges.
    quality_ledger = None
    if getattr(tc, "quality", True):
        from ..obs.quality import QualityLedger

        quality_ledger = QualityLedger(
            run_dir if master else None,
            reward_keys=REWARD_KEYS,
            hack_window=getattr(tc, "quality_hack_window", 4),
        )

    # pod flight-recorder gauges (obs/podtrace.py), published by the
    # end-of-run merge on rank 0 — same reference-swap discipline as
    # latest_scalars_ref, served through the exporter's linger window
    pod_gauges_ref: Dict[str, Dict[str, Any]] = {"gauges": {}}

    def _healthz() -> Dict[str, Any]:
        from ..resilience.elastic import membership_view

        payload: Dict[str, Any] = {
            "backend": backend.name,
            "run_dir": str(run_dir),
            "topology": topology,
            # live membership (resilience/elastic.py): incarnation, live
            # ranks, every roll-call verdict / reshard restore this run saw
            "membership": membership_view(),
            # the same content resilience.host<i>.json carries — pod
            # liveness is one curl per host, not a file read per machine
            "resilience": host_snapshot_payload(),
            "queue": None,  # trainer has no serve queue; field shape shared
        }
        # last sentry verdict for this run dir, if one was taken (the
        # tools/sentry.py CLI writes it): one curl answers "is this run
        # healthy AND is it fast"
        try:
            from ..obs.regress import VERDICT_FILE

            vpath = run_dir / VERDICT_FILE
            if vpath.exists():
                vdoc = json.loads(vpath.read_text())
                payload["sentry_verdict"] = {
                    "path": str(vpath),
                    "pass": bool(vdoc.get("pass")),
                    "breaches": len(vdoc.get("breaches") or []),
                    "checked": vdoc.get("checked"),
                }
        except Exception as e:
            payload["sentry_verdict"] = {"error": repr(e)}
        return payload

    exporter = maybe_exporter(
        exporter_port(tc.metrics_port),
        host=tc.metrics_host,
        registries=[registry, res_registry]
        + ([slo_eval.registry] if slo_eval is not None else [])
        + ([anomaly_watchdog.registry] if anomaly_watchdog is not None else []),
        scalar_sources=[
            lambda: latest_scalars_ref["scalars"],  # immutable after publish
            lambda: pod_gauges_ref["gauges"],  # pod/* after the merge
            ledger.program_gauges,  # ledger-derived per-program gauges
        ],
        healthz_source=_healthz,
    )
    if exporter is not None:
        logger.info(
            f"live telemetry: /metrics + /healthz on port {exporter.port} "
            f"(process {jax.process_index()})"
        )

    install_fault_plan(tc.faults)
    preempt = PreemptionHandler().install()
    rollback_ctrl = RollbackController(
        policy=tc.rollback_policy, max_rollbacks=tc.max_rollbacks,
        sigma_shrink=tc.rollback_sigma_shrink, explode_norm=tc.theta_explode_norm,
    )
    store = CheckpointStore(run_dir, keep=tc.ckpt_keep)
    # Pod-wide two-phase commit (resilience/coord.py): single-process it is
    # exactly the PR 4 save path; multi-process every host writes + read-back
    # verifies its slot and a unanimous digest vote gates publication.
    coord_ckpt = CoordinatedCheckpoint(run_dir, keep=tc.ckpt_keep)
    if master:
        # stale outcome markers from a previous incarnation: this run is live
        # now, and restart tooling keyed on the markers must not misread a
        # resumed run as still preempted/halted
        for stale in (PREEMPT_MARKER, HALT_MARKER):
            (run_dir / stale).unlink(missing_ok=True)
    # tc_live diverges from tc only under the sigma-shrink rollback policy
    # (σ scaled down after a divergence → the step recompiles).
    tc_live = tc

    def _stall_warn(name: str, phase: str, elapsed: float) -> None:
        registry.inc("stalls")
        print(
            f"[obs] WATCHDOG: {name}/{phase} still running after {elapsed:.0f}s "
            f"(stall cap {tc.stall_cap_s:.0f}s) — see PERF.md 'Observability'",
            file=sys.stderr, flush=True,
        )
        if tc.stall_action == "checkpoint_exit":
            # escalation (runs on the heartbeat thread — request() only
            # latches flags): a straggling host stalls its whole pod at the
            # next collective, so convert the stall into a graceful
            # preemption — checkpoint at the next boundary and exit 0 on
            # EVERY host via the preemption broadcast, instead of burning
            # the grace window printing warnings
            preempt.request(f"stall escalation: {name}/{phase} exceeded "
                            f"{tc.stall_cap_s:.0f}s (--stall_action checkpoint_exit)")

    def _hb(phase: str, **kw):
        # heartbeats go to each process's OWN stderr (never a shared file),
        # tagged with process_index — a stalled non-master host must be as
        # visible as a stalled master
        return maybe_heartbeat(
            "train", phase,
            interval_s=tc.heartbeat_interval_s,
            stall_cap_s=tc.stall_cap_s, on_stall=_stall_warn,
            stall_payload={"stall_action": tc.stall_action}, **kw,
        )

    # ES degeneracy watchdog: N consecutive zero-fitness generations (the
    # es/fitness_zero health metric) means the update has been a no-op for a
    # while — rewards went constant / all-NaN and the degenerate-spread
    # guard is silently zeroing every fitness (obs/es_health.py).
    def _degen_warn(consecutive: int) -> None:
        registry.inc("es_degenerate_warnings")
        emit_heartbeat("train", "es_degenerate", consecutive=consecutive)
        print(
            f"[obs] WATCHDOG: fitness degenerate for {consecutive} consecutive "
            "logged generations — the ES update is a no-op (constant or "
            "all-NaN rewards; see es/fitness_zero and es/reward_std in "
            "metrics.jsonl and PERF.md 'ES health')",
            file=sys.stderr, flush=True,
        )

    degen_watchdog = DegeneracyWatchdog(tc.es_degenerate_warn_epochs, _degen_warn)

    # Uninstall the observability globals on every exit path: spans from
    # later ad-hoc work (or another run) must never append into this run's
    # finished trace.jsonl or counters. `profiling` lives outside the try so
    # the finally can flush a still-open jax.profiler trace when the run
    # raises mid-profile-window (a lost trace is exactly the artifact the
    # window existed to capture).
    profiling = False
    try:
        # everything above (run directory, logger, registries, exporter,
        # watchdogs, checkpoint stores), which ran before this run's tracer
        # had its file
        tracer.event("trainer_init", t_entered, time.perf_counter())
        with tracer.span("setup"):
            theta = backend.init_theta(jax.random.fold_in(jax.random.PRNGKey(tc.seed), 17))
            start_epoch = 0
            restored_delta = None
            if tc.resume:
                # expect_topology: refuse (loudly, naming both geometries) to
                # resume a slot written under a different process count or
                # pop split instead of silently replaying the wrong one —
                # unless --on_topology_mismatch reshard, which restores the
                # replicated arrays and re-splits the member slices over the
                # NEW geometry (resilience/checkpoints.py; pop_size must be
                # unchanged). The experimental spanning-mesh branch keeps
                # the hard refusal: its pop-slice plan lives inside one
                # cross-process program this code cannot recompute.
                on_mismatch = tc.on_topology_mismatch
                if on_mismatch == "reshard" and pc > 1 and not host_shard:
                    on_mismatch = "raise"
                try:
                    res = store.restore(theta, with_delta=True,
                                        expect_topology=topology,
                                        on_mismatch=on_mismatch)
                except TopologyMismatch:
                    if tc.on_topology_mismatch == "reshard" and on_mismatch == "raise":
                        print(
                            "[resilience] --on_topology_mismatch reshard is "
                            "REFUSED for the spanning-mesh --pop_host_shard "
                            "off branch: the population split lives inside "
                            "one cross-process program; relaunch host-"
                            "sharded or with the matching geometry",
                            file=sys.stderr, flush=True,
                        )
                    raise
                if res is not None:
                    theta, start_epoch, restored_delta = res.theta, res.epoch, res.prev_delta
                    logger.info(f"resumed from epoch {start_epoch} (slot {res.slot})")
                    if res.resharded:
                        from ..resilience import elastic

                        stored_topo = (res.meta or {}).get("topology") or {}
                        logger.info(
                            f"reshard-on-restore: slot topology {stored_topo}"
                            f" -> {topology}; this host now evaluates "
                            f"members [{host_lo}..{host_lo + host_lpop - 1}]"
                        )
                        # (the restore itself already ticked
                        # resilience/elastic_reshard_restores)
                        elastic.note_membership(
                            list(range(pc)),
                            transition={
                                "kind": "reshard_restore",
                                "epoch": int(start_epoch),
                                "from": stored_topo, "to": topology,
                            },
                        )
                        if master:
                            elastic.write_transition(run_dir, {
                                "kind": "reshard_restore",
                                "epoch": int(start_epoch),
                                "from": stored_topo, "to": topology,
                                "slot": res.slot,
                            })
                    # Recovery state must survive preemption too: a run whose
                    # σ was shrunk by a rollback would otherwise re-diverge
                    # after every restart with a fresh max_rollbacks budget —
                    # an infinite diverge→rollback→preempt loop that never
                    # reaches the promised halt.
                    slot_cfg = (res.meta or {}).get("config") or {}
                    rollback_ctrl.rollbacks = int(slot_cfg.get("_rollbacks", 0) or 0)
                    slot_sigma = slot_cfg.get("sigma")
                    # only a rollback-shrunk σ overrides the config: a user
                    # intentionally changing --sigma between incarnations
                    # must win when no rollback happened
                    if (
                        rollback_ctrl.rollbacks > 0 and slot_sigma is not None
                        and float(slot_sigma) != tc_live.sigma
                    ):
                        tc_live = dataclasses.replace(tc_live, sigma=float(slot_sigma))
                        logger.info(
                            f"resuming with effective sigma={tc_live.sigma:g} from the "
                            f"checkpoint (config sigma={tc.sigma:g} was shrunk by "
                            f"{rollback_ctrl.rollbacks} rollback(s))"
                        )
                else:
                    restored = load_legacy_checkpoint(run_dir, theta)  # pre-slot dirs
                    if restored is not None:
                        theta, start_epoch = restored
                        logger.info(f"resumed from epoch {start_epoch} (legacy checkpoint)")
            from ..backends.base import make_frozen

            frozen = make_frozen(backend, reward_fn)
            # Previous applied update Δθ_{t−1}, threaded through the stateful
            # step so es/update_cosine is computed in-graph (obs/es_health.py).
            # Zeros at a fresh start; restored from the slot on resume, so the
            # post-resume cosine stream is identical to an uninterrupted run
            # (the resume-parity contract, tests/test_resilience.py).
            # jnp.array (a guaranteed COPY) and not jnp.asarray: restored
            # numpy leaves can be zero-copy aliased into the donated step
            # arguments, leaving the run's θ aliasing npz-owned memory that
            # dies with the restore scope.
            theta = jax.tree_util.tree_map(jnp.array, theta)
            prev_delta = jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, x.dtype), theta
            )
            if restored_delta is not None:
                prev_delta = jax.tree_util.tree_map(jnp.array, restored_delta)
            if mesh is not None:
                # Stage θ and the frozen params replicated over the mesh up front: the
                # step outputs θ' replicated, so a host-placed initial θ would force
                # one throwaway recompile at epoch start+1 (different input sharding).
                # replicate_to_mesh handles meshes that span processes (pods).
                from ..parallel.mesh import replicate_to_mesh

                theta = replicate_to_mesh(theta, mesh)
                prev_delta = replicate_to_mesh(prev_delta, mesh)
                frozen = replicate_to_mesh(frozen, mesh)

        t_setup_done = time.perf_counter()  # → the ``loop_init`` span
        # elastic runtime facts (resilience/elastic.py): the incarnation id
        # every process agrees on (start epoch + launch size — what makes a
        # stale liveness key from a previous incarnation detectable) and the
        # live gather width (shrinks under --elastic_action continue; sizes
        # the reassembled [pop, B] reward matrix below).
        incarnation = f"i{start_epoch}.n{pc}"
        _elastic.set_incarnation(incarnation)
        n_live = pc

        step_cache: Dict[Tuple[int, int], Callable] = {}
        # fitness-gather stamps of the current dispatch (host-sharded pods):
        # the gather is the epoch's FIRST cross-host barrier, so a host's
        # entry stamp is its true arrival (a slow eval shows up here, not at
        # the later scalar gather) — the pod flight recorder's anchor point
        anchor_cell: Dict[str, Tuple[float, float]] = {}

        # Per-epoch host inputs (flat_ids, epoch key) must be staged as
        # *global* replicated arrays when the mesh spans processes: a
        # multi-controller jit rejects host-local inputs, and every process
        # computes identical values (same prompts file, same seed) so the
        # replication is exact. Single-process meshes skip the round-trip.
        if mesh_spans_processes(mesh):
            def _stage(x):
                return replicate_to_mesh(x, mesh)
        else:
            def _stage(x):
                return x

        from ..utils.mfu import (
            device_hbm_bandwidth,
            device_ici_bandwidth,
            device_peak_flops,
            mfu,
        )

        # Per-geometry ledger record (flops, bytes_accessed, peak_bytes, ...)
        # from the compile site — the MFU and roofline inputs per dispatch.
        step_cost: Dict[Tuple[int, int], Dict[str, Any]] = {}
        # host-wall seconds of the latest dispatch per program label — the
        # fallback "measured" side obs/calib.py reconciles when a profiler
        # capture has no device planes (CPU backend) or none was taken
        host_step_s: Dict[str, float] = {}
        n_mesh_devices = (
            int(np.prod(list(mesh.shape.values()))) if mesh is not None else 1
        )
        if tc.profile_epochs > 0:
            # EVERY host captures (was master-only): each process traces its
            # own devices into profile/ (rank 0) or profile.<i>/ — the
            # trace.jsonl segmentation convention (obs/multihost.py), so pod
            # windows attribute per-host device time. `profiling` stays
            # host-consistent (all hosts true), and the chain gate below
            # keys off tc.profile_epochs anyway.
            from ..obs.multihost import profile_segment_path

            _profile_dir = profile_segment_path(run_dir)
            jax.profiler.start_trace(str(_profile_dir))
            profiling = True
            logger.info(f"profiler trace on for {tc.profile_epochs} epochs → {_profile_dir}")

        jit_cache: Dict[Tuple[int, int], Callable] = {}
        chain_cache: Dict[Tuple[int, int, int], Callable] = {}
        out_struct: Dict[Tuple[int, int], Tuple[Any, Any]] = {}

        def _base_geometry(m: int, r: int) -> Dict[str, Any]:
            """The geometry key every compile site records."""
            return {
                "m": m, "r": r, "pop": tc.pop_size,
                "member_batch": tc.member_batch,
                "remat": tc_live.remat,
                "noise_dtype": tc_live.noise_dtype,
                "tower_dtype": tc_live.tower_dtype,
                "base_quant": tc_live.base_quant,
                # topology (every compile site records it, so ledger
                # collective bytes are always attributable to a mesh)
                "mesh_shape": dict(mesh.shape) if mesh is not None else None,
                "n_devices": n_mesh_devices,
            }

        def _epochs_until_due(e: int) -> int:
            """Distance to the next epoch with per-epoch host work (histograms,
            strips, checkpoint) — 0 means e itself is due. Chains must not cross
            such an epoch: its handling needs θ_before and a host round-trip.
            Armed fault-injection epochs count as due for the same reason —
            a fault buried in a chain interior could never fire."""
            d = None
            periods = [tc.log_hist_every, tc.log_images_every, tc.save_every,
                       getattr(tc, "snapshot_every", 0)]
            if pc > 1:
                # the desync fingerprint agreement check is per-epoch host
                # work too: buried in a chain interior it would silently run
                # at boundary cadence instead of the configured one
                periods.append(tc.desync_check_every)
            for every in periods:
                if every:
                    rr = (every - (e + 1) % every) % every
                    d = rr if d is None else min(d, rr)
            plan = get_fault_plan()
            if plan is not None:
                nxt = plan.next_armed_epoch(e)
                if nxt is not None:
                    d = (nxt - e) if d is None else min(d, nxt - e)
            return 10**9 if d is None else d

        last_saved_boundary = -1

        def _do_save(boundary: int, reward: float) -> None:
            """One durable slot at an epoch boundary: θ + Δθ_{t−1} + manifest
            via the coordinated commit (single-process: the plain atomic slot
            store; pods: every host writes + verifies, a unanimous digest
            vote publishes — resilience/coord.py), deduplicated so a
            preemption landing on a save_every boundary writes once. A
            refused commit leaves ``last_saved_boundary`` unchanged, so the
            next due boundary retries instead of trusting a torn slot.
            COLLECTIVE in multi-process runs: every host must reach each call
            (the gating below derives only from replicated state)."""
            nonlocal last_saved_boundary
            if last_saved_boundary == boundary:
                return
            # config carries the EFFECTIVE hypers (tc_live: σ after any
            # shrink) + the spent rollback budget, so recovery state
            # survives a preemption/crash between rollback and completion
            committed = coord_ckpt.save(
                state.theta, boundary, summary_reward=reward,
                backend_name=backend.name,
                config={**dataclasses.asdict(tc_live),
                        "_rollbacks": rollback_ctrl.rollbacks},
                topology=topology,
                prev_delta=prev_delta,
                legacy_mirror=tc.ckpt_legacy_mirror,
            )
            if committed:
                last_saved_boundary = boundary
                res_registry.gauge("last_saved_epoch", boundary)
            # per-host resilience summary beside the (master-only)
            # metrics.jsonl — the run_report per-host panel reads these
            write_host_snapshot(run_dir, epoch=boundary,
                                extra={"committed": bool(committed)})

        state = TrainState(theta=theta, epoch=start_epoch,
                           rollbacks=rollback_ctrl.rollbacks)
        epoch = start_epoch
        # epochs fully applied to state.theta so far — the boundary an
        # elastic survivor checkpoint commits at (bumped after each
        # successful dispatch; a fitness gather that times out mid-epoch
        # leaves it at the previous boundary)
        completed_boundary = start_epoch

        def _elastic_checkpoint_exit(survivors, round_id) -> str:
            """The checkpoint_exit half of the elastic action: commit one
            last slot among the AGREED survivors (two-phase, digest-voted —
            resilience/elastic.survivor_commit) and leave the loop for a
            relaunch at the new topology. A refused commit still exits
            cleanly: the last ratified slot remains authoritative."""
            from ..parallel.collectives import kv_client
            from ..resilience.elastic import survivor_commit

            committed = survivor_commit(
                run_dir, state.theta, int(completed_boundary),
                client=kv_client(), rank=jax.process_index(),
                survivors=survivors, round_id=round_id,
                incarnation=incarnation, keep=tc.ckpt_keep,
                prev_delta=prev_delta, backend_name=backend.name,
                config={**dataclasses.asdict(tc_live),
                        "_rollbacks": rollback_ctrl.rollbacks},
                topology=topology,
            )
            res_registry.inc("elastic_checkpoint_exits")
            state.epoch = int(completed_boundary)
            state.elastic_exit = True
            logger.info(
                f"elastic checkpoint_exit at epoch {completed_boundary} "
                f"(survivor slot "
                f"{'committed' if committed else 'REFUSED — last ratified slot stands'}); "
                f"relaunch at {len(survivors)} process(es) with "
                "--resume auto --on_topology_mismatch reshard"
            )
            return "exit"

        def _adopt_restored(restored, *, clear_programs: bool) -> None:
            """Install a restored slot as the live state — the one restore
            discipline shared by the rollback and elastic-continue paths:
            owned copies (jnp.array, a guaranteed COPY — donated step args
            must never alias npz-owned memory, the setup-time restore
            hazard), zeros Δθ fallback, mesh replication, and the replayed-
            boundary reset (the slot at an already-saved boundary may be
            the rejected/torn one; the save-dedup must not keep it newest
            forever). ``clear_programs`` drops every cached program when σ
            or the member split changed (they recompile next epoch)."""
            nonlocal prev_delta, last_saved_boundary
            state.theta = jax.tree_util.tree_map(jnp.array, restored.theta)
            prev_delta = (
                jax.tree_util.tree_map(jnp.array, restored.prev_delta)
                if restored.prev_delta is not None
                else jax.tree_util.tree_map(
                    lambda x: jnp.zeros(x.shape, x.dtype), state.theta
                )
            )
            if mesh is not None:
                state.theta = replicate_to_mesh(state.theta, mesh)
                prev_delta = replicate_to_mesh(prev_delta, mesh)
            if clear_programs:
                step_cache.clear()
                jit_cache.clear()
                chain_cache.clear()
                out_struct.clear()
                step_cost.clear()
            last_saved_boundary = -1

        def _handle_gather_timeout(gt: "GatherTimeout") -> str:
            """A host-level KV gather timed out: a peer died hard, or is
            slow beyond the deadline. One bounded roll-call round arbitrates
            (resilience/elastic.py); the survivors then take
            ``tc.elastic_action``. Returns "exit" (leave the epoch loop) or
            "continue" (membership shrank / state rolled back — re-enter at
            the updated epoch). The all-alive verdict re-raises loudly: a
            straggler beyond the deadline is an operator problem, and
            neither hanging nor silently replaying a torn gather is an
            answer."""
            nonlocal epoch, prev_delta, host_lo, host_lpop, n_live, \
                last_saved_boundary, completed_boundary
            from ..parallel.collectives import (
                kv_client,
                live_ranks,
                set_live_ranks,
            )
            from ..parallel.mesh import host_slices
            from ..resilience.elastic import (
                note_membership,
                roll_call,
                write_transition,
            )

            res_registry.inc("elastic_gather_timeouts")
            rank = jax.process_index()
            print(f"[resilience] ELASTIC: {gt} — starting roll-call",
                  file=sys.stderr, flush=True)
            rc_res = roll_call(
                kv_client(), rank=rank, ranks=live_ranks(),
                incarnation=incarnation, round_id=f"g{gt.seq}",
            )
            if rc_res.all_alive:
                raise RuntimeError(
                    f"host gather hg{gt.seq} timed out but roll-call found "
                    f"every rank alive (ranks {rc_res.survivors}) — a "
                    f"straggler beyond the KV deadline ({gt.timeout_ms} ms);"
                    " raise HYPERSCALEES_KV_TIMEOUT_MS or fix the slow host"
                ) from gt
            if rc_res.evicted:
                # our liveness key arrived past a peer's deadline: the
                # survivor set — identical on every member by the pure-
                # intersection rule — excludes us. Stand down cleanly; the
                # survivors own the run now, and a self-insistent straggler
                # would fork it.
                print(
                    f"[resilience] ELASTIC: this host (rank {rank}) was "
                    f"voted OUT by roll-call {rc_res.round_id} (survivors "
                    f"{rc_res.survivors}) — standing down cleanly",
                    file=sys.stderr, flush=True,
                )
                res_registry.inc("elastic_evicted")
                state.epoch = int(completed_boundary)
                state.elastic_exit = True
                state.elastic_evicted = True
                return "exit"
            survivors = rc_res.survivors
            action = tc.elastic_action
            print(
                f"[resilience] ELASTIC: roll-call {rc_res.round_id} verdict "
                f"— dead host(s) {rc_res.dead}, survivors {survivors} "
                f"(roll-call took {rc_res.duration_s * 1e3:.0f} ms); "
                f"action={action}",
                file=sys.stderr, flush=True,
            )
            if action == "continue" and tc.pop_size % len(survivors):
                print(
                    f"[resilience] ELASTIC: cannot re-split pop_size="
                    f"{tc.pop_size} over {len(survivors)} survivor(s) — "
                    "falling back to checkpoint_exit",
                    file=sys.stderr, flush=True,
                )
                action = "checkpoint_exit"
            transition = {
                "kind": "rollcall", "round": rc_res.round_id,
                "epoch": int(completed_boundary), "dead": rc_res.dead,
                "survivors": survivors, "action": action,
                "incarnation": incarnation,
                # detection latency = the gather deadline that fired + the
                # bounded roll-call round (PERF.md round 19)
                "detect_s": round(gt.timeout_ms / 1e3 + rc_res.duration_s, 3),
            }
            note_membership(survivors, transition=transition)
            if rank == survivors[0]:
                write_transition(run_dir, transition)
            write_host_snapshot(run_dir, epoch=int(completed_boundary),
                                extra={"elastic": transition})
            if action == "checkpoint_exit":
                return _elastic_checkpoint_exit(survivors, rc_res.round_id)

            # ---- continue: adopt the lost hosts' member slices ------------
            set_live_ranks(survivors)
            n_live = len(survivors)
            if 0 not in survivors:
                # coord.store() re-elects the canonical checkpoint owner,
                # but the observability master (metrics.jsonl, markers,
                # programs.jsonl, report artifacts) is rank 0 and is NOT
                # re-elected — training continues correct but master-blind
                print(
                    "[resilience] ELASTIC WARNING: rank 0 (the "
                    "observability master) is among the dead — metrics.jsonl"
                    "/markers/report artifacts stop; per-host /metrics "
                    "exporters and host snapshots continue. Prefer "
                    "checkpoint_exit + relaunch to restore full telemetry",
                    file=sys.stderr, flush=True,
                )
            restored = None
            try:
                # the last RATIFIED slot is the only pod-agreed state; the
                # in-memory θ is bit-identical across survivors by the
                # replicated-update contract, but agreement proven by the
                # commit digest beats agreement assumed from an invariant
                restored = store.restore(state.theta, with_delta=True,
                                         expect_topology=topology)
            except OSError as e:
                logger.info(f"elastic restore failed after retries ({e!r})")
            if restored is None:
                print(
                    "[resilience] ELASTIC: continue requested but no "
                    "ratified slot to adopt from — falling back to "
                    "checkpoint_exit (never a silent wrong-split replay)",
                    file=sys.stderr, flush=True,
                )
                return _elastic_checkpoint_exit(survivors, rc_res.round_id)
            host_lo, host_lpop = host_slices(
                tc.pop_size, n_live)[survivors.index(rank)]
            # clear_programs: the eval_slice programs have the OLD member
            # slice baked in — the next epoch recompiles for the survivor
            # split (same discipline as the σ-shrink rollback)
            _adopt_restored(restored, clear_programs=True)
            anchor_cell.pop("t", None)
            epoch = int(restored.epoch)
            # θ is the ratified slot's content now — a second GatherTimeout
            # before the next dispatch completes must commit THIS boundary
            completed_boundary = epoch
            state.epoch = epoch
            res_registry.inc("elastic_continues")
            res_registry.gauge("elastic_live_hosts", n_live)
            logger.info(
                f"elastic continue: survivors {survivors} adopt the lost "
                f"member slices — this host now evaluates members "
                f"[{host_lo}..{host_lo + host_lpop - 1}]; replaying from "
                f"ratified slot {restored.slot} (epoch {epoch})"
            )
            return "continue"

        # between ``setup`` and the first ``epoch``: the loop's caches and
        # closures, and the profiler's start where --profile_epochs asks
        tracer.event("loop_init", t_setup_done, time.perf_counter())
        while epoch < tc.num_epochs:
            try:
                with tracer.span("epoch", epoch=epoch):
                    # steady-state epochs run the configured (possibly very
                    # short) gather deadline; a compile below re-arms the
                    # grace for THIS epoch's gathers — peers are compiling
                    # the same program and must not read as dead
                    # (collectives.set_gather_grace)
                    if pc > 1:
                        from ..parallel.collectives import set_gather_grace

                        set_gather_grace(False)
                    t0 = time.perf_counter()
                    with tracer.span("plan"):
                        info: StepInfo = backend.step_info(epoch, tc.prompts_per_gen, tc.batches_per_gen)
                        m, r = len(info.unique_ids), info.repeats
                        flat_ids = _stage(jnp.asarray(np.asarray(info.flat_ids, np.int32)))
                        key = _stage(epoch_key(tc.seed, epoch))
                    if (m, r) not in step_cache:
                        if pc > 1:
                            # every host compiles this geometry at this
                            # epoch: give the epoch's gathers the compile-
                            # grace deadline so a fast-compiling host never
                            # declares its still-compiling peers dead
                            from ..parallel.collectives import set_gather_grace

                            set_gather_grace(True)
                        base_geometry = _base_geometry(m, r)
                        if host_shard:
                            # Pod step = two local programs + one host gather
                            # (make_host_sharded_programs). Both AOT-compiled and
                            # ledger-recorded; step_cost carries the eval program
                            # (it holds ~all the FLOPs the MFU line reports).
                            with tracer.span("compile", m=m, r=r), _hb("compile"):
                                with tracer.span("make_step"):
                                    eval_j, upd_j = make_host_sharded_programs(
                                        backend, reward_fn, tc_live, m, r, mesh,
                                        (host_lo, host_lpop),
                                    )
                                prog_e = lower_and_compile(
                                    eval_j, (frozen, state.theta, flat_ids, key),
                                    label=f"es_eval_slice_m{m}r{r}",
                                    geometry={**base_geometry,
                                              "host_slice": [host_lo, host_lpop]},
                                )
                                # reward-leaf structs come from the lowering
                                # already in hand — jax.eval_shape here would
                                # re-trace the whole generate→reward program
                                # (the largest in the system) a second time
                                rew_struct = jax.tree_util.tree_map(
                                    lambda s: jax.ShapeDtypeStruct(
                                        (n_live * s.shape[0], *s.shape[1:]), s.dtype
                                    ),
                                    prog_e.lowered.out_info,
                                )
                                prog_u = lower_and_compile(
                                    upd_j, (state.theta, prev_delta, rew_struct, key),
                                    label=f"es_update_m{m}r{r}", geometry=base_geometry,
                                )
                            step_cost[(m, r)] = prog_e.record
                            compiled_e, compiled_u = prog_e.compiled, prog_u.compiled

                            def _host_step(fz, th, dl, ids_, key_,
                                           _ev=compiled_e, _up=compiled_u):
                                rew_local = _ev(fz, th, ids_, key_)
                                rew_local = {
                                    k: np.asarray(jax.device_get(v))
                                    for k, v in rew_local.items()
                                }
                                # the ONLY cross-host data of the epoch: [pop, B]
                                # float32 reward rows, bit-exact in rank order.
                                # Entry/exit stamps feed the epoch_anchor event
                                # (obs/podtrace.py): entry = this host's arrival
                                # at the epoch's natural barrier, exit = the
                                # barrier release (near-simultaneous pod-wide —
                                # the exact clock-alignment instant).
                                t_a0 = time.perf_counter()
                                rew_full = host_allgather_rows(rew_local)
                                anchor_cell["t"] = (t_a0, time.perf_counter())
                                return _up(th, dl, rew_full, key_)

                            step_cache[(m, r)] = _host_step
                            registry.inc("compiles", 2)
                        else:
                            # One AOT compile per (m, r) geometry, reused for both
                            # execution and FLOPs accounting — the jit dispatch path
                            # would compile the same program a second time.
                            with tracer.span("compile", m=m, r=r), _hb("compile"):
                                with tracer.span("make_step"):
                                    jitted = make_es_step(
                                        backend, reward_fn, tc_live, m, r, mesh,
                                        stateful_delta=True,
                                    )
                                # one ledger record per AOT compile (obs/xla_cost.py)
                                # → run_dir/programs.jsonl + obs/ gauges
                                prog = lower_and_compile(
                                    jitted,
                                    (frozen, state.theta, prev_delta, flat_ids, key),
                                    label=f"es_step_m{m}r{r}", geometry=base_geometry,
                                )
                            jit_cache[(m, r)] = jitted
                            step_cache[(m, r)] = prog.compiled
                            step_cost[(m, r)] = prog.record
                            registry.inc("compiles")
                        registry.gauge("compile_cache_entries", compile_cache_entries())
                    step = step_cache[(m, r)]

                    # Epochs fused per dispatch: K>1 only in steady state (geometry warm,
                    # nothing due inside the chain, outside the profile window) — per-
                    # dispatch RTT is the dominant cost at small geometry (bench: chained
                    # vs plain). NOTE the gate must be host-CONSISTENT, so it keys off
                    # tc.profile_epochs (same on every host), never local profiler
                    # state: multi-host processes dispatching different programs
                    # (chained vs not) would deadlock the pod's collectives.
                    in_profile_window = (
                        tc.profile_epochs > 0 and epoch - start_epoch < tc.profile_epochs
                    )
                    K = 1
                    # host-sharded pods never chain: the fitness gather is a host
                    # boundary in the middle of every epoch, so a fused K-epoch
                    # device program cannot exist in this mode
                    if (
                        tc.steps_per_dispatch > 1 and not host_shard
                        and not in_profile_window
                        and (m, r) in out_struct and _epochs_until_due(epoch) > 0
                    ):
                        K = min(tc.steps_per_dispatch, tc.num_epochs - epoch, _epochs_until_due(epoch))

                    if K > 1:
                        infos = [info] + [
                            backend.step_info(e, tc.prompts_per_gen, tc.batches_per_gen)
                            for e in range(epoch + 1, epoch + K)
                        ]
                        if any((len(i.unique_ids), i.repeats) != (m, r) for i in infos):
                            K, infos = 1, [info]  # geometry changed mid-chain: fall back
                    if K > 1:
                        ids_k = _stage(jnp.asarray(
                            np.stack([np.asarray(i.flat_ids, np.int32) for i in infos])
                        ))
                        keys_k = _stage(
                            jnp.stack([epoch_key(tc.seed, epoch + j) for j in range(K)])
                        )
                        if (m, r, K) not in chain_cache:
                            if pc > 1:
                                from ..parallel.collectives import set_gather_grace

                                set_gather_grace(True)
                            inner = jit_cache[(m, r)]
                            m0, s0 = out_struct[(m, r)]

                            logger.info(f"compiling {K}-epoch chained step for (m={m}, r={r})")
                            with tracer.span("compile", m=m, r=r, chain=K), _hb("compile"):
                                with tracer.span("make_step"):
                                    mz = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, x.dtype), m0)
                                    sz = jnp.zeros(s0.shape, s0.dtype)

                                    def multi(fz, th, dl, ik, kk):
                                        def body(i, carry):
                                            th_, dl_, _, _ = carry
                                            return inner(fz, th_, dl_, ik[i], kk[i])

                                        # Δθ chains through the carry, so es/update_cosine
                                        # stays per-generation-consecutive inside a chain.
                                        return jax.lax.fori_loop(0, K, body, (th, dl, mz, sz))

                                    jitted_k = jax.jit(multi, donate_argnums=(1, 2))
                                chain_cache[(m, r, K)] = lower_and_compile(
                                    jitted_k,
                                    (frozen, state.theta, prev_delta, ids_k, keys_k),
                                    label=f"es_chain_m{m}r{r}x{K}", chain=K,
                                    geometry=_base_geometry(m, r),
                                ).compiled
                            registry.inc("compiles")
                            registry.gauge("compile_cache_entries", compile_cache_entries())
                        # no device gauges inside the timed window — a gauge is a
                        # device query contending with the dispatch being measured
                        with tracer.span("dispatch", epochs=K), _hb("dispatch", gauges=None):
                            with tracer.span("enqueue"):
                                state.theta, prev_delta, metrics, opt_scores = chain_cache[(m, r, K)](
                                    frozen, state.theta, prev_delta, ids_k, keys_k
                                )
                            # device_get is the execution sync (the fetched values
                            # depend on every chained epoch), so it belongs inside
                            # the dispatch span.
                            with tracer.span("fetch"):
                                metrics = jax.device_get(metrics)
                        info = infos[-1]  # logged prompts = the chain's last epoch
                    else:
                        hist_due = master and tc.log_hist_every and (epoch + 1) % tc.log_hist_every == 0
                        strips_due = master and tc.log_images_every and (epoch + 1) % tc.log_images_every == 0
                        snapshot_due = (master
                                        and getattr(tc, "snapshot_every", 0)
                                        and (epoch + 1) % tc.snapshot_every == 0)
                        theta_before = None
                        if hist_due or strips_due or snapshot_due:
                            # θ is donated into the step; keep a (LoRA-sized, tiny) copy for
                            # Δθ histograms and member-image regeneration
                            theta_before = jax.tree_util.tree_map(jnp.copy, state.theta)

                        with tracer.span("dispatch", epochs=1), _hb("dispatch", gauges=None):
                            # slow@K fault (host-scopable): an injected straggle
                            # INSIDE the traced dispatch phase, so this host's
                            # arrival at the per-epoch gather below is late —
                            # the condition the pod flight recorder's straggler
                            # attribution (obs/podtrace.py) exists to catch
                            if fault_epoch("slow", epoch):
                                from ..resilience import slow_fault_seconds

                                time.sleep(slow_fault_seconds())
                            # enqueue: the call of the compiled step returns once
                            # the program is launched; fetch: device_get waits for
                            # it and copies the metrics out
                            with tracer.span("enqueue"):
                                state.theta, prev_delta, metrics, opt_scores = step(
                                    frozen, state.theta, prev_delta, flat_ids, key
                                )
                            out_struct.setdefault((m, r), (metrics, opt_scores))
                            with tracer.span("fetch"):
                                metrics = jax.device_get(metrics)

                    # the timing boundary first: the memory gauge below is a
                    # device query whose latency must not leak into step_time_s
                    dt = time.perf_counter() - t0
                    epoch_last = epoch + K - 1
                    # epochs [start, completed_boundary) are fully applied to
                    # state.theta — the boundary a survivor checkpoint commits
                    # at when a LATER gather this epoch times out (the fitness
                    # gather raising inside step() never reaches this line, so
                    # the boundary correctly stays at the previous epoch)
                    completed_boundary = epoch_last + 1
                    registry.inc("dispatches")
                    registry.inc("epochs_dispatched", K)
                    # streaming step-time histogram: the latency series the SLO
                    # evaluator and /metrics percentiles read (per-epoch time —
                    # a chained dispatch contributes its amortized share)
                    registry.observe("train_step_time_seconds", dt / K)
                    record_device_memory(registry)
                    n_images = tc.pop_size * m * r * K
                    metrics = _write_probe_once(metrics, run_dir if master else None, epoch_last)
                    scalars = {
                        k: (v.tolist() if getattr(v, "ndim", 0) else float(v)) for k, v in metrics.items()
                    }
                    scalars.update(
                        epoch=epoch_last,
                        # incarnation tag: metrics.jsonl accumulates across
                        # restarts, and elastic relaunches replay epochs —
                        # sentry ingestion folds segments on this (obs/regress)
                        incarnation=int(start_epoch),
                        epochs_chained=K,
                        step_time_s=dt / K,
                        images_scored=n_images,
                        images_per_sec=n_images / max(dt, 1e-9),
                        prompts=info.texts,
                    )
                    prog = step_cost.get((m, r), {})
                    if prog.get("label"):
                        # full-dispatch wall time keyed by the label of the
                        # program actually dispatched (the chained program's
                        # ledger record covers all K epochs)
                        _lbl = (f"es_chain_m{m}r{r}x{K}" if K > 1
                                else prog["label"])
                        host_step_s[f"train/{_lbl}"] = dt
                    u = mfu(prog.get("flops"), dt / K, n_mesh_devices)
                    if u is not None:
                        scalars["mfu"] = u
                    # Roofline verdict for this dispatch (obs/xla_cost.py): which
                    # hardware resource binds the step — compute, HBM bandwidth,
                    # or latency (dispatch/RTT overhead the program model can't
                    # see). Absent on platforms with unknown peaks (CPU).
                    rf = roofline(
                        prog.get("flops"), prog.get("bytes_accessed"), dt / K,
                        peak_flops=device_peak_flops(),
                        hbm_bw=device_hbm_bandwidth(), n_devices=n_mesh_devices,
                        collective_bytes=prog.get("collective_bytes"),
                        ici_bw=device_ici_bandwidth(),
                    )
                    if rf["bound"] is not None:
                        scalars["roofline/bound"] = rf["bound"]
                        scalars["roofline/intensity"] = rf["intensity"]
                        for rk in ("t_compute_s", "t_bandwidth_s", "t_comms_s",
                                   "t_roofline_s"):
                            if rf[rk] is not None:
                                scalars[f"roofline/{rk}"] = rf[rk]
                    # degeneracy watchdog: one observation per logged dispatch —
                    # deliberately NOT scaled by K (chained runs observe only the
                    # tail generation; see DegeneracyWatchdog's counting note)
                    degen_watchdog.update(float(scalars.get("es/fitness_zero", 0.0)) >= 0.5)
                    # ---- per-epoch host agreement gather (pods) ---------------
                    # ONE host-level gather (collectives.host_scalar_allgather)
                    # carries four things: the cross-host metric means, the
                    # desync θ-fingerprint rows, the preemption broadcast flag,
                    # and the non-finite-guard flag — so pod-level agreement
                    # costs one tiny collective per epoch and zero extra device
                    # dispatches. The preempt fault
                    # fires BEFORE the gather so a host-scoped preempt@K:hostI
                    # rides this epoch's rows and every host leaves the loop at
                    # the SAME boundary (a lone exiting host deadlocks the pod's
                    # next in-graph collective).
                    if fault_epoch("preempt", epoch_last):
                        preempt.request(f"fault-injection preempt@{epoch_last}")
                    # nan_theta also fires BEFORE the gather: the non-finite
                    # guard's verdict below must be pod-AGREED — a host-scoped
                    # nan_theta@K:hostI (or a real one-host fork past the explode
                    # norm) rolling back one host alone would desynchronize the
                    # order-keyed host gathers of every later epoch
                    if fault_epoch("nan_theta", epoch_last):
                        state.theta = jax.tree_util.tree_map(
                            lambda x: jnp.full(x.shape, jnp.nan, x.dtype), state.theta
                        )
                        scalars["theta_norm"] = float("nan")
                    local_bad = rollback_ctrl.is_bad(scalars.get("theta_norm"))
                    preempt_now = preempt.requested
                    bad_theta = local_bad
                    desync_detected = False
                    # epoch_anchor (pod flight recorder, obs/podtrace.py):
                    # entry stamp = when THIS host arrived at the epoch's first
                    # cross-host barrier (straggler analytics), exit stamp =
                    # when every host had (near-simultaneous in true time → the
                    # exact clock-alignment point). Host-sharded pods anchor at
                    # the fitness gather inside the step (anchor_cell, the
                    # natural barrier); spanning-mesh pods fall back to the
                    # scalar gather below; single-process runs anchor a
                    # zero-width event so the merge degrades to a no-op merge
                    # instead of a special case.
                    t_anchor0 = t_anchor1 = time.perf_counter()
                    if pc > 1:
                        reduce_keys = host_reduce_keys(scalars)
                        desync_due = (
                            tc.desync_check_every > 0
                            and (epoch_last + 1) % tc.desync_check_every == 0
                        )
                        payload = {k: scalars[k] for k in reduce_keys}
                        payload["_preempt_req"] = 1.0 if preempt.requested else 0.0
                        payload["_bad_theta"] = 1.0 if local_bad else 0.0
                        if desync_due:
                            payload.update(fingerprint_payload(scalars))
                        t_g0 = time.perf_counter()
                        gathered = host_scalar_allgather(payload)
                        t_g1 = time.perf_counter()
                        # prefer the fitness-gather stamps recorded inside this
                        # dispatch (host-sharded pods); the scalar gather is the
                        # fallback barrier for spanning-mesh pods
                        t_anchor0, t_anchor1 = anchor_cell.pop("t", (t_g0, t_g1))
                        # host-local wall-clock/throughput → global means so
                        # metrics.jsonl never logs one host's private view
                        # (reward stats are already replicated-global — pop_eval
                        # all-gathers scores in-graph)
                        scalars.update({k: float(gathered[k].mean()) for k in reduce_keys})
                        scalars["process_count"] = pc
                        preempt_now = bool(gathered["_preempt_req"].max() > 0)
                        if preempt_now and not preempt.requested:
                            # adopt a peer's request so THIS host also checkpoints
                            # and exits 0 at the boundary below
                            preempt.request("preemption broadcast from a peer host")
                        # any host's bad θ is the POD's bad θ: every host takes
                        # the identical rollback/halt branch below
                        bad_theta = bool(gathered["_bad_theta"].max() > 0)
                        if desync_due and not fingerprints_agree(gathered):
                            desync_detected = True
                            res_registry.inc("desync")
                            print(
                                f"[resilience] WATCHDOG: cross-host theta "
                                f"fingerprint DISAGREES at epoch {epoch_last} "
                                f"(theta_norm rows: "
                                f"{[float(v) for v in gathered['_desync_fp/theta_norm']]})"
                                f" — hosts have silently forked; action="
                                f"{tc.desync_action}",
                                file=sys.stderr, flush=True,
                            )
                    # every process records its anchor into its OWN trace
                    # segment; tools/podtrace aligns the segments on the exit
                    # stamps and attributes stragglers from the entry stamps
                    tracer.event("epoch_anchor", t_anchor0, t_anchor1,
                                 epoch=int(epoch_last))

                    # ---- fault injection + non-finite guard (resilience/) -----
                    # desync poisons ONE host's θ with a tiny finite perturbation
                    # (host round-trip: per-host math on a global array would
                    # assert in multi-controller jax) — invisible to the
                    # non-finite guard, caught only by the fingerprint agreement
                    # at the next due check
                    if fault_epoch("desync", epoch_last):
                        def _bump(x):
                            h = np.asarray(jax.device_get(x))
                            return (h * 1.001).astype(h.dtype)

                        bumped = jax.tree_util.tree_map(_bump, state.theta)
                        if mesh is not None:
                            from ..parallel.mesh import replicate_to_mesh

                            state.theta = replicate_to_mesh(bumped, mesh)
                        else:
                            state.theta = jax.tree_util.tree_map(jnp.array, bumped)
                    # bad_theta (computed pre-gather, pod-agreed above): a single
                    # NaN/Inf anywhere in θ poisons the global norm the step
                    # already computes, so the whole-tree health check costs zero
                    # extra device dispatches
                    rollback_action = None
                    if bad_theta:
                        rollback_action = rollback_ctrl.next_action()
                        state.rollbacks = rollback_ctrl.rollbacks
                        res_registry.inc("rollbacks")
                        print(
                            f"[resilience] WATCHDOG: non-finite/diverged theta at epoch "
                            f"{epoch_last} (theta_norm={scalars.get('theta_norm')}) — "
                            f"rollback #{rollback_ctrl.rollbacks}, action={rollback_action}",
                            file=sys.stderr, flush=True,
                        )
                    elif desync_detected:
                        # a fork is a hardware/IO event, not an optimizer
                        # divergence: "rollback" replays from the last agreed
                        # slot with σ untouched (re-syncing every host), "halt"
                        # stops the pod; both draw on the max_rollbacks budget
                        rollback_action = rollback_ctrl.next_action(
                            "replay" if tc.desync_action == "rollback" else "halt"
                        )
                        state.rollbacks = rollback_ctrl.rollbacks
                        res_registry.inc("rollbacks")
                    guard_tripped = bad_theta or desync_detected
                    if K == 1 and hist_due and not guard_tripped:
                        with tracer.span("hist"):
                            scalars.update(
                                _histograms(theta_before, state.theta, np.asarray(jax.device_get(opt_scores)))
                            )
                    # SLO burn-rate evaluation over the streaming histograms —
                    # once per logged dispatch, gauges ride in the same payload
                    if slo_eval is not None:
                        slo_eval.tick()
                        scalars.update(slo_eval.registry.snapshot())
                    # ES-health anomaly tick (obs/anomaly.py): consumes the
                    # scalars already fetched above — the cross-host-reduced
                    # es/* means in pods, so every host reaches the same verdict
                    if anomaly_watchdog is not None:
                        anomaly_watchdog.observe(epoch_last, scalars)
                        scalars.update(anomaly_watchdog.registry.snapshot())
                    # model-quality tick (obs/quality.py): quality.jsonl row +
                    # hardest-prompt ranking + reward-hacking detection over
                    # the same fetched scalars; returns the scalar quality/*
                    # gauges that pass the latest_scalars filter below
                    if quality_ledger is not None:
                        scalars.update(
                            quality_ledger.observe(epoch_last, scalars)
                        )
                    # operational + resilience counters/gauges ride along in the
                    # same JSONL payload (obs/* and resilience/* prefixes)
                    scalars.update(registry.snapshot())
                    scalars.update(res_registry.snapshot())
                    with tracer.span("log"):
                        logger.log(epoch_last, scalars)
                    # live views: the exporter's latest-scalars source (es/*,
                    # reward/*, roofline — everything numeric) + /healthz epoch
                    latest_scalars_ref["scalars"] = {
                        k: v for k, v in scalars.items()
                        if isinstance(v, (int, float)) and not k.startswith("obs/")
                        and not k.startswith("resilience/")
                        # own registries export these two directly
                        and not k.startswith("slo/")
                        and not k.startswith("anomaly/")
                    }
                    note_health(last_completed_epoch=int(epoch_last))

                    if guard_tripped:
                        kind = "non-finite theta" if bad_theta else "cross-host desync"
                        restored = None
                        if rollback_action != "halt":
                            try:
                                # state.theta is poisoned but still a valid structural
                                # template for validating the slot against. Every
                                # host reads the same canonical (published-only)
                                # store, so a pod re-syncs onto identical bytes.
                                restored = store.restore(
                                    state.theta, with_delta=True, expect_topology=topology
                                )
                            except OSError as e:  # transient-I/O retries exhausted
                                logger.info(f"rollback restore failed after retries ({e!r})")
                            # pod-agreed verdict: hosts read the same canonical
                            # store, but a host-local I/O failure must still halt
                            # EVERY host together — one host halting alone would
                            # leave its peers blocked in the next gather
                            restore_failed = restored is None
                            if pc > 1:
                                restore_failed = host_flag_any(restore_failed)
                            if restore_failed:
                                logger.info(
                                    "a peer host has no valid checkpoint slot — halting together"
                                    if restored is not None
                                    else "rollback requested but no valid checkpoint slot — halting"
                                )
                                restored = None
                                rollback_action = "halt"
                        if rollback_action == "halt":
                            if master:
                                write_marker(run_dir, HALT_MARKER, {
                                    "epoch": int(epoch_last),
                                    "reason": kind,
                                    "rollbacks": rollback_ctrl.rollbacks,
                                    "theta_norm": str(scalars.get("theta_norm")),
                                    "policy": (rollback_ctrl.policy if bad_theta
                                               else f"desync_{tc.desync_action}"),
                                })
                            state.halted = True
                            logger.info(
                                f"HALT ({kind}) after {rollback_ctrl.rollbacks} rollback(s) "
                                f"at epoch {epoch_last} — see {HALT_MARKER}"
                            )
                            break
                        # clear_programs only under sigma_shrink: σ is baked
                        # into the compiled step; replay/skip reuse programs
                        _adopt_restored(
                            restored,
                            clear_programs=(rollback_action == "sigma_shrink"),
                        )
                        res_registry.gauge("last_good_epoch", restored.epoch)
                        if rollback_action == "sigma_shrink":
                            # replay from the slot's epoch with gentler noise:
                            # the CRN keys are unchanged, σ is not → new
                            # trajectory (programs recompile next epoch)
                            tc_live = dataclasses.replace(
                                tc_live, sigma=tc_live.sigma * rollback_ctrl.sigma_shrink
                            )
                            epoch = restored.epoch
                            # θ is now the restored slot's: a survivor
                            # checkpoint after a later GatherTimeout must
                            # stamp the restored boundary, not the
                            # pre-rollback one
                            completed_boundary = restored.epoch
                            logger.info(
                                f"rollback → slot {restored.slot}: replaying from epoch "
                                f"{epoch} with sigma={tc_live.sigma:g}"
                            )
                        elif rollback_action == "replay":
                            # desync re-sync: same σ, same CRN keys, same compiled
                            # programs — every host replays from the last agreed
                            # slot on identical bytes
                            epoch = restored.epoch
                            completed_boundary = restored.epoch
                            logger.info(
                                f"desync rollback → slot {restored.slot}: every host "
                                f"replaying from epoch {epoch} (sigma unchanged)"
                            )
                        else:  # skip: keep restored θ, draw fresh noise past the bad epoch
                            epoch = epoch_last + 1
                            # epoch skips FORWARD but θ is the restored
                            # slot's content — an elastic commit of this θ
                            # must carry the slot's boundary (resuming from
                            # it replays, never silently skips, the gap)
                            completed_boundary = restored.epoch
                            logger.info(
                                f"rollback → slot {restored.slot}: skipping past epoch {epoch_last}"
                            )
                        state.epoch = epoch
                        continue

                    if K == 1 and strips_due:
                        with tracer.span("strip"):
                            _save_member_strips(
                                backend, theta_before, tc_live, epoch, info,
                                np.asarray(jax.device_get(opt_scores)), run_dir,
                            )
                    if K == 1 and snapshot_due:
                        # decoded-image grid of the BEST member's prompts —
                        # CRN-exact regeneration from the pre-update θ, saved
                        # under run_dir/snapshots/ and embedded in the run
                        # report's Quality panel. Best-effort: a decode or PNG
                        # failure must never kill training.
                        with tracer.span("snapshot"):
                            try:
                                _save_quality_snapshot(
                                    backend, theta_before, tc_live, epoch,
                                    info,
                                    np.asarray(jax.device_get(opt_scores)),
                                    run_dir,
                                )
                            except Exception as e:
                                registry.inc("cleanup_errors")
                                print(
                                    f"[quality] WARNING: snapshot failed "
                                    f"({type(e).__name__}: {e})",
                                    file=sys.stderr, flush=True,
                                )
                    if profiling and epoch_last + 1 - start_epoch >= tc.profile_epochs:
                        jax.profiler.stop_trace()
                        profiling = False
                        if master:
                            # measured-vs-model reconciliation (obs/calib.py):
                            # parse the just-flushed .xplane.pb capture, join
                            # device durations to programs.jsonl, publish
                            # calib/* gauges (→ /metrics + metrics.jsonl) and
                            # the sentry-ingestible CALIB artifact. Best-
                            # effort: calibration must never kill a run.
                            try:
                                from ..obs import calib as _calib

                                _payload = _calib.calibrate_run(
                                    run_dir, host_measured=host_step_s,
                                    registry=registry,
                                )
                                if _payload["rows"]:
                                    _calib.write_calib(
                                        _payload, run_dir / "CALIB_train.json"
                                    )
                                    logger.info(
                                        "calibration: "
                                        f"{_payload['headline']['rows']} row(s), "
                                        f"{_payload['headline']['device_rows']} "
                                        "with device time → CALIB_train.json"
                                    )
                            except Exception as e:
                                registry.inc("cleanup_errors")
                                print(
                                    f"[obs] WARNING: calibration failed "
                                    f"({type(e).__name__}: {e})",
                                    file=sys.stderr, flush=True,
                                )

                    # die fault: a HARD death — os._exit, no SIGTERM, no
                    # broadcast, no Python cleanup. The peers only learn of it
                    # when their next KV gather times out (GatherTimeout →
                    # elastic roll-call). The graceful twin is preempt@K.
                    if fault_epoch("die", epoch_last):
                        print(
                            f"[resilience] FAULT die@{epoch_last}: hard exit "
                            "(os._exit, no broadcast)",
                            file=sys.stderr, flush=True,
                        )
                        os._exit(1)
                    # crash fault fires BEFORE the periodic save — an unclean
                    # death loses everything since the last committed slot, which
                    # is precisely what the restore scan must recover from
                    if fault_epoch("crash", epoch_last):
                        raise SimulatedCrash(f"injected crash at epoch {epoch_last}")

                    # collective in pods (coordinated commit): gated only on
                    # replicated state, so every host reaches the same boundaries
                    if tc.save_every and (
                        (epoch_last + 1) % tc.save_every == 0 or epoch_last + 1 == tc.num_epochs
                    ):
                        with tracer.span("checkpoint"):
                            _do_save(epoch_last + 1, float(np.asarray(metrics["opt_score_mean"])))
                    res_registry.gauge("last_good_epoch", epoch_last + 1)
                    if on_epoch_end is not None:
                        import inspect

                        # called once per dispatch (the chain's last epoch) when chaining
                        if len(inspect.signature(on_epoch_end).parameters) >= 3:
                            on_epoch_end(epoch_last, scalars, state.theta)
                        else:
                            on_epoch_end(epoch_last, scalars)
                    epoch = epoch_last + 1
                    state.epoch = epoch

                    # ---- preemption: honor SIGTERM/SIGINT (or the preempt fault,
                    # or a stall escalation) at the epoch boundary — checkpoint,
                    # marker, clean exit so a restart with --resume auto continues
                    # bit-identically. Pods decide on the BROADCAST flag (the
                    # agreement gather above): a signal only one host received
                    # still exits every host together, and a signal that arrived
                    # after this epoch's gather waits one boundary so no host
                    # leaves its peers blocked in a collective.
                    if preempt_now if pc > 1 else preempt.requested:
                        with tracer.span("checkpoint"):
                            _do_save(epoch, float(np.asarray(metrics["opt_score_mean"])))
                        if master:
                            write_marker(run_dir, PREEMPT_MARKER, {
                                "epoch": int(epoch), "reason": preempt.reason,
                            })
                        res_registry.gauge("preempted", 1)
                        state.preempted = True
                        logger.info(
                            f"preempted at epoch boundary {epoch} — checkpoint saved; "
                            "resume with --resume auto"
                        )
                        break

            except GatherTimeout as gt:
                if _handle_gather_timeout(gt) == "exit":
                    break
                continue
        return state
    finally:
        # The profiler stop lives HERE, not on the happy path: a run that
        # raises mid-profile-window must still flush its trace to
        # run_dir/profile instead of leaving the profiler running.
        if profiling:
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                # swallowed on purpose (cleanup must not mask the real
                # failure) but never silently: post-mortems need to see it
                registry.inc("cleanup_errors")
                emit_heartbeat("train", "cleanup_error", error=repr(e))
                print(
                    f"[obs] WARNING: cleanup swallowed {e!r} from "
                    "jax.profiler.stop_trace (see obs/cleanup_errors)",
                    file=sys.stderr, flush=True,
                )
        # final per-host resilience summary (resilience.host<i>.json): the
        # run-report panel's only source for non-master hosts, whose
        # resilience/* counters never reach the master-only metrics.jsonl
        try:
            write_host_snapshot(run_dir, epoch=state.epoch, extra={
                "preempted": state.preempted, "halted": state.halted,
                "rollbacks": state.rollbacks,
            })
        except Exception:
            pass  # best-effort summary; never mask the real exit path
        # sample-efficiency artifact (obs/quality.py): fold the run's FINAL
        # metrics.jsonl trajectory into the committed-shape QUALITY payload
        # (reward curve vs cumulative images and device-seconds, calib-joined
        # when a profiler window produced CALIB_train.json). Master-only and
        # best-effort, like the calibration write.
        if master and getattr(tc, "quality", True):
            try:
                from ..obs.quality import build_quality_artifact, write_quality

                _qpayload = build_quality_artifact(run_dir)
                if _qpayload["curve"]:
                    write_quality(_qpayload, run_dir / "QUALITY_train.json")
                    logger.info(
                        f"quality: {_qpayload['epochs']} epoch(s), final "
                        f"reward {_qpayload.get('final_reward'):.6g}, "
                        f"{_qpayload['images_total']:.0f} images "
                        f"({_qpayload['device_s_source']} device-seconds) → "
                        "QUALITY_train.json"
                    )
            except Exception as e:
                registry.inc("cleanup_errors")
                print(
                    f"[quality] WARNING: artifact build failed "
                    f"({type(e).__name__}: {e})",
                    file=sys.stderr, flush=True,
                )
        # pod flight-recorder merge (obs/podtrace.py): rank 0 merges every
        # host's trace segment on the epoch anchors → pod_summary.json +
        # pod/* gauges on the exporter (served through the linger window).
        # Best-effort and post-loop only — the in-loop cost of the recorder
        # is one zero-width trace event per epoch (PERF.md round 18).
        if master and tc.trace and pc > 1:
            try:
                from ..obs.podtrace import (
                    pod_gauges,
                    pod_summary,
                    write_pod_summary,
                )

                summary = pod_summary(run_dir)
                if summary is not None and summary.get("n_hosts", 1) > 1:
                    write_pod_summary(run_dir, summary)
                    pod_gauges_ref["gauges"] = pod_gauges(summary)
                    strag = summary.get("straggler_host")
                    if strag is not None:
                        logger.info(
                            f"pod merge: straggler host {strag} (critical-"
                            f"path share "
                            f"{summary['critical_path_share'][strag]:.2f}, "
                            f"barrier wait "
                            f"{summary['epoch_spread_mean_s'] * 1e3:.0f} "
                            "ms/epoch) — pod_summary.json"
                        )
            except Exception as e:
                print(f"[obs] WARNING: pod trace merge failed ({e!r})",
                      file=sys.stderr, flush=True)
        # the exporter dies with the run: a later same-process run (sweeps,
        # tests) must bind its own port against its own registries. An
        # optional drain window first — short runs end before a pull-based
        # scraper's next poll, and the final state would otherwise be
        # unobservable (the batch-job analog of a push gateway).
        if exporter is not None:
            if tc.metrics_linger_s > 0:
                emit_heartbeat("train", "metrics_linger",
                               linger_s=tc.metrics_linger_s)
                time.sleep(tc.metrics_linger_s)
            try:
                exporter.stop()
            except Exception:
                pass
        set_span_observer(None)
        # gather-deadline grace and elastic membership are process-global:
        # a later same-process run must start from the default state
        try:
            from ..parallel.collectives import set_gather_grace, set_live_ranks

            set_gather_grace(False)
            set_live_ranks(None)
        except Exception:
            pass
        preempt.uninstall()
        # armed-but-unfired faults must never leak into a later same-process
        # run (tests, sweeps); re-arm per run via config/env
        set_fault_plan(None)
        set_resilience_registry(None)
        tracer.close()
        set_tracer(None)
        set_registry(None)
        set_ledger(None)


def _subsample_flat(theta: Pytree, limit: int = 50_000) -> np.ndarray:
    """Host-side flattened θ values, evenly subsampled (utills.py:352-357)."""
    leaves = [np.asarray(jax.device_get(x)).ravel() for x in jax.tree_util.tree_leaves(theta)]
    flat = np.concatenate(leaves) if leaves else np.zeros((0,), np.float32)
    if flat.size > limit:
        idx = np.linspace(0, flat.size - 1, limit).astype(np.int64)
        flat = flat[idx]
    return flat


def _hist_payload(values: np.ndarray, bins: int = 64) -> Dict[str, Any]:
    counts, edges = np.histogram(values, bins=bins)
    return {"counts": counts.tolist(), "edges": edges.tolist()}


def _histograms(theta_before: Pytree, theta_after: Pytree, opt_scores: np.ndarray) -> Dict[str, Any]:
    """θ / Δθ value distributions + raw population scores (the reference's
    wandb histograms, unifed_es.py:815-819, as JSONL-serializable payloads)."""
    t0 = _subsample_flat(theta_before)
    t1 = _subsample_flat(theta_after)
    return {
        "hist/theta": _hist_payload(t1),
        "hist/delta_theta": _hist_payload(t1 - t0),
        "hist/pop_scores": opt_scores.tolist(),
    }


def _save_member_strips(
    backend: ESBackend,
    theta_before: Pytree,
    tc: TrainConfig,
    epoch: int,
    info: StepInfo,
    opt_scores: np.ndarray,
    run_dir: Path,
) -> None:
    """Best/median/worst candidate strips per epoch dir (the reference saves
    them from the live population loop, unifed_es.py:243-264; CRN lets us
    re-generate any member exactly from (seed, epoch, member) instead)."""
    from ..utils.images import make_prompt_strip

    finite = np.where(np.isfinite(opt_scores))[0]
    if finite.size == 0:
        return
    order = finite[np.argsort(opt_scores[finite])]
    members = {
        "worst": int(order[0]),
        "median": int(order[len(order) // 2]),
        "best": int(order[-1]),
    }
    out_dir = run_dir / f"epoch_{epoch:04d}"
    for name, member in members.items():
        imgs = regenerate_member_images(backend, theta_before, tc, epoch, member, info)
        strip = make_prompt_strip(list(imgs), len(info.texts))
        if strip is not None:
            out_dir.mkdir(parents=True, exist_ok=True)
            strip.save(out_dir / f"{name}_member{member}_score{opt_scores[member]:.4f}.png")


def _save_quality_snapshot(
    backend: ESBackend,
    theta_before: Pytree,
    tc: TrainConfig,
    epoch: int,
    info: StepInfo,
    opt_scores: np.ndarray,
    run_dir: Path,
) -> Optional[Path]:
    """Periodic decoded-image grid for the Quality panel: the BEST member's
    full batch, one row per repeat × one column per unique prompt
    (``--snapshot_every``; the reference repo's wandb image logging,
    reproduced as plain PNGs under ``run_dir/snapshots/``). CRN-exact like
    the member strips — regenerated from (seed, epoch, member), nothing held
    in device memory between epochs."""
    from PIL import Image

    from ..utils.images import to_pil

    finite = np.where(np.isfinite(opt_scores))[0]
    if finite.size == 0:
        return None
    best = int(finite[np.argmax(opt_scores[finite])])
    imgs = regenerate_member_images(backend, theta_before, tc, epoch, best, info)
    m = len(info.texts)
    if m <= 0 or len(imgs) == 0:
        return None
    rows = max(1, len(imgs) // m)
    tile = 256
    grid = Image.new("RGB", (tile * m, tile * rows), color=(0, 0, 0))
    # grouped layout [repeat][prompt] — the trainer's reshape order
    for r_i in range(rows):
        for p_i in range(m):
            j = r_i * m + p_i
            if j >= len(imgs) or imgs[j] is None:
                continue
            t = to_pil(imgs[j]).convert("RGB").resize(
                (tile, tile), Image.LANCZOS)
            grid.paste(t, (p_i * tile, r_i * tile))
    out_dir = run_dir / "snapshots"
    out_dir.mkdir(parents=True, exist_ok=True)
    out = out_dir / (
        f"epoch_{epoch:05d}_member{best}_score{opt_scores[best]:.4f}.png"
    )
    grid.save(out)
    return out


def regenerate_member_images(
    backend: ESBackend,
    theta: Pytree,
    tc: TrainConfig,
    epoch: int,
    member: int,
    info: StepInfo,
) -> np.ndarray:
    """Deterministically re-generate one member's images for logging strips.

    CRN makes this exact: the member's perturbation and the shared generation
    key are fully determined by (seed, epoch, member) — no need to keep the
    whole population's images in device memory (the reference saves strips
    from the live loop instead, unifed_es.py:243-264).
    """
    es_cfg = tc.es_config()
    key = epoch_key(tc.seed, epoch)
    k_noise, k_gen = jax.random.split(key)
    noise = sample_noise(k_noise, theta, tc.pop_size, es_cfg)
    theta_k = perturb_member(theta, noise, member, tc.pop_size, es_cfg)
    flat_ids = jnp.asarray(np.asarray(info.flat_ids, np.int32))
    return np.asarray(jax.device_get(backend.generate(theta_k, flat_ids, k_gen)))

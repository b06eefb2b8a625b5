"""Training configuration (the reference's ~100-flag CLI distilled into one
typed dataclass tree — SURVEY.md §5.6 generation 3)."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from ..es.noiser import EggRollConfig


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    # ES core (reference flags: --pop_size --sigma --lr_scale --egg_rank
    # --antithetic --promptnorm, unifed_es.py:332-494)
    num_epochs: int = 100
    pop_size: int = 8
    sigma: float = 0.01
    lr_scale: float = 1.0
    egg_rank: int = 4
    antithetic: bool = True
    promptnorm: bool = True

    # per-epoch batch plan (--prompts_per_gen / --batches_per_gen)
    prompts_per_gen: int = 2
    batches_per_gen: int = 1  # repeats r — images per prompt per member

    # evaluation scheduling: members evaluated concurrently inside the jitted
    # step (lax.map batch_size). The TPU analog of the reference's
    # sequential HOT LOOP 1 (unifed_es.py:159) — raise until memory-bound.
    member_batch: int = 1

    # ---- memory/bandwidth optimization layer (PERF.md round 10) ----------
    # member-interior tiling: each member's generate→decode→preprocess→reward
    # pipeline runs through lax.map over image sub-batches of this size, so
    # the 1024px decode + CLIP tower temps are bounded by one tile instead of
    # the full [m·r] batch (0 = untiled). Value-identical to untiled — the
    # chunk-invariance contract (parallel/pop_eval.py).
    reward_tile: int = 0
    # activation rematerialization policy applied to the DiT scan blocks and
    # DC-AE decoder stages ("none" | "blocks" | "full"). The trainer only
    # *records* it (the backend's model configs carry the applied value —
    # train/cli.py sets both from one flag); θ-trajectory is bit-identical
    # across modes.
    remat: str = "none"
    # storage dtype of the factored ES noise U/V/E — the largest ES-state
    # arrays ("float32" | "bfloat16"; bfloat16 halves them, contractions
    # keep f32 accumulation — es/noiser.py).
    noise_dtype: str = "float32"
    # reward towers' serving compute dtype ("float32" | "bfloat16"). Like
    # remat, recorded here for the ledger — the applied value lives in the
    # tower configs (train/cli.py build_reward_fn / rungs.sana_rung_model).
    tower_dtype: str = "float32"

    # frozen-base storage quantization ("off" | "int8"): the base kernel
    # trees (DiT, DC-AE decoder, CLIP reward towers) stored per-output-
    # channel symmetric int8 in HBM, dequantized at each use site
    # (ops/quant.py) — halves the dominant remaining byte term (the base is
    # re-read per member). Like remat/tower_dtype, recorded here for the
    # ledger; the applied value lives in the frozen param trees themselves
    # (train/cli.py / bench.build quantize them at build time). "off" leaves
    # every tree untouched — the bit-for-bit parity anchor.
    base_quant: str = "off"

    # pop-sharded EGGROLL update (parallel/pop_update.py): "auto" shards the
    # fitness-weighted noise contraction over the mesh's pop axis whenever
    # the base-sample count tiles it (one psum of the adapter-tree partial
    # sums rebuilds Δθ; per-device update FLOPs drop ~n_pop×), falling back
    # to the replicated update otherwise; "on" requires it (raises when the
    # sharding can't exist); "off" keeps the replicated update — the
    # bit-for-bit parity anchor. Mesh-less programs are always replicated.
    pop_shard_update: str = "auto"

    # epochs fused into ONE dispatched program (lax.fori_loop over the ES
    # step): amortizes the per-dispatch host round-trip, the dominant cost
    # at small geometry (PERF.md "tiny" rung). Chains never cross a
    # histogram/strip/checkpoint boundary and metrics are logged once per
    # chain (the last epoch's values). 1 = one dispatch per epoch.
    steps_per_dispatch: int = 1

    # stabilizers (--theta_max_norm / --max_step_norm, defaults per reference)
    theta_max_norm: float = 40.0
    max_step_norm: float = 0.0

    # reward mix (reference default 0.3/0.3/0.2/0.2, rewards.py:171)
    reward_weights: Tuple[float, float, float, float] = (0.3, 0.3, 0.2, 0.2)

    # bookkeeping
    seed: int = 0
    save_every: int = 10
    log_images_every: int = 0  # 0 = never: best/median/worst member strips
    # θ/Δθ value histograms + population reward distribution in the JSONL
    # payload (reference wandb histograms, unifed_es.py:815-819)
    log_hist_every: int = 10
    # capture a jax.profiler trace of the first N epochs into run_dir/profile
    profile_epochs: int = 0
    # observability (obs/): host-side span timeline → run_dir/trace.jsonl
    # (aggregate with tools/trace_report.py; complements profile_epochs'
    # device-side op traces)
    trace: bool = False
    # live telemetry (obs/exporter.py): serve /metrics (Prometheus text)
    # + /healthz (JSON liveness) from a stdlib daemon thread on this port
    # (0 = off). Pod mode offsets by process index (obs/multihost.
    # exporter_port), so every host exports its own telemetry slice.
    metrics_port: int = 0
    # bind address for the exporter. The default serves all interfaces
    # (pods are scraped cross-host by a central Prometheus); operators on
    # shared/internet-reachable machines set 127.0.0.1 for loopback-only
    # (the endpoint is unauthenticated and /healthz names run_dir paths).
    metrics_host: str = "0.0.0.0"
    # exporter drain window: keep /metrics + /healthz up this many seconds
    # AFTER the run completes, so pull-based scrapers (and the CI smoke's
    # curl) can collect the final state of a short run — the batch-job
    # analog of a push gateway. 0 = stop with the run.
    metrics_linger_s: float = 0.0
    # declarative SLOs evaluated once per logged epoch over the streaming
    # histograms (obs/slo.py grammar: "latency_p95=2s,availability=99.9");
    # burn-rate gauges land under slo/* in metrics.jsonl and /metrics, and
    # alerts ride the heartbeat machinery on stderr (None = off)
    slo: Optional[str] = None
    # periodic liveness lines on stderr while compile/dispatch phases block
    # (0 = off): a flagship compile blocks for minutes and must not look
    # like a hang.
    heartbeat_interval_s: float = 0.0
    # stall watchdog: warn via callback when a heartbeat-wrapped phase runs
    # longer than this (0 = off; needs heartbeat_interval_s > 0)
    stall_cap_s: float = 0.0
    # what a stall escalates to: "warn" keeps the stderr WATCHDOG line only;
    # "checkpoint_exit" additionally latches a graceful preemption request —
    # checkpoint at the next epoch boundary and exit 0, coordinated across
    # every host of a pod via the preemption broadcast (a straggler host is
    # a whole-pod problem: its peers block in the next collective)
    stall_action: str = "warn"
    # ES degeneracy watchdog: warn (stderr + obs/es_degenerate_warnings
    # counter) after this many CONSECUTIVE zero-fitness generations — the
    # silent failure mode where the degenerate-spread guard in es/scoring.py
    # zeroes every fitness and θ stops moving with healthy-looking logs
    # (0 = off). Observed via the es/fitness_zero metric (obs/es_health.py).
    es_degenerate_warn_epochs: int = 5
    # ES-health anomaly watchdog (obs/anomaly.py): rolling robust-z /
    # changepoint detection over the es/* streams (update-cosine collapse,
    # pair-asym spikes, cap saturation, reward-std collapse) — host-side,
    # one tick per logged dispatch, zero device work. Fires into
    # anomalies.jsonl + anomaly/* gauges + loud stderr ALERT/CLEAR +
    # /healthz. On by default: the minimum-history gate (anomaly_min_epochs)
    # keeps short smoke runs structurally silent.
    anomaly_detect: bool = True
    # rolling baseline window (logged dispatches) per watched stream
    anomaly_window: int = 32
    # no verdicts before this many observations exist for a stream
    anomaly_min_epochs: int = 8
    # robust z-score magnitude that counts as anomalous (confirmed over
    # consecutive ticks before an ALERT fires)
    anomaly_z: float = 8.0
    # model-quality observability (obs/quality.py): per-prompt × per-term
    # reward attribution inside the jitted step (zero extra dispatches — the
    # es_health contract), quality.jsonl ledger + hardest-prompt ranking +
    # reward-hacking detector host-side, quality/* gauges on /metrics, and
    # the QUALITY_train.json sample-efficiency artifact at run end
    quality: bool = True
    # hacking detector: a non-combined term falling this many CONSECUTIVE
    # logged generations while combined rises fires the stderr ALERT
    quality_hack_window: int = 4
    # decoded-image grid snapshots every N epochs (0 = off): regenerate the
    # best member's images CRN-exact and save a prompt-grid PNG under
    # run_dir/snapshots/ — embedded in the run report's Quality panel
    snapshot_every: int = 0
    run_dir: str = "runs/default"
    resume: bool = True  # the reference writes θ meta but never reads it back
    run_name: Optional[str] = None

    # fault tolerance (resilience/; README "Fault tolerance & preemption
    # runbook"). Checkpoints are versioned slots (run_dir/ckpt/step_<N>/,
    # atomic commit, per-array sha256) — keep the newest ckpt_keep slots
    # (0 = keep all; keep ≥ 2 so a torn newest slot still has a fallback).
    ckpt_keep: int = 3
    # also write the legacy latest_theta.npz/latest_meta.json pair (old
    # tooling reads it; costs one extra θ write per save)
    ckpt_legacy_mirror: bool = True
    # non-finite/divergence guard: when θ's global norm goes NaN/Inf (or
    # exceeds theta_explode_norm, 0 = off), roll back to the last good slot
    # and apply the policy — sigma_shrink (replay with σ × rollback_sigma_
    # shrink), skip (fresh noise past the bad epoch), halt. After
    # max_rollbacks recoveries the run halts regardless (halted.json).
    rollback_policy: str = "sigma_shrink"
    max_rollbacks: int = 3
    rollback_sigma_shrink: float = 0.5
    theta_explode_norm: float = 0.0
    # deterministic fault injection spec (resilience/faultinject.py grammar,
    # incl. host scopes like preempt@3:host1; tests + CI chaos job — None
    # also falls back to $HYPERSCALEES_FAULTS)
    faults: Optional[str] = None

    # ---- pod launch (multi-process runs) ---------------------------------
    # How the population spans processes. "auto"/"on": host-sharded — each
    # process evaluates its contiguous member slice in a process-LOCAL
    # compiled program and only the [pop, B] fitness rows cross hosts per
    # epoch (collectives.host_allgather_rows; the EGGROLL pod contract, and
    # the only distributed form XLA:CPU can execute, so every recovery path
    # tests on a 2-proc CPU rig). "off": one spanning-mesh SPMD program
    # (TPU pods that shard tp/data across hosts). Single-process: ignored.
    pop_host_shard: str = "auto"

    # ---- pod-scale resilience (resilience/coord.py; multi-process runs) --
    # cross-host θ-fingerprint agreement check every N epochs (0 = off).
    # Piggybacks on the per-epoch host scalar gather — zero extra device
    # dispatches, zero extra collectives — and is skipped entirely when
    # process_count == 1, so the default costs single-chip runs nothing.
    desync_check_every: int = 8
    # on divergence: "rollback" restores the last agreed slot on every host
    # (re-syncing the pod; draws on the max_rollbacks budget, σ untouched),
    # "halt" stops the whole pod with halted.json
    desync_action: str = "rollback"

    # ---- elastic topology (resilience/elastic.py; ISSUE 15) --------------
    # resume behavior when the newest slot's launch topology (process count
    # / device pop shards) differs from this launch: "raise" refuses with
    # TopologyMismatch (the PR 6 contract), "reshard" restores the
    # replicated θ/Δθ anyway and re-splits the member slice plan over the
    # NEW geometry — gated on pop_size unchanged, refused for the
    # experimental spanning-mesh --pop_host_shard off branch. This is how a
    # fleet shrinks/grows with preemptible capacity: relaunch at the new N
    # with --on_topology_mismatch reshard.
    on_topology_mismatch: str = "raise"
    # what the survivors do after a hard host failure (a KV gather timeout
    # whose roll-call confirms dead peers): "checkpoint_exit" commits one
    # last slot among the agreed survivors (two-phase, digest-voted) and
    # exits cleanly for a relaunch at the new topology; "continue" adopts
    # the lost hosts' member slices from the last ratified slot and keeps
    # training with the survivor set (requires pop_size divisible by the
    # survivor count — falls back to checkpoint_exit loudly otherwise).
    # Either way: never an indefinite hang, never a silent wrong-split
    # replay.
    elastic_action: str = "checkpoint_exit"

    def es_config(self) -> EggRollConfig:
        return EggRollConfig(
            sigma=self.sigma,
            lr_scale=self.lr_scale,
            rank=self.egg_rank,
            antithetic=self.antithetic,
            noise_dtype=self.noise_dtype,
        )

    def auto_run_name(self, backend_name: str) -> str:
        """Reference-style run-name encoding of key hypers (unifed_es.py:521-527)."""
        if self.run_name:
            return self.run_name
        return (
            f"{backend_name}_pop{self.pop_size}_sig{self.sigma}_lr{self.lr_scale}"
            f"_r{self.egg_rank}_m{self.prompts_per_gen}x{self.batches_per_gen}"
            f"{'_anti' if self.antithetic else ''}{'_pn' if self.promptnorm else ''}"
        )

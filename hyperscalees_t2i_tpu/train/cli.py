"""Unified ES training CLI — the reference ``unifed_es.py`` re-designed.

One command trains any generator family behind the backend protocol
(``python -m hyperscalees_t2i_tpu.train.cli --backend
{sana_one_step,sana_pipeline,var,zimage,infinity} ...`` — reference
``unifed_es.py:336-494``'s ~100-flag surface distilled; same spirit, typed
configs underneath, SURVEY.md §5.6).

Reward towers: real CLIP-B/32 + PickScore(CLIP-H) weights are converted from
HF checkpoints when available locally (zero-egress safe); otherwise
``--allow_random_rewards true`` builds BOTH towers from a seed, clearly
warned, so a smoke run on a sealed machine still runs the full program.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp


def str2bool(v: str) -> bool:
    """Reference's tolerant bool parser (unifed_es.py str2bool)."""
    if isinstance(v, bool):
        return v
    if v.lower() in ("1", "true", "t", "yes", "y"):
        return True
    if v.lower() in ("0", "false", "f", "no", "n"):
        return False
    raise argparse.ArgumentTypeError(f"boolean expected, got {v!r}")


def parse_resume(v: str) -> bool:
    """``--resume`` values: ``auto`` (the runbook spelling — resume from the
    newest valid checkpoint slot when one exists) is an alias of true."""
    if isinstance(v, str) and v.lower() == "auto":
        return True
    return str2bool(v)


def removed_pop_fuse(v: str) -> bool:
    """``--pop_fuse`` chose between two member paths until the factored one
    became the only one: ``true`` is what the program does and is accepted,
    ``false`` asks for a path that no longer exists and is refused."""
    if not str2bool(v):
        raise argparse.ArgumentTypeError(
            "the materialized member path was removed: every member's adapter "
            "reaches the forward factored (es.factored_member_theta); drop the flag"
        )
    return True


def parse_float_list(s: Optional[str]) -> Optional[Tuple[float, ...]]:
    if not s:
        return None
    return tuple(float(x) for x in s.split(",") if x.strip())


def add_backend_flags(p: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """Flags consumed by ``build_backend`` — shared with the eval harness so
    every CLI that constructs a backend accepts the same surface."""
    p.add_argument("--backend", required=True,
                   choices=["sana_one_step", "sana_pipeline", "var", "zimage", "infinity", "lm_ar"])
    p.add_argument("--model_scale", default="full", choices=["tiny", "small", "full"],
                   help="architecture size (tiny/small for smoke runs); lm_ar takes its "
                        "sizes from --lm_config and this sizes its reward towers only")
    p.add_argument("--lm_config", default=None,
                   help="lm_ar: a config.json-shaped file — the model's published keys "
                        "plus this chip's share (experts_held, expert_offset, "
                        "vocab_rows_held) and image_tokens; its model_type names the family "
                        "(models/lm.config_from_json)")
    p.add_argument("--prompt_token_ids", default=None,
                   help='lm_ar: {"prompts": [text], "ids": [[int]]} from the model\'s own '
                        "tokenizer; without it ids are synthesized from --prompts_txt")
    # data
    p.add_argument("--prompts_txt", default=None)
    p.add_argument("--encoded_prompts", default=None,
                   help="encoded-prompt cache (.pt from the reference or .npz)")
    p.add_argument("--labels_path", default=None, help="ImageNet class names (var)")
    p.add_argument("--var_classes", default=None, help="comma class pool, or 'all' (var)")
    # LoRA
    p.add_argument("--lora_r", type=int, default=8)
    p.add_argument("--lora_alpha", type=float, default=16.0)
    p.add_argument("--train_vae_decoder_lora", type=str2bool, default=False)
    # generation
    p.add_argument("--guidance_scale", type=float, default=None)
    p.add_argument("--num_inference_steps", type=int, default=None)
    p.add_argument("--latent_size", type=int, default=None, help="latent grid (per side)")
    p.add_argument("--cfg_list", default=None, help="per-scale guidance, comma list (infinity)")
    p.add_argument("--tau_list", default=None, help="per-scale temperature, comma list (infinity)")
    p.add_argument("--enable_positive_prompt", action="store_true",
                   help="infinity: append the face-quality suffix to person "
                        "prompts (reference --inf_enable_positive_prompt)")
    p.add_argument("--infinity_variant", default=None,
                   help="model preset: 2b, 8b, layer12..layer48 (unifed_es.py INFINITY_VARIANTS)")
    p.add_argument("--pn", default=None, help="scale-schedule preset: 0.06M, 0.25M, 1M")
    p.add_argument("--patch_nums", default=None,
                   help="explicit comma scale schedule for non-canonical VAR "
                        "checkpoints (e.g. 1,2,3,4,5,6,8,10,13,16); the VQ "
                        "pyramid auto-syncs")
    p.add_argument("--quantize_transformer", type=str2bool, default=False)
    # pretrained weights (weights/ converters; reference loads via diffusers /
    # downloaded .pth, models/SanaSprint.py:10-58, models/VAR.py:86-94)
    p.add_argument("--weights", default=None,
                   help="generator checkpoint: diffusers Sana transformer "
                        "(file/dir/safetensors) or var_d*.pth; geometry is "
                        "inferred for sana")
    p.add_argument("--vae_weights", default=None,
                   help="VAE checkpoint: vae_ch160v4096z32.pth for var")
    return p


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="Unified EGGROLL-ES trainer (TPU-native)")
    add_backend_flags(p)
    # ES core (reference: --pop_size --sigma --lr_scale --egg_rank ...)
    p.add_argument("--pop_size", type=int, default=8)
    p.add_argument("--sigma", type=float, default=0.01)
    p.add_argument("--lr_scale", type=float, default=1.0)
    p.add_argument("--egg_rank", type=int, default=4)
    p.add_argument("--antithetic", type=str2bool, default=True)
    p.add_argument("--promptnorm", type=str2bool, default=True)
    p.add_argument("--num_epochs", type=int, default=100)
    p.add_argument("--prompts_per_gen", type=int, default=2)
    p.add_argument("--batches_per_gen", type=int, default=1)
    p.add_argument("--member_batch", type=int, default=1)
    p.add_argument("--steps_per_dispatch", type=int, default=1,
                   help="epochs fused into one dispatched program (amortizes "
                        "the host round-trip; logging cadence follows)")
    # memory/bandwidth optimization layer (PERF.md round 10)
    p.add_argument("--remat", default="none", choices=["none", "blocks", "full"],
                   help="activation rematerialization for the DiT scan blocks "
                        "and DC-AE decoder stages (sana backends); theta "
                        "trajectory is bit-identical across modes")
    p.add_argument("--reward_tile", type=int, default=0,
                   help="member-interior tiling: run each member's decode→"
                        "reward pipeline over image sub-batches of this size "
                        "(bounds 1024px decode + CLIP temps; 0 = untiled, "
                        "value-identical either way)")
    p.add_argument("--noise_dtype", default="float32",
                   choices=["float32", "bfloat16", "bf16"],
                   help="storage dtype of the factored ES noise U/V/E "
                        "(bfloat16 halves the largest ES-state arrays; "
                        "update einsums keep f32 accumulation)")
    p.add_argument("--tower_dtype", default="float32",
                   choices=["float32", "bfloat16", "bf16"],
                   help="reward towers' serving compute dtype (bfloat16 "
                        "halves CLIP activation/resize bytes; layernorm/"
                        "softmax internals stay f32). The v5e flagship fit "
                        "recipe uses bfloat16 (rungs.RUNG_OPT)")
    # parse-only: the benchmark's configuration files still pass it
    p.add_argument("--pop_fuse", type=removed_pop_fuse, help=argparse.SUPPRESS)
    p.add_argument("--base_quant", default="off", choices=["off", "int8"],
                   help="frozen-base storage quantization: int8 stores the "
                        "base kernel trees (DiT, DC-AE decoder, CLIP reward "
                        "towers) per-output-channel symmetric int8 in HBM, "
                        "dequantized at each use site (ops/quant.py) — "
                        "halves the base-weight bytes the hot path re-reads "
                        "per member; LoRA/ES deltas live in the adapter and "
                        "are untouched. The big rungs ship int8 "
                        "(rungs.RUNG_OPT); off is the parity anchor")
    p.add_argument("--pop_shard_update", default="auto",
                   choices=["auto", "on", "off"],
                   help="pop-sharded EGGROLL update: shard the fitness-"
                        "weighted noise contraction over the mesh's pop axis "
                        "(one psum of the adapter-tree partial sums rebuilds "
                        "the full Δθ; per-device update FLOPs drop ~n_pop×). "
                        "auto = whenever the base-sample count tiles the pop "
                        "axis; on = required (error otherwise); off = the "
                        "replicated update, the bit-for-bit parity anchor")
    p.add_argument("--theta_max_norm", type=float, default=40.0)
    p.add_argument("--max_step_norm", type=float, default=0.0)
    # rewards (reference: --w_aesthetic --w_text --w_noart --w_pick)
    p.add_argument("--w_aesthetic", type=float, default=0.3)
    p.add_argument("--w_text", type=float, default=0.3)
    p.add_argument("--w_noart", type=float, default=0.2)
    p.add_argument("--w_pick", type=float, default=0.2)
    p.add_argument("--clip_model", default="openai/clip-vit-base-patch32")
    p.add_argument("--pickscore_model", default="yuvalkirstain/PickScore_v1")
    p.add_argument("--use_pickscore", type=str2bool, default=True)
    p.add_argument("--allow_random_rewards", type=str2bool, default=False,
                   help="proceed with random-init reward towers when HF weights are unavailable")
    # parallelism
    p.add_argument("--pop_shards", type=int, default=0,
                   help="devices on the pop mesh axis (0 = auto: gcd(pop, n_dev))")
    # multihost launch (one process per host; the flags mirror the
    # JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID env vars
    # and win over them — parallel/mesh.initialize_multihost)
    p.add_argument("--coordinator", default=None,
                   help="host:port of process 0's jax.distributed coordinator "
                        "(enables the multihost launch path; see README "
                        "'Multihost launch & pod resilience runbook')")
    p.add_argument("--num_processes", type=int, default=None,
                   help="total processes in the pod (with --coordinator)")
    p.add_argument("--process_id", type=int, default=None,
                   help="this process's rank in [0, num_processes) "
                        "(with --coordinator)")
    p.add_argument("--pop_host_shard", default="auto",
                   choices=["auto", "on", "off"],
                   help="multi-process population split: auto/on = each host "
                        "evaluates its member slice locally, fitness rows "
                        "allgathered at host level (pod default; required on "
                        "CPU pods); off = one spanning-mesh SPMD program")
    # bookkeeping
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--save_every", type=int, default=10)
    p.add_argument("--log_images_every", type=int, default=0,
                   help="save best/median/worst member strips every N epochs")
    p.add_argument("--log_hist_every", type=int, default=10,
                   help="θ/Δθ/reward histograms in metrics.jsonl every N epochs")
    p.add_argument("--profile_epochs", type=int, default=0,
                   help="capture a jax.profiler trace of the first N epochs")
    p.add_argument("--trace", type=str2bool, nargs="?", const=True, default=False,
                   help="write a host-side span timeline to run_dir/trace.jsonl "
                        "(aggregate with tools/trace_report.py)")
    p.add_argument("--metrics_port", type=int, default=0,
                   help="live telemetry: serve /metrics (Prometheus) + "
                        "/healthz (JSON) on this port from a stdlib daemon "
                        "thread (0 = off). Pods offset the port by process "
                        "index — host i exports on port+i (README 'Live "
                        "telemetry & SLOs')")
    p.add_argument("--metrics_host", default="0.0.0.0",
                   help="exporter bind address (default all interfaces — "
                        "pods scrape cross-host; use 127.0.0.1 for "
                        "loopback-only on shared machines: the endpoint "
                        "is unauthenticated)")
    p.add_argument("--metrics_linger_s", type=float, default=0.0,
                   help="keep the exporter up this many seconds after the "
                        "run ends so pull-based scrapers catch the final "
                        "state of a short run (0 = stop with the run)")
    p.add_argument("--slo", default=None,
                   help="declarative SLOs evaluated per epoch, e.g. "
                        "'latency_p95=2s,availability=99.9' — burn-rate "
                        "gauges under slo/* plus loud stderr alerts "
                        "(obs/slo.py; needs nothing else enabled)")
    p.add_argument("--heartbeat_interval_s", type=float, default=0.0,
                   help="liveness lines on stderr every N seconds during "
                        "compile/dispatch phases (0 = off)")
    p.add_argument("--stall_cap_s", type=float, default=0.0,
                   help="warn when a heartbeat-wrapped phase exceeds this many "
                        "seconds (0 = off; needs --heartbeat_interval_s)")
    p.add_argument("--stall_action", default="warn",
                   choices=["warn", "checkpoint_exit"],
                   help="stall-watchdog escalation: warn = stderr line only; "
                        "checkpoint_exit = latch a graceful preemption "
                        "(checkpoint at the next epoch boundary + exit 0, "
                        "broadcast to every host of a pod)")
    p.add_argument("--es_degenerate_warn_epochs", type=int, default=5,
                   help="warn after N consecutive zero-fitness generations "
                        "(the silent degenerate-spread failure; 0 = off)")
    p.add_argument("--anomaly_detect", type=str2bool, default=True,
                   help="ES-health anomaly watchdog: robust changepoint "
                        "detection over es/* streams (update-cosine "
                        "collapse, pair-asym spikes, cap saturation, "
                        "reward-std collapse) → anomalies.jsonl + anomaly/* "
                        "gauges + stderr ALERT/CLEAR + /healthz "
                        "(obs/anomaly.py)")
    p.add_argument("--anomaly_window", type=int, default=32,
                   help="anomaly watchdog rolling-baseline window, in "
                        "logged dispatches")
    p.add_argument("--anomaly_min_epochs", type=int, default=8,
                   help="observations required per stream before the "
                        "watchdog issues any verdict (keeps short smoke "
                        "runs structurally silent)")
    p.add_argument("--anomaly_z", type=float, default=8.0,
                   help="robust z-score magnitude that counts as anomalous")
    p.add_argument("--quality", type=str2bool, default=True,
                   help="model-quality observability (obs/quality.py): "
                        "in-step per-prompt × per-term reward attribution "
                        "(zero extra dispatches), quality.jsonl ledger + "
                        "reward-hacking detector, quality/* gauges, and the "
                        "QUALITY_train.json sample-efficiency artifact")
    p.add_argument("--quality_hack_window", type=int, default=4,
                   help="reward-hacking detector: consecutive logged "
                        "generations a term must fall while combined rises "
                        "before the ALERT fires (0 = detector off)")
    p.add_argument("--snapshot_every", type=int, default=0,
                   help="save a decoded-image grid of the best member's "
                        "prompts every N epochs under run_dir/snapshots/ "
                        "(CRN-exact regeneration, host-side PNG; 0 = off)")
    p.add_argument("--run_dir", default="runs")
    p.add_argument("--run_name", default=None)
    p.add_argument("--resume", type=parse_resume, default=True,
                   help="auto/true: resume from the newest valid checkpoint "
                        "slot (falls back past corrupt slots, then to the "
                        "legacy single-slot layout); false: start fresh")
    # fault tolerance (resilience/; README "Fault tolerance & preemption
    # runbook")
    p.add_argument("--ckpt_keep", type=int, default=3,
                   help="checkpoint slots retained (0 = keep all; keep >= 2 "
                        "so a torn newest slot still has a fallback)")
    p.add_argument("--ckpt_legacy_mirror", type=str2bool, default=True,
                   help="also write the legacy latest_theta.npz mirror")
    p.add_argument("--rollback_policy", default="sigma_shrink",
                   choices=["sigma_shrink", "skip", "halt"],
                   help="action when theta goes non-finite: replay from the "
                        "last good slot with shrunken sigma, skip past the "
                        "bad epoch, or halt immediately")
    p.add_argument("--max_rollbacks", type=int, default=3,
                   help="halt (halted.json, exit 3) after this many rollbacks")
    p.add_argument("--rollback_sigma_shrink", type=float, default=0.5,
                   help="sigma multiplier per sigma_shrink rollback")
    p.add_argument("--theta_explode_norm", type=float, default=0.0,
                   help="also roll back when ||theta|| exceeds this (0 = "
                        "only non-finite triggers)")
    p.add_argument("--faults", default=None,
                   help="deterministic fault-injection spec, e.g. "
                        "'preempt@1;io_error:ckpt_write*2'; tokens take an "
                        "optional :hostI scope ('torn_write@2:host1') "
                        "(resilience/faultinject.py; chaos testing only)")
    # pod-scale resilience (resilience/coord.py; active when multi-process)
    p.add_argument("--desync_check_every", type=int, default=8,
                   help="cross-host theta-fingerprint agreement check every "
                        "N epochs (0 = off; free — rides the per-epoch host "
                        "gather; no-op single-process)")
    p.add_argument("--desync_action", default="rollback",
                   choices=["rollback", "halt"],
                   help="on cross-host divergence: rollback = every host "
                        "restores the last agreed slot and replays (sigma "
                        "unchanged, draws on --max_rollbacks), halt = stop "
                        "the pod with halted.json")
    # elastic topology (resilience/elastic.py; README "Elastic topology
    # runbook")
    p.add_argument("--on_topology_mismatch", default="raise",
                   choices=["raise", "reshard"],
                   help="resume into a different process count: raise = "
                        "refuse with TopologyMismatch (default); reshard = "
                        "restore the replicated theta anyway and re-split "
                        "the member slices over the new geometry (pop_size "
                        "must be unchanged; refused for --pop_host_shard "
                        "off spanning-mesh launches)")
    p.add_argument("--elastic_action", default="checkpoint_exit",
                   choices=["checkpoint_exit", "continue"],
                   help="survivors' action after a hard host failure "
                        "(gather timeout + roll-call confirms dead peers): "
                        "checkpoint_exit = commit one survivor-voted slot "
                        "and exit 0 for a relaunch at the new topology; "
                        "continue = adopt the lost members from the last "
                        "ratified slot and keep training with the survivor "
                        "set")
    return p


def _scaled(args, full: dict, small: dict, tiny: dict) -> dict:
    return {"full": full, "small": small, "tiny": tiny}[args.model_scale]


def build_backend(args):
    from ..backends.infinity_backend import InfinityBackend, InfinityBackendConfig
    from ..backends.sana_backend import SanaBackend, SanaBackendConfig
    from ..backends.var_backend import VarBackend, VarBackendConfig
    from ..backends.zimage_backend import ZImageBackend, ZImageBackendConfig
    from ..es.sampling import parse_int_list
    from ..models import bsq, dcae, infinity as inf_mod, msvq, sana, var as var_mod, vaekl, zimage

    if args.backend in ("sana_one_step", "sana_pipeline"):
        params = None
        if getattr(args, "weights", None):
            from ..weights import convert_sana_transformer, infer_sana_config, load_state_dict

            if getattr(args, "vae_weights", None):
                sys.exit(
                    "ERROR: no DC-AE (AutoencoderDC) converter exists yet — "
                    "--vae_weights is not supported for the sana backends. "
                    "Drop the flag (the DC-AE decoder will be random-init; "
                    "pixel outputs/rewards are then NOT meaningful)."
                )
            sd = load_state_dict(args.weights)
            model_cfg = infer_sana_config(sd)
            params = convert_sana_transformer(sd, model_cfg)
            print(
                f"[cli] loaded sana weights: {model_cfg.n_layers}L d={model_cfg.d_model} "
                f"caption={model_cfg.caption_dim}",
                flush=True,
            )
            print(
                "[cli] WARNING: DC-AE decoder is random-init (no AutoencoderDC "
                "converter yet) — decoded pixels and pixel-space rewards are "
                "not meaningful until a converted VAE is supplied",
                flush=True,
            )
        else:
            mkw = _scaled(args, {}, dict(d_model=1120, n_layers=6, n_heads=35, cross_n_heads=10),
                          dict(d_model=64, n_layers=2, n_heads=4, cross_n_heads=4, caption_dim=32,
                               in_channels=4, out_channels=4, compute_dtype=jnp.float32))
            model_cfg = sana.SanaConfig(**mkw)
        vkw = _scaled(args, {}, dict(channels=(256, 256, 128, 128, 64, 32)),
                      dict(latent_channels=4, channels=(16, 16), blocks_per_stage=(1, 1),
                           attn_stages=(), compute_dtype=jnp.float32))
        lat = args.latent_size or (32 if args.model_scale == "full" else 8)
        # one --remat flag drives both remat sites (DiT scan blocks + DC-AE
        # decoder stages); getattr: the eval harness shares build_backend but
        # not the training-flag surface
        remat = getattr(args, "remat", "none")
        model_cfg = dataclasses.replace(model_cfg, remat=remat)
        cfg = SanaBackendConfig(
            backend_mode="one_step" if args.backend == "sana_one_step" else "pipeline",
            model=model_cfg, vae=dcae.DCAEConfig(**vkw, remat=remat),
            prompts_txt_path=args.prompts_txt, encoded_prompt_path=args.encoded_prompts,
            guidance_scale=args.guidance_scale if args.guidance_scale is not None else 1.0,
            num_inference_steps=args.num_inference_steps or 2,
            width_latent=lat, height_latent=lat,
            lora_r=args.lora_r, lora_alpha=args.lora_alpha,
        )
        return SanaBackend(cfg, params=params)

    if args.backend == "lm_ar":
        from ..backends.lm_backend import LMArBackend, LMBackendConfig
        from ..models import lm

        if not args.lm_config:
            sys.exit("ERROR: --backend lm_ar needs --lm_config <config.json-shaped file> "
                     "(the model's published keys plus experts_held / vocab_rows_held)")
        return LMArBackend(LMBackendConfig(
            model=lm.config_from_json(args.lm_config),
            prompts_txt_path=args.prompts_txt, prompt_token_ids_path=args.prompt_token_ids,
            base_quant=getattr(args, "base_quant", "off"),
            lora_r=args.lora_r, lora_alpha=args.lora_alpha,
        ))

    if args.backend == "var":
        vq_kw = _scaled(args, {}, dict(ch=80, ch_mult=(1, 2, 2, 4), num_res_blocks=1),
                        dict(vocab_size=64, c_vae=8, patch_nums=(1, 2, 4), phi_partial=2,
                             ch=8, ch_mult=(1, 1), num_res_blocks=1,
                             compute_dtype=jnp.float32))
        mkw = _scaled(args, {}, dict(depth=12, d_model=768, n_heads=12),
                      dict(num_classes=10, depth=2, d_model=32, n_heads=4, ff_ratio=2.0,
                           patch_nums=(1, 2, 4), compute_dtype=jnp.float32, top_k=0, top_p=0.0))
        vq = msvq.MSVQConfig(**vq_kw)
        model = var_mod.VARConfig(vq=vq, **mkw)
        params = None
        if getattr(args, "weights", None):
            if not getattr(args, "vae_weights", None):
                sys.exit("ERROR: --backend var --weights also needs --vae_weights "
                         "(vae_ch160v4096z32.pth)")
            from ..weights import infer_var_config, load_state_dict, load_var_params

            # geometry from the checkpoint itself — the reference ships four
            # sizes (var_d{16,20,24,30}.pth) and only the VQVAE/CompVis side
            # is canonical across them
            gs = args.guidance_scale if args.guidance_scale is not None else 4.0
            sd = load_state_dict(args.weights)
            overrides = dict(cfg_scale=gs)
            if args.patch_nums:
                # non-canonical scale schedule (vq pyramid auto-syncs)
                overrides["patch_nums"] = tuple(parse_int_list(args.patch_nums))
            model = infer_var_config(sd, **overrides)
            params = load_var_params(sd, args.vae_weights, model)
            print(
                f"[cli] loaded var weights: depth={model.depth} "
                f"d={model.d_model} heads={model.n_heads}",
                flush=True,
            )
        parsed = parse_int_list(args.var_classes) if args.var_classes else None
        # parse_int_list's ""/"all" sentinel means "whole class table" → None
        pool = tuple(parsed) if isinstance(parsed, (list, tuple)) else None
        cfg = VarBackendConfig(
            model=model, class_pool=pool, labels_path=args.labels_path,
            cfg_scale=model.cfg_scale if params is not None
            else (args.guidance_scale if args.guidance_scale is not None else 4.0),
            lora_r=args.lora_r, lora_alpha=args.lora_alpha,
        )
        return VarBackend(cfg, params=params)

    if args.backend == "zimage":
        params = vae_params = None
        if getattr(args, "weights", None):
            from ..weights import load_state_dict, strip_prefix
            from ..weights.zimage import (
                convert_kl_decoder,
                convert_zimage_transformer,
                infer_kl_decoder_config,
                infer_zimage_config,
            )

            sd = strip_prefix(load_state_dict(args.weights), "model")
            model_cfg = infer_zimage_config(sd)
            params = convert_zimage_transformer(sd, model_cfg)
            print(
                f"[cli] loaded zimage weights: {model_cfg.n_layers}L "
                f"d={model_cfg.d_model} caption={model_cfg.caption_dim}",
                flush=True,
            )
            vae_cfg = vaekl.VAEDecoderConfig(blocks_per_stage=3)  # diffusers layout
            if getattr(args, "vae_weights", None):
                sd_v = load_state_dict(args.vae_weights)
                vae_cfg = infer_kl_decoder_config(sd_v)
                vae_params = convert_kl_decoder(sd_v, vae_cfg)
                print(
                    f"[cli] loaded KL-VAE decoder weights (ch={vae_cfg.ch})",
                    flush=True,
                )
            else:
                print(
                    "[cli] WARNING: KL-VAE decoder is random-init — decoded "
                    "pixels and pixel-space rewards are not meaningful until "
                    "--vae_weights supplies the AutoencoderKL checkpoint",
                    flush=True,
                )
        else:
            mkw = _scaled(args, {}, dict(d_model=512, n_layers=6, n_heads=8),
                          dict(in_channels=4, d_model=24, n_layers=2, n_heads=2, caption_dim=12,
                               ff_ratio=2.0, compute_dtype=jnp.float32))
            model_cfg = zimage.ZImageConfig(**mkw)
            vkw = _scaled(args, {}, dict(ch=(256, 128, 64)),
                          dict(latent_channels=4, ch=(8, 8), blocks_per_stage=1, compute_dtype=jnp.float32))
            vae_cfg = vaekl.VAEDecoderConfig(**vkw)
        lat = args.latent_size or (16 if args.model_scale != "tiny" else 4)
        cfg = ZImageBackendConfig(
            model=model_cfg, vae=vae_cfg,
            prompts_txt_path=args.prompts_txt, encoded_prompt_path=args.encoded_prompts,
            num_steps=args.num_inference_steps or 8,
            guidance_scale=args.guidance_scale if args.guidance_scale is not None else 0.0,
            width_latent=lat, height_latent=lat,
            quantize_transformer=args.quantize_transformer,
            lora_r=args.lora_r, lora_alpha=args.lora_alpha,
            train_vae_decoder_lora=args.train_vae_decoder_lora,
        )
        return ZImageBackend(cfg, params=params, vae_params=vae_params)

    if args.backend == "infinity":
        params = None
        if getattr(args, "weights", None):
            from ..weights import load_state_dict, strip_prefix
            from ..weights.infinity import (
                convert_infinity_transformer,
                infer_infinity_config,
            )

            overrides = {}
            if args.infinity_variant:  # explicit geometry wins (sets n_heads)
                overrides = dict(inf_mod.INFINITY_PRESETS[args.infinity_variant])
            sd = strip_prefix(load_state_dict(args.weights), "module")
            model = infer_infinity_config(sd, **overrides)
            if args.pn:  # scale schedule must be set BEFORE conversion:
                # lvl_emb is sliced to len(patch_nums) at convert time
                pns = inf_mod.PN_PRESETS[args.pn]
                model = dataclasses.replace(
                    model, patch_nums=pns,
                    vq=dataclasses.replace(model.vq, patch_nums=pns),
                )
            params = convert_infinity_transformer(sd, model)
            print(
                f"[cli] loaded infinity weights: depth={model.depth} "
                f"d={model.d_model} bits={model.vq.bits}",
                flush=True,
            )
        elif args.infinity_variant:
            model = inf_mod.from_preset(args.infinity_variant)
        else:
            mkw = _scaled(args, {}, dict(depth=8, d_model=512, n_heads=8),
                          dict(depth=2, d_model=16, n_heads=2, ff_ratio=2.0, text_dim=12,
                               patch_nums=(1, 2, 4), compute_dtype=jnp.float32))
            model = inf_mod.InfinityConfig(**mkw)
        if args.pn and params is None:  # weights path applied pn pre-convert
            pns = inf_mod.PN_PRESETS[args.pn]
            model = dataclasses.replace(
                model, patch_nums=pns, vq=dataclasses.replace(model.vq, patch_nums=pns)
            )
        elif args.model_scale == "tiny" and params is None:
            # vq bits must stay in sync with converted word_embed/head dims
            model = dataclasses.replace(
                model,
                vq=bsq.BSQConfig(bits=4, patch_nums=model.patch_nums, phi_partial=2,
                                 dec_ch=(8, 8), dec_blocks=1, compute_dtype=jnp.float32),
            )
        cfg = InfinityBackendConfig(
            model=model, prompts_txt_path=args.prompts_txt,
            encoded_prompt_path=args.encoded_prompts,
            vae_weights=getattr(args, "vae_weights", None),
            enable_positive_prompt=getattr(args, "enable_positive_prompt", False),
            cfg_list=parse_float_list(args.cfg_list), tau_list=parse_float_list(args.tau_list),
            lora_r=args.lora_r, lora_alpha=args.lora_alpha,
        )
        return InfinityBackend(cfg, params=params)

    raise ValueError(args.backend)


def load_clip_tower(name: str, cfg) -> Optional[Any]:
    """Convert a locally-cached HF CLIP checkpoint to our param layout
    (models/clip.py convert_hf_clip_state_dict). None when unavailable (on a
    machine without network set ``HF_HUB_OFFLINE=1`` so the lookup fails at
    once instead of after connection retries)."""
    try:  # pragma: no cover - environment dependent
        from transformers import CLIPModel

        from ..models.clip import convert_hf_clip_state_dict

        m = CLIPModel.from_pretrained(name)
        return convert_hf_clip_state_dict(m.state_dict(), cfg)
    except Exception:
        return None


def build_reward_fn(args, backend):
    from ..models import clip as clip_mod
    from ..obs import block_if_tracing, span as obs_span
    from ..ops.quant import maybe_quantize_tree
    from ..rewards.suite import (
        AESTHETIC_TEXT,
        NEGATIVE_TEXT,
        RewardWeights,
        clip_text_embed_table,
        make_clip_reward_fn,
        pickscore_text_embeds,
        tokenize_with_hf,
    )

    weights = RewardWeights(args.w_aesthetic, args.w_text, args.w_noart, args.w_pick)
    cparams = pparams = pcfg = None
    if args.model_scale == "tiny":
        ccfg = clip_mod.CLIPConfig(
            vision=clip_mod.CLIPTowerConfig(16, 2, 2, 32),
            text=clip_mod.CLIPTowerConfig(16, 2, 2, 32),
            image_size=32, patch_size=16, vocab_size=49408, max_positions=77,
            projection_dim=16,
        )
    else:
        # the towers the trainer dispatches must be configurable to the
        # geometry the preflight fit gate certified (rungs.RUNG_OPT ships
        # bf16 serving dtype + remat at the big rungs) — stock f32 towers
        # stay the default for bit-compat with older runs
        from ..utils.pytree import resolve_float_dtype

        tower_dt = resolve_float_dtype(getattr(args, "tower_dtype", "float32"))
        tower_remat = getattr(args, "remat", "none")
        ccfg = dataclasses.replace(
            clip_mod.CLIP_B32, compute_dtype=tower_dt, remat=tower_remat
        )
        with obs_span("clip_b"):
            cparams = load_clip_tower(args.clip_model, ccfg)
        if cparams is None:
            if not args.allow_random_rewards:
                sys.exit(
                    "ERROR: CLIP weights unavailable (no local HF cache). Pass "
                    "--allow_random_rewards true for a smoke run with random towers."
                )
            print("[cli] WARNING: random-init CLIP reward tower (smoke mode)", flush=True)
        if args.use_pickscore:
            pcfg = dataclasses.replace(
                clip_mod.CLIP_H14, compute_dtype=tower_dt, remat=tower_remat
            )
            with obs_span("clip_h"):
                pparams = load_clip_tower(args.pickscore_model, pcfg)
            if pparams is None and args.allow_random_rewards:
                # the smoke's program must be the flagship program: the
                # largest reward tower is built from a seed like the CLIP-B
                # one, never dropped
                print("[cli] WARNING: random-init PickScore (CLIP-H/14) "
                      "reward tower (smoke mode)", flush=True)
            elif pparams is None:
                # renormalize the remaining components so the combined
                # objective keeps the same total mass instead of silently
                # shrinking by w_pick (reference just warns and proceeds,
                # unifed_es.py)
                pcfg = None
                rest = weights.aesthetic + weights.align + weights.no_artifacts
                if rest > 0 and weights.pickscore > 0:
                    scale = (rest + weights.pickscore) / rest
                    weights = RewardWeights(
                        aesthetic=weights.aesthetic * scale,
                        align=weights.align * scale,
                        no_artifacts=weights.no_artifacts * scale,
                        pickscore=0.0,
                    )
                print(
                    "[cli] WARNING: PickScore tower unavailable → pickscore dropped, "
                    f"remaining reward weights renormalized to {weights}",
                    flush=True,
                )

    base_quant = getattr(args, "base_quant", "off")

    def towers(cparams, pparams):
        """Everything the reward needs, in ONE compiled program: seeded
        towers where no checkpoint was loaded, the text-embed tables from
        the FULL-precision towers (one-time work — quantizing the text side
        would buy nothing at run time), then the per-step towers under
        ``--base_quant``. Loaded float trees arrive donated, so a float and
        an int8 copy of CLIP-H never outlive the call together."""
        if cparams is None:
            cparams = clip_mod.init_clip(jax.random.PRNGKey(11), ccfg)
        out = {"table": clip_text_embed_table(cparams, ccfg, ids, eot, mask)}
        if pcfg is not None:
            if pparams is None:
                pparams = clip_mod.init_clip(jax.random.PRNGKey(12), pcfg)
            out["pick_embeds"] = pickscore_text_embeds(pparams, pcfg, *ptok)
            out["pparams"] = maybe_quantize_tree(pparams, base_quant)
        out["cparams"] = maybe_quantize_tree(cparams, base_quant)
        return out

    # tokenization and the one ``towers`` program (seeded towers where none
    # was loaded, both text tables, the int8 towers)
    with obs_span("text_tables"):
        texts = list(backend.texts)
        ids, eot, mask = tokenize_with_hf(texts + [AESTHETIC_TEXT, NEGATIVE_TEXT], args.clip_model)
        ptok = tokenize_with_hf(texts, args.pickscore_model) if pcfg is not None else None
        out = block_if_tracing(jax.jit(towers, donate_argnums=(0, 1))(cparams, pparams))
    return make_clip_reward_fn(
        out["cparams"], ccfg, out["table"], weights=weights,
        pick_params=out.get("pparams"), pick_cfg=pcfg,
        pick_text_embeds=out.get("pick_embeds"),
    )


def train_config(args):
    """The parsed flags as the trainer's ``TrainConfig``."""
    from .config import TrainConfig

    return TrainConfig(
        num_epochs=args.num_epochs, pop_size=args.pop_size, sigma=args.sigma,
        lr_scale=args.lr_scale, egg_rank=args.egg_rank, antithetic=args.antithetic,
        promptnorm=args.promptnorm, prompts_per_gen=args.prompts_per_gen,
        batches_per_gen=args.batches_per_gen, member_batch=args.member_batch,
        steps_per_dispatch=args.steps_per_dispatch,
        reward_tile=args.reward_tile, remat=args.remat,
        pop_shard_update=args.pop_shard_update, base_quant=args.base_quant,
        noise_dtype="bfloat16" if args.noise_dtype == "bf16" else args.noise_dtype,
        tower_dtype="bfloat16" if args.tower_dtype == "bf16" else args.tower_dtype,
        theta_max_norm=args.theta_max_norm, max_step_norm=args.max_step_norm,
        reward_weights=(args.w_aesthetic, args.w_text, args.w_noart, args.w_pick),
        seed=args.seed, save_every=args.save_every,
        log_images_every=args.log_images_every,
        log_hist_every=args.log_hist_every,
        profile_epochs=args.profile_epochs,
        trace=args.trace, metrics_port=args.metrics_port,
        metrics_host=args.metrics_host,
        metrics_linger_s=args.metrics_linger_s, slo=args.slo,
        heartbeat_interval_s=args.heartbeat_interval_s,
        stall_cap_s=args.stall_cap_s, stall_action=args.stall_action,
        es_degenerate_warn_epochs=args.es_degenerate_warn_epochs,
        anomaly_detect=args.anomaly_detect,
        anomaly_window=args.anomaly_window,
        anomaly_min_epochs=args.anomaly_min_epochs,
        anomaly_z=args.anomaly_z,
        quality=args.quality,
        quality_hack_window=args.quality_hack_window,
        snapshot_every=args.snapshot_every,
        run_dir=args.run_dir, run_name=args.run_name, resume=args.resume,
        ckpt_keep=args.ckpt_keep, ckpt_legacy_mirror=args.ckpt_legacy_mirror,
        rollback_policy=args.rollback_policy, max_rollbacks=args.max_rollbacks,
        rollback_sigma_shrink=args.rollback_sigma_shrink,
        theta_explode_norm=args.theta_explode_norm, faults=args.faults,
        pop_host_shard=args.pop_host_shard,
        desync_check_every=args.desync_check_every,
        desync_action=args.desync_action,
        on_topology_mismatch=args.on_topology_mismatch,
        elastic_action=args.elastic_action,
    )


def main(argv=None) -> None:
    import time

    t_entered = time.perf_counter()
    from ..obs import Tracer, get_tracer, record_startup, set_tracer
    from ..obs.multihost import jax_backend_initialized
    from ..utils.compile_cache import place_compile_cache

    # a caller that already brought the backend up (the benchmark's harness,
    # a test) paid for it before this point, under ``startup``; a bare
    # ``python -m`` launch pays under ``devices``
    backend_up = jax_backend_initialized()
    args = build_parser().parse_args(argv)
    place_compile_cache()

    # Multihost launch path: the CLI flags materialize as the coordinator
    # env vars BEFORE any jax backend touch (initialize_multihost reads
    # them; jax.distributed must initialize before XLA backend init).
    if args.coordinator:
        import os

        if args.num_processes is None or args.process_id is None:
            sys.exit("ERROR: --coordinator needs --num_processes and --process_id")
        os.environ["JAX_COORDINATOR_ADDRESS"] = args.coordinator
        os.environ["JAX_NUM_PROCESSES"] = str(args.num_processes)
        os.environ["JAX_PROCESS_ID"] = str(args.process_id)
    # --trace true: the tracer exists from here, so the build below is under
    # spans. It has no file yet — run_training names the run directory and
    # adopts it. What came before it is written back-dated: ``startup`` from
    # the operating system's stamp of the process's start, ``parse_args``
    # from this function's first line.
    if args.trace:
        set_tracer(Tracer(enabled=True))
        record_startup(t_entered, backend_initialized=backend_up)
        get_tracer().event("parse_args", t_entered, time.perf_counter())
    try:
        _run(args)
    finally:
        if args.trace:
            get_tracer().close()
            set_tracer(None)


def build_mesh(args):
    """The device mesh the flags ask for: ``{pop, data}`` over this process's
    devices (host-sharded pods) or over all of them; None on one device."""
    from ..parallel import POP_AXIS, make_mesh

    # Host-sharded pods (the multi-process default) build a LOCAL mesh: each
    # process compiles programs over its own devices only — the population
    # slice it owns — and fitness rows cross hosts outside the program
    # (train/trainer.make_host_sharded_programs). --pop_host_shard off keeps
    # the single global-mesh SPMD program instead.
    pc = jax.process_count()
    # "on" forces the host-sharded (split eval/update) program form even
    # single-process: elastic fleets run it at EVERY size so a 1-proc run
    # and the pod it shrinks from/grows into dispatch the same per-slice
    # programs — the bit-identity anchor of reshard-on-restore.
    host_shard = args.pop_host_shard == "on" or (
        pc > 1 and args.pop_host_shard != "off"
    )
    if host_shard and args.pop_size % pc:
        sys.exit(
            f"ERROR: host-sharded population needs --pop_size divisible by "
            f"the process count ({args.pop_size} % {pc} != 0); adjust "
            "--pop_size or pass --pop_host_shard off"
        )
    devs = jax.local_devices() if host_shard else jax.devices()
    # the pop rows a mesh on THIS process would shard: the local slice in
    # host-shard mode, the whole population otherwise
    mesh_pop = args.pop_size // pc if host_shard else args.pop_size
    n_dev = len(devs)
    shards = args.pop_shards
    if shards == 0:
        import math

        shards = math.gcd(mesh_pop, n_dev)
    if n_dev <= 1 or shards < 1:
        return None
    from ..parallel import DATA_AXIS

    if shards > n_dev:
        sys.exit(f"ERROR: --pop_shards {shards} > {n_dev} available devices")
    # remaining devices shard each member's image batch (data axis) so
    # small populations still fill the slice (pop_eval pads both axes)
    n_data = n_dev // shards
    if shards * n_data < n_dev:
        print(
            f"[cli] WARNING: pop_shards={shards} does not divide {n_dev} "
            f"devices; {n_dev - shards * n_data} devices idle",
            flush=True,
        )
    mesh = make_mesh({POP_AXIS: shards, DATA_AXIS: n_data}, devices=devs)
    scope = "local" if host_shard else "global"
    print(f"[cli] mesh: {dict(mesh.shape)} over {n_dev} {scope} devices",
          flush=True)
    return mesh


def _run(args) -> None:
    """``main`` after the tracer is installed: build, mesh, train. Every
    statement lies under a span (PERF.md §3: the set-up waterfall)."""
    from ..obs import block_if_tracing, span as obs_span

    with obs_span("imports"):
        from ..parallel import initialize_multihost
        from .trainer import run_training

    # multi-host init and the backend's bring-up (``jax.process_count()``
    # there is the first call that needs one)
    with obs_span("devices"):
        initialize_multihost()
    with obs_span("build_backend"):
        backend = build_backend(args)
    with obs_span("backend_setup"):
        backend.setup()
        block_if_tracing(backend.frozen)
    if args.base_quant == "int8":
        # quantize the frozen generator trees in place AFTER setup (params
        # exist) and BEFORE init_theta (the adapter tree then targets
        # kernel_q8/q8 paths — same adapter structure and init values either
        # way, lora.init_lora). The trained delta never touches the base.
        from ..ops.quant import quantize_frozen

        with obs_span("quantize"):
            backend.params = quantize_frozen(backend.params, "int8")
            if getattr(backend, "vae_params", None) is not None:
                backend.vae_params = quantize_frozen(backend.vae_params, "int8")
            block_if_tracing(backend.frozen)
        print("[cli] base_quant=int8: frozen generator kernels stored int8 "
              "(per-output-channel, ops/quant.py)", flush=True)
    with obs_span("build_reward"):
        reward_fn = build_reward_fn(args, backend)
    with obs_span("mesh"):
        mesh = build_mesh(args)
        tc = train_config(args)

    # best/median/worst member strips + histograms + profiler traces are
    # handled inside run_training (reference unifed_es.py:243-264,807-821)
    state = run_training(backend, reward_fn, tc, mesh=mesh)
    if state.elastic_exit:
        # exit 0: like preemption, an elastic membership change is a
        # *successful* shutdown
        if state.elastic_evicted:
            # this rank was voted out and committed NOTHING; under
            # --elastic_action continue the survivors are still training in
            # this run dir — a relaunch here would write over a live run
            if args.elastic_action == "continue":
                print(f"[cli] voted out of the pod at epoch {state.epoch} — "
                      "standing down; the survivors continue IN-PLACE in "
                      "this run dir. Do NOT relaunch into it "
                      "(see elastic.json)", flush=True)
            else:
                print(f"[cli] voted out of the pod at epoch {state.epoch} — "
                      "standing down; the survivors commit and exit for a "
                      "relaunch at the new process count (see elastic.json)",
                      flush=True)
        else:
            # the survivors committed a slot among themselves and the
            # scheduler relaunches at the new process count
            print(f"[cli] elastic membership change at epoch {state.epoch} "
                  "— survivor checkpoint committed; relaunch at the new "
                  "process count with --resume auto --on_topology_mismatch "
                  "reshard (see elastic.json)", flush=True)
        sys.exit(0)
    if state.preempted:
        # exit 0: preemption is a *successful* shutdown — the scheduler's
        # restart resumes bit-identically from the saved slot
        print(f"[cli] preempted at epoch {state.epoch} — checkpoint saved; "
              "restart with --resume auto to continue", flush=True)
        sys.exit(0)
    if state.halted:
        print(f"[cli] HALTED by rollback policy at epoch {state.epoch} after "
              f"{state.rollbacks} rollback(s) — see halted.json in the run dir",
              flush=True)
        sys.exit(3)
    print(f"[cli] training done at epoch {state.epoch}", flush=True)


if __name__ == "__main__":
    main()

"""Headline benchmark: ES population-evals/sec (images scored per second).

Measures the full jitted ES epoch step — factored EGGROLL noise → LoRA-adapted
one-step Sana-Sprint generation → 1024px DC-AE decode → in-graph CLIP-B/32 +
PickScore(CLIP-H) rewards → promptnorm → ES update — and reports images scored
per second, **host-synchronized**.

Honesty contract:

- A rung at a real size runs on a TPU or not at all: the ladder that finds
  no TPU exits non-zero and publishes no value. Only the ``tiny`` rung runs
  on the CPU, as the explicit smoke the tests use
  (``JAX_PLATFORMS=cpu BENCH_TINY=1 python bench.py``), and says
  ``"platform": "cpu"``.
- Every timed window ends with ``jax.device_get`` of a scalar that data-depends
  on *all* timed steps (θ is chained through them), so the clock stops only
  after real execution.
- MFU is computed from the compiled executable's own XLA cost analysis
  (``utils/mfu.py``) and printed in the JSON line. **If MFU > 1.0 the bench
  exits non-zero** — a physically impossible number is never published. The
  JSON also carries ``mfu_gate_armed``: off the TPU no peak is known and the
  gate cannot fire, which must be visible rather than silent. A TPU whose
  ``device_kind`` the peak table lacks is an error (utils/mfu.py).
- Physical-floor gate (round 5): a rung whose per-step time is below
  ``max(xla_flops, 2·param_count·imgs) / (peak·n_dev)`` errors instead of
  publishing — armed even when XLA cost analysis is partial
  (``physical_floor_check``).
- Dispatch amortization (round 5): small rungs also time a ``fori_loop``-
  chained program (``RUNG_CHAIN`` steps per host dispatch) — the sustained
  number a training loop sees; the single-dispatch time stays in the record
  so the per-step host dispatch cost is measured, not guessed.
- Geometry is a ladder (tiny → small → popscale → mid → flagship): one
  streaming child runs all rungs, one after another in one process (a chip
  belongs to one process at a time; the parent never touches a backend),
  and prints a JSON line per completed rung immediately; the parent
  enforces the budget and per-rung stall caps, keeps every partial result,
  and respawns a child for the remaining rungs if one rung stalls.
- Phase timestamps (init/build/compile/warmup/timed) stream to stderr so a
  timeout is diagnosable from the tail. Liveness heartbeats come from the
  shared ``hyperscalees_t2i_tpu.obs.heartbeat`` module and go to **stderr**
  as well — stdout carries ONLY rung/result JSON, so a heartbeat firing
  mid-print can never corrupt the last-line JSON contract.
- A large-population rung (pop 64, ``member_batch`` chunking active) exercises
  the population axis — the reference's headline scale is pop 128
  (``/root/reference/runES.py:434-435``).

The reference publishes no throughput numbers (BASELINE.md); its inner loop is
sequential per member with one reward-model call *per image*
(``/root/reference/unifed_es.py:159-206``). ``vs_baseline`` is computed
against an estimated 3.0 imgs/sec for that loop on a single A100 and is only
claimed at flagship geometry (elsewhere it is null).

Every rung's AOT compile also appends a record to the per-program XLA
ledger (obs/xla_cost.py → BENCH_PROGRAMS_JSONL, default
bench_runs/programs.jsonl), and rung records carry the schema-3 ledger
fields: bytes_accessed, peak-HBM estimate, lowering_s, StableHLO size/hash,
and a roofline verdict (compute-/bandwidth-/latency-bound) with the
predicted step time the verdict is relative to.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "mfu", ...}.
Env knobs: BENCH_TINY=1 (tiny rung only), BENCH_BUDGET_S (default 540),
BENCH_STEPS, BENCH_CHAIN (steps per dispatched program; 0 disables),
BENCH_RUNGS (comma list), BENCH_PROGRAMS_JSONL (ledger path),
BENCH_POP / BENCH_PROMPTS (honored
ONLY when invoked directly with --rung; stripped from ladder children so a
single-rung override can't silently rescale every rung).

Scaling mode (round 13): ``bench.py --scaling [--rungs tiny]
[--devices 1,2,4] [--out SCALING.json]`` runs ONE rung at each forced
host-platform device count (a fresh child per count, XLA_FLAGS set before
jax import) and emits a SCALING artifact: per-count rung records plus a
summary with imgs/sec/chip, efficiency vs the 1-device baseline, collective
bytes/step, and the cross-count ``opt_scores_digest`` reward-parity anchor
(BENCH_SCALING_TIMEOUT_S bounds each child).

Serve mode (round 16 / ISSUE 12): ``bench.py --serve [--rung tiny]
[--adapters N] [--images B] [--batches K] [--out SERVE.json]`` measures
multi-tenant serving throughput on one rung: the serve engine's
adapter-batched dispatch (N requests coalesced into one program call) vs
the naive per-adapter composition (one jit dispatch + per-request adapter
staging — the pre-engine demo path, the headline denominator) vs the
engine's one-slot AOT program (the batching-only ablation), interleaved
per timed round so shared-host jitter cancels in the ratio, with
per-request parity recorded and one ``site="serve"`` ledger record per
program. (The ladder child's legacy spawn spelling ``--serve R1,R2`` — a
bare comma-list of rung names — still dispatches to child mode.)

Compile cache: wherever ``JAX_COMPILATION_CACHE_DIR`` says, else
``<checkout>/.jax_cache`` (utils/compile_cache.py — the one rule every entry
point shares); children inherit it through the environment. A warm cache
shows as ``compile_s − lowering_s ≈ 0``; rung records carry
``compile_cache_dir``/``compile_cache_entries`` as the proof.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from typing import Optional

# Shared observability primitives (stdlib-only imports — the ladder parent
# never initializes a jax backend: the chip belongs to its child).
from hyperscalees_t2i_tpu.obs.heartbeat import Heartbeat, emit_heartbeat
from hyperscalees_t2i_tpu.obs.metrics import compile_cache_entries
from hyperscalees_t2i_tpu.ops.pallas_gate import active_pallas_flags
from hyperscalees_t2i_tpu.obs.xla_cost import (
    ProgramLedger,
    record_compile,
    roofline,
    set_ledger,
)

# Geometry ladder shared with tools/preflight.py (one definition — the
# offline preflight must analyze exactly the programs this file times).
# Re-exported here because tests and drivers address them as bench.RUNG_*.
from hyperscalees_t2i_tpu.rungs import (  # noqa: F401  (re-exports)
    BENCH_PROMPT_SET,
    PROMPT_EMBED_LEN,
    PROMPT_TOKEN_LEN,
    RUNG_CHAIN,
    RUNG_CHAIN_FIT_GATED,
    RUNG_EST_S,
    RUNG_OPT,
    RUNG_ORDER,
    RUNG_PLAN,
    SCALING_DEVICE_COUNTS,
    forced_host_devices_flags,
    rung_opt,
    sana_rung_model,
    small_clip_cfg as _small_clip_cfg,
)

def apply_profile_argv(argv: list, environ=os.environ) -> list:
    """``--profile DIR`` (round 21): capture a bounded ``jax.profiler``
    window around each rung's timed steps, writing ``.xplane.pb`` traces
    under ``DIR/<rung>/`` (what ``obs/xplane.py`` attributes and
    ``obs/calib.py`` reconciles against the roofline). BENCH_PROFILE_DIR
    reaches ladder children through the environment, and the flag is
    stripped so the remaining args dispatch as usual."""
    argv = list(argv)
    profile_dir = None
    for i, tok in enumerate(argv):
        if tok == "--profile":
            if i + 1 >= len(argv):
                raise SystemExit("--profile needs a directory argument")
            profile_dir = argv[i + 1]
            del argv[i:i + 2]
            break
        if tok.startswith("--profile="):
            profile_dir = tok.split("=", 1)[1]
            if not profile_dir:
                raise SystemExit("--profile needs a directory argument")
            del argv[i]
            break
    if profile_dir is not None:
        profile_dir = os.path.abspath(profile_dir)
        os.makedirs(profile_dir, exist_ok=True)
        environ["BENCH_PROFILE_DIR"] = profile_dir
    return argv

# The reference's inner loop (unifed_es.py:159-206) is sequential per member
# with a per-image reward call; no throughput number is published, so this is
# our estimate for that loop on one A100 at flagship-like geometry (one-step
# 1.6B DiT + 1024px decode + CLIP/PickScore per image ≈ 0.3-0.4 s/img
# generation + reward + PIL round-trips). Labeled estimated in the output.
BASELINE_IMGS_PER_SEC = 3.0

# RUNG_PLAN / RUNG_ORDER / RUNG_EST_S / RUNG_CHAIN moved to
# hyperscalees_t2i_tpu/rungs.py (shared with the offline preflight) and
# re-imported above.


def analytic_floor_flops(frozen, theta, imgs: int) -> float:
    """Best-effort analytic lower bound on one ES step's FLOPs: every scored
    image runs at least one full forward in which every float parameter
    participates in ≥1 multiply-add (2 FLOPs). Independent of XLA cost
    analysis, so the physical-floor gate still arms when cost analysis is
    partial or absent."""
    import jax
    import numpy as np

    n = 0
    for leaf in jax.tree_util.tree_leaves((frozen, theta)):
        dt = getattr(leaf, "dtype", None)
        if dt is not None and np.issubdtype(np.dtype(dt), np.floating):
            n += int(np.prod(leaf.shape))
    return 2.0 * n * max(imgs, 1)


def physical_floor_check(step_time_s, floor_flops, peak_flops, n_dev) -> Optional[str]:
    """Error string when a measured per-step time is below the physical floor
    ``floor_flops / (peak · n_dev)`` — generalizes the MFU>1 honesty gate
    (the r2 dispatch-timing failure class) to rungs where XLA cost analysis
    is partial. None = plausible (or the gate cannot arm: unknown peak)."""
    if peak_flops is None or not floor_flops or floor_flops <= 0:
        return None
    floor_s = floor_flops / (peak_flops * max(n_dev, 1))
    if step_time_s < floor_s:
        return (
            f"IMPOSSIBLE: step_time {step_time_s:.6g}s < physical floor "
            f"{floor_s:.6g}s ({floor_flops / 1e12:.4g} TFLOP at peak) — "
            f"timing is not execution-synced"
        )
    return None

_T0 = time.perf_counter()


def _log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


# Bump when the artifact layout changes incompatibly. Version 1 = the
# unstamped pre-PR2 artifacts; version 2 adds the stamp
# fields below so tools/bench_report.py --trend can line artifacts up into a
# cross-PR trajectory (previously impossible: nothing said which code/jax
# produced a number, so artifacts weren't comparable). Version 3 adds the
# XLA-ledger fields per rung (bytes_accessed, peak_bytes_est, lowering_s,
# StableHLO size/hash, roofline verdict + predicted step time) — additive,
# so v2 consumers (bench_report --trend) keep parsing v3 and vice versa.
# Version 4 adds the collective-traffic fields (collective_bytes/_ops from
# the partitioned HLO, t_comms_s), the warmup-step opt_scores digest (the
# scaling bench's cross-device-count reward-parity anchor), and the
# SCALING_r* artifact family (bench.py --scaling) — additive again: v2/v3
# artifacts keep parsing everywhere, older consumers see extra fields.
BENCH_SCHEMA_VERSION = 4


def artifact_stamp() -> dict:
    """Provenance stamp merged into every bench artifact: schema version,
    jax version, and git sha. Deliberately jax-IMPORT-free (importlib
    metadata only): the parent process must stay free of jax so it can never
    block on backend init. Mesh shape is per-rung (the child knows it)."""
    try:
        from importlib.metadata import version

        jax_version = version("jax")
    except Exception:
        jax_version = None
    sha = None
    try:
        import subprocess as _sp

        out = _sp.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        )
        sha = out.stdout.strip() or None
    except Exception:
        pass
    return {
        "schema_version": BENCH_SCHEMA_VERSION,
        "jax_version": jax_version,
        "git_sha": sha,
    }


# Long blocking phases (XLA compile, warmup) are wrapped in the shared
# ``obs.Heartbeat``: {"hb": rung, "phase": ...} JSON lines every 20s on
# STDERR so the parent's stall detector sees a live child instead of silence.

# ---------------------------------------------------------------------------
# child: one geometry rung, honestly timed
# ---------------------------------------------------------------------------

def _cast_tree(tree, dtype):
    from hyperscalees_t2i_tpu.utils.pytree import cast_floating

    return cast_floating(tree, dtype)


# BENCH_PROMPT_SET and the small CLIP tower config moved to
# hyperscalees_t2i_tpu/rungs.py (imported above).


def _init_clip_table(key, clip_mod, clip_cfg, M: int, Ltok: int = 8):
    """bf16 CLIP params + the [M+2, ...] text-embed table (random token ids:
    throughput benchmark). Call inside a jitted init program."""
    import jax
    import jax.numpy as jnp

    from hyperscalees_t2i_tpu.rewards.suite import clip_text_embed_table

    kc, ki = jax.random.split(key)
    cparams = _cast_tree(clip_mod.init_clip(kc, clip_cfg), jnp.bfloat16)
    ids = jax.random.randint(ki, (M + 2, Ltok), 0, clip_cfg.vocab_size)
    return {"cparams": cparams, "table": clip_text_embed_table(cparams, clip_cfg, ids)}


def pallas_kernel_parity() -> Optional[float]:
    """max |kernel − XLA| of the Pallas decode-attention kernel against the
    fused-XLA reference path, on THIS platform's device (CPU tests can only
    lower the kernel for Mosaic, never execute it — the number that matters
    is measured where the kernel actually runs). None off the TPU, where the
    gate selects the XLA path (nothing to compare)."""
    import jax
    import jax.numpy as jnp

    from hyperscalees_t2i_tpu.ops.attention import decode_attention, should_use_pallas

    if not should_use_pallas():
        return None
    B, nq, L, H, dh = 2, 16, 640, 8, 64
    kq, kk, kv, km = jax.random.split(jax.random.PRNGKey(42), 4)
    q = jax.random.normal(kq, (B, nq, H, dh), jnp.bfloat16)
    k = jax.random.normal(kk, (B, L, H, dh), jnp.bfloat16)
    v = jax.random.normal(kv, (B, L, H, dh), jnp.bfloat16)
    mask = jax.random.bernoulli(km, 0.9, (B, L))
    diffs = []
    for kv_len, m in ((600, None), (None, mask)):
        a = decode_attention(q, k, v, kv_len=kv_len, kv_mask=m, use_pallas=True)
        b = decode_attention(q, k, v, kv_len=kv_len, kv_mask=m, use_pallas=False)
        diffs.append(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))))
    return max(diffs)


def _build_ar():
    """VAR next-scale AR backend + tiny CLIP reward: the rung that runs the
    Pallas decode-attention kernel on hardware (ops/attention.py — the CPU
    tier lowers it for Mosaic but cannot execute it)."""
    import jax
    import jax.numpy as jnp

    from hyperscalees_t2i_tpu.backends.var_backend import VarBackend, VarBackendConfig
    from hyperscalees_t2i_tpu.models import clip as clip_mod
    from hyperscalees_t2i_tpu.models import msvq, var as var_mod
    from hyperscalees_t2i_tpu.rewards.suite import make_clip_reward_fn

    vq = msvq.MSVQConfig(ch=32, ch_mult=(1, 2, 2), num_res_blocks=1)
    # toy class table: the reward table below is built from random token ids,
    # so the 1000-name ImageNet label fetch would be pure (blocking) waste
    model = var_mod.VARConfig(vq=vq, depth=6, d_model=512, n_heads=8, num_classes=16)
    bcfg = VarBackendConfig(model=model, class_pool=tuple(range(16)))
    clip_b = _small_clip_cfg(clip_mod)
    M = 16

    def _init_all(key):
        kt, kc = jax.random.split(key)
        params = _cast_tree(var_mod.init_var(kt, model), jnp.bfloat16)
        return {"params": params, **_init_clip_table(kc, clip_mod, clip_b, M)}

    out = jax.jit(_init_all)(jax.random.PRNGKey(0))
    jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    backend = VarBackend(bcfg, params=out["params"])
    backend.setup()
    reward_fn = make_clip_reward_fn(out["cparams"], clip_b, out["table"])
    return backend, reward_fn


def build(
    scale: str,
    remat: str = "none",
    tower_dtype: str = "float32",
    base_quant: str = "off",
):
    """Backend + reward fn at the requested geometry rung.

    All device-array construction (param init, bf16 casts, text-embed tables)
    happens inside ONE jitted function instead of an op-by-op eager init (one
    small compile per op). One fused program also lands in the persistent
    compile cache, so repeat bench runs skip it entirely.

    ``base_quant="int8"`` stores the frozen base trees (generator, VAE,
    CLIP image towers) per-output-channel int8 (ops/quant.py). Text-embed
    tables are built from the full-precision towers FIRST (one-time work —
    only the per-step image path goes int8), matching train/cli.py. The AR
    rung ignores the knob (its RUNG_OPT entry ships it off).
    """
    import jax
    import jax.numpy as jnp

    from hyperscalees_t2i_tpu.backends.sana_backend import SanaBackend
    from hyperscalees_t2i_tpu.models import clip as clip_mod
    from hyperscalees_t2i_tpu.models import dcae, sana
    from hyperscalees_t2i_tpu.rewards.suite import make_clip_reward_fn, pickscore_text_embeds

    if scale == "ar_small":
        return _build_ar()
    # Per-scale model/VAE/reward-tower configs live in rungs.sana_rung_model
    # (shared with tools/preflight.py so the offline analysis can never
    # drift from the geometry being timed here).
    spec = sana_rung_model(scale, remat=remat, tower_dtype=tower_dtype)
    bcfg, clip_b, clip_h = spec["bcfg"], spec["clip_b"], spec["clip_h"]
    latent_only = spec["latent_only"]

    backend = SanaBackend(bcfg)
    prompts = list(BENCH_PROMPT_SET)
    M, Ltxt, Ltok = len(prompts), PROMPT_EMBED_LEN, PROMPT_TOKEN_LEN

    def _init_gen(key):
        """Generator-side arrays in one compiled program. Weights are
        random-init bf16 (throughput benchmark; serving dtype)."""
        kt2, kv2, ke = jax.random.split(key, 3)
        out = {
            "params": _cast_tree(sana.init_sana(kt2, bcfg.model), jnp.bfloat16),
            "prompt_embeds": jax.random.normal(
                ke, (M, Ltxt, bcfg.model.caption_dim), jnp.float32
            ),
        }
        if bcfg.decode_images:
            out["vae"] = _cast_tree(dcae.init_decoder(kv2, bcfg.vae), jnp.bfloat16)
        return out

    def _init_rewards(key):
        """Reward towers + text-embed tables (includes a CLIP text forward)."""
        kc, kp, ki = jax.random.split(key, 3)
        out = _init_clip_table(kc, clip_mod, clip_b, M, Ltok)
        if clip_h is not None:
            pparams = _cast_tree(clip_mod.init_clip(kp, clip_h), jnp.bfloat16)
            out["pparams"] = pparams
            out["ptable"] = pickscore_text_embeds(
                pparams, clip_h,
                jax.random.randint(ki, (M, Ltok), 0, clip_h.vocab_size),
            )
        return out

    t0 = time.perf_counter()
    out = jax.jit(_init_gen)(jax.random.PRNGKey(0))
    jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    _log(f"build[{scale}]: generator arrays in {time.perf_counter() - t0:.1f}s")
    if not latent_only:
        t0 = time.perf_counter()
        rew = jax.jit(_init_rewards)(jax.random.PRNGKey(1))
        # without the sync this logs dispatch time and the leftover device work
        # leaks into warmup_step_s (can falsely trip the warm_s>60 step cut)
        jax.tree_util.tree_map(lambda x: x.block_until_ready(), rew)
        out.update(rew)
        _log(f"build[{scale}]: reward arrays in {time.perf_counter() - t0:.1f}s")
    if base_quant == "int8":
        # one jitted, donated quantize pass over every frozen tree (the text
        # tables above were already built from the full-precision towers)
        from hyperscalees_t2i_tpu.ops.quant import quantize_frozen

        to_q = {
            k: out[k]
            for k in ("params", "vae", "cparams", "pparams")
            if out.get(k) is not None
        }
        t0 = time.perf_counter()
        quantized = quantize_frozen(to_q, "int8")
        jax.tree_util.tree_map(lambda x: x.block_until_ready(), quantized)
        out.update(quantized)
        _log(f"build[{scale}]: base trees quantized int8 in "
             f"{time.perf_counter() - t0:.1f}s")
    backend.params = out["params"]
    backend.vae_params = out.get("vae")
    backend.prompts = prompts
    backend.prompt_embeds = out["prompt_embeds"]
    backend.prompt_mask = jnp.ones((M, Ltxt), bool)
    backend.setup()  # no-op given the assignments; keeps the contract
    if latent_only:
        def reward_fn(latents, prompt_ids):
            # negligible-cost statistic: the rung isolates generation + ES
            return {"combined": latents.astype(jnp.float32).mean(axis=(1, 2, 3))}
    else:
        reward_fn = make_clip_reward_fn(
            out["cparams"], clip_b, out["table"],
            pick_params=out.get("pparams"), pick_cfg=clip_h,
            pick_text_embeds=out.get("ptable"),
        )
    return backend, reward_fn


def run_rung(rung: str, allow_env_overrides: bool = True) -> dict:
    """Build, compile (AOT, reused for execution), and honestly time one rung."""
    import jax
    import jax.numpy as jnp

    from hyperscalees_t2i_tpu.backends.base import make_frozen
    from hyperscalees_t2i_tpu.ops.pallas_gate import selected_kernels
    from hyperscalees_t2i_tpu.parallel import gcd_pop_data_mesh, replicated
    from hyperscalees_t2i_tpu.train.config import TrainConfig
    from hyperscalees_t2i_tpu.train.trainer import make_es_step
    from hyperscalees_t2i_tpu.utils.mfu import device_hbm_bandwidth, device_peak_flops

    scale, pop, m, member_batch = RUNG_PLAN[rung]
    if allow_env_overrides:
        pop = int(os.environ.get("BENCH_POP", pop))
        m = int(os.environ.get("BENCH_PROMPTS", m))
    steps = int(os.environ.get("BENCH_STEPS", "3"))
    repeats = 1
    # shipped memory/bandwidth knobs per rung (rungs.RUNG_OPT): remat goes
    # into the model configs, reward_tile/noise_dtype into the step config
    opt = rung_opt(rung)

    _log(f"{rung}: building models (scale={scale} pop={pop} m={m} "
         f"remat={opt['remat']} tile={opt['reward_tile']} noise={opt['noise_dtype']} "
         f"towers={opt['tower_dtype']} "
         f"base={opt.get('base_quant', 'off')})")
    t_build0 = time.perf_counter()
    with Heartbeat(rung, "build"):
        backend, reward_fn = build(
            scale, remat=opt["remat"], tower_dtype=opt["tower_dtype"],
            base_quant=opt.get("base_quant", "off"),
        )
    n_dev = len(jax.devices())
    mesh = None
    if n_dev > 1:
        # Always fill the whole slice: gcd(pop, n_dev) on the pop axis, the
        # remainder on data (pop_eval pads both axes as needed). The shared
        # recipe — preflight --devices analyzes exactly this mesh.
        mesh = gcd_pop_data_mesh(pop, n_dev)

    tc = TrainConfig(pop_size=pop, sigma=0.01, egg_rank=4, prompts_per_gen=m,
                     batches_per_gen=repeats, member_batch=member_batch, promptnorm=True,
                     remat=opt["remat"], reward_tile=opt["reward_tile"],
                     noise_dtype=opt["noise_dtype"],
                     base_quant=opt.get("base_quant", "off"),
                     quality=opt.get("quality", False))
    num_unique = min(m, backend.num_items)
    step = make_es_step(backend, reward_fn, tc, num_unique, repeats, mesh)

    theta = backend.init_theta(jax.random.PRNGKey(1))
    frozen = make_frozen(backend, reward_fn)
    if mesh is not None:
        # Stage θ + frozen params replicated so the timed loop reuses the
        # warmup compile (host-placed inputs would change input shardings).
        theta = jax.device_put(theta, replicated(mesh))
        frozen = jax.device_put(frozen, replicated(mesh))
    info = backend.step_info(0, num_unique, repeats)
    flat_ids = jnp.asarray(info.flat_ids, jnp.int32)
    key = jax.random.PRNGKey(2)
    build_s = time.perf_counter() - t_build0

    # One AOT compile, reused for both cost analysis and execution — the jit
    # dispatch path would compile a second time.
    _log(f"{rung}: built in {build_s:.1f}s; compiling")
    t_c0 = time.perf_counter()
    with Heartbeat(rung, "compile"):
        lowered = step.lower(frozen, theta, flat_ids, key)
        lowering_s = time.perf_counter() - t_c0
        compiled = lowered.compile()
    compile_s = time.perf_counter() - t_c0
    # One ledger record per AOT compile (obs/xla_cost.py): normalized cost/
    # memory analysis, StableHLO stats, donation audit → programs.jsonl.
    prog = record_compile(
        site="bench", label=rung, lowered=lowered, compiled=compiled,
        lowering_s=lowering_s, compile_s=compile_s - lowering_s,
        geometry={"scale": scale, "pop": pop, "m": num_unique, "r": repeats,
                  "member_batch": member_batch, **opt,
                  "mesh_shape": dict(mesh.shape) if mesh is not None else None,
                  "n_devices": n_dev},
    )
    step_flops = prog.get("flops")

    # Warmup executes the program once end-to-end (device_get forces it).
    _log(f"{rung}: compiled in {compile_s:.1f}s; warmup step")
    # Measurement-adjacent phases run WITHOUT device-memory gauges: a gauge
    # is a device query, and a beat landing inside a timed window would
    # contend with the dispatch/device_get being measured.
    t_w0 = time.perf_counter()
    with Heartbeat(rung, "warmup", gauges=None):
        theta, metrics, opt_s = compiled(frozen, theta, flat_ids, key)
        float(jax.device_get(metrics["opt_score_mean"]))
    warm_s = time.perf_counter() - t_w0
    # Reward-parity anchor (schema 4): the warmup step's per-member
    # promptnormed scores, from a fresh deterministic θ and a fixed key —
    # two runs of the same rung at DIFFERENT device counts must produce the
    # same digest (pop_eval's item_index contract: sharding never changes a
    # member's rewards). The scaling CI smoke asserts it bit-for-bit.
    import hashlib as _hashlib

    import numpy as _np

    opt_scores_digest = _hashlib.sha256(
        _np.ascontiguousarray(
            _np.asarray(jax.device_get(opt_s), _np.float32)
        ).tobytes()
    ).hexdigest()[:16]

    # Adaptive step count: keep the timed window bounded on a slow platform.
    if warm_s > 60 and steps > 1:
        steps = 1

    _log(f"{rung}: warmup {warm_s:.1f}s; timing {steps} steps")
    # Bounded profiler window (--profile / BENCH_PROFILE_DIR): capture
    # exactly the timed steps — warmup and compile stay out of the trace so
    # the device timeline is the steady state obs/calib.py reconciles.
    profile_dir = os.environ.get("BENCH_PROFILE_DIR") or None
    if profile_dir:
        profile_dir = os.path.join(profile_dir, rung)
        jax.profiler.start_trace(profile_dir)
        _log(f"{rung}: profiling timed steps -> {profile_dir}")
    t0 = time.perf_counter()
    try:
        with Heartbeat(rung, "timed", gauges=None):
            for e in range(steps):
                theta, metrics, _ = compiled(
                    frozen, theta, flat_ids, jax.random.fold_in(jax.random.PRNGKey(3), e)
                )
            # θ chains through every step and the fetched scalar depends on the
            # last θ, so this transfer cannot complete before all timed steps
            # execute.
            score = float(jax.device_get(metrics["opt_score_mean"]))
    finally:
        # trainer finally-flush discipline: a mid-window raise still flushes
        # the trace, and a stop failure never masks the real error
        if profile_dir:
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                _log(f"{rung}: WARNING profiler stop failed "
                     f"({type(e).__name__}: {e})")
    dt = time.perf_counter() - t0
    _log(f"{rung}: timed {dt:.2f}s total")

    imgs_per_step = pop * num_unique * repeats
    step_time = dt / steps

    # --- dispatch amortization: K steps fused into one dispatched program ---
    chain = int(os.environ.get("BENCH_CHAIN", RUNG_CHAIN.get(rung, 0)))
    if chain > 1 and warm_s > 60 and "BENCH_CHAIN" not in os.environ:
        # slow platform for this rung (same signal that cut the step count):
        # a K× chained program would blow the ladder budget for a number
        # dispatch overhead barely affects at this step size. An explicit
        # BENCH_CHAIN always wins.
        _log(f"{rung}: warmup {warm_s:.0f}s > 60s — skipping the chained "
             "program (set BENCH_CHAIN to force it)")
        chain = 0
    chain_time = None
    if chain > 1:
        # metric shapes come from the warmup's concrete pytree — no second
        # trace of the ES step just for shapes
        m0_tree = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, x.dtype), metrics
        )

        def multi(fz, th, ids, k):
            def body(e, carry):
                th_, _ = carry
                th2, m, _ = step(fz, th_, ids, jax.random.fold_in(k, e))
                return (th2, m)

            return jax.lax.fori_loop(0, chain, body, (th, m0_tree))

        _log(f"{rung}: compiling {chain}-step chained program")
        with Heartbeat(rung, "chain-compile"):
            t_cc0 = time.perf_counter()
            lowered_c = jax.jit(multi).lower(frozen, theta, flat_ids, key)
            lowering_c_s = time.perf_counter() - t_cc0
            cchain = lowered_c.compile()
            prog_c = record_compile(
                site="bench", label=f"{rung}-chain{chain}",
                lowered=lowered_c, compiled=cchain, chain=chain,
                lowering_s=lowering_c_s,
                compile_s=time.perf_counter() - t_cc0 - lowering_c_s,
                geometry={"scale": scale, "pop": pop, "m": num_unique,
                          "r": repeats, "member_batch": member_batch, **opt,
                          "mesh_shape": (dict(mesh.shape)
                                         if mesh is not None else None),
                          "n_devices": n_dev},
            )
        # Fit gate (rungs.RUNG_CHAIN_FIT_GATED): the CHAINED program's own
        # compiled peak-HBM estimate must fit the device before it is ever
        # *executed* — chaining amortizes dispatch tax, it must never
        # resurrect a no-fit (compiling is host-side and safe; executing is
        # what OOMs). Applies even under a BENCH_CHAIN override. Off the TPU
        # there is no capacity to protect (CPU smoke rigs).
        chain_fits = True
        if rung in RUNG_CHAIN_FIT_GATED:
            from hyperscalees_t2i_tpu.utils.mfu import device_hbm_bytes

            cap = device_hbm_bytes()
            peak_c = prog_c.get("peak_bytes")
            if cap is not None and peak_c is not None and peak_c > cap:
                _log(f"{rung}: chained program NOT executed — its peak "
                     f"est {peak_c / 1e9:.1f} GB exceeds device HBM "
                     f"{cap / 1e9:.0f} GB (fit gate); plain timing kept")
                chain_fits = False
        if chain_fits:
            with Heartbeat(rung, "chain-warmup", gauges=None):
                th2, m2 = cchain(frozen, theta, flat_ids, key)
                float(jax.device_get(m2["opt_score_mean"]))  # warm, exec-synced
            t0 = time.perf_counter()
            with Heartbeat(rung, "chain-timed", gauges=None):
                th2, m2 = cchain(frozen, theta, flat_ids, jax.random.PRNGKey(5))
                # exec-sync only: the record keeps the plain-loop score so
                # opt_score_mean means the same thing with or without chaining
                float(jax.device_get(m2["opt_score_mean"]))
            chain_time = (time.perf_counter() - t0) / chain
            _log(f"{rung}: chained per-step {chain_time:.4f}s vs plain {step_time:.4f}s")

    # Headline = sustained throughput: the chained program is what a training
    # loop dispatches (the plain number stays in the record for the split).
    headline_time = chain_time if chain_time is not None else step_time
    peak = device_peak_flops()
    mfu_val = None
    if step_flops is not None and peak is not None:
        # NOTE: cost_analysis FLOPs may be per-device post-partition on some
        # backends; dividing by n_dev keeps the estimate conservative
        # (understates MFU), so the >1.0 gate can only be harder to trip.
        mfu_val = step_flops / (headline_time * peak * max(n_dev, 1))
    val = imgs_per_step / headline_time

    # Physical-floor honesty gate: arms off XLA cost analysis when present
    # (the accurate count), else off the analytic parameter-count bound —
    # which is only a heuristic (frozen reward towers hold params a step
    # never executes, e.g. precomputed text-side CLIP), so it must never
    # override a real XLA figure (code-review r5).
    floor_flops = step_flops if step_flops else analytic_floor_flops(frozen, theta, imgs_per_step)
    # Both published timings face the gate: the plain loop is exactly where
    # the r2 dispatch-timing class lives, and a negative dispatch_tax_s or
    # impossible step_time_single_dispatch_s must never be published.
    for label, tval in (("chained", chain_time), ("single-dispatch", step_time)):
        if tval is None:
            continue
        floor_err = physical_floor_check(tval, floor_flops, peak, n_dev)
        if floor_err:
            raise RuntimeError(f"{label}: {floor_err}")
    cache_entries = compile_cache_entries()
    # Roofline verdict for the published timing (obs/xla_cost.py): which
    # hardware resource binds this rung, and what step time the static
    # program cost predicts at 100% efficiency on that resource.
    from hyperscalees_t2i_tpu.utils.mfu import device_ici_bandwidth

    rf = roofline(
        step_flops, prog.get("bytes_accessed"), headline_time,
        peak_flops=peak, hbm_bw=device_hbm_bandwidth(), n_devices=n_dev,
        collective_bytes=prog.get("collective_bytes"),
        ici_bw=device_ici_bandwidth(),
    )
    rec = {
        "rung": rung,
        "geometry": scale,
        "imgs_per_sec": round(val, 4),
        "pop": pop,
        "prompts": num_unique,
        "member_batch": member_batch,
        # shipped optimization-layer knobs (schema-3 additive fields): the
        # byte/HBM numbers below are only comparable across artifacts that
        # agree on these
        "remat": opt["remat"],
        "reward_tile": opt["reward_tile"],
        "noise_dtype": opt["noise_dtype"],
        "tower_dtype": opt["tower_dtype"],
        "base_quant": opt.get("base_quant", "off"),
        "steps_timed": steps,
        "step_time_s": round(headline_time, 4),
        # dispatch-vs-compute split: plain = one host dispatch per step,
        # chained = `chain` steps per dispatch; the difference is host dispatch
        "step_time_single_dispatch_s": round(step_time, 4),
        "chain": chain if chain_time is not None else 0,
        "dispatch_tax_s": round(step_time - chain_time, 4) if chain_time is not None else None,
        "physical_floor_s": (
            round(floor_flops / (peak * max(n_dev, 1)), 6) if peak else None
        ),
        "mfu": round(mfu_val, 6) if mfu_val is not None else None,
        "step_tflops": round(step_flops / 1e12, 4) if step_flops else None,
        # XLA-ledger fields (schema 3, obs/xla_cost.py): data movement, the
        # peak-HBM estimate, program-size evidence (regenerates PERF.md's
        # hand-made table), and the roofline verdict for the headline timing
        "bytes_accessed": prog.get("bytes_accessed"),
        "peak_bytes_est": prog.get("peak_bytes"),
        "peak_bytes_source": prog.get("peak_bytes_source"),
        "lowering_s": round(lowering_s, 3),
        "stablehlo_lines": prog.get("stablehlo_lines"),
        "stablehlo_bytes": prog.get("stablehlo_bytes"),
        "stablehlo_sha256": prog.get("stablehlo_sha256"),
        "roofline_bound": rf["bound"],
        "predicted_step_time_s": (
            round(rf["t_roofline_s"], 6) if rf["t_roofline_s"] else None
        ),
        # collective traffic of the compiled (partitioned) step — per-device
        # bytes through the interconnect per step (schema 4, obs/xla_cost)
        "collective_bytes": prog.get("collective_bytes"),
        "collective_ops": prog.get("collective_ops"),
        "t_comms_s": (
            round(rf["t_comms_s"], 6) if rf.get("t_comms_s") else None
        ),
        "opt_scores_digest": opt_scores_digest,
        "compile_s": round(compile_s, 2),
        "warmup_step_s": round(warm_s, 2),
        "build_s": round(build_s, 2),
        "n_devices": n_dev,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "peak_flops_known": peak is not None,
        "compile_cache_entries": cache_entries,
        # persistent-cache provenance (--compile_cache): which cache this
        # run compiled against — a warm cache shows compile_s−lowering_s≈0
        "compile_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR") or None,
        # kernel provenance (round 15): the Pallas env flags active for this
        # measurement, what every kernel gate selected on this backend, the
        # Mosaic custom calls the compiled step really carries — what makes
        # kernel-on and kernel-off artifacts distinguishable in the trend
        "pallas_env": active_pallas_flags(),
        "pallas_selected": selected_kernels(),
        "pallas_kernels": prog.get("pallas_kernels"),
        # device-truth provenance (round 21): where the --profile capture
        # landed (None = unprofiled) — obs/calib.py joins its .xplane.pb
        # module timings back to this rung's ledger record
        "profile_dir": profile_dir,
        "opt_score_mean": score,
        "sync": "device_get",
        # provenance stamp (schema_version / jax_version / git_sha) + the
        # actual device mesh — what makes artifacts comparable across PRs
        # (tools/bench_report.py --trend)
        **artifact_stamp(),
        "mesh_shape": dict(mesh.shape) if mesh is not None else None,
    }
    if rung == "ar":
        # recorded kernel-vs-XLA agreement on the platform that actually
        # executes the Pallas kernel (None off the TPU: no kernel ran). A
        # kernel that fails here fails the rung.
        with Heartbeat(rung, "parity"):
            rec["kernel_parity_maxdiff"] = pallas_kernel_parity()
    return rec


def _install_bench_ledger() -> None:
    """Per-compiled-program ledger for bench children (obs/xla_cost.py):
    every rung's AOT compile appends one record to ``programs.jsonl``
    (override the path with BENCH_PROGRAMS_JSONL). The parent never compiles,
    so it never installs one."""
    set_ledger(ProgramLedger(
        os.environ.get("BENCH_PROGRAMS_JSONL", "bench_runs/programs.jsonl")
    ))


def no_tpu_refusal(rungs) -> Optional[str]:
    """Why these rungs may not run on this backend, or None. A rung at a
    real size runs on a TPU or not at all — a CPU number is never published
    under a device metric's name; only the ``tiny`` rung is a CPU smoke."""
    import jax

    devs = jax.devices()
    real = [r for r in rungs if RUNG_PLAN[r][0] != "tiny"]
    if devs[0].platform == "tpu" or not real:
        return None
    return (
        f"no TPU: jax found {len(devs)}×{devs[0].platform} "
        f"({devs[0].device_kind}); {real} run at a real size and are never "
        "measured on another platform (only the tiny rung is a CPU smoke)"
    )


def serve_rungs(rungs: list, deadline_monotonic_s: float) -> int:
    """Child: init the backend ONCE, then run rungs in order, streaming one
    JSON line per rung to stdout (flushed) as each completes."""
    _install_bench_ledger()
    _log(f"child start; rungs={rungs}; initializing jax backend")
    import jax

    devs = jax.devices()
    _log(f"backend up: {len(devs)}×{devs[0].platform} ({devs[0].device_kind})")
    # parent-visible init marker: lets the failure JSON distinguish "backend
    # never came up" from per-rung compute timeouts. stderr, like all
    # liveness output — the parent reads hb lines there.
    emit_heartbeat("_startup", "backend_up")
    refusal = no_tpu_refusal(rungs)
    if refusal:
        _log(refusal)
        for rung in rungs:
            print(json.dumps({"rung": rung, "error": refusal}), flush=True)
        return 2
    rc = 0
    for i, rung in enumerate(rungs):
        remaining = deadline_monotonic_s - time.monotonic()
        est = RUNG_EST_S.get(rung, 120)
        if remaining < est:
            print(json.dumps({
                "rung": rung,
                "error": f"skipped: insufficient budget ({remaining:.0f}s left < est {est}s)",
            }), flush=True)
            continue
        try:
            print(json.dumps(run_rung(rung, allow_env_overrides=False)), flush=True)
        except Exception as e:  # one bad rung must not kill the ladder
            _log(f"{rung}: FAILED {type(e).__name__}: {e}")
            print(json.dumps({
                "rung": rung, "error": f"{type(e).__name__}: {e}"[:500],
            }), flush=True)
            rc = 1
    return rc


# ---------------------------------------------------------------------------
# scaling mode: one rung at 1/2/4(/8) forced host-platform devices
# (parent stays jax-free; each count is a fresh child so XLA_FLAGS lands
# before jax import — the same parent/child split as the ladder)
# ---------------------------------------------------------------------------

def scaling_summary(rows: dict) -> list:
    """Pure summary math over ``{str(n_devices): rung_record}``: imgs/sec/
    chip, efficiency vs the 1-device baseline, and the collective share of
    step time (None when the platform's ICI bandwidth is unknown — a CPU
    run publishes collective *bytes* but refuses to invent a time share). Separated from the child-spawning driver so tests exercise the
    artifact math without paying a bench run."""
    base = rows.get("1") or {}
    base_per_chip = base.get("imgs_per_sec")  # at n=1, per-chip == total
    out = []
    for n_str in sorted(rows, key=int):
        r = rows[n_str]
        n = int(n_str)
        ips = r.get("imgs_per_sec")
        per_chip = ips / n if ips else None
        eff = (
            per_chip / base_per_chip if per_chip and base_per_chip else None
        )
        t_comms, st = r.get("t_comms_s"), r.get("step_time_s")
        out.append({
            "devices": n,
            "imgs_per_sec": ips,
            "imgs_per_sec_per_chip": round(per_chip, 4) if per_chip else None,
            "efficiency": round(eff, 4) if eff is not None else None,
            "step_time_s": st,
            "mesh_shape": r.get("mesh_shape"),
            "collective_bytes": r.get("collective_bytes"),
            "collective_ops": r.get("collective_ops"),
            "collective_time_share_est": (
                round(t_comms / st, 4) if t_comms and st else None
            ),
            "opt_scores_digest": r.get("opt_scores_digest"),
            "error": r.get("error"),
        })
    return out


def run_scaling(rung: str, device_counts, out_path: Optional[str] = None) -> int:
    """Spawn one ``--rung`` child per forced device count and assemble the
    SCALING artifact: one JSON document on stdout (and ``out_path``) with
    the full per-count rung records under ``rows`` plus the derived
    ``summary`` (imgs/sec/chip, efficiency, collective share).

    Each child runs on the forced-CPU host platform with
    ``--xla_force_host_platform_device_count=N`` in XLA_FLAGS *before* jax
    import — honest about what it is (``platform_forced: cpu``): virtual
    host devices share the machine's cores, so CPU efficiency numbers are a
    plumbing/parity signal, not a TPU scaling claim (PERF.md round 13). The
    per-member reward math is device-count-invariant by contract
    (``opt_scores_digest`` must agree across rows — CI asserts it).
    """
    rows: dict = {}
    timeout_s = float(os.environ.get(
        "BENCH_SCALING_TIMEOUT_S", str(max(600, RUNG_EST_S.get(rung, 120) * 8))
    ))
    for n in device_counts:
        env = dict(os.environ)
        # single-rung env overrides must not silently rescale the ladder
        for k in ("BENCH_POP", "BENCH_PROMPTS"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["BENCH_FORCED_CPU"] = "1"
        env["XLA_FLAGS"] = forced_host_devices_flags(env.get("XLA_FLAGS", ""), n)
        env.setdefault("BENCH_PROGRAMS_JSONL", "bench_runs/programs.jsonl")
        _log(f"scaling[{rung}]: spawning child at {n} forced host device(s)")
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--rung", rung],
                stdout=subprocess.PIPE, text=True, env=env, timeout=timeout_s,
            )
            line = next(
                (ln for ln in reversed(proc.stdout.splitlines())
                 if ln.strip().startswith("{")), None,
            )
            if proc.returncode != 0 or line is None:
                rows[str(n)] = {
                    "rung": rung,
                    "error": f"child rc={proc.returncode}, "
                             f"{'no JSON line' if line is None else 'nonzero exit'}",
                }
            else:
                rows[str(n)] = json.loads(line)
        except subprocess.TimeoutExpired:
            rows[str(n)] = {
                "rung": rung,
                "error": f"timeout after {timeout_s:.0f}s at {n} device(s)",
            }
        got = rows[str(n)]
        _log(f"scaling[{rung}]: {n} device(s) -> "
             + (f"{got['imgs_per_sec']} imgs/sec" if "imgs_per_sec" in got
                else got.get("error", "?")))
    doc = {
        "metric": "scaling-efficiency (imgs scored/sec/chip)",
        "rung": rung,
        "device_counts": [int(n) for n in device_counts],
        # these are forced-host-platform numbers, not accelerator scaling
        "platform_forced": "cpu",
        "rows": rows,
        "summary": scaling_summary(rows),
        **artifact_stamp(),
    }
    out_line = json.dumps(doc)
    print(out_line)
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            f.write(out_line + "\n")
        _log(f"scaling[{rung}]: artifact -> {out_path}")
    return 0 if all("imgs_per_sec" in r for r in rows.values()) else 1


def scaling_main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="bench.py --scaling",
        description="1→N scaling-efficiency bench at forced host devices",
    )
    ap.add_argument("--scaling", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--rungs", "--rung", dest="rung", default="tiny",
                    help="the ONE rung to scale (default: tiny)")
    ap.add_argument("--devices", default=",".join(map(str, SCALING_DEVICE_COUNTS)),
                    help="comma list of forced host-platform device counts "
                         f"(default: {','.join(map(str, SCALING_DEVICE_COUNTS))})")
    ap.add_argument("--out", default=None,
                    help="also write the SCALING artifact JSON to this path")
    args = ap.parse_args(argv)
    rung_list = [r.strip() for r in args.rung.split(",") if r.strip()]
    if len(rung_list) != 1:
        # the flag spells --rungs for ladder-CLI symmetry, but a scaling run
        # scales ONE rung — silently dropping the rest would publish an
        # artifact the user believes covers more than it does
        print(f"--scaling runs exactly one rung, got {rung_list!r} "
              "(run once per rung; each produces its own SCALING artifact)",
              file=sys.stderr)
        return 2
    rung = rung_list[0]
    if rung not in RUNG_PLAN:
        print(f"unknown rung {rung!r} (have: {sorted(RUNG_PLAN)})",
              file=sys.stderr)
        return 2
    try:
        counts = [int(c) for c in args.devices.split(",") if c.strip()]
    except ValueError:
        counts = []
    if not counts or sorted(set(counts)) != counts or counts[0] != 1:
        print("--devices must be a strictly increasing integer list starting "
              "at 1 (the 1-device row is the efficiency baseline)",
              file=sys.stderr)
        return 2
    return run_scaling(rung, counts, out_path=args.out)


# ---------------------------------------------------------------------------
# serve mode (ISSUE 12): adapter-batched vs sequential-per-adapter serving
# throughput on one rung — the committed number behind the serve/ engine's
# batching claim (SERVE_r*.json)
# ---------------------------------------------------------------------------

def _build_serve_backend(scale: str, base_quant: str):
    """Generator-only build for the serve bench: exactly ``build()``'s
    generator arrays (one jitted init program, bf16 cast, synthesized
    prompt embeddings, optional int8 base) minus the reward towers — serving
    is generate-only, and paying a CLIP/PickScore init for a program that
    never runs them would distort build_s at the big rungs."""
    import jax
    import jax.numpy as jnp

    from hyperscalees_t2i_tpu.backends.sana_backend import SanaBackend
    from hyperscalees_t2i_tpu.models import dcae, sana

    spec = sana_rung_model(scale)
    bcfg = spec["bcfg"]
    backend = SanaBackend(bcfg)
    prompts = list(BENCH_PROMPT_SET)
    M, Ltxt = len(prompts), PROMPT_EMBED_LEN

    def _init_gen(key):
        kt2, kv2, ke = jax.random.split(key, 3)
        out = {
            "params": _cast_tree(sana.init_sana(kt2, bcfg.model), jnp.bfloat16),
            "prompt_embeds": jax.random.normal(
                ke, (M, Ltxt, bcfg.model.caption_dim), jnp.float32
            ),
        }
        if bcfg.decode_images:
            out["vae"] = _cast_tree(dcae.init_decoder(kv2, bcfg.vae), jnp.bfloat16)
        return out

    out = jax.jit(_init_gen)(jax.random.PRNGKey(0))
    jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    if base_quant == "int8":
        from hyperscalees_t2i_tpu.ops.quant import quantize_frozen

        quantized = quantize_frozen(
            {k: out[k] for k in ("params", "vae") if out.get(k) is not None}, "int8"
        )
        jax.tree_util.tree_map(lambda x: x.block_until_ready(), quantized)
        out.update(quantized)
    backend.params = out["params"]
    backend.vae_params = out.get("vae")
    backend.prompts = prompts
    backend.prompt_embeds = out["prompt_embeds"]
    backend.prompt_mask = jnp.ones((M, Ltxt), bool)
    backend.setup()
    return backend


def run_serve_bench(
    rung: str, adapters: int = 0, images: int = 0, batches: int = 3,
    metrics_port: int = 0, metrics_host: str = "0.0.0.0",
) -> dict:
    """Adapter-batched vs sequential-per-adapter serving throughput.

    THREE measured modes over the same backend and the same N distinct
    adapters, so the win decomposes instead of hiding in one ratio:

    - ``batched`` — the serve engine at ``adapter_batch=N``: N requests
      coalesced into one compiled dispatch (continuous batching, steady
      state);
    - ``sequential per-adapter`` (the headline denominator) — the *naive
      per-adapter composition*: one ``jax.jit`` dispatch per request with
      the adapter staged per request. This is not a strawman: it is
      byte-for-byte the composition ``tools/demo.py`` shipped before the
      serve engine existed, and the overhead "LoRA Is Slower Than You
      Think" (PAPERS.md) documents for per-tenant serving;
    - ``sequential AOT`` — the engine's own one-slot program
      (``adapter_batch=1``: AOT compile + staging cache, no batching): the
      strict ablation separating the batching win from the AOT/staging win.

    Every timed path is execution-synced (images device-get per dispatch),
    per-request parity across all three paths is recorded in the artifact
    (bitwise on CPU tiny — the same contract tests/test_serve.py asserts),
    and the serve programs' ledger records ride along so the win carries
    its bytes/FLOPs, not just a ratio.
    """
    import jax
    import numpy as np

    from hyperscalees_t2i_tpu.obs import MetricsRegistry, get_registry, set_registry
    from hyperscalees_t2i_tpu.rungs import SERVE_PLAN
    from hyperscalees_t2i_tpu.serve import ServeConfig, ServeEngine

    scale, _pop, _m, _mb = RUNG_PLAN[rung]
    plan = SERVE_PLAN.get(rung, {})
    N = adapters or int(plan.get("adapter_batch", 4))
    B = images or int(plan.get("images_per_request", 1))
    member_batch = int(plan.get("member_batch", 0))
    opt = rung_opt(rung)
    set_registry(MetricsRegistry())

    _log(f"serve[{rung}]: building generator (scale={scale} adapters={N} "
         f"images={B} base={opt.get('base_quant', 'off')})")
    t0 = time.perf_counter()
    with Heartbeat(f"serve:{rung}", "build"):
        backend = _build_serve_backend(scale, opt.get("base_quant", "off"))
    build_s = time.perf_counter() - t0

    # N distinct adapters: LoRA init gives b=0 (identity adapter), so each
    # gets a small random perturbation on every leaf — distinct tenants must
    # produce distinct images or the hot-swap measurement proves nothing
    template = backend.init_theta(jax.random.PRNGKey(0))
    thetas = []
    for i in range(N):
        k = jax.random.fold_in(jax.random.PRNGKey(7), i)
        thetas.append(jax.tree_util.tree_map(
            lambda x, kk=k: x + 0.05 * jax.random.normal(kk, x.shape, x.dtype),
            backend.init_theta(jax.random.fold_in(jax.random.PRNGKey(8), i)),
        ))

    eng_b = ServeEngine(
        backend, ServeConfig(adapter_batch=N, images_per_request=B,
                             member_batch=member_batch,
                             metrics_port=metrics_port,
                             metrics_host=metrics_host),
        theta_template=template,
    )
    if eng_b.exporter is not None:
        _log(f"serve[{rung}]: live /metrics + /healthz on port "
             f"{eng_b.exporter.port}")
    for i, th in enumerate(thetas):
        eng_b.put_adapter(f"tenant{i}", th)
    eng_s = ServeEngine(
        backend, ServeConfig(adapter_batch=1, images_per_request=B),
        theta_template=template, store=eng_b.store,
    )

    M = backend.num_items
    def submit_round(eng, round_idx):
        for i in range(N):
            eng.submit(f"tenant{i}", [(i + j) % M for j in range(B)],
                       seed=1000 * round_idx + i)

    # the naive per-adapter composition (the pre-ISSUE-12 demo path): ONE
    # jax.jit dispatch per request, adapter tree staged from host per
    # request. Same generate_p, same frozen arrays, same keys → outputs
    # must match the engine's bitwise on CPU.
    naive_fn = jax.jit(
        lambda fz, th, ids_, key_: backend.generate_p(fz, th, ids_, key_)
    )
    frozen = backend.frozen
    import jax.numpy as jnp

    thetas_np = [
        jax.tree_util.tree_map(lambda x: np.asarray(jax.device_get(x)), t)
        for t in thetas
    ]

    def naive_request(i, seed):
        ids_ = jnp.asarray([(i + j) % M for j in range(B)], jnp.int32)
        out = naive_fn(frozen, thetas_np[i], ids_, jax.random.PRNGKey(seed))
        return np.asarray(jax.device_get(out))

    _log(f"serve[{rung}]: compiling + warming all three paths")
    with Heartbeat(f"serve:{rung}", "compile"):
        eng_b.warmup(); eng_s.warmup()
        naive_request(0, 0)
        # parity round: same requests (same seeds) through all three paths
        submit_round(eng_b, 0)
        batched_res = {r.request.adapter_id: r for r in eng_b.flush()}
        seq_imgs = {
            f"tenant{i}": eng_s.generate(
                f"tenant{i}", [(i + j) % M for j in range(B)], seed=i
            )
            for i in range(N)
        }
        naive_imgs = {f"tenant{i}": naive_request(i, i) for i in range(N)}
    diffs = [
        float(np.max(np.abs(
            np.asarray(batched_res[a].images, np.float32)
            - np.asarray(ref[a], np.float32)
        )))
        for ref in (seq_imgs, naive_imgs) for a in ref
    ]
    parity_max = max(diffs)
    parity_bitwise = all(
        np.array_equal(batched_res[a].images, ref[a])
        for ref in (seq_imgs, naive_imgs) for a in ref
    )
    # hot-swap probe: the SAME prompt and seed for every tenant, so the
    # outputs can differ only through the adapter argument — the parity
    # round above varies prompts/seeds per slot and cannot prove this
    for i in range(N):
        eng_b.submit(f"tenant{i}", [0] * B, seed=424242)
    probe = {r.request.adapter_id: r.images for r in eng_b.flush()}
    t0_img = probe["tenant0"]
    hot_swap_effective = any(
        not np.array_equal(t0_img, probe[f"tenant{i}"]) for i in range(1, N)
    )

    # Timed rounds are INTERLEAVED (batched → naive → AOT per round) so a
    # shared-host load burst taxes every mode equally instead of whichever
    # mode it happened to land on — the published ratio is what stabilizes.
    _log(f"serve[{rung}]: timing {batches} interleaved rounds "
         "(batched / naive / AOT)")
    dt_b = dt_s = dt_sa = 0.0
    with Heartbeat(f"serve:{rung}", "timed", gauges=None):
        for r in range(1, batches + 1):
            t0 = time.perf_counter()
            submit_round(eng_b, r)
            eng_b.flush()  # execution-synced per dispatch (device_get inside)
            dt_b += time.perf_counter() - t0
            t0 = time.perf_counter()
            for i in range(N):
                naive_request(i, 1000 * r + i)
            dt_s += time.perf_counter() - t0
            t0 = time.perf_counter()
            for i in range(N):
                eng_s.generate(f"tenant{i}", [(i + j) % M for j in range(B)],
                               seed=1000 * r + i)
            dt_sa += time.perf_counter() - t0
    batched_ips = N * B * batches / dt_b
    seq_ips = N * B * batches / dt_s
    seq_aot_ips = N * B * batches / dt_sa

    snap = get_registry().snapshot()
    stats_b = eng_b.stats()
    rec = {
        "metric": "serve throughput (imgs/sec, adapter-batched vs sequential)",
        "mode": "serve",
        "rung": rung,
        "geometry": scale,
        "adapters": N,
        "images_per_request": B,
        "member_batch": member_batch,
        "batches_timed": batches,
        "batched_imgs_per_sec": round(batched_ips, 4),
        # the naive per-adapter composition (pre-engine demo path: one jit
        # dispatch + per-request adapter staging) — the headline denominator
        "sequential_imgs_per_sec": round(seq_ips, 4),
        "batched_vs_sequential": round(batched_ips / seq_ips, 4),
        # ablation: the engine's own one-slot AOT program — separates the
        # batching win from the AOT/staging win
        "sequential_aot_imgs_per_sec": round(seq_aot_ips, 4),
        "batched_vs_sequential_aot": round(batched_ips / seq_aot_ips, 4),
        "batched_dispatch_s": round(dt_b / batches, 4),
        "sequential_request_s": round(dt_s / (batches * N), 4),
        "sequential_aot_request_s": round(dt_sa / (batches * N), 4),
        "parity_bitwise": bool(parity_bitwise),
        "parity_max_abs_diff": parity_max,
        "hot_swap_effective": bool(hot_swap_effective),
        # ledger facts per serve program (site="serve" records also land in
        # BENCH_PROGRAMS_JSONL): the win carries its bytes/FLOPs
        "programs": stats_b["programs"] | eng_s.stats()["programs"],
        "hbm_budget_bytes": stats_b["hbm_budget_bytes"],
        "adapter_store": {
            "resident": stats_b["store"]["resident"],
            "resident_bytes": stats_b["store"]["resident_bytes"],
        },
        "serve_compiles": snap.get("obs/serve_compiles"),
        "serve_traces": snap.get("obs/serve_traces"),
        "serve_dispatches": snap.get("obs/serve_dispatches"),
        "build_s": round(build_s, 2),
        "n_devices": len(jax.devices()),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "base_quant": opt.get("base_quant", "off"),
        "sync": "device_get",
        **artifact_stamp(),
    }
    return rec


def serve_bench_main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="bench.py --serve",
        description="multi-tenant serving bench: adapter-batched vs "
                    "sequential-per-adapter imgs/sec on one rung",
    )
    ap.add_argument("--serve", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--rung", default="tiny",
                    help="the rung geometry to serve (default: tiny)")
    ap.add_argument("--adapters", type=int, default=0,
                    help="distinct adapters / batched width "
                         "(default: rungs.SERVE_PLAN)")
    ap.add_argument("--images", type=int, default=0,
                    help="images per request (default: rungs.SERVE_PLAN)")
    ap.add_argument("--batches", type=int, default=3,
                    help="timed rounds per path (default 3)")
    ap.add_argument("--metrics_port", type=int, default=0,
                    help="serve live /metrics + /healthz from the batched "
                         "engine on this port while the bench runs (0 = "
                         "off; the CI serve smoke scrapes it mid-run)")
    ap.add_argument("--metrics_host", default="0.0.0.0",
                    help="exporter bind address (127.0.0.1 for "
                         "loopback-only; the endpoint is unauthenticated)")
    ap.add_argument("--metrics_linger_s", type=float, default=0.0,
                    help="keep the exporter up this many seconds after the "
                         "bench finishes so a pull-based scraper catches "
                         "the final state (0 = exit immediately)")
    ap.add_argument("--out", default=None,
                    help="also write the SERVE artifact JSON to this path")
    args = ap.parse_args(argv)
    if args.rung not in RUNG_PLAN:
        print(f"unknown rung {args.rung!r} (have: {sorted(RUNG_PLAN)})",
              file=sys.stderr)
        return 2
    refusal = no_tpu_refusal([args.rung])
    if refusal:
        print(refusal, file=sys.stderr)
        return 2
    _install_bench_ledger()
    rec = run_serve_bench(args.rung, args.adapters, args.images, args.batches,
                          metrics_port=args.metrics_port,
                          metrics_host=args.metrics_host)
    line = json.dumps(rec)
    print(line)
    if args.out:
        out_dir = os.path.dirname(os.path.abspath(args.out))
        os.makedirs(out_dir, exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
        _log(f"serve[{args.rung}]: artifact -> {args.out}")
    if args.metrics_port and args.metrics_linger_s > 0:
        # drain window: the exporter daemon thread dies with the process;
        # hold the process so a pull-based scraper catches the final state
        _log(f"serve: /metrics draining for {args.metrics_linger_s:g}s")
        time.sleep(args.metrics_linger_s)
    return 0


def run_fleet_bench(rung: str, widths, batches: int = 3,
                    base_quant: str | None = None) -> dict:
    """Fleet training bench (ISSUE 20): the fused J-job (job, member)-batched
    ES step vs J sequential single-job steps on one rung.

    One build, then per J: AOT-compile the fused ``make_fleet_step`` program
    and J per-job solo steps, warm both, time ``batches`` interleaved rounds
    (fused → sequential per round, execution-synced via a fetched scalar off
    the last θ), and record:

    - ``fused_imgs_per_sec_chip`` vs ``sequential_imgs_per_sec_chip`` — the
      amortization headline (per chip so pod artifacts stay comparable),
    - ``bytes_per_job`` from the fused program's ledger record vs the solo
      program's bytes — the ledger proof riding the ratio,
    - per-job reward rows, fused vs solo: ``parity_max_abs_diff`` and the
      ``parity_close`` verdict against the written tolerance — epoch-0 rows
      from identical init θ (train/fleet.reward_rows_close; two XLA programs
      agree to rounding, not to a hash), with both sides' sha256 as content
      identifiers.

    Jobs are DISTINCT tenants: per-job σ/lr_scale/seed (argument values in
    the fused program — the same job mix at fixed J can never retrace).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hyperscalees_t2i_tpu.backends.base import make_frozen
    from hyperscalees_t2i_tpu.lora import stack_adapters
    from hyperscalees_t2i_tpu.obs import MetricsRegistry, get_registry, set_registry
    from hyperscalees_t2i_tpu.train.config import TrainConfig
    from hyperscalees_t2i_tpu.train.fleet import (
        make_solo_reward_rows,
        reward_rows_close,
        reward_rows_digest,
    )
    from hyperscalees_t2i_tpu.train.trainer import (
        fleet_scalar_args,
        make_es_step,
        make_fleet_step,
    )

    scale, pop, m, member_batch = RUNG_PLAN[rung]
    pop = int(os.environ.get("BENCH_POP", pop))
    m = int(os.environ.get("BENCH_PROMPTS", m))
    opt = rung_opt(rung)
    if base_quant is not None:
        # the fleet workload IS the resident int8 base (PR 9): the fused
        # step's amortization claim is dequantized-base-tile-read-once-per-
        # token-tile, so the bench defaults the base to int8 even on rungs
        # whose solo ladder runs unquantized
        opt["base_quant"] = base_quant
    set_registry(MetricsRegistry())

    _log(f"fleet[{rung}]: building models (scale={scale} pop={pop} m={m})")
    t0 = time.perf_counter()
    with Heartbeat(f"fleet:{rung}", "build"):
        backend, reward_fn = build(
            scale, remat=opt["remat"], tower_dtype=opt["tower_dtype"],
            base_quant=opt.get("base_quant", "off"),
        )
    build_s = time.perf_counter() - t0
    n_dev = len(jax.devices())

    def job_tc(j):
        # distinct per-job hypers: σ/lr_scale/seed differ per job, cohort
        # geometry shared — exactly what the fused program argument-batches.
        return TrainConfig(
            pop_size=pop, sigma=0.01 * (1.0 + 0.5 * j), lr_scale=1.0 + 0.25 * j,
            egg_rank=4, prompts_per_gen=m, batches_per_gen=1,
            member_batch=member_batch, promptnorm=True,
            remat=opt["remat"], reward_tile=opt["reward_tile"],
            noise_dtype=opt["noise_dtype"],
            base_quant=opt.get("base_quant", "off"), quality=False, seed=11 + j,
        )

    num_unique = min(m, backend.num_items)
    repeats = 1
    frozen = make_frozen(backend, reward_fn)
    info = backend.step_info(0, num_unique, repeats)
    flat_ids = jnp.asarray(info.flat_ids, jnp.int32)

    max_j = max(widths)
    tcs = [job_tc(j) for j in range(max_j)]
    thetas = [
        backend.init_theta(jax.random.fold_in(jax.random.PRNGKey(t.seed), 17))
        for t in tcs
    ]
    # host master copies: the solo/fused steps donate their θ/Δ arguments,
    # so every chain start stages fresh device trees from these
    thetas_np = [
        jax.tree_util.tree_map(lambda x: np.asarray(jax.device_get(x)), th)
        for th in thetas
    ]
    from hyperscalees_t2i_tpu.es import epoch_key

    keys = [epoch_key(t.seed, 0) for t in tcs]

    # solo side once per job (shared across widths): compiled step + the
    # parity rows program (train/fleet.make_solo_reward_rows — the solo step
    # never exposes its reward rows)
    _log(f"fleet[{rung}]: compiling {max_j} solo steps + parity rows")
    solo_steps, solo_rows = [], []
    with Heartbeat(f"fleet:{rung}", "solo-compile"):
        for j, t in enumerate(tcs):
            # donate=False: the bench re-executes these programs many times
            # in one process; XLA:CPU input donation has shown silent buffer
            # clobbering under that pattern (training keeps donation)
            step = make_es_step(backend, reward_fn, t, num_unique, repeats,
                                stateful_delta=True, donate=False)
            zeros = jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, x.dtype), thetas[j]
            )
            lowered = step.lower(frozen, thetas[j], zeros, flat_ids, keys[j])
            compiled = lowered.compile()
            record_compile(
                site="bench", label=f"fleet-{rung}-solo-job{j}",
                lowered=lowered, compiled=compiled,
                geometry={"scale": scale, "pop": pop, "m": num_unique,
                          "r": repeats, "member_batch": member_batch,
                          "fleet_width": 1, **opt},
            )
            solo_steps.append(compiled)
            rows_fn = make_solo_reward_rows(backend, reward_fn, t)
            rows = rows_fn(frozen, thetas[j], flat_ids, keys[j])
            solo_rows.append(np.asarray(jax.device_get(rows)))

    rows_out, solo_prog_bytes = [], None
    snap0 = get_registry().snapshot()
    for J in widths:
        jt = tcs[:J]
        stacked = jax.tree_util.tree_map(
            jnp.asarray, stack_adapters(thetas_np[:J])
        )
        szeros = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, x.dtype), stacked
        )
        ids_j = jnp.stack([flat_ids] * J)
        keys_j = jnp.stack(keys[:J])
        sig, csc, lrs = fleet_scalar_args(jt)
        args = (frozen, stacked, szeros, ids_j, keys_j,
                jnp.asarray(sig), jnp.asarray(csc), jnp.asarray(lrs))

        _log(f"fleet[{rung}]: J={J} compiling fused step")
        fleet_step = make_fleet_step(backend, reward_fn, jt[0], num_unique,
                                     repeats, J, donate=False)
        t_c0 = time.perf_counter()
        with Heartbeat(f"fleet:{rung}", f"compile-j{J}"):
            lowered = fleet_step.lower(*args)
            lowering_s = time.perf_counter() - t_c0
            compiled = lowered.compile()
        compile_s = time.perf_counter() - t_c0
        prog = record_compile(
            site="bench", label=f"fleet-{rung}-j{J}",
            lowered=lowered, compiled=compiled,
            lowering_s=lowering_s, compile_s=compile_s - lowering_s,
            geometry={"scale": scale, "pop": pop, "m": num_unique,
                      "r": repeats, "member_batch": member_batch,
                      "fleet_width": J, **opt},
        )

        # the steps donate their θ/Δ arguments, so every execution gets
        # freshly staged device trees (staging happens OUTSIDE the timed
        # windows on both paths — the measurement is dispatch+execute+fetch)
        def fused_args():
            st = jax.tree_util.tree_map(
                jnp.asarray, stack_adapters(thetas_np[:J])
            )
            sz = jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, x.dtype), st
            )
            return (frozen, st, sz, ids_j, keys_j,
                    jnp.asarray(sig), jnp.asarray(csc), jnp.asarray(lrs))

        def solo_args(j):
            th = jax.tree_util.tree_map(jnp.asarray, thetas_np[j])
            de = jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, x.dtype), th
            )
            return (frozen, th, de, flat_ids, keys[j])

        # warmup + epoch-0 parity surface in one execution (the per-job
        # reward rows ride the metrics pytree)
        with Heartbeat(f"fleet:{rung}", f"warmup-j{J}", gauges=None):
            _, _, metrics_f, _ = compiled(*fused_args())
            fleet_rows = np.asarray(
                jax.device_get(metrics_f["fleet_reward_rows"])
            )
            for j in range(J):
                _, _, ms, _ = solo_steps[j](*solo_args(j))
                float(jax.device_get(ms["opt_score_mean"]))
        closeness = [reward_rows_close(fleet_rows[j], solo_rows[j]) for j in range(J)]

        # interleaved timed rounds: fused then sequential per round, so a
        # host load burst taxes both paths equally (serve-bench discipline).
        # Sync discipline mirrors the real loops EXACTLY: the fleet scheduler
        # fetches the full metrics pytree ONCE per tick (train/fleet.py
        # tick()); a sequential single-job run fetches its full metrics dict
        # every epoch (run_training's `metrics = jax.device_get(metrics)`) —
        # so the sequential side pays one dispatch + one full-metrics fetch
        # PER JOB, exactly the host round-trips fleet batching removes.
        _log(f"fleet[{rung}]: J={J} timing {batches} interleaved rounds")
        dt_f = dt_s = 0.0
        with Heartbeat(f"fleet:{rung}", f"timed-j{J}", gauges=None):
            for r in range(batches):
                a = fused_args()
                t0 = time.perf_counter()
                _, _, mf, _ = compiled(*a)
                jax.device_get(mf)
                dt_f += time.perf_counter() - t0
                sargs = [solo_args(j) for j in range(J)]
                t0 = time.perf_counter()
                for j in range(J):
                    _, _, ms, _ = solo_steps[j](*sargs[j])
                    jax.device_get(ms)
                dt_s += time.perf_counter() - t0
        imgs = J * pop * num_unique * repeats * batches
        fused_ips = imgs / dt_f / max(n_dev, 1)
        seq_ips = imgs / dt_s / max(n_dev, 1)
        fused_bytes = prog.get("bytes_accessed")
        if J == 1:
            solo_prog_bytes = fused_bytes
        rows_out.append({
            "width": J,
            "fused_imgs_per_sec_chip": round(fused_ips, 4),
            "sequential_imgs_per_sec_chip": round(seq_ips, 4),
            "fused_vs_sequential": round(fused_ips / seq_ips, 4),
            "fused_step_s": round(dt_f / batches, 4),
            "sequential_step_s": round(dt_s / batches, 4),
            "bytes_accessed": fused_bytes,
            "bytes_per_job": (
                round(fused_bytes / J) if fused_bytes is not None else None
            ),
            "peak_bytes_est": prog.get("peak_bytes"),
            "stablehlo_sha256": prog.get("stablehlo_sha256"),
            "compile_s": round(compile_s, 2),
            "reward_rows_sha256": [reward_rows_digest(fleet_rows[j]) for j in range(J)],
            "solo_rows_sha256": [reward_rows_digest(r) for r in solo_rows[:J]],
            "parity_max_abs_diff": max(d for _, d in closeness),
            "parity_close": all(ok for ok, _ in closeness),
        })
    snap1 = get_registry().snapshot()
    rec = {
        "metric": "fleet training throughput (imgs/sec/chip, fused J-job "
                  "step vs J sequential single-job steps)",
        "mode": "fleet",
        "rung": rung,
        "geometry": scale,
        "pop": pop,
        "prompts": num_unique,
        "member_batch": member_batch,
        "batches_timed": batches,
        "widths": rows_out,
        # flat-retrace evidence: fleet_traces must equal the number of fused
        # compiles (one per width) — a job-mix-driven retrace would exceed it
        "fleet_traces": (snap1.get("obs/fleet_traces") or 0)
                        - (snap0.get("obs/fleet_traces") or 0),
        "widths_compiled": len(widths),
        "solo_bytes_accessed": solo_prog_bytes,
        "parity_close": all(r["parity_close"] for r in rows_out),
        "build_s": round(build_s, 2),
        "n_devices": n_dev,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "base_quant": opt.get("base_quant", "off"),
        "sync": "device_get",
        **artifact_stamp(),
    }
    return rec


def fleet_bench_main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="bench.py --fleet",
        description="fleet training bench: fused J-job ES step vs J "
                    "sequential single-job steps on one rung",
    )
    ap.add_argument("--fleet", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--rung", default="tiny",
                    help="the rung geometry to fleet-train (default: tiny)")
    ap.add_argument("--widths", default="1,2,4",
                    help="comma list of fleet widths J (default: 1,2,4)")
    ap.add_argument("--batches", type=int, default=3,
                    help="timed rounds per width (default 3)")
    ap.add_argument("--base", default="int8", choices=["off", "int8"],
                    help="frozen-base quantization (default int8 — the "
                         "resident-base workload the fleet step amortizes)")
    ap.add_argument("--out", default=None,
                    help="also write the FLEET artifact JSON to this path")
    args = ap.parse_args(argv)
    if args.rung not in RUNG_PLAN:
        print(f"unknown rung {args.rung!r} (have: {sorted(RUNG_PLAN)})",
              file=sys.stderr)
        return 2
    try:
        widths = [int(w) for w in args.widths.split(",") if w.strip()]
    except ValueError:
        print(f"bad --widths {args.widths!r}", file=sys.stderr)
        return 2
    if not widths or any(w < 1 for w in widths):
        print(f"bad --widths {args.widths!r}", file=sys.stderr)
        return 2
    refusal = no_tpu_refusal([args.rung])
    if refusal:
        print(refusal, file=sys.stderr)
        return 2
    _install_bench_ledger()
    rec = run_fleet_bench(args.rung, widths, args.batches,
                          base_quant=args.base)
    line = json.dumps(rec)
    print(line)
    if args.out:
        out_dir = os.path.dirname(os.path.abspath(args.out))
        os.makedirs(out_dir, exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
        _log(f"fleet[{args.rung}]: artifact -> {args.out}")
    return 0


# ---------------------------------------------------------------------------
# parent: budget + stall enforcement over a streaming child (no jax here —
# the parent must never block on backend init)
# ---------------------------------------------------------------------------

class _ChildReader:
    """Streams a serve-mode child. Rung/result JSON arrives on the child's
    stdout; heartbeats arrive on its STDERR (shared obs.Heartbeat contract —
    stdout stays a pure results channel). Both streams are pumped: hb lines
    are parsed into ``lines`` for the stall detector, and every stderr line
    is forwarded verbatim to our own stderr so timeouts stay diagnosable."""

    def __init__(self, rungs, deadline):
        env = dict(os.environ)
        # single-rung overrides must not silently rescale ladder rungs
        env.pop("BENCH_POP", None)
        env.pop("BENCH_PROMPTS", None)
        env["BENCH_DEADLINE_IN_S"] = str(max(10.0, deadline - time.monotonic()))
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--serve", ",".join(rungs)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        self.lines: list = []  # appended from both pump threads (GIL-atomic)
        self._t = threading.Thread(target=self._pump, daemon=True)
        self._t_err = threading.Thread(target=self._pump_err, daemon=True)
        self._t.start()
        self._t_err.start()

    def _pump(self):
        for line in self.proc.stdout:
            line = line.strip()
            if line.startswith("{"):
                try:
                    self.lines.append(json.loads(line))
                except json.JSONDecodeError:
                    pass

    def _pump_err(self):
        for raw in self.proc.stderr:
            line = raw.strip()
            if line.startswith("{"):
                try:
                    item = json.loads(line)
                except json.JSONDecodeError:
                    item = None
                # ONLY heartbeats are liveness signals; any other JSON-shaped
                # stderr noise must not be mistaken for a rung result.
                if isinstance(item, dict) and "hb" in item:
                    self.lines.append(item)
            sys.stderr.write(raw)
            sys.stderr.flush()  # keep the tail live — that's what it's for

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        # A rung line may be sitting in the pipe buffer at kill time; the
        # pump threads see EOF after the kill — join them so ``lines`` is
        # complete before the caller records errors (code-review r4).
        self._t.join(timeout=5)
        self._t_err.join(timeout=5)


def main() -> int:
    budget = float(os.environ.get("BENCH_BUDGET_S", "540"))
    deadline = time.monotonic() + budget - 15  # reporting reserve
    if os.environ.get("BENCH_TINY") == "1":
        rungs = ["tiny"]
    else:
        rungs = [r.strip() for r in os.environ.get("BENCH_RUNGS", ",".join(RUNG_ORDER)).split(",") if r.strip()]

    results = {r: {"rung": r, "error": "no result (budget exhausted)"} for r in rungs}
    pending = list(rungs)
    backend_came_up = [False]
    attempts = 0
    while pending and time.monotonic() < deadline - 30 and attempts < 3:
        attempts += 1
        _log(f"spawning ladder child (attempt {attempts}) for {pending}")
        reader = _ChildReader(pending, deadline)
        consumed = [0]

        last_hb = [None]

        def drain() -> bool:
            """Fold newly arrived rung lines into results; True if the child
            made *progress*. A heartbeat only counts as progress when its
            (rung, phase) differs from the previous one — a repeated
            same-phase heartbeat proves the process is alive, not that the
            phase is advancing, and must not disarm the stall cap
            (code-review r4)."""
            any_new = False
            while len(reader.lines) > consumed[0]:
                item = reader.lines[consumed[0]]
                consumed[0] += 1
                backend_came_up[0] = True  # any child line implies init done
                if "hb" in item:
                    state = (item.get("hb"), item.get("phase"))
                    if state != last_hb[0]:
                        last_hb[0] = state
                        any_new = True
                    continue
                any_new = True
                rung = item.get("rung")
                ok = "imgs_per_sec" in item  # content validation
                if rung in results:
                    results[rung] = item
                    if rung in pending:
                        pending.remove(rung)
                _log(f"rung {rung}: {'ok' if ok else item.get('error', '?')}")
            return any_new

        # Stall cap applies per rung AFTER the first line arrives; the first
        # line additionally absorbs backend init, so it is only bounded by
        # the global deadline.
        rung_wait_start = time.monotonic()
        got_first_line = False
        stalled_rung = None
        while pending:
            now = time.monotonic()
            if drain():
                got_first_line = True
                rung_wait_start = now
                continue
            if now >= deadline:
                _log("global deadline reached; killing child")
                break
            if reader.proc.poll() is not None:
                reader._t.join(timeout=5)
                drain()
                _log(f"child exited rc={reader.proc.returncode}; {len(pending)} rungs unreported")
                break
            if got_first_line:
                # 240s floor: a big-geometry XLA compile can legitimately
                # sit in one phase for minutes (phase-change heartbeats
                # reset this clock; same-phase ones do not)
                n_left = max(len(pending), 1)
                cap = max(240.0, (deadline - rung_wait_start) / n_left)
                if now - rung_wait_start > cap:
                    stalled_rung = pending[0]
                    _log(f"rung {stalled_rung} stalled (> {cap:.0f}s); killing child, will retry rest")
                    break
            time.sleep(1.0)
        # Every exit path: kill (joins the pump thread) then drain once more —
        # a completed rung line must never be replaced by an error record.
        reader.kill()
        drain()
        if stalled_rung is not None and stalled_rung in pending:
            results[stalled_rung] = {
                "rung": stalled_rung, "error": "stalled: no result within per-rung cap",
            }
            pending.remove(stalled_rung)
        if not pending:
            break

    ok = [r for r in results.values() if "imgs_per_sec" in r]
    if not ok:
        err = "no rung completed"
        if attempts == 0:
            err += " (budget too small to spawn a ladder child)"
        elif not backend_came_up[0]:
            err += " (JAX backend init never returned)"
        print(json.dumps({
            "metric": "population-evals/sec (imgs scored/sec)",
            "value": None, "unit": "imgs/sec", "vs_baseline": None,
            "error": err, "backend_came_up": backend_came_up[0],
            **artifact_stamp(),
            "rungs": results,
        }))
        return 1

    # MFU sanity gate: a reading above 1.0 is physically impossible — refuse
    # to publish it (the r2 failure mode).
    bad = [r for r in ok if r.get("mfu") is not None and r["mfu"] > 1.0]
    if bad:
        print(json.dumps({
            "metric": "population-evals/sec (imgs scored/sec)",
            "value": None, "unit": "imgs/sec", "vs_baseline": None,
            "error": f"IMPOSSIBLE MFU > 1.0 — timing is not execution-synced: "
                     f"{[(r['rung'], r['mfu']) for r in bad]}",
            "backend_came_up": backend_came_up[0],
            **artifact_stamp(),
            "rungs": results,
        }))
        return 1

    order = {name: i for i, name in enumerate(
        ["tiny", "small", "popscale", "mid", "midpop", "flagship", "flagpop"]
    )}
    head = max(ok, key=lambda r: order.get(r["rung"], -1))
    # vs_baseline is only claimed at flagship geometry on a real accelerator
    # (also covers deliberate JAX_PLATFORMS=cpu smoke runs of the ladder)
    vs = (
        round(head["imgs_per_sec"] / BASELINE_IMGS_PER_SEC, 4)
        if head["geometry"] == "flagship" and head.get("platform") == "tpu"
        else None
    )
    # The gate is ARMED only if the headline rung actually carries an MFU —
    # off the TPU no peak is known and the gate cannot fire, and that fact
    # must be visible in the artifact.
    print(json.dumps({
        "metric": "population-evals/sec (imgs scored/sec)",
        "value": head["imgs_per_sec"],
        "unit": "imgs/sec",
        # only claimed at flagship geometry; the denominator is our own
        # single-A100 estimate of the reference's sequential loop (module doc)
        "vs_baseline": vs,
        "baseline_estimated": True,
        "geometry": head["geometry"],
        "pop": head["pop"],
        "member_batch": head["member_batch"],
        "mfu": head.get("mfu"),
        "mfu_gate_armed": head.get("mfu") is not None,
        "platform": head.get("platform"),
        **artifact_stamp(),
        "rungs": results,
    }))
    return 0


if __name__ == "__main__":
    # --profile DIR rides the environment (BENCH_PROFILE_DIR) to the children
    _argv = apply_profile_argv(sys.argv[1:])
    from hyperscalees_t2i_tpu.utils.compile_cache import place_compile_cache

    # imports jax, initializes no backend: in the ladder and scaling parents
    # the chip stays the child's, and the child inherits the directory
    place_compile_cache()
    if "--scaling" in _argv:
        sys.exit(scaling_main(_argv))
    if len(_argv) >= 2 and _argv[0] == "--rung":
        _install_bench_ledger()
        # --scaling's children are CPU by design and marked so
        # (BENCH_FORCED_CPU; the artifact says platform_forced)
        _refusal = (
            None if os.environ.get("BENCH_FORCED_CPU") else no_tpu_refusal([_argv[1]])
        )
        if _refusal:
            print(_refusal, file=sys.stderr)
            sys.exit(2)
        print(json.dumps(run_rung(_argv[1], allow_env_overrides=True)))
        sys.exit(0)
    if len(_argv) >= 2 and _argv[0] == "--serve" and not _argv[1].startswith("-") \
            and all(r in RUNG_PLAN for r in _argv[1].split(",") if r):
        # ladder CHILD mode (the parent's spawn spelling, `--serve R1,R2`,
        # predates the serving engine); the serve *bench* below takes its
        # rung via --rung
        rungs = [r for r in _argv[1].split(",") if r]
        deadline = time.monotonic() + float(os.environ.get("BENCH_DEADLINE_IN_S", "525"))
        sys.exit(serve_rungs(rungs, deadline))
    if "--serve" in _argv:
        # serving bench (ISSUE 12): adapter-batched vs sequential imgs/sec
        sys.exit(serve_bench_main(_argv))
    if "--fleet" in _argv:
        # fleet training bench (ISSUE 20): fused J-job ES step vs J
        # sequential single-job steps
        sys.exit(fleet_bench_main(_argv))
    sys.exit(main())

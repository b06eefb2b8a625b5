"""The benchmark geometry ladder, shared by bench.py and tools/preflight.py.

One definition of every rung's shape — population/prompt/member-batch plan
(:data:`RUNG_PLAN`) and the per-scale model/VAE/reward-tower configs
(:func:`sana_rung_model`) — so the offline preflight analyzes *exactly* the
programs the bench times and the trainer dispatches. Before this module the
configs lived inline in ``bench.build()`` and any out-of-band analysis
(PERF.md's hand-made program-size table) had to re-derive them.

Import discipline: module-level code is **stdlib-only** — bench.py's ladder
parent imports these tables and must never pay, or trigger, a jax import
(it reads liveness from a child whose backend init can block for minutes).
:func:`sana_rung_model` imports the model configs lazily.
"""

from __future__ import annotations

from typing import Any, Dict

# rung name -> (scale tag, pop, prompts, member_batch)
RUNG_PLAN = {
    "tiny": ("tiny", 4, 4, 1),
    "small": ("small", 4, 4, 1),
    # pop 128 = the reference's headline population (runES.py:434-435)
    "popscale": ("small", 128, 4, 8),
    "mid": ("mid", 4, 4, 1),
    "flagship": ("flagship", 4, 4, 1),
    # opt-in (BENCH_RUNGS=ar): VAR next-scale AR — exercises the Pallas
    # decode-attention kernel on real TPU, which the CPU test tier can only
    # lower, not execute (ops/attention.py)
    "ar": ("ar_small", 16, 4, 4),
    # opt-in population-scaling rungs at the big geometries (PERF.md "Next
    # levers" #3: MFU climbs with population — same lever that took small
    # geometry 0.25% → 0.89%); separate from the ladder so the plain
    # mid/flagship first-compiles land in the cache first
    "midpop": ("mid", 32, 4, 8),
    "flagpop": ("flagship", 16, 4, 4),
    # opt-in hotspot decomposition: flagship geometry with the 1024px DC-AE
    # decode + CLIP rewards replaced by a trivial latent reward — the
    # difference against the full flagship rung measures the decode+reward
    # share of the step directly (PERF.md predicted hotspots), no trace
    # parsing required
    "flaggen": ("flagship_gen", 4, 4, 1),
}
# tiny first: a guaranteed-completing rung.
RUNG_ORDER = ["tiny", "small", "popscale", "mid", "flagship"]

# Conservative build+compile+run cost guesses per rung (seconds), used by the
# bench child to skip rungs it can't finish inside its deadline (a skip line
# beats a parent kill: the report says *why*).
RUNG_EST_S = {
    "tiny": 40, "small": 60, "popscale": 60, "mid": 120, "flagship": 240,
    "ar": 150, "midpop": 180, "flagpop": 360, "flaggen": 180,
}

# Steps fused into ONE dispatched program (lax.fori_loop over the ES step) to
# amortize the per-dispatch host round-trip, which dominates the small rungs
# (on the chip: not measured). The flagship rung defaults to 0 (no second large XLA compile risked before the
# plain program has landed in the persistent cache); BENCH_CHAIN overrides
# for all rungs. `mid` chains since PR 5's memory diet made it fit one chip
# (17.3→2.8 GB peak), but only through the fit gate below.
RUNG_CHAIN = {"tiny": 16, "small": 8, "popscale": 4, "mid": 2, "flagship": 0, "ar": 4}
# Rungs whose chained program is gated on the measured fit verdict: bench
# EXECUTES their chained program only when that chained program's own
# compiled peak-HBM estimate fits the running device (utils/mfu capacity
# table; compiling is host-side and safe, executing is what OOMs) —
# chaining can amortize dispatch tax, never resurrect a no-fit. The gate
# applies even under a BENCH_CHAIN override. Unknown capacity (CPU smoke
# rigs, unlisted chips) passes: the gate protects real accelerators.
RUNG_CHAIN_FIT_GATED = ("mid", "midpop", "flagship", "flagpop")

# serve/ (ISSUE 12): default serving geometry per rung — adapter slots per
# compiled program (the continuous batcher's coalescing width; preflight
# --serve verifies the fit offline) and images per request. One table so the
# engine default, bench.py --serve, and preflight --serve analyze/run the
# same geometry. member_batch 0 = the whole adapter axis in one vmapped
# chunk (right for the small rungs; big rungs chunk like training does).
SERVE_PLAN = {
    "tiny": {"adapter_batch": 16, "images_per_request": 1, "member_batch": 0},
    "small": {"adapter_batch": 4, "images_per_request": 1, "member_batch": 0},
    "popscale": {"adapter_batch": 8, "images_per_request": 1, "member_batch": 4},
    "mid": {"adapter_batch": 4, "images_per_request": 1, "member_batch": 1},
    "flagship": {"adapter_batch": 2, "images_per_request": 1, "member_batch": 1},
}

# tools/loadgen.py (ISSUE 16): default open-loop capacity-sweep plan per
# rung — the offered-load ladder (req/s, stepped in order; the knee detector
# reads the first rate that violates the SLO or leaves the queue growing),
# the per-step window, the Zipf popularity exponent + synthetic adapter
# population, the store budget expressed in ADAPTERS (loadgen converts to
# bytes from the rung's measured adapter size, so the budget forces real
# eviction churn at every rung), and the open-loop p99 SLO the headline
# "req/s at p99 ≤ X" capacity number is defined against. One table so the
# CI capacity smoke, the committed CAPACITY_r01 sweep, and an operator's
# ad-hoc run measure the same workload. Tiny is CPU-calibrated (the only
# rung the test tier executes); the big rungs carry TPU-shaped ladders an
# operator refines from a real pod (the SERVE_PLAN discipline).
CAPACITY_PLAN = {
    "tiny": {"rates": [4.0, 16.0, 64.0, 128.0, 256.0, 512.0], "window_s": 4.0,
             "zipf_s": 1.1, "population": 64, "store_adapters": 24,
             "slo_p99_s": 2.0},
    "small": {"rates": [1.0, 2.0, 4.0, 8.0, 16.0], "window_s": 10.0,
              "zipf_s": 1.1, "population": 1000, "store_adapters": 128,
              "slo_p99_s": 5.0},
    "popscale": {"rates": [2.0, 4.0, 8.0, 16.0, 32.0], "window_s": 10.0,
                 "zipf_s": 1.1, "population": 10000, "store_adapters": 256,
                 "slo_p99_s": 5.0},
    "mid": {"rates": [0.5, 1.0, 2.0, 4.0, 8.0], "window_s": 20.0,
            "zipf_s": 1.1, "population": 10000, "store_adapters": 64,
            "slo_p99_s": 10.0},
    "flagship": {"rates": [0.25, 0.5, 1.0, 2.0], "window_s": 30.0,
                 "zipf_s": 1.1, "population": 100000, "store_adapters": 32,
                 "slo_p99_s": 20.0},
}

# bench.py --scaling: default forced host-platform device counts of the
# 1→N scaling-efficiency ladder (each count is a separate child process so
# XLA_FLAGS lands before jax import). 8 is opt-in via --devices — the CPU
# rigs the bench falls back to rarely have 8 idle cores to back 8 virtual
# chips, and a core-starved 8-way run reads as a scaling regression when it
# is only oversubscription (the CPU-fallback caveat, PERF.md round 13).
SCALING_DEVICE_COUNTS = (1, 2, 4)

# Throughput geometry: a handful of distinct prompts so the scored batch is
# [pop, m] like a real epoch (the synthesized-embedding path needs only text).
BENCH_PROMPT_SET = [
    "a photo of a cat wearing a tiny hat",
    "an oil painting of a lighthouse in a storm",
    "a macro shot of a dew-covered spider web",
    "a watercolor fox in a snowy forest",
    "a neon-lit street market at night",
    "an astronaut riding a horse on the moon",
    "a bowl of ramen with chopsticks, studio light",
    "a stained-glass window of a blue whale",
]

# text-embed geometry shared by every sana rung (bench.build and preflight's
# abstract mirror must agree or the analyzed program isn't the timed one)
PROMPT_EMBED_LEN = 32  # Ltxt
PROMPT_TOKEN_LEN = 8  # Ltok

# Per-rung memory/bandwidth optimization defaults (PERF.md round 10): remat
# policy for the DiT blocks + DC-AE decoder stages + CLIP encoder scans,
# member-interior reward tiling (decode→CLIP through lax.map over image
# sub-batches), the factored-noise store dtype, and the reward towers'
# serving compute dtype. The small rungs keep everything off — they fit
# trivially; the big-decode rungs ship with the layer ON (that default is
# what the CI preflight gate verifies fits a v5e). bench and preflight read
# THIS one table so the analyzed geometry is the timed geometry; the trainer takes the same
# knobs as CLI flags (all-off defaults for bit-compat with older runs) — a
# flagship training launch on a 16 GB chip must pass the RUNG_OPT values
# explicitly (README "Memory & bandwidth knobs").
DEFAULT_OPT = {
    "remat": "none", "reward_tile": 0,
    "noise_dtype": "float32", "tower_dtype": "float32",
    "base_quant": "off",
    # bench/preflight programs measure the PURE ES step: the in-graph
    # quality attribution (obs/quality.py, trainer default ON) is excluded
    # here so every cost ledger stays comparable across rounds — its own
    # cost is priced separately (PERF.md round 22: +0.0033% FLOPs).
    "quality": False,
}
_BIG_OPT = {
    "remat": "blocks", "noise_dtype": "bfloat16", "tower_dtype": "bfloat16",
    "base_quant": "int8",
}
# base_quant (PERF.md round 14): the frozen base (DiT + DC-AE decoder +
# CLIP reward towers) stored per-output-channel int8 in HBM, dequantized at
# each use site (ops/quant.py) — the base is re-read per member, so the
# saving compounds with population. Ships ON wherever the bf16 diet ships;
# tiny/small stay float (parity anchors — and below the min-size floor
# anyway). The trained LoRA delta lives entirely in the adapter tree, so
# targeted kernels quantize like any other.
RUNG_OPT = {
    "tiny": dict(DEFAULT_OPT),
    "small": dict(DEFAULT_OPT),
    "popscale": {**DEFAULT_OPT, "base_quant": "int8"},
    "ar": dict(DEFAULT_OPT),
    "mid": {**_BIG_OPT, "reward_tile": 2},
    "midpop": {**_BIG_OPT, "reward_tile": 2},
    "flagship": {**_BIG_OPT, "reward_tile": 1},
    "flagpop": {**_BIG_OPT, "reward_tile": 1},
    "flaggen": {**_BIG_OPT, "reward_tile": 0},
}


def rung_opt(rung: str) -> Dict[str, Any]:
    """The rung's optimization-layer knobs (falls back to all-off)."""
    return dict(RUNG_OPT.get(rung, DEFAULT_OPT))


def kernel_marks(d: Dict[str, Any]) -> list:
    """Comparability markers of a geometry / rung-record dict — the fields
    that decide whether two measurements compare at all: the int8 base
    (``q8``) and the Pallas kernel env flags active at measurement time
    (``P:...``, short names per ops/pallas_gate.PALLAS_ENV_FLAGS). THE one
    derivation —
    :func:`knobs_str` (preflight/ledger rows) and ``bench_report``'s trend
    cells both render from it, so a knob added here shows up everywhere.
    Schema-additive: absent keys render nothing."""
    marks = []
    if d.get("base_quant") == "int8":
        marks.append("q8")
    if d.get("pallas_env"):
        from .ops.pallas_gate import pallas_flag_marks

        p = pallas_flag_marks(d["pallas_env"])
        if p:
            marks.append(f"P:{p}")
    return marks


def knobs_str(d: Dict[str, Any]) -> str:
    """Compact one-token summary of the optimization knobs in a geometry /
    rung-record dict — ``remat/tN/n-dt/w-dt`` plus the
    :func:`kernel_marks` suffix (``[/q8][/P:...]``). The ONE
    definition both the preflight report and ``bench_report`` render, so
    ledger rows and bench rows always read the same (stdlib-only, like the
    rest of this module)."""
    def dt(v: Any) -> str:
        return "bf16" if str(v).startswith("bf") else "f32"

    return (
        f"{d.get('remat', 'none')}/t{d.get('reward_tile', 0)}"
        f"/n-{dt(d.get('noise_dtype', 'float32'))}"
        f"/w-{dt(d.get('tower_dtype', 'float32'))}"
        + "".join(f"/{m}" for m in kernel_marks(d))
    )


def forced_host_devices_flags(existing: str, n: int) -> str:
    """An XLA_FLAGS value with any prior forced-host-device-count flag
    replaced by ``--xla_force_host_platform_device_count=n``. Stdlib-only
    and shared: the scaling bench's child env and ``preflight --devices``
    must spell the forcing identically (it only works when it reaches the
    env BEFORE the first jax backend init)."""
    flags = [
        f for f in (existing or "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    return " ".join(flags + [f"--xla_force_host_platform_device_count={n}"])


def small_clip_cfg(clip_mod: Any):
    """~15M-param CLIP reward tower shared by the 'small'/'popscale'/'ar'
    rungs (one definition — the M+2 table-row layout must stay in sync)."""
    tower = clip_mod.CLIPTowerConfig(256, 4, 4, 1024)
    return clip_mod.CLIPConfig(
        vision=tower, text=tower, image_size=128, patch_size=32, projection_dim=256
    )


def sana_rung_model(
    scale: str, remat: str = "none", tower_dtype: str = "float32"
) -> Dict[str, Any]:
    """Model/VAE/reward-tower configs for one Sana-family geometry rung.

    Returns ``{"bcfg", "clip_b", "clip_h", "latent_only"}`` — ``clip_h`` is
    None where the rung has no PickScore tower; ``latent_only`` marks the
    flaggen decomposition rung (no decode, trivial latent reward). The AR
    rung (``ar_small``) is not a Sana geometry and stays in bench.py.

    ``remat`` is applied to the DiT, DC-AE, and CLIP-tower configs (one
    knob, every remat site); ``tower_dtype`` sets the reward towers' serving
    compute dtype. Both default to the all-off values so ``RUNG_OPT``'s
    baseline override reproduces the pre-optimization program exactly.
    """
    import dataclasses

    from .backends.sana_backend import SanaBackendConfig
    from .models import clip as clip_mod
    from .models import dcae, sana

    def _tower(cfg):
        """Apply the tower knobs to a CLIP config — EVERY rung's towers go
        through here (identity at the all-off defaults), so an override like
        ``--tower_dtype bfloat16`` analyzes what the knobs column claims."""
        from .utils.pytree import resolve_float_dtype

        return dataclasses.replace(
            cfg, compute_dtype=resolve_float_dtype(tower_dtype), remat=remat
        )

    # flaggen = the flagship branch minus decode+rewards: both sides of the
    # (flagship − flaggen) hotspot subtraction MUST share one init path so
    # the difference can never measure geometry drift (code-review r5)
    latent_only = scale == "flagship_gen"
    if scale == "tiny":
        model = sana.SanaConfig(
            in_channels=4, out_channels=4, d_model=32, n_layers=2, n_heads=4,
            cross_n_heads=4, caption_dim=16, ff_ratio=2.0,
        )
        vae = dcae.DCAEConfig(latent_channels=4, channels=(16, 16, 8), blocks_per_stage=(1, 1, 1), attn_stages=())
        bcfg = SanaBackendConfig(model=model, vae=vae, width_latent=8, height_latent=8)
        tower = clip_mod.CLIPTowerConfig(32, 2, 2, 64)
        clip_b = _tower(clip_mod.CLIPConfig(
            vision=tower, text=tower, image_size=32, patch_size=16,
            vocab_size=64, max_positions=8, projection_dim=32,
        ))
        clip_h = clip_b
    elif scale == "small":
        # ~25M-class DiT, 128px decode — cheap probe + pop-scaling rung.
        model = sana.SanaConfig(
            in_channels=8, out_channels=8, d_model=384, n_layers=4, n_heads=12,
            cross_n_heads=6, caption_dim=384, ff_ratio=2.5,
        )
        vae = dcae.DCAEConfig(latent_channels=8, channels=(128, 128, 64, 32), blocks_per_stage=(1, 1, 1, 1), attn_stages=(0,))
        bcfg = SanaBackendConfig(model=model, vae=vae, width_latent=16, height_latent=16)
        clip_b = _tower(small_clip_cfg(clip_mod))
        clip_h = clip_b
    elif scale == "mid":
        # ~400M-class DiT, 512px decode, real CLIP-B/32 reward tower.
        # RUNG_OPT ships tower_dtype=bfloat16 here (layernorm/softmax
        # internals stay f32 — the tower weights are bf16-cast at these
        # rungs anyway, and f32 activations were doubling the reward
        # towers' HBM traffic).
        model = sana.SanaConfig(
            d_model=1152, n_layers=12, n_heads=36, cross_n_heads=16,
            caption_dim=2304, ff_ratio=2.5,
        )
        vae = dcae.DCAEConfig(channels=(512, 512, 256, 256, 128, 64))
        bcfg = SanaBackendConfig(model=model, vae=vae, width_latent=16, height_latent=16)
        clip_b = _tower(clip_mod.CLIP_B32)
        clip_h = None
    elif scale in ("flagship", "flagship_gen"):
        # Sana-Sprint 1.6B (SanaConfig defaults), 32×32 DC-AE f32 latents →
        # 1024px decode; real CLIP-B/32 + CLIP-H(PickScore) towers (bf16
        # serving dtype via RUNG_OPT — see the mid rung note).
        bcfg = SanaBackendConfig(
            width_latent=32, height_latent=32, decode_images=not latent_only
        )
        clip_b = _tower(clip_mod.CLIP_B32)
        clip_h = _tower(clip_mod.CLIP_H14)
    else:
        raise ValueError(f"unknown sana rung scale: {scale!r}")
    if remat != "none":
        bcfg.model = dataclasses.replace(bcfg.model, remat=remat)
        bcfg.vae = dataclasses.replace(bcfg.vae, remat=remat)
    return {"bcfg": bcfg, "clip_b": clip_b, "clip_h": clip_h, "latent_only": latent_only}

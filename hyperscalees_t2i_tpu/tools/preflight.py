"""Offline preflight: abstract-lower every bench rung on CPU, no weights.

Usage::

    python -m hyperscalees_t2i_tpu.tools.preflight                # all 5 rungs
    python -m hyperscalees_t2i_tpu.tools.preflight --rungs tiny,small
    python -m hyperscalees_t2i_tpu.tools.preflight --chip v5e \\
        --out runs/myrun --report preflight.txt

Answers the two questions budgeted chip time must never be spent
discovering:

1. **Does it fit?** Every rung's ES-step program is lowered from
   ``ShapeDtypeStruct`` trees — *no parameters are ever materialized, no
   accelerator is touched* — then compiled by CPU XLA for its
   ``memory_analysis()``. The estimated peak HBM is checked against each
   chip kind's capacity (``utils/mfu.py`` ``CHIPS``); a no-fit on the
   target chip exits **nonzero**, so CI and runbooks can gate on it.
2. **How fast could it go?** ``cost_analysis()`` FLOPs/bytes give a
   predicted step time per assumed MFU — max(compute@MFU, bandwidth floor)
   — the number a measured rung is compared against (bench roofline
   verdict, obs/xla_cost.py).

Each analyzed program also appends a normal ledger record
(``site="preflight"``) to ``<out>/programs.jsonl``, so the PERF.md
program-size table (lowering time, StableHLO lines/bytes/hash) regenerates
from artifacts instead of by hand.

Caveat on the memory estimate: CPU XLA's buffer assignment is not TPU's
(different fusion/remat decisions), so ``peak_bytes`` is an *estimate* —
good enough to catch the order-of-magnitude no-fits that matter before a
chip run, not a byte-accurate allocator prediction.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

from ..obs.heartbeat import Heartbeat
from ..obs.xla_cost import ProgramLedger, program_record, roofline
from ..rungs import (
    BENCH_PROMPT_SET,
    DEFAULT_OPT,
    PROMPT_EMBED_LEN,
    PROMPT_TOKEN_LEN,
    RUNG_ORDER,
    RUNG_PLAN,
    rung_opt,
    sana_rung_model,
)

# chip kinds in the fit table (rows resolve through utils/mfu.py's tables)
CHIPS = ("v5e", "v5p", "v4", "v6e")
# assumed-MFU columns of the predicted step-time table. 0.25-0.40 is the
# realistic band for big matmuls; 0.05 is the measured small-geometry regime
ASSUMED_MFUS = (0.05, 0.10, 0.25, 0.40)


def abstract_step_inputs(
    scale: str, pop: int, m: int, member_batch: int,
    opt: Optional[Dict[str, Any]] = None,
):
    """Everything ``make_es_step(...).lower(...)`` needs, as abstract trees.

    Mirrors ``bench.build()`` shape-for-shape (same configs via
    ``rungs.sana_rung_model``, same prompt/table geometry) but every array is
    a ``jax.eval_shape`` product — nothing is allocated, so the flagship
    1.6B-param program lowers on a laptop-class CPU in seconds.

    ``opt`` carries the memory/bandwidth knobs (``remat``/``reward_tile``/
    ``noise_dtype``, default all-off) — the preflight must analyze the
    program at the same optimization geometry the bench/trainer would run.
    """
    import jax
    import jax.numpy as jnp

    from ..backends.base import make_frozen
    from ..backends.sana_backend import SanaBackend
    from ..models import clip as clip_mod
    from ..models import dcae, sana
    from ..rewards.suite import (
        clip_text_embed_table,
        make_clip_reward_fn,
        pickscore_text_embeds,
    )
    from ..train.config import TrainConfig
    from ..utils.pytree import cast_floating

    opt = {**DEFAULT_OPT, **(opt or {})}
    spec = sana_rung_model(scale, remat=opt["remat"], tower_dtype=opt["tower_dtype"])
    bcfg, clip_b, clip_h = spec["bcfg"], spec["clip_b"], spec["clip_h"]
    prompts = list(BENCH_PROMPT_SET)
    M, Ltxt, Ltok = len(prompts), PROMPT_EMBED_LEN, PROMPT_TOKEN_LEN
    key = jax.random.PRNGKey(0)

    def shapes(fn, *args):
        return jax.eval_shape(fn, *args)

    # --base_quant int8: the frozen base trees are quantized abstractly, the
    # same maybe_quantize_tree call bench.build/train.cli apply concretely —
    # the analyzed program consumes kernel_q8 exactly like the timed one.
    # "off" applies NO transform at all (identity would still be an
    # eval_shape round-trip; the all-off program must stay bit-identical).
    base_quant = opt.get("base_quant", "off")

    def q(tree):
        if base_quant == "off":
            return tree
        from ..ops.quant import maybe_quantize_tree

        return shapes(lambda t: maybe_quantize_tree(t, base_quant), tree)

    backend = SanaBackend(bcfg)
    backend.params = q(shapes(
        lambda k: cast_floating(sana.init_sana(k, bcfg.model), jnp.bfloat16), key
    ))
    if bcfg.decode_images:
        backend.vae_params = q(shapes(
            lambda k: cast_floating(dcae.init_decoder(k, bcfg.vae), jnp.bfloat16), key
        ))
    backend.prompts = prompts
    backend.prompt_embeds = jax.ShapeDtypeStruct(
        (M, Ltxt, bcfg.model.caption_dim), jnp.float32
    )
    backend.prompt_mask = jax.ShapeDtypeStruct((M, Ltxt), jnp.bool_)

    if spec["latent_only"]:
        def reward_fn(latents, prompt_ids):
            return {"combined": latents.astype(jnp.float32).mean(axis=(1, 2, 3))}
    else:
        cparams = shapes(
            lambda k: cast_floating(clip_mod.init_clip(k, clip_b), jnp.bfloat16), key
        )
        # text tables come from the full-precision towers (one-time work);
        # only the per-step image towers are quantized — bench.build order
        table = shapes(
            lambda p: clip_text_embed_table(
                p, clip_b, jnp.zeros((M + 2, Ltok), jnp.int32)
            ),
            cparams,
        )
        cparams = q(cparams)
        pparams = ptable = None
        if clip_h is not None:
            pparams = shapes(
                lambda k: cast_floating(clip_mod.init_clip(k, clip_h), jnp.bfloat16),
                key,
            )
            ptable = shapes(
                lambda p: pickscore_text_embeds(
                    p, clip_h, jnp.zeros((M, Ltok), jnp.int32)
                ),
                pparams,
            )
            pparams = q(pparams)
        reward_fn = make_clip_reward_fn(
            cparams, clip_b, table,
            pick_params=pparams, pick_cfg=clip_h, pick_text_embeds=ptable,
        )

    tc = TrainConfig(
        pop_size=pop, sigma=0.01, egg_rank=4, prompts_per_gen=m,
        batches_per_gen=1, member_batch=member_batch, promptnorm=True,
        remat=opt["remat"], reward_tile=opt["reward_tile"],
        noise_dtype=opt["noise_dtype"],
        pop_shard_update=opt.get("pop_shard_update", "auto"),
        base_quant=base_quant,
        quality=opt.get("quality", False),
    )
    num_unique = min(m, M)
    theta = shapes(backend.init_theta, key)
    frozen = make_frozen(backend, reward_fn)
    ids = jax.ShapeDtypeStruct((num_unique,), jnp.int32)
    key_s = jax.ShapeDtypeStruct(key.shape, key.dtype)
    return backend, reward_fn, tc, frozen, theta, ids, key_s, num_unique


def _rung_mesh(pop: int, devices: int):
    """The bench's slice-filling mesh at a forced device count — the SHARED
    ``parallel.gcd_pop_data_mesh`` recipe, so --devices analyzes exactly the
    program ``bench.run_rung`` times."""
    import jax

    from ..parallel import gcd_pop_data_mesh

    devs = jax.devices()
    if devices > len(devs):
        raise RuntimeError(
            f"--devices {devices} but only {len(devs)} host-platform devices "
            "exist — the forced count must be set before jax backend init "
            "(preflight main does this; in-process callers get the platform "
            "as configured)"
        )
    return gcd_pop_data_mesh(pop, devices, devices=devs[:devices])


def analyze_rung(
    rung: str,
    ledger: Optional[ProgramLedger] = None,
    opt_override: Optional[Dict[str, Any]] = None,
    devices: int = 0,
) -> Dict[str, Any]:
    """Lower + CPU-compile one rung's ES step abstractly; return its ledger
    record extended with the rung plan fields.

    ``opt_override`` replaces individual ``rungs.RUNG_OPT`` knobs (remat /
    reward_tile / noise_dtype) — how CI produces the before/after ledger
    diff without editing the shipped table.

    ``devices > 1`` lowers the *sharded* program over a pop×data mesh of
    that many host-platform devices (the bench's mesh recipe) — the
    partitioned module's ``peak_bytes`` is then the **per-shard** peak and
    ``collective_bytes`` the per-device interconnect traffic per step."""
    from ..train.trainer import make_es_step

    scale, pop, m, member_batch = RUNG_PLAN[rung]
    opt = rung_opt(rung)
    opt.update({k: v for k, v in (opt_override or {}).items() if v is not None})
    (backend, reward_fn, tc, frozen, theta, ids, key_s,
     num_unique) = abstract_step_inputs(scale, pop, m, member_batch, opt)
    mesh = _rung_mesh(pop, devices) if devices and devices > 1 else None
    step = make_es_step(backend, reward_fn, tc, num_unique, 1, mesh)
    t0 = time.perf_counter()
    lowered = step.lower(frozen, theta, ids, key_s)
    lowering_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    rec = program_record(
        site="preflight", label=rung, lowered=lowered, compiled=compiled,
        lowering_s=lowering_s, compile_s=compile_s,
        geometry={"scale": scale, "pop": pop, "m": num_unique, "r": 1,
                  "member_batch": member_batch, **opt,
                  "mesh_shape": dict(mesh.shape) if mesh is not None else None,
                  "n_devices": devices if mesh is not None else 1},
        extra={"rung": rung, "imgs_per_step": pop * num_unique},
    )
    _add_chip_true_estimates(rec, (frozen, theta), compiled)
    if ledger is not None:
        ledger.write(rec)
    return rec


def analyze_update_programs(
    rung: str,
    devices: int,
    ledger: Optional[ProgramLedger] = None,
    opt_override: Optional[Dict[str, Any]] = None,
) -> List[Dict[str, Any]]:
    """Isolate the EGGROLL update: lower + compile ``(θ, noise, fitness) →
    θ'`` replicated AND pop-sharded on a ``devices``-way mesh, one ledger
    record each.

    This is the ledger proof of the pop-sharded update's economics: the two
    programs take identical inputs and produce the same θ' (rounding-tight),
    so their ``flops`` fields compare per-device update work directly —
    noise *sampling* is deliberately outside (noise enters as an argument),
    keeping RNG integer ops out of the contraction count — and the sharded
    record's ``collective_bytes`` is the psum's price. Empty list when the
    base-sample count does not tile the mesh's pop axis (nothing to prove).
    """
    import jax

    from ..es import sample_noise
    from ..es.noiser import es_update
    from ..parallel.mesh import POP_AXIS
    from ..parallel.pop_update import make_sharded_es_update, pop_shard_update_plan

    scale, pop, m, member_batch = RUNG_PLAN[rung]
    opt = rung_opt(rung)
    opt.update({k: v for k, v in (opt_override or {}).items() if v is not None})
    # an explicit --pop_shard_update off means "analyze the replicated
    # configuration" — publishing the sharded variant anyway would put a
    # program the user excluded into the report; on/auto both want the
    # comparison, planned permissively (a non-tiling base is a loud skip
    # here, not an error: this section is diagnostic, not a launch path).
    # Both skips run BEFORE the abstract-input build — nothing to analyze,
    # nothing paid.
    mode = opt.get("pop_shard_update") or "auto"
    if mode == "off":
        print(f"[preflight] {rung}: update isolation skipped "
              "(--pop_shard_update off)", file=sys.stderr, flush=True)
        return []
    mesh = _rung_mesh(pop, devices)
    # antithetic is fixed (TrainConfig default) at every preflight geometry
    ok, reason = pop_shard_update_plan("auto", pop, True, mesh)
    if not ok:
        print(f"[preflight] {rung}: update isolation skipped ({reason})",
              file=sys.stderr, flush=True)
        return []
    (backend, reward_fn, tc, frozen, theta, ids, key_s,
     num_unique) = abstract_step_inputs(scale, pop, m, member_batch, opt)
    es_cfg = tc.es_config()
    noise = jax.eval_shape(
        lambda k, t: sample_noise(k, t, pop, es_cfg), key_s, theta
    )
    fitness = jax.ShapeDtypeStruct((pop,), "float32")
    sharded_update = make_sharded_es_update(mesh, pop, es_cfg)
    variants = (
        ("replicated", lambda th, nz, f: es_update(th, nz, f, pop, es_cfg)),
        ("pop_sharded", sharded_update),
    )
    records = []
    for name, fn in variants:
        t0 = time.perf_counter()
        lowered = jax.jit(fn).lower(theta, noise, fitness)
        lowering_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        rec = program_record(
            site="preflight", label=f"{rung}-update-{name}",
            lowered=lowered, compiled=compiled,
            lowering_s=lowering_s, compile_s=compile_s,
            geometry={"scale": scale, "pop": pop, "update_variant": name,
                      "mesh_shape": dict(mesh.shape), "n_devices": devices,
                      "update_shards": int(mesh.shape[POP_AXIS]),
                      "noise_dtype": opt["noise_dtype"]},
            extra={"rung": rung},
        )
        records.append(rec)
        if ledger is not None:
            ledger.write(rec)
    return records


def _add_chip_true_estimates(
    rec: Dict[str, Any], inputs: Any, compiled: Any = None
) -> None:
    """Extend a ledger record with the chip-true peak AND bytes estimates —
    the raw CPU figures minus XLA:CPU's float-legalization copies, which a
    native-bf16/int8 chip (every TPU kind in ``utils/mfu.py``) never
    allocates or moves. Two verified copy classes:

    - **bf16 upcasts** (PERF.md round 10): XLA:CPU cannot execute bf16
      dot/conv; its float-normalization pass materializes a full-size f32
      copy of every bf16 parameter array the program carries through its
      loops (verified in the optimized HLO: the scan carries
      ``f32[32,5120,1280]``-shaped clones of the bf16 CLIP-H stacks).
      Estimated as 2× the bf16 argument bytes (= the f32 copy set).
    - **int8 dequant copies** (PERF.md round 14, ``--base_quant int8``):
      every ``dequantize_kernel`` site lowers on CPU to a materialized float
      copy of the (sliced) kernel, measured per program by
      ``obs.xla_cost.legalization_stats`` from the optimized HLO — a
      native-int8 chip fuses the dequant into the consuming dot/conv
      operand read and moves only the s8 bytes.

    ``peak_bytes_chip_est`` subtracts the (estimated) f32 upcast copy set
    plus the *hoisted* (ENTRY-level, loop-carried — provably live through
    the member loop) dequant copies; body-local transient dequant temps are
    left IN, keeping the peak conservative. ``bytes_accessed_chip_est``
    subtracts each measured copy's WRITE only (1× the copy bytes): the
    copies are loop-carried, so their reads are layer-sized slices the
    accounting counts once per body — nearly the bytes a chip reads from
    the original operand anyway — while the full-size write is purely
    CPU-only. Raw figures remain published unchanged; remaining
    CPU-specific slack (im2col conv temps, activation-dtype normalization)
    is deliberately left IN both estimates.
    """
    import jax
    import jax.numpy as jnp

    from ..obs.xla_cost import legalization_stats

    bf16_bytes = 0
    for leaf in jax.tree_util.tree_leaves(inputs):
        if getattr(leaf, "dtype", None) == jnp.bfloat16:
            n = 1
            for d in leaf.shape:
                n *= d
            bf16_bytes += 2 * n
    rec["cpu_f32_upcast_bytes"] = float(2 * bf16_bytes)
    dq = legalization_stats(compiled) if compiled is not None else {}
    rec.update(dq)
    dq_hoisted = dq.get("int8_dequant_hoisted_bytes", 0.0) or 0.0
    copy_writes = (dq.get("int8_dequant_copy_bytes", 0.0) or 0.0) + (
        dq.get("bf16_upcast_copy_bytes", 0.0) or 0.0
    )
    peak = rec.get("peak_bytes")
    if peak is not None:
        floor = (rec.get("argument_bytes") or 0.0) + (rec.get("output_bytes") or 0.0)
        rec["peak_bytes_chip_est"] = max(
            peak - rec["cpu_f32_upcast_bytes"] - dq_hoisted, floor
        )
    bts = rec.get("bytes_accessed")
    if bts is not None:
        rec["bytes_accessed_chip_est"] = max(bts - copy_writes, 0.0)


def _gb(v: Optional[float]) -> str:
    return f"{v / 1e9:7.2f}" if v is not None else "      ?"


def _fit_peak(rec: Dict[str, Any]) -> Optional[float]:
    """The peak estimate the fit verdict judges: the chip-true figure when
    the record carries one (see :func:`_add_chip_true_estimates`), else the raw
    CPU number (older/external records)."""
    v = rec.get("peak_bytes_chip_est")
    return v if v is not None else rec.get("peak_bytes")


def _col(v: Any, w: int = 9) -> str:
    return f"{str(v):>{w}}"


def render_report(
    records: List[Dict[str, Any]],
    target_chip: str,
    hbm_override_bytes: Optional[float] = None,
    update_records: Optional[List[Dict[str, Any]]] = None,
    devices: int = 0,
) -> tuple:
    """(report text, exit code): nonzero when any analyzed rung's estimated
    peak HBM exceeds the target chip's capacity. ``hbm_override_bytes``
    substitutes the target capacity (unknown chips, tests).

    ``update_records`` (``analyze_update_programs`` output) adds the
    pop-sharded-update comparison section; ``devices > 1`` labels the whole
    report as per-shard (the analyzed modules are partitioned)."""
    from ..utils.mfu import (
        hbm_bw_for_kind,
        hbm_bytes_for_kind,
        ici_bw_for_kind,
        peak_flops_for_kind,
    )

    lines: List[str] = []
    lines.append(
        "# Offline preflight — abstract CPU lowering, no weights materialized"
    )
    lines.append(
        f"# target chip: {target_chip}  ·  peak-HBM estimates are CPU-XLA "
        "buffer accounting (order-of-magnitude, not allocator-exact)"
    )
    if devices and devices > 1:
        lines.append(
            f"# --devices {devices}: programs are lowered SHARDED over a "
            "pop×data mesh of forced host-platform devices — peak figures "
            "are PER-SHARD (the partitioned module), collective bytes are "
            "per-device interconnect traffic per step"
        )
    lines.append("")

    # --- per-program static cost -------------------------------------------
    lines.append("## Program cost (per ES step)")
    lines.append(
        "# knobs = remat/reward_tile/n-<noise dtype>/w-<tower dtype> — the "
        "analyzed operating geometry (rungs.RUNG_OPT unless overridden)"
    )
    lines.append(
        "# chip peak / chip GB moved = the CPU figures minus XLA:CPU's "
        "float-legalization copies (bf16 f32-upcasts + int8 dequant copies "
        "— never allocated/moved by a native-bf16/int8 chip; the fit "
        "verdict below uses the chip peak column when present)"
    )
    head = ("rung", "geometry", "pop", "knobs", "TFLOP", "GB moved",
            "chip GB mv", "cpu peak GB", "chip peak GB", "coll ops",
            "coll MB", "lower s", "compile s", "HLO lines", "sha")
    lines.append(" ".join(
        _col(h, 24 if h == "knobs" else 12 if "peak" in h else
             10 if h == "chip GB mv" else 9) for h in head
    ))

    from ..rungs import knobs_str

    for r in records:
        g = r.get("geometry", {})
        flops, bts = r.get("flops"), r.get("bytes_accessed")
        knobs = knobs_str(g)
        lines.append(" ".join([
            _col(r.get("rung", r.get("label", "?"))),
            _col(g.get("scale", "?")),
            _col(g.get("pop", "?")),
            _col(knobs, 24),
            _col(f"{flops / 1e12:.3f}" if flops else "?"),
            _col(f"{bts / 1e9:.2f}" if bts else "?"),
            _col(
                f"{r['bytes_accessed_chip_est'] / 1e9:.2f}"
                if r.get("bytes_accessed_chip_est") is not None else "?", 10
            ),
            _col(_gb(r.get("peak_bytes")).strip(), 12),
            _col(_gb(_fit_peak(r)).strip(), 12),
            _col(r.get("collective_ops", "?")),
            _col(
                f"{r['collective_bytes'] / 1e6:.3f}"
                if r.get("collective_bytes") is not None else "?"
            ),
            _col(f"{r['lowering_s']:.1f}" if r.get("lowering_s") else "?"),
            _col(f"{r['compile_s']:.1f}" if r.get("compile_s") else "?"),
            _col(r.get("stablehlo_lines", "?")),
            _col(r.get("stablehlo_sha256", "?")[:8], 9),
        ]))
    lines.append("")

    # --- HBM fit table ------------------------------------------------------
    # The *verdict* is computed against the target chip unconditionally
    # (override > capacity table) — a --chip value outside the display
    # columns (v3, an unknown chip with --hbm-gb) must still gate, never
    # silently pass. The table is display; the target column is appended
    # when it isn't already one of the standard CHIPS.
    target_cap = (
        hbm_override_bytes if hbm_override_bytes is not None
        else hbm_bytes_for_kind(target_chip)
    )
    lines.append("## HBM fit (chip-true est peak vs per-chip capacity)")
    cap_cols = [(chip, hbm_bytes_for_kind(chip)) for chip in CHIPS]
    if target_chip not in CHIPS:
        cap_cols.append((target_chip, target_cap))
    cap_cols = [
        (chip, target_cap if chip == target_chip else cap)
        for chip, cap in cap_cols
    ]
    lines.append(" ".join(
        [_col("rung")] + [
            _col(f"{chip}({cap / 1e9:g}G)" if cap else chip)
            for chip, cap in cap_cols
        ]
    ))
    failures: List[str] = []
    unverdicted: List[str] = []
    for r in records:
        cells = [_col(r.get("rung", "?"))]
        peak_est = _fit_peak(r)
        for chip, cap in cap_cols:
            if peak_est is None or cap is None:
                cells.append(_col("?"))
            else:
                cells.append(_col("fit" if peak_est <= cap else "NO-FIT"))
        lines.append(" ".join(cells))
        # the gate, independent of which chips the table happens to show
        if peak_est is None or target_cap is None:
            unverdicted.append(str(r.get("rung", "?")))
        elif peak_est > target_cap:
            failures.append(
                f"{r.get('rung', '?')} (est {peak_est / 1e9:.1f} GB > "
                f"{target_cap / 1e9:g} GB)"
            )
    lines.append("")

    # --- pop-sharded update: isolated-program FLOPs + psum price -----------
    if update_records:
        by_variant: Dict[str, Dict[str, Dict[str, Any]]] = {}
        for r in update_records:
            g = r.get("geometry", {})
            by_variant.setdefault(r.get("rung", "?"), {})[
                g.get("update_variant", "?")
            ] = r
        lines.append(
            "## Pop-sharded EGGROLL update — isolated (θ, noise, fitness)→θ' "
            "programs"
        )
        lines.append(
            "# same inputs, same θ' (rounding-tight): the flops ratio is the "
            "per-device update-work saving; collective bytes are the psum "
            "that rebuilds Δθ"
        )
        lines.append(" ".join([
            _col("rung"), _col("variant", 12), _col("shards"), _col("GFLOP"),
            _col("GB moved"), _col("coll KB"), _col("flops ratio", 12),
        ]))
        for rung_name, variants in by_variant.items():
            rep = variants.get("replicated", {})
            for name in ("replicated", "pop_sharded"):
                r = variants.get(name)
                if r is None:
                    continue
                flops, bts = r.get("flops"), r.get("bytes_accessed")
                ratio = "—"
                if name == "pop_sharded" and flops and rep.get("flops"):
                    ratio = f"{rep['flops'] / flops:.2f}x"
                lines.append(" ".join([
                    _col(rung_name),
                    _col(name, 12),
                    _col(r.get("geometry", {}).get("update_shards", "?")),
                    _col(f"{flops / 1e9:.4f}" if flops else "?"),
                    _col(f"{bts / 1e9:.4f}" if bts else "?"),
                    _col(
                        f"{r['collective_bytes'] / 1e3:.1f}"
                        if r.get("collective_bytes") is not None else "?"
                    ),
                    _col(ratio, 12),
                ]))
        lines.append("")

    # --- predicted step time on the target chip ----------------------------
    peak_f = peak_flops_for_kind(target_chip)
    bw = hbm_bw_for_kind(target_chip)
    ici = ici_bw_for_kind(target_chip)
    if peak_f and bw:
        lines.append(
            f"## Predicted step time on {target_chip} "
            f"({peak_f / 1e12:.0f} TFLOP/s, {bw / 1e9:.0f} GB/s HBM"
            + (f", {ici / 1e9:.0f} GB/s ICI" if ici else "")
            + ", 1 chip) — max(compute@MFU, bandwidth floor, comms floor)"
        )
        lines.append(" ".join(
            [_col("rung")]
            + [_col(f"@MFU {u:.2f}") for u in ASSUMED_MFUS]
            + [_col("bw floor s", 11), _col("comms s"), _col("bound")]
        ))
        for r in records:
            flops, bts = r.get("flops"), r.get("bytes_accessed")
            rf = roofline(
                flops, bts, peak_flops=peak_f, hbm_bw=bw,
                collective_bytes=r.get("collective_bytes"), ici_bw=ici,
            )
            cells = [_col(r.get("rung", "?"))]
            for u in ASSUMED_MFUS:
                if flops and peak_f:
                    t = max(flops / (peak_f * u), rf["t_bandwidth_s"] or 0.0,
                            rf["t_comms_s"] or 0.0)
                    cells.append(_col(f"{t:.4f}"))
                else:
                    cells.append(_col("?"))
            cells.append(_col(
                f"{rf['t_bandwidth_s']:.4f}" if rf["t_bandwidth_s"] else "?", 11
            ))
            cells.append(_col(
                f"{rf['t_comms_s']:.4f}" if rf["t_comms_s"] else "—"
            ))
            cells.append(_col(rf["bound"] or "?"))
            lines.append(" ".join(cells))
        lines.append("")

    if failures:
        lines.append(f"VERDICT: NO-FIT on {target_chip}: " + ", ".join(failures))
        rc = 1
    elif unverdicted:
        # no capacity figure for the target chip (or no memory estimate for
        # a rung): refusing to judge must fail loudly, not pass silently
        lines.append(
            f"VERDICT: cannot evaluate HBM fit on {target_chip} for: "
            + ", ".join(unverdicted)
            + " (unknown capacity/estimate — pass --hbm-gb for unlisted chips)"
        )
        rc = 2
    else:
        lines.append(f"VERDICT: all analyzed rungs fit {target_chip} HBM")
        rc = 0
    return "\n".join(lines) + "\n", rc


def render_serve_report(
    records: List[Dict[str, Any]],
    target_chip: str,
    hbm_override_bytes: Optional[float] = None,
) -> tuple:
    """(report text, exit code) for serving geometries (``--serve``): the
    admission gate's offline answer. Nonzero when any geometry's estimated
    peak HBM exceeds the target chip's capacity — the same verdict the
    engine's online gate enforces, runnable with zero weights."""
    from ..utils.mfu import hbm_bytes_for_kind

    target_cap = (
        hbm_override_bytes if hbm_override_bytes is not None
        else hbm_bytes_for_kind(target_chip)
    )
    lines = [
        "# Serving preflight — adapter-batched generate program, abstract "
        "CPU lowering, no weights",
        f"# target chip: {target_chip} — admission verdict for "
        "serve/ServeEngine geometries (site=\"serve\" ledger records)",
        "",
        " ".join([
            _col("geometry", 20), _col("A"), _col("B"), _col("rank"),
            _col("GFLOP"), _col("GB moved"), _col("cpu peak GB", 12),
            _col("chip peak GB", 12), _col("lower s"), _col("compile s"),
            _col("sha", 9), _col("verdict", 8),
        ]),
    ]
    failures: List[str] = []
    unverdicted: List[str] = []
    for r in records:
        g = r.get("geometry", {})
        peak_est = _fit_peak(r)
        if peak_est is None or target_cap is None:
            verdict = "?"
            unverdicted.append(str(r.get("label", "?")))
        elif peak_est > target_cap:
            verdict = "NO-FIT"
            failures.append(
                f"{r.get('label', '?')} (est {peak_est / 1e9:.2f} GB > "
                f"{target_cap / 1e9:g} GB)"
            )
        else:
            verdict = "fit"
        flops, bts = r.get("flops"), r.get("bytes_accessed")
        lines.append(" ".join([
            _col(r.get("label", "?"), 20),
            _col(g.get("adapter_batch", "?")),
            _col(g.get("images_per_request", "?")),
            _col(g.get("lora_rank") or "dflt"),
            _col(f"{flops / 1e9:.3f}" if flops else "?"),
            _col(f"{bts / 1e9:.3f}" if bts else "?"),
            _col(_gb(r.get("peak_bytes")).strip(), 12),
            _col(_gb(peak_est).strip(), 12),
            _col(f"{r['lowering_s']:.1f}" if r.get("lowering_s") else "?"),
            _col(f"{r['compile_s']:.1f}" if r.get("compile_s") else "?"),
            _col((r.get("stablehlo_sha256") or "?")[:8], 9),
            _col(verdict, 8),
        ]))
    lines.append("")
    if failures:
        lines.append(
            f"VERDICT: serve admission REFUSED on {target_chip}: "
            + ", ".join(failures)
        )
        rc = 1
    elif unverdicted:
        lines.append(
            f"VERDICT: cannot evaluate serve fit on {target_chip} for: "
            + ", ".join(unverdicted)
            + " (unknown capacity/estimate — pass --hbm-gb for unlisted chips)"
        )
        rc = 2
    else:
        lines.append(
            f"VERDICT: all serving geometries ADMITTED on {target_chip}"
        )
        rc = 0
    return "\n".join(lines) + "\n", rc


def render_fleet_report(
    pairs: List[tuple],
    target_chip: str,
    hbm_override_bytes: Optional[float] = None,
) -> tuple:
    """(report text, exit code) for fleet geometries (``--fleet RUNG:J``):
    the fleet admission gate's offline answer PLUS the amortization ledger
    proof. ``pairs`` is ``[(fleet_rec, solo_rec), ...]`` — the fused J-job
    step record and the same rung's single-job step record.

    Exit code: 1 when any fused geometry's estimated peak exceeds the chip
    (fleet admission REFUSED — same convention as ``--serve``), 2 when a
    verdict can't be computed, 0 when every geometry fits AND the fused
    program moves fewer total bytes than J sequential single-job steps.

    Caveat the numbers inherit from the cost model (PR 9): XLA's
    cost_analysis counts a scan body ONCE regardless of trip count, so both
    the fused and the solo figures are per-body — the comparison is of
    *program-resident* traffic (the resident base read once per program vs
    once per job), which is exactly the quantity fleet batching amortizes.
    """
    from ..utils.mfu import hbm_bytes_for_kind

    target_cap = (
        hbm_override_bytes if hbm_override_bytes is not None
        else hbm_bytes_for_kind(target_chip)
    )
    lines = [
        "# Fleet preflight — fused (job, member)-batched ES step, abstract "
        "CPU lowering, no weights",
        f"# target chip: {target_chip} — admission verdict for "
        "train/fleet.FleetScheduler geometries (site=\"fleet\" ledger "
        "records) + amortization proof vs J sequential single-job steps",
        "",
        " ".join([
            _col("geometry", 18), _col("J"), _col("GFLOP", 10),
            _col("GB moved", 10), _col("GB/job", 10),
            _col("Jx solo GB", 10), _col("amort", 7),
            _col("chip peak GB", 12), _col("verdict", 8),
        ]),
    ]
    failures: List[str] = []
    unverdicted: List[str] = []
    unamortized: List[str] = []
    for fleet_rec, solo_rec in pairs:
        label = fleet_rec.get("label", "?")
        width = int(fleet_rec.get("extra", {}).get("fleet_width")
                    or fleet_rec.get("geometry", {}).get("fleet_width") or 1)
        peak_est = _fit_peak(fleet_rec)
        if peak_est is None or target_cap is None:
            verdict = "?"
            unverdicted.append(str(label))
        elif peak_est > target_cap:
            verdict = "NO-FIT"
            failures.append(
                f"{label} (est {peak_est / 1e9:.2f} GB > "
                f"{target_cap / 1e9:g} GB)"
            )
        else:
            verdict = "fit"
        fb = fleet_rec.get("bytes_accessed_chip_est")
        if fb is None:
            fb = fleet_rec.get("bytes_accessed")
        sb = solo_rec.get("bytes_accessed_chip_est")
        if sb is None:
            sb = solo_rec.get("bytes_accessed")
        amort = "?"
        if fb is not None and sb is not None:
            seq_total = width * sb
            amort = "yes" if fb < seq_total else "NO"
            if fb >= seq_total and width > 1:
                unamortized.append(
                    f"{label} (fused {fb / 1e9:.3f} GB >= {width}x solo "
                    f"{seq_total / 1e9:.3f} GB)"
                )
        flops = fleet_rec.get("flops")
        lines.append(" ".join([
            _col(label, 18),
            _col(width),
            _col(f"{flops / 1e9:.3f}" if flops else "?", 10),
            _col(f"{fb / 1e9:.3f}" if fb is not None else "?", 10),
            _col(f"{fb / width / 1e9:.3f}" if fb is not None else "?", 10),
            _col(f"{width * sb / 1e9:.3f}" if sb is not None else "?", 10),
            _col(amort, 7),
            _col(_gb(peak_est).strip(), 12),
            _col(verdict, 8),
        ]))
    lines.append("")
    if failures:
        lines.append(
            f"VERDICT: fleet admission REFUSED on {target_chip}: "
            + ", ".join(failures)
        )
        rc = 1
    elif unverdicted:
        lines.append(
            f"VERDICT: cannot evaluate fleet fit on {target_chip} for: "
            + ", ".join(unverdicted)
            + " (unknown capacity/estimate — pass --hbm-gb for unlisted chips)"
        )
        rc = 2
    elif unamortized:
        lines.append(
            "VERDICT: fleet fits but does NOT amortize: " + ", ".join(unamortized)
        )
        rc = 2
    else:
        lines.append(
            f"VERDICT: all fleet geometries ADMITTED on {target_chip}; fused "
            "steps move fewer total bytes than their sequential equivalents"
        )
        rc = 0
    return "\n".join(lines) + "\n", rc


def main(argv=None) -> int:
    # CPU-only by design (abstract lowering needs no chip, and a process
    # that touches the TPU holds it): force the platform before any backend
    # init.
    import os

    import jax

    jax.config.update("jax_platforms", "cpu")

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rungs", default=",".join(RUNG_ORDER),
                    help="comma list of rungs to analyze (default: the ladder)")
    ap.add_argument("--chip", default="v5e",
                    help="target chip kind for the fit verdict / exit code")
    ap.add_argument("--hbm-gb", type=float, default=None,
                    help="override the target chip's HBM capacity (GB) — for "
                         "unknown chips and for exercising the no-fit path")
    # optimization-layer overrides (default: the rung's shipped RUNG_OPT
    # knobs). CI analyzes flagship twice — shipped vs all-off — and diffs
    # the ledger records; operators use these to answer "would geometry X
    # fit" before a chip run.
    ap.add_argument("--remat", default=None, choices=["none", "blocks", "full"],
                    help="override the rung's remat policy")
    ap.add_argument("--reward_tile", type=int, default=None,
                    help="override the rung's member-interior reward tile "
                         "(0 = untiled)")
    ap.add_argument("--noise_dtype", default=None,
                    choices=["float32", "bfloat16", "bf16"],
                    help="override the rung's ES-noise store dtype")
    ap.add_argument("--tower_dtype", default=None,
                    choices=["float32", "bfloat16", "bf16"],
                    help="override the rung's reward-tower serving compute "
                         "dtype")
    ap.add_argument("--base_quant", default=None, choices=["off", "int8"],
                    help="override the rung's frozen-base storage "
                         "quantization (int8 = per-output-channel int8 base "
                         "kernels dequantized at use, ops/quant.py)")
    ap.add_argument("--pop_shard_update", default=None,
                    choices=["auto", "on", "off"],
                    help="override the pop-sharded-update mode the sharded "
                         "programs are analyzed with (meaningful with "
                         "--devices; default auto)")
    ap.add_argument("--devices", type=int, default=0,
                    help="lower the SHARDED programs over this many forced "
                         "host-platform devices (pop×data mesh, the bench "
                         "recipe): peak HBM becomes per-shard, collective "
                         "bytes per step are extracted from the partitioned "
                         "HLO, and the isolated update programs (replicated "
                         "vs pop-sharded) are compared. 0/1 = the existing "
                         "single-device analysis")
    ap.add_argument("--serve", action="append", default=None,
                    metavar="RUNG:ADAPTERS[:RANK]",
                    help="serving-admission mode (repeatable): abstract-"
                         "lower the serve/ adapter-batched generate program "
                         "for this geometry instead of the training rungs, "
                         "append site=\"serve\" ledger records, and exit "
                         "nonzero when the est peak HBM exceeds the target "
                         "chip — the engine admission gate's offline answer, "
                         "zero weights needed (e.g. --serve flagship:8:16)")
    ap.add_argument("--serve_images", type=int, default=None,
                    help="images per request for --serve geometries "
                         "(default: rungs.SERVE_PLAN)")
    ap.add_argument("--fleet", action="append", default=None,
                    metavar="RUNG:J",
                    help="fleet-admission mode (repeatable): abstract-lower "
                         "the fused J-job (job, member)-batched ES step for "
                         "this rung, append site=\"fleet\" ledger records "
                         "next to the rung's single-job record, and render "
                         "the amortization + fit verdict (train/fleet."
                         "FleetScheduler's offline gate; e.g. --fleet "
                         "popscale:4). Exit 1 on no-fit, 2 when "
                         "unverdicted or unamortized.")
    ap.add_argument("--out", default=None,
                    help="dir to append ledger records to (<out>/programs.jsonl)")
    ap.add_argument("--report", default=None,
                    help="also write the report text to this path")
    args = ap.parse_args(argv)

    if args.serve:
        from ..serve.admission import analyze_serve_geometry, parse_serve_geometry

        ledger = (
            ProgramLedger(Path(args.out) / "programs.jsonl") if args.out else None
        )
        records = []
        for spec in args.serve:
            try:
                rung, adapters, rank = parse_serve_geometry(spec)
            except ValueError as e:
                print(f"[preflight] {e}", file=sys.stderr)
                return 2
            print(f"[preflight] serve {spec}: abstract lowering + CPU "
                  "compile ...", file=sys.stderr, flush=True)
            with Heartbeat(f"preflight:serve:{rung}", "compile", gauges=None):
                rec = analyze_serve_geometry(
                    rung, adapters, images_per_request=args.serve_images,
                    rank=rank, ledger=ledger,
                )
            records.append(rec)
        hbm_override = args.hbm_gb * 1e9 if args.hbm_gb is not None else None
        report, rc = render_serve_report(records, args.chip, hbm_override)
        print(report, end="")
        if args.report:
            Path(args.report).parent.mkdir(parents=True, exist_ok=True)
            Path(args.report).write_text(report)
            print(f"[preflight] report → {args.report}", file=sys.stderr)
        return rc

    if args.fleet:
        from ..train.fleet import analyze_fleet_geometry, parse_fleet_geometry

        ledger = (
            ProgramLedger(Path(args.out) / "programs.jsonl") if args.out else None
        )
        opt_override = {
            "remat": args.remat,
            "reward_tile": args.reward_tile,
            "noise_dtype": args.noise_dtype,
            "tower_dtype": args.tower_dtype,
            "base_quant": args.base_quant,
        }
        pairs = []
        solo_cache: Dict[str, Dict[str, Any]] = {}
        for spec in args.fleet:
            try:
                rung, width = parse_fleet_geometry(spec)
            except ValueError as e:
                print(f"[preflight] {e}", file=sys.stderr)
                return 2
            # the sequential baseline: the rung's ordinary single-job step,
            # analyzed once per rung and ledgered alongside (site="preflight")
            if rung not in solo_cache:
                print(f"[preflight] fleet {spec}: single-job baseline ...",
                      file=sys.stderr, flush=True)
                with Heartbeat(f"preflight:fleet:{rung}", "solo-compile",
                               gauges=None):
                    solo_cache[rung] = analyze_rung(
                        rung, ledger, opt_override=opt_override
                    )
            print(f"[preflight] fleet {spec}: fused {width}-job lowering + "
                  "CPU compile ...", file=sys.stderr, flush=True)
            with Heartbeat(f"preflight:fleet:{rung}", "compile", gauges=None):
                rec = analyze_fleet_geometry(
                    rung, width, ledger=ledger, opt_override=opt_override
                )
            pairs.append((rec, solo_cache[rung]))
        hbm_override = args.hbm_gb * 1e9 if args.hbm_gb is not None else None
        report, rc = render_fleet_report(pairs, args.chip, hbm_override)
        print(report, end="")
        if args.report:
            Path(args.report).parent.mkdir(parents=True, exist_ok=True)
            Path(args.report).write_text(report)
            print(f"[preflight] report → {args.report}", file=sys.stderr)
        return rc

    rungs = [r.strip() for r in args.rungs.split(",") if r.strip()]
    unknown = [r for r in rungs if r not in RUNG_PLAN]
    if unknown:
        print(f"unknown rungs: {unknown} (have: {sorted(RUNG_PLAN)})",
              file=sys.stderr)
        return 2
    if args.devices > 1:
        # The forced host-platform device count must be in XLA_FLAGS before
        # the first backend init (jax is imported, the backend is not —
        # verified on this jax: the env var is read at CPU client creation).
        from ..rungs import forced_host_devices_flags

        os.environ["XLA_FLAGS"] = forced_host_devices_flags(
            os.environ.get("XLA_FLAGS", ""), args.devices
        )
    ledger = ProgramLedger(Path(args.out) / "programs.jsonl") if args.out else None
    opt_override = {
        "remat": args.remat,
        "reward_tile": args.reward_tile,
        "noise_dtype": args.noise_dtype,
        "tower_dtype": args.tower_dtype,
        "pop_shard_update": args.pop_shard_update,
        "base_quant": args.base_quant,
    }

    records = []
    update_records: List[Dict[str, Any]] = []
    for rung in rungs:
        print(f"[preflight] {rung}: abstract lowering + CPU compile ...",
              file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        # heartbeats: CI logs stay live through the minute-class CPU compiles
        with Heartbeat(f"preflight:{rung}", "compile", gauges=None):
            rec = analyze_rung(
                rung, ledger, opt_override=opt_override, devices=args.devices
            )
        print(f"[preflight] {rung}: done in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr, flush=True)
        records.append(rec)
        if args.devices > 1:
            print(f"[preflight] {rung}: isolating the update programs ...",
                  file=sys.stderr, flush=True)
            with Heartbeat(f"preflight:{rung}", "update-isolation", gauges=None):
                update_records.extend(analyze_update_programs(
                    rung, args.devices, ledger, opt_override=opt_override
                ))

    hbm_override = args.hbm_gb * 1e9 if args.hbm_gb is not None else None
    report, rc = render_report(
        records, args.chip, hbm_override,
        update_records=update_records, devices=args.devices,
    )
    print(report, end="")
    if args.report:
        Path(args.report).parent.mkdir(parents=True, exist_ok=True)
        Path(args.report).write_text(report)
        print(f"[preflight] report → {args.report}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py [--pop_size N] [--out DIR]     # on a TPU
    python3 chip_smoke.py --tiny                         # CPU rehearsal

One process, the trainer's own entry point and nothing the trainer would not
do: ``hyperscalees_t2i_tpu.train.cli.main`` takes 3 epochs of
``--backend sana_one_step --model_scale full`` — Sana-Sprint 1.6B as
``SanaConfig`` states it (d_model 2240 × 20 layers), 32×32 latents → 1024 px
DC-AE decode, CLIP-B/32 and CLIP-H/14 reward towers, promptnorm, the EGGROLL
update — on weights made from a seed, at the operating knobs of
``rungs.RUNG_OPT["flagship"]``, over every local device through the CLI's
own mesh logic (one chip → no mesh; four chips → ``{pop: 4, data: 1}``).

What must hold, or the exit code is not 0 and no result line is printed:

- JAX sees a TPU. The real size runs nowhere else: no accelerator → exit 2
  with the reason. ``--tiny`` is the only CPU mode and has to be asked for.
- each Pallas kernel the default TPU gates select (``ops/pallas_gate``)
  compiles, runs and agrees with its XLA composition at one real call shape
  (``tools/kernel_check``: the tolerance is written there with its reason);
- 3 steps were executed *in this process* (a run directory left by an
  earlier call is never resumed), every reward and ``theta_norm`` finite;
- the step compiled exactly once and never retraced;
- the Pallas kernels in the compiled step are the ones the gates selected;
- on a TPU, every local device reports a non-zero peak memory.

Reported, not asserted: the reward spread across members and
``es/fitness_zero`` — with bf16 towers and σ = 0.01 on random weights the
fitness may be degenerate, which is a finding, not a failed start.

Everything written goes under ``--out`` (default ``chiprun_out/smoke``); the
compile cache is wherever ``JAX_COMPILATION_CACHE_DIR`` says, else
``<checkout>/.jax_cache`` (``utils/compile_cache``). The full report is
``<out>/smoke.json`` and the ``[smoke] report`` line; the last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
EPOCHS = 3


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke FAILED: {what}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse the same command at toy widths on whatever "
                         "platform jax has (the CPU, in a sandbox)")
    ap.add_argument("--pop_size", type=int, default=4)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "smoke"))
    args = ap.parse_args(argv)

    # the machine has no network: a Hugging Face lookup must fail at once,
    # not after connection retries
    os.environ.setdefault("HF_HUB_OFFLINE", "1")
    os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")
    if args.tiny:
        # toy kernels sit under the int8 size floor; without this the
        # rehearsal would skip the int8 + fused-LoRA path the real size runs
        os.environ.setdefault("HSES_BASE_QUANT_MIN_SIZE", "1")

    from hyperscalees_t2i_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    import jax

    t_start = time.perf_counter()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"[smoke] device {json.dumps(device)}; compile cache {cache_dir}", flush=True)
    on_tpu = device["platform"] == "tpu"
    if not (on_tpu or args.tiny):
        print(f"[smoke] REFUSED: the real size runs on a TPU only and jax found "
              f"{device['count']}×{device['platform']} ({device['kind']}). "
              "There is no fallback; `--tiny` is the explicit CPU rehearsal.",
              file=sys.stderr, flush=True)
        return 2
    if on_tpu:
        from hyperscalees_t2i_tpu.utils.mfu import device_chip

        device_chip()  # a TPU kind the peak table lacks is an error

    # ---- kernels the default gates select, against their XLA composition --
    from hyperscalees_t2i_tpu.ops.pallas_gate import selected_kernels
    from hyperscalees_t2i_tpu.tools import kernel_check

    selected = selected_kernels()
    print(f"[smoke] kernel gates: {json.dumps(selected)}", flush=True)
    kernel_records = []
    for name in (k for k, on in selected.items() if on):
        case = next(c for c in kernel_check.cases() if c.kernel == name)
        rec = kernel_check.run_case(case)
        print(f"[smoke] kernel {json.dumps(rec)}", flush=True)
        _check(rec["ok"], f"kernel {name} disagrees with its XLA composition: {rec}")
        kernel_records.append(rec)

    # ---- the trainer's main path ----------------------------------------
    from hyperscalees_t2i_tpu.obs.xla_cost import load_programs
    from hyperscalees_t2i_tpu.rungs import rung_opt
    from hyperscalees_t2i_tpu.train import cli
    from hyperscalees_t2i_tpu.utils.jsonl import read_jsonl_rows

    out = Path(args.out)
    run_dir = out / "run"
    rows_before = len(read_jsonl_rows(run_dir / "metrics.jsonl"))
    programs_before = len(load_programs(run_dir))
    opt = rung_opt("flagship")
    flags = [
        "--backend", "sana_one_step",
        "--model_scale", "tiny" if args.tiny else "full",
        "--prompts_txt", str(ROOT / "data" / "prompts_train.txt"),
        "--remat", opt["remat"], "--reward_tile", str(opt["reward_tile"]),
        "--noise_dtype", opt["noise_dtype"], "--tower_dtype", opt["tower_dtype"],
        "--base_quant", opt["base_quant"],
        "--pop_size", str(args.pop_size), "--prompts_per_gen", "4",
        "--member_batch", "1", "--num_epochs", str(EPOCHS),
        "--allow_random_rewards", "true",
        "--run_dir", str(out), "--run_name", "run", "--resume", "false",
        # per-member scores of every epoch into metrics.jsonl (hist/pop_scores):
        # what a one-chip and a four-chip run are compared on
        "--log_hist_every", "1",
    ]
    print(f"[smoke] train.cli {' '.join(flags)}", flush=True)
    t_train = time.perf_counter()
    try:
        cli.main(flags)
    except SystemExit as e:  # preempted / halted / refused: never a pass
        raise RuntimeError(f"chip_smoke FAILED: train.cli exited early ({e.code!r})") from e
    train_s = time.perf_counter() - t_train

    rows = read_jsonl_rows(run_dir / "metrics.jsonl")[rows_before:]
    _check([r["epoch"] for r in rows] == list(range(EPOCHS)),
           f"expected epochs 0..{EPOCHS - 1} executed in this process, got "
           f"{[r.get('epoch') for r in rows]}")
    last = rows[-1]
    _check(last["obs/dispatches"] == EPOCHS, f"dispatches {last['obs/dispatches']} != {EPOCHS}")
    _check(last["obs/compiles"] == 1, f"step compiled {last['obs/compiles']} times, not once")
    _check(last["obs/pop_eval_traces"] == 1,
           f"pop_eval traced {last['obs/pop_eval_traces']} times: a retrace")
    for r in rows:
        for k, v in r.items():
            if k == "theta_norm" or k.startswith("reward/"):
                _check(isinstance(v, float) and math.isfinite(v),
                       f"epoch {r['epoch']}: {k} = {v!r} is not finite")
        _check(len(r["hist/pop_scores"]) == args.pop_size,
               f"epoch {r['epoch']}: {len(r['hist/pop_scores'])} member scores")

    steps = [p for p in load_programs(run_dir)[programs_before:]
             if p["label"].startswith("es_step_")]
    _check(len(steps) == 1, f"{len(steps)} step programs in the ledger, expected 1")
    step = steps[0]
    # the Sana path has no decode-attention site, no DeltaNet and no Mamba-2
    # layer; every other selected kernel must be in the step, and nothing else
    expected = {k for k, on in selected.items() if on} - {"decode_attention", "gated_delta_step", "ssd_step"}
    found = set(step["pallas_kernels"])
    _check(found == expected,
           f"kernels in the compiled step {sorted(found)} != selected by the "
           f"gates {sorted(expected)}")

    peaks = None
    if on_tpu:
        peaks = {str(d.id): d.memory_stats()["peak_bytes_in_use"]
                 for d in jax.local_devices()}
        _check(all(v > 0 for v in peaks.values()),
               f"a device reports no memory use: {peaks}")

    report = {
        "ok": True,
        "device": device,
        "rehearsal": args.tiny,
        "mesh": step["geometry"].get("mesh_shape"),
        "pop_size": args.pop_size,
        "epochs": EPOCHS,
        "kernels_selected": selected,
        "kernels_in_step": step["pallas_kernels"],
        "kernel_checks": kernel_records,
        "step_lowering_s": step["lowering_s"],
        "step_compile_s": step["compile_s"],
        "step_peak_bytes_compiler": step.get("peak_bytes"),
        "step_collective_ops": step.get("collective_ops"),
        "step_time_s": [r["step_time_s"] for r in rows],
        "train_s": round(train_s, 2),
        "total_s": round(time.perf_counter() - t_start, 2),
        "peak_bytes_in_use": peaks if peaks is not None else "not measured",
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": last.get("obs/compile_cache_entries"),
        "theta_norm": [r["theta_norm"] for r in rows],
        "reward_combined_mean": [r["reward/combined_mean"] for r in rows],
        # reported, not asserted (module docstring)
        "es_reward_std": [r["es/reward_std"] for r in rows],
        "es_fitness_zero": [r["es/fitness_zero"] for r in rows],
        "pop_scores": [r["hist/pop_scores"] for r in rows],
    }
    (out / "smoke.json").write_text(json.dumps(report, indent=1) + "\n")
    print(f"[smoke] report {json.dumps(report)}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

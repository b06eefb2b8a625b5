"""Closed loop of ES training steps through ``train.cli.main``.

The harness meets the program where a user does, and nowhere else:

- ``train.cli.main(flags)``: the configuration's flags + the traffic's flags +
  the generated inputs + ``--seed``; every other flag stays at the CLI's default;
- the name ``run_training`` that ``cli.main`` imports from ``train.trainer``: it
  is wrapped for the length of the call so that the ``on_epoch_end`` parameter
  the function already has receives the harness's hook (no program file is
  edited, no step builder or model module is imported);
- the files the run leaves in its run directory, read after the window.

The hook stamps each epoch's completion on the harness's clock. The warm-up
epochs (compile, then one warm step) are set-up; the window opens when the
last of them completes and closes at the first epoch completed at or after
``--seconds``. The run is then ended by an exception raised from the hook and
caught here — the trainer's loop unwinds through its own ``finally`` and writes
no checkpoint, which the preemption marker would.
"""

from __future__ import annotations

import importlib
import json
import math
import shutil
import time
from pathlib import Path
from typing import Any, Dict, List, Tuple

from ..record import MARK, RunRecord

NEVER_REACHED_EPOCHS = 1_000_000
# a traced run goes on this many epochs after the profiler stopped, so that the
# host loop is also seen without the cost of starting and stopping it
EPOCHS_AFTER_TRACE = 2
# hist/pop_scores are promptnorm scores: standardized per prompt across the
# members, so their mean over the population is 0 by definition. They are
# O(1) float32 values; summing `pop` of them rounds at ~pop * 1.2e-7, while a
# member dropped or duplicated moves the mean by O(1/pop) >= 0.03 at pop 32.
POP_SCORE_MEAN_TOL = 1e-3


class WindowClosed(Exception):
    """Raised from the epoch hook to end a run whose window has closed."""


def _finite(v: Any) -> bool:
    return isinstance(v, (int, float)) and math.isfinite(v)


def bf16_spacing(v: float) -> float:
    """Distance between neighbouring bfloat16 values at |v| (8 bits of precision)."""
    return 2.0 ** (math.floor(math.log2(abs(v))) - 7) if v else 2.0 ** -133


def epoch_failed(row: Dict[str, Any]) -> List[str]:
    why = []
    for k, v in row.items():
        if (k.startswith("reward/") or k in ("theta_norm", "delta_norm")) \
                and isinstance(v, (int, float)) and not math.isfinite(v):
            why.append(f"{k}={v!r}")
    for k in ("theta_norm", "delta_norm"):
        if k not in row:
            why.append(f"{k} missing")
    if row.get("es/finite_frac", 1.0) < 1.0:
        why.append(f"es/finite_frac={row['es/finite_frac']}")
    return why


def run(job) -> Tuple[RunRecord, Dict[str, Any]]:
    """Runs the cell; returns the record and ``{correct, attempted, failed, report}``."""
    import jax

    from hyperscalees_t2i_tpu.train import cli

    trainer = importlib.import_module(cli.__package__ + ".trainer")

    traffic, config = job.traffic, job.config
    flags: Dict[str, str] = dict(config["flags"])
    flags.update(traffic["flags"])
    input_spec = dict(config["inputs"])
    warmup = int(traffic["warmup_epochs"])
    traced_epochs = int(traffic["traced_epochs"])
    if job.rehearse:
        flags.update(config["rehearse"].get("flags", {}))
        input_spec.update(config["rehearse"].get("inputs", {}))
        traced_epochs = int(traffic.get("rehearse", {}).get("traced_epochs", traced_epochs))

    gen = importlib.import_module(f"benchmarks.inputs.{input_spec['kind']}")
    inputs_dir = job.out_dir / "inputs"  # tens of MB of captions: gone when the run is
    inputs_dir.mkdir()
    input_flags = gen.make(input_spec, config["model"], job.seed, inputs_dir, job.bench_dir)
    flags.update(dict(zip(input_flags[::2], input_flags[1::2])))
    flags.update({
        "--seed": str(job.seed), "--resume": "false", "--save_every": "0",
        "--run_dir": str(job.out_dir), "--run_name": "run",
        "--num_epochs": str(NEVER_REACHED_EPOCHS),
    })
    if job.trace:
        flags["--trace"] = "true"
    argv = [x for kv in flags.items() for x in kv]

    profile_dir = job.out_dir / "profile" if job.trace else None
    rec = RunRecord(
        job=job, run_dir=job.out_dir / "run", profile_dir=profile_dir, flags=flags,
        work_per_step=int(flags["--pop_size"]) * int(flags["--prompts_per_gen"])
        * int(flags.get("--batches_per_gen", "1")),
        first_epoch=warmup,
    )
    state = {"profiling": False}
    earliest_close = warmup + (traced_epochs + EPOCHS_AFTER_TRACE - 1 if job.trace else 0)

    def hook(epoch: int, scalars: Dict[str, Any]) -> None:
        now = time.perf_counter()
        rec.epoch_stamps.append((epoch, now))
        note_resident(rec)
        if state["profiling"]:
            with jax.profiler.TraceAnnotation(MARK):
                rec.mark_stamps.append((epoch, time.perf_counter()))
            if epoch >= warmup - 1 + traced_epochs:
                jax.profiler.stop_trace()
                state["profiling"] = False
                rec.t_trace_done = time.perf_counter()
        if epoch == warmup - 1:
            rec.t_open = now
            if job.trace:
                # inside the window on purpose: the traced run's throughput
                # against the untraced one's is what the tracing costs
                jax.profiler.start_trace(str(profile_dir), profiler_options=profile_options())
                state["profiling"] = True
                with jax.profiler.TraceAnnotation(MARK):
                    rec.mark_stamps.append((epoch, time.perf_counter()))
        elif epoch >= earliest_close and now - rec.t_open >= job.seconds:
            rec.t_close, rec.last_epoch = now, epoch
            raise WindowClosed

    real_run_training = trainer.run_training

    def run_training_with_hook(backend, reward_fn, tc, *a, **kw):
        rec.peak_after_build = max(device_stat("peak_bytes_in_use"), default=0) or None
        kw["on_epoch_end"] = hook
        return real_run_training(backend, reward_fn, tc, *a, **kw)

    print(f"[bench] train.cli {' '.join(argv)}", flush=True)
    raised = None
    trainer.run_training = run_training_with_hook
    rec.t_entry = time.perf_counter()
    try:
        cli.main(argv)
        raised = "train.cli returned before the window closed"
    except WindowClosed:
        pass
    except SystemExit as e:
        raised = f"train.cli exited early ({e.code!r})"
    except Exception as e:  # a step that raises is a failed operation, reported as one
        import traceback

        traceback.print_exc()
        raised = f"{type(e).__name__}: {e}"
    finally:
        trainer.run_training = real_run_training
        if state["profiling"]:
            jax.profiler.stop_trace()
        shutil.rmtree(inputs_dir, ignore_errors=True)
    if raised and rec.epoch_stamps and rec.t_open:
        # the window is cut at the last epoch that completed
        rec.last_epoch, rec.t_close = rec.epoch_stamps[-1]
    if not rec.t_open or rec.last_epoch < rec.first_epoch:
        raise RuntimeError(f"no epoch completed inside the window: {raised}")

    rec.end_to_end["images_per_s_per_chip"] = (
        rec.epochs * rec.work_per_step / rec.window_s / job.chips
    )
    return rec, judge(rec, raised, job)


def profile_options():
    """Device ops and the harness's marks, without the Python call tracer and
    the HLO dump: they would be most of the trace's bytes and of its cost."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


def device_stat(stat: str) -> List[int]:
    """``memory_stats()[stat]`` of every local device, in ``jax.local_devices()``
    order; empty where the backend reports none (the CPU)."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    return [int(m.get(stat) or 0) for m in stats] if all(stats) else []


def note_resident(rec: RunRecord) -> None:
    """Most bytes in use between steps, per device: what a step starts from."""
    now = device_stat("bytes_in_use")
    if now:
        rec.resident_bytes = [max(a, b) for a, b in zip(rec.resident_bytes or [0] * len(now), now)]


def peak_bytes(rec: RunRecord):
    """Peak device memory of the process on its fullest chip, from the runtime's
    own counters (``memory_stats()``) and nothing else. The TPU runtime books a
    running program's scratch as *reserved*, apart from the buffers *in use*
    (its ``bytes_reservable_limit`` is ``bytes_limit`` less the in-use peak; VAR's
    step shows 2.05 GB in use beside 6.32 GB reserved, PR 23). So a chip's
    high-water mark is the larger of ``peak_bytes_in_use`` (every buffer the
    process ever held, the build included) and what a step holds: the bytes in
    use between steps + ``peak_bytes_reserved``. Both peaks are the process's
    whole life's: were the largest reservation a build program's, the second
    term would overstate the step. The compiler's own figure for the step is
    the per-layer metric ``step_compiled_peak_gb``; it is printed here beside
    the counters and enters nothing."""
    in_use, reserved = device_stat("peak_bytes_in_use"), device_stat("peak_bytes_reserved")
    if not in_use:
        return None
    resident = rec.resident_bytes or [0] * len(in_use)
    per_chip = [max(u, r + v) for u, r, v in zip(in_use, resident, reserved)]
    steps = rec.step_programs
    compiled = float(steps[0].get("peak_bytes") or 0) if steps else 0.0

    def gb(xs: List[int]) -> str:
        return "/".join(f"{x / 1e9:.3f}" for x in xs)

    print(f"[bench] peak device memory by the runtime's counters, GB a chip: peak_bytes_in_use {gb(in_use)} "
          f"(after the build {(rec.peak_after_build or 0) / 1e9:.3f}); in use between steps {gb(resident)} + "
          f"peak_bytes_reserved {gb(reserved)}; the larger a chip {gb(per_chip)}, the fullest is reported. "
          f"The compiler's peak of the step program: {compiled / 1e9:.3f}", flush=True)
    return max(per_chip)


def judge(rec: RunRecord, raised, job) -> Dict[str, Any]:
    """``correct`` and the counts, with every check named on an earlier line."""
    rows = rec.window_rows
    by_epoch = {r["epoch"]: r for r in rec.rows}
    failed_rows = {r["epoch"]: epoch_failed(r) for r in rows}
    failed = sum(1 for why in failed_rows.values() if why) + (1 if raised else 0)
    attempted = rec.epochs + (1 if raised else 0)
    checks: List[Tuple[str, bool, str]] = []

    def check(name: str, ok: bool, detail: str = "") -> None:
        checks.append((name, bool(ok), detail))

    check("no_failed_epoch", failed == 0,
          "; ".join(f"epoch {e}: {w}" for e, w in failed_rows.items() if w) or str(raised or ""))
    check("every_window_epoch_logged", len(rows) == rec.epochs,
          f"{len(rows)} rows for {rec.epochs} epochs")
    steps = rec.step_programs
    check("step_compiled_once", len(steps) == 1, f"{len(steps)} es_step_* programs")
    before = by_epoch.get(rec.first_epoch - 1, {})
    last = by_epoch.get(rec.last_epoch, {})
    for counter in ("obs/compiles", "obs/pop_eval_traces"):
        check(f"{counter}_flat_in_window",
              counter in last and before.get(counter) == last.get(counter) == 1,
              f"{before.get(counter)} -> {last.get(counter)}")
    check("one_dispatch_per_epoch",
          last.get("obs/dispatches", -1) - before.get("obs/dispatches", 0) == rec.epochs,
          f"{before.get('obs/dispatches')} -> {last.get('obs/dispatches')}")
    check("update_applied", all(_finite(r.get("delta_norm")) and r["delta_norm"] > 0 for r in rows),
          str([r.get("delta_norm") for r in rows][:4]))
    scored = sum(r.get("images_scored", 0) for r in rows)
    check("images_scored_as_counted", scored == rec.epochs * rec.work_per_step,
          f"{scored} vs {rec.epochs} x {rec.work_per_step}")
    pop = int(rec.flag("--pop_size"))
    hist_rows = [r for r in rec.rows if "hist/pop_scores" in r]
    bad = [r["epoch"] for r in hist_rows
           if len(r["hist/pop_scores"]) != pop
           or not all(_finite(s) for s in r["hist/pop_scores"])
           or abs(sum(r["hist/pop_scores"]) / pop) > POP_SCORE_MEAN_TOL]
    check("pop_scores_promptnormed", not bad,
          f"{len(hist_rows)} rows with hist/pop_scores; bad epochs {bad}")

    epoch0 = {k: v for k, v in by_epoch.get(0, {}).items()
              if k.startswith("reward/") and k.endswith("_mean") and _finite(v)}
    expected_path = rec.bench_dir / "expected" / rec.cell["name"] / f"seed{job.seed}.json"
    if job.rehearse or not expected_path.exists():
        print(f"[bench] expected rewards: skipped ({'rehearsal' if job.rehearse else 'no ' + str(expected_path.relative_to(rec.bench_dir))})", flush=True)
    else:
        want = json.loads(expected_path.read_text())["epoch0_reward_means"]
        off = {k: (epoch0.get(k), v) for k, v in want.items()
               if k not in epoch0 or abs(epoch0[k] - v) > 2 * bf16_spacing(v)}
        check("epoch0_rewards_as_recorded", not off, f"{off}")

    for name, ok, detail in checks:
        print(f"[bench] check {name}: {'ok' if ok else 'FAILED'} {detail if not ok else ''}".rstrip(), flush=True)
    kernels = steps[0].get("pallas_kernels") if steps else None
    print(f"[bench] pallas kernels in the step (reported, not judged): {json.dumps(kernels)}", flush=True)
    return {
        "correct": all(ok for _, ok, _ in checks),
        "attempted": attempted, "failed": failed,
        # the driver's own lines of result.json
        "report": {
            "checks": [[n, ok, d] for n, ok, d in checks],
            "epoch0_reward_means": epoch0,
            "step_time_s": [r.get("step_time_s") for r in rows],
            "step_program": {k: steps[0].get(k) for k in (
                "label", "lowering_s", "compile_s", "peak_bytes", "argument_bytes", "temp_bytes",
                "pallas_kernels", "collective_ops", "stablehlo_lines")} if steps else None,
            "peak_after_build_bytes": rec.peak_after_build, "resident_bytes": rec.resident_bytes,
        },
    }

#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by the names BENCHMARK.json gives
it, never by a table in this file:

    configs/<config>.json        sizes, CLI flags, generated inputs, kernel sites
    traffic/<traffic>.json       kind, CLI flags, warm-up and traced epochs, images a kernel call
    drivers/<kind>.py            runs the cell, judges ``correct``
    inputs/<kind>.py             makes a configuration's inputs from the seed
    layer_metrics/<metric>.py    one reader per per-layer metric
    flops/<family>.py            operations one unit of work requires
    expected/<cell>/seed<k>.json the system's own recorded output, where kept
    peaks.json                   the chips this benchmark may run on

The real size runs on a TPU only: no accelerator, another chip count than the
cell's, or a ``device_kind`` that ``peaks.json`` lacks → exit 2, no result line.
``--rehearse`` is the one explicit CPU mode: toy widths, forced host devices,
``"platform": "cpu"`` in the result; its numbers are not measurements.
A traced run deletes the profiler's files once they are reduced, unless the
environment has ``BENCH_KEEP_TRACE`` set (a builder's aid, no argument).

The last line of stdout is the result: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device`` and, traced, ``breakdown``.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()
CLOCK_ANCHOR = (time.time(), time.perf_counter())

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
MANIFEST = ROOT / "BENCHMARK.json"


def by_name(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"[bench] no {what} named {name!r} in {MANIFEST.name}")


def metrics_of(section: List[Dict[str, Any]], cell: str) -> List[Dict[str, Any]]:
    return [m for m in section if "workloads" not in m or cell in m["workloads"]]


def refuse(why: str) -> int:
    print(f"[bench] REFUSED: {why}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths on forced CPU devices; not a measurement")
    ap.add_argument("--out", default=None,
                    help="output directory (default <checkout>/chiprun_out/bench/<cell>/seed<n>-trace<t>)")
    args = ap.parse_args(argv)

    manifest = json.loads(MANIFEST.read_text())
    cell = by_name(manifest["workloads"], args.workload, "workload")
    config_entry = by_name(manifest["configs"], cell["config"], "config")
    config = json.loads((ROOT / config_entry["file"]).read_text())
    traffic = json.loads((BENCH_DIR / "traffic" / f"{cell['traffic']}.json").read_text())
    chips = int(cell["chips"])
    peaks_table = json.loads((BENCH_DIR / "peaks.json").read_text())["device_kinds"]

    # a sealed machine: a Hugging Face lookup must fail at once, not after retries
    os.environ.setdefault("HF_HUB_OFFLINE", "1")
    os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                       + f" --xla_force_host_platform_device_count={chips}").strip()
        for k, v in config.get("rehearse", {}).get("env", {}).items():
            os.environ.setdefault(k, v)

    sys.path.insert(0, str(ROOT))
    from hyperscalees_t2i_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    print(f"[bench] cell {cell['name']} seed {args.seed} seconds {args.seconds} trace {args.trace}; "
          f"device {json.dumps(device)}; compile cache {cache_dir}", flush=True)
    peaks = None
    if args.rehearse:
        if device["platform"] != "cpu" or device["count"] != chips:
            return refuse(f"--rehearse wants {chips} CPU device(s), jax has {device}")
    else:
        if device["platform"] != "tpu":
            return refuse(f"the real size runs on a TPU only and jax found {device}. "
                          "There is no fallback; --rehearse is the explicit CPU mode.")
        if device["count"] != chips:
            return refuse(f"cell {cell['name']} is defined on {chips} chip(s), jax has {device['count']}")
        if device["kind"] not in peaks_table:
            return refuse(f"device_kind {device['kind']!r} is not in benchmarks/peaks.json: "
                          "add it with its published peaks and their source")
        peaks = peaks_table[device["kind"]]

    out_dir = Path(args.out) if args.out else (
        ROOT / "chiprun_out" / "bench" / cell["name"] / f"seed{args.seed}-trace{args.trace}")
    out_dir = out_dir.resolve()
    if out_dir.exists():
        shutil.rmtree(out_dir)  # a run directory left by an earlier run is never resumed
    out_dir.mkdir(parents=True)

    from benchmarks.record import Job

    job = Job(cell=cell, config=config, traffic=traffic, chips=chips, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace), rehearse=args.rehearse,
              out_dir=out_dir, bench_dir=BENCH_DIR, peaks=peaks,
              t_process_start=T_PROCESS_START, clock_anchor=CLOCK_ANCHOR)
    driver = importlib.import_module(f"benchmarks.drivers.{traffic['kind']}")
    rec, verdict = driver.run(job)

    peak = driver.peak_bytes(rec)
    values: Dict[str, Optional[float]] = dict(rec.end_to_end)
    values["setup_s"] = rec.t_open - rec.t_process_start
    values["peak_hbm_gb"] = peak / 1e9 if peak else None
    print(f"[bench] window {rec.window_s:.3f} s, epochs {rec.first_epoch}..{rec.last_epoch} "
          f"({rec.epochs} x {rec.work_per_step} images); set-up {values['setup_s']:.2f} s "
          f"(of which before train.cli {rec.t_entry - rec.t_process_start:.2f} s)", flush=True)

    wanted = metrics_of(manifest["per_layer" if args.trace else "end_to_end"], cell["name"])
    if args.trace:
        for m in wanted:
            reader = importlib.import_module(f"benchmarks.layer_metrics.{m['name']}")
            values[m["name"]] = reader.read(rec)
    else:
        # only a rehearsal may lack one: the CPU reports no device memory
        missing = [m["name"] for m in wanted
                   if m["name"] not in values or (values[m["name"]] is None and not args.rehearse)]
        if missing:
            raise SystemExit(f"[bench] driver {traffic['kind']} gave no value for {missing}")
    # a reader that found nothing to read returned None: its metric is left out
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if values[m["name"]] is not None or not args.trace}

    device["memory_peak_bytes"] = int(peak or 0)
    result: Dict[str, Any] = {
        "correct": verdict["correct"], "attempted": verdict["attempted"],
        "failed": verdict["failed"], "metrics": metrics, "device": device,
    }
    if args.rehearse:
        result["rehearsal"] = True
    if args.trace and rec.trace is not None:
        device["busy_s"], device["window_s"] = rec.trace.busy_s, rec.trace.window_s
        result["breakdown"] = breakdown(rec)
    for note in rec.notes:
        print(f"[bench] note {note}", flush=True)

    report = {**result, "cell": cell["name"], "seed": args.seed, "seconds": args.seconds,
              "traced": args.trace, "window_s": rec.window_s, "epochs": rec.epochs,
              "end_to_end_values": {m["name"]: values.get(m["name"]) for m in manifest["end_to_end"]},
              "epoch_stamps_s": [[e, t - rec.t_open] for e, t in rec.epoch_stamps],
              "memory_stats": {str(d.id): d.memory_stats() for d in jax.local_devices()},
              "driver": verdict.get("report")}
    if args.trace and rec.trace is not None:
        # the trace's own vocabulary: the only view of it once the machine is gone
        report["trace"] = {
            "lines_seen": rec.trace.lines_seen, "census": rec.trace.census(),
            "span_kind": rec.trace.devices[0].span_kind, "periods": rec.trace.periods,
            "clock_offset_s": rec.trace_clock_offset_s, "marks": len(rec.trace.marks_ns),
            "per_chip": [{"chip": d.chip, "busy_s": d.busy_ns * 1e-9, "span_s": d.span_s,
                          "collective_s": d.collective_ns * 1e-9,
                          "collective_exposed_s": d.collective_exposed_ns * 1e-9,
                          "gaps": len(d.gaps)} for d in rec.trace.devices]}
    (out_dir / "result.json").write_text(json.dumps(report, indent=1) + "\n")
    if rec.profile_dir is not None and not os.environ.get("BENCH_KEEP_TRACE"):
        shutil.rmtree(rec.profile_dir, ignore_errors=True)  # tens of MB a traced run

    if args.trace and not args.rehearse and not device.get("busy_s"):
        return refuse("the traced run shows no operation on the device")
    print(json.dumps(result), flush=True)
    return 0


def breakdown(rec, ops: int = 10, gaps: int = 10) -> Dict[str, List]:
    """The device ops with most own time, and the device's idle time by what
    the host was doing: every idle gap of the idlest chip goes to the
    ``trace.jsonl`` span open at its midpoint (``unattributed`` when the two
    clocks could not be aligned)."""
    tr = rec.trace
    offset = rec.trace_clock_offset_s
    by: Dict[str, float] = {}
    for lo, hi in tr.idlest.gaps:
        label = "unattributed" if offset is None else rec.host_span_at((lo + hi) * 0.5e-9 + offset)
        by[label] = by.get(label, 0.0) + (hi - lo) * 1e-9
    idle = sorted(by.items(), key=lambda kv: -kv[1])[:gaps]
    return {"device_ops": tr.top_ops(ops), "idle_gaps": [[k, v] for k, v in idle]}


if __name__ == "__main__":
    sys.exit(main())

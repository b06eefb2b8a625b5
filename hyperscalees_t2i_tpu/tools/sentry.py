"""Cross-run regression sentry CLI — gate a new run against prior runs.

Usage::

    # check a candidate against prior runs (ad-hoc baselines)
    python -m hyperscalees_t2i_tpu.tools.sentry check runs/new \\
        --baseline runs/prior1 --baseline runs/prior2

    # check against the committed manifest (what CI's regression_gate does)
    python -m hyperscalees_t2i_tpu.tools.sentry check ci_runs/smoke \\
        --manifest SENTRY_BASELINE.json

    # refresh the committed manifest from known-good runs
    python -m hyperscalees_t2i_tpu.tools.sentry baseline \\
        --out SENTRY_BASELINE.json runs/good1 runs/good2 bench_runs/BENCH.json

Sources are run dirs (metrics.jsonl + programs.jsonl + CAPACITY*.json +
CALIB*.json + QUALITY*.json), ``*.jsonl`` ledgers (committed
``PREFLIGHT_*``), ``BENCH_*.json`` bench artifacts, ``CAPACITY_*.json``
capacity curves, ``CALIB_*.json`` calibration artifacts,
``WINDOW_r*.json`` window rollups, or ``QUALITY_*.json`` model-quality
artifacts (higher-is-better gates over final reward, AUC-over-images,
and images-to-threshold — the direction-aware twin of the step-time
axis) — the ingestion, robust median+MAD baselines, direction-aware
bounds, and the jax-sensitive + chip-sensitive skip disciplines all live
in ``obs/regress.py``.

``check`` writes ``sentry_verdict.json`` (into the candidate run dir by
default, ``--out`` overrides — the trainer's ``/healthz`` surfaces that
file as ``sentry_verdict``), prints every breach naming the metric, its
baseline, and the observed value, and exits **2 on breach** (0 pass,
1 usage/ingest error) so CI gates on it directly.

Baseline refresh discipline (README "Flight recorder & regression
sentry"): regenerate the manifest ONLY from runs whose perf change was
intentional and reviewed — a sentry whose baseline silently tracks every
regression is a sentry that never fires.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path
from typing import List

from ..obs import regress

EXIT_BREACH = 2


def _ingest_sources(paths: List[str]) -> List[List[regress.Observation]]:
    out = []
    for p in paths:
        obs = regress.ingest(p)
        if not obs:
            print(f"[sentry] WARNING: no observations in {p}", file=sys.stderr)
        out.append(obs)
    return out


def cmd_baseline(args: argparse.Namespace) -> int:
    baselines = regress.build_baselines(_ingest_sources(args.sources))
    excluded = {m.strip() for m in (args.exclude or "").split(",") if m.strip()}
    if excluded:
        baselines = [b for b in baselines if b.metric not in excluded]
    if not baselines:
        print("[sentry] ERROR: no observations in any baseline source",
              file=sys.stderr)
        return 1
    merged = 0
    if args.merge:
        # keep existing manifest entries whose (metric, key) the new sources
        # did not re-observe — e.g. fold a fresh capacity sweep into a
        # manifest whose train/bench baselines are still good
        fresh = {(b.metric, b.key) for b in baselines}
        kept = [b for b in regress.load_manifest(args.out)["baselines"]
                if (b.metric, b.key) not in fresh
                and b.metric not in excluded]
        merged = len(kept)
        baselines = sorted(kept + baselines,
                           key=lambda b: (b.metric, b.key))
    out = regress.write_manifest(args.out, baselines, note=args.note)
    print(f"sentry manifest → {out} ({len(baselines)} baselines"
          + (f", kept {merged} existing" if args.merge else "")
          + (f", excluded {sorted(excluded)}" if excluded else "")
          + f", gen_jax={regress.running_jax_version()})")
    return 0


def cmd_check(args: argparse.Namespace) -> int:
    baselines: List[regress.Baseline] = []
    baseline_jax = None
    if args.manifest:
        m = regress.load_manifest(args.manifest)
        baselines.extend(m["baselines"])
        baseline_jax = m["gen_jax"]
    if args.baseline:
        baselines.extend(
            regress.build_baselines(_ingest_sources(args.baseline))
        )
        # ad-hoc baselines were ingested under the running jax: no skip
        if baseline_jax is None:
            baseline_jax = regress.running_jax_version()
    if not baselines:
        print("[sentry] ERROR: need --baseline and/or --manifest",
              file=sys.stderr)
        return 1

    candidate = Path(args.candidate)
    observations = regress.ingest(candidate)
    verdict = regress.evaluate(
        baselines, observations,
        jax_version=regress.running_jax_version(),
        baseline_jax=baseline_jax,
    )
    verdict["candidate"] = str(candidate)

    out = Path(args.out) if args.out else (
        candidate / regress.VERDICT_FILE if candidate.is_dir()
        else Path(regress.VERDICT_FILE)
    )
    regress.write_verdict(verdict, out)

    print(f"# sentry verdict: {out}")
    print(f"checked {verdict['checked']} baselines "
          f"({len(verdict['skipped'])} skipped) against {candidate}")
    for s in verdict["skipped"]:
        print(f"  skip {s['metric']}[{s['key']}]: {s['reason']}")
    for c in verdict.get("sha_changes", []):
        print(f"  note {c['key']}: StableHLO sha changed "
              f"({str(c['baseline_sha'])[:8]} → {str(c['observed_sha'])[:8]}"
              ") — program rebuilt; byte/FLOP bounds arbitrate")
    if verdict["breaches"]:
        for b in verdict["breaches"]:
            worse = "above" if b["direction"] == "upper" else "below"
            print(
                f"BREACH {b['metric']}[{b['key']}]: observed "
                f"{b['observed']:.6g} is {worse} bound {b['bound']:.6g} "
                f"(baseline {b['baseline']:.6g} ± MAD {b['baseline_mad']:.3g} "
                f"over {b['baseline_n']} run(s); from {b['source']})"
            )
        print(f"VERDICT: FAIL — {len(verdict['breaches'])} regression(s)")
        return EXIT_BREACH
    print("VERDICT: pass")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    b = sub.add_parser("baseline",
                       help="write a baseline manifest from known-good runs")
    b.add_argument("sources", nargs="+",
                   help="run dirs / *.jsonl ledgers / BENCH_*.json artifacts")
    b.add_argument("--out", default="SENTRY_BASELINE.json")
    b.add_argument("--note", default="",
                   help="free-text provenance note stored in the manifest")
    b.add_argument("--exclude", default="",
                   help="comma list of metric classes to leave out of the "
                        "manifest — a COMMITTED manifest should exclude "
                        "wall-clock metrics (step_time_s,compile_s) whose "
                        "baselines were taken on a different machine class "
                        "than CI; same-machine checks via --baseline keep "
                        "them")
    b.add_argument("--merge", action="store_true",
                   help="merge into an existing --out manifest: entries for "
                        "(metric, key) pairs the new sources re-observe are "
                        "replaced, everything else is kept")
    b.set_defaults(fn=cmd_baseline)

    c = sub.add_parser("check", help="check a candidate against baselines")
    c.add_argument("candidate",
                   help="run dir / ledger / bench artifact to check")
    c.add_argument("--baseline", action="append", default=[],
                   help="prior-run source (repeatable)")
    c.add_argument("--manifest", default=None,
                   help="committed baseline manifest (SENTRY_BASELINE.json)")
    c.add_argument("--out", default=None,
                   help="verdict path (default: <candidate>/sentry_verdict"
                        ".json for run dirs)")
    c.set_defaults(fn=cmd_check)

    args = ap.parse_args(argv)
    try:
        return args.fn(args)
    except (OSError, ValueError) as e:
        print(f"[sentry] ERROR: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())

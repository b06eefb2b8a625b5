"""What the readers of the program's own names share (PR 25).

Two joins, both by name and both empty on a program that lacks the names (the
parent of PR 25): every function here then returns None, its reader returns
None, and the metric is left out of the line.

- device time by scope: the trace's leaf ops (``rec.trace.devices[*].leaves``)
  against ``run_dir/scopes/<label>.json``, the instruction -> ``jax.named_scope``
  table the program writes for each compiled step (``programs.jsonl`` names
  it). Each instant an op runs goes to one leaf (the earlier one where two
  overlap), so the leaves partition the busy time: the scopes and
  ``unattributed`` add up to ``step_device_s``. A table entry that begins
  ``~`` is one the program inferred from the graph (a compiler-made op takes
  the scope of the ops it serves) and not one its metadata names: it counts
  under its scope, and ``unscoped_device_s`` says in a note, every run, how
  much of each scope is such a guess.
- host annotations: with ``--trace true`` every span of the program's tracer
  is also a ``TraceAnnotation`` event of the profiler's host plane, on the
  device events' clock. ``enqueue`` and ``fetch`` are read there against the
  step module's executions, so no offset between two clocks enters — but the
  profiler itself aligns its device and host planes only to about a
  millisecond, so launch and fetch latency each carry that error and only
  their sum is exact (``host_round_trip_note``).
"""

from __future__ import annotations

import bisect
import json
import statistics
from typing import Dict, List, Optional, Tuple

from .. import trace_reduce

UNATTRIBUTED = "unattributed"
INFERRED = "~"
BETWEEN_STEPS = "between_steps"


def _cached(rec, key: str, make):
    store = rec.__dict__.setdefault("_scopes_cache", {})
    if key not in store:
        store[key] = make()
    return store[key]


# ---------------------------------------------------------------- device time

def scope_table(rec) -> Dict[str, str]:
    """instruction name -> scope path (``~`` in front where the program
    inferred it), merged over the step programs' tables."""
    def make():
        table: Dict[str, str] = {}
        for prog in rec.step_programs:
            rel = prog.get("scope_table")
            path = rec.run_dir / rel if rel else None
            if path is not None and path.exists():
                table.update(json.loads(path.read_text()))
        return table
    return _cached(rec, "table", make)


def _seconds_by_entry(rec) -> Optional[Dict[str, float]]:
    """Seconds a traced step, mean over the chips, of the leaf ops under each
    table entry as written (``generate/dit_ffn``, ``~generate/dit_ffn``); ops
    absent from the table under ``unattributed``. None without a trace or a
    table."""
    def make():
        tr, table = rec.trace, scope_table(rec)
        if tr is None or not table:
            return None
        acc: Dict[str, float] = {}
        for d in tr.devices:
            lo, hi = d.span_ns
            covered = lo  # where two leaves overlap (an async collective under a
            for e in d.leaves:  # kernel), the time is the earlier one's: the sum is the union
                start, end = max(e.start_ns, covered), min(e.end_ns, hi)
                if end > start:
                    scope = table.get(e.name, UNATTRIBUTED)
                    acc[scope] = acc.get(scope, 0.0) + (end - start)
                    covered = end
        per = 1e-9 / len(tr.devices) / tr.periods
        return {k: v * per for k, v in acc.items()}
    return _cached(rec, "by_entry", make)


def seconds_by_scope(rec) -> Optional[Dict[str, float]]:
    """``_seconds_by_entry`` with the inferred entries counted under their
    scope: seconds a traced step by scope path."""
    by = _seconds_by_entry(rec)
    if by is None:
        return None
    acc: Dict[str, float] = {}
    for entry, s in by.items():
        acc[entry.lstrip(INFERRED)] = acc.get(entry.lstrip(INFERRED), 0.0) + s
    return acc


def scope_seconds(rec, *tops: str) -> Optional[float]:
    """Seconds a traced step of the ops whose scope starts with one of ``tops``."""
    by = seconds_by_scope(rec)
    if by is None:
        return None
    return sum(s for scope, s in by.items() if scope.split("/")[0] in tops)


def inner_note(rec, top: str) -> None:
    """One line of ``rec.notes``: ``top`` split by its inner scopes; where
    they nest a level deeper (VAR's ``scale<k>/blocks``), a second line by the
    innermost name over all of them."""
    by = seconds_by_scope(rec) or {}
    mine = {k: v for k, v in by.items() if k.split("/")[0] == top}
    if not mine:
        return

    def line(group) -> str:
        acc: Dict[str, float] = {}
        for scope, s in mine.items():
            key = group(scope.split("/"))
            if key is not None:
                acc[key] = acc.get(key, 0.0) + s
        return ", ".join(f"{k} {v:.4f} s" for k, v in sorted(acc.items(), key=lambda kv: -kv[1]))

    rec.notes.append(f"{top} a step: " + line(lambda p: p[1] if len(p) > 1 else "(own)"))
    if any(len(k.split("/")) > 2 for k in mine):
        rec.notes.append(f"{top} a step, by innermost scope: " + line(lambda p: p[-1] if len(p) > 2 else None))


def unscoped_note(rec, k: int = 5) -> None:
    """How much of each top-level scope the program's metadata names and how
    much it inferred from the graph; the ``k`` largest ops with no scope; and
    the idle time inside the traced span by the scope of the op that follows
    each gap (the gap in front of a step's first op is the host's:
    ``between_steps``)."""
    tr, table = rec.trace, scope_table(rec)
    if tr is None or not table:
        return
    named: Dict[str, float] = {}
    guessed: Dict[str, float] = {}
    for entry, s in _seconds_by_entry(rec).items():
        into = guessed if entry.startswith(INFERRED) else named
        top = entry.lstrip(INFERRED).split("/")[0]
        into[top] = into.get(top, 0.0) + s
    rec.notes.append("scope a step, named by the program's metadata + inferred from the graph: " + ", ".join(
        f"{top} {named.get(top, 0.0):.4f} + {guessed.get(top, 0.0):.4f} s"
        for top in sorted(set(named) | set(guessed), key=lambda t: (-(named.get(t, 0.0) + guessed.get(t, 0.0)), t))
        if top != UNATTRIBUTED))
    acc: Dict[str, float] = {}
    for d in tr.devices:
        for e in d.leaves:
            if table.get(e.name, UNATTRIBUTED) == UNATTRIBUTED:
                acc[e.name] = acc.get(e.name, 0.0) + e.dur_ns
    per = 1e-9 / len(tr.devices) / tr.periods
    labels = tr.devices[0].label_by_name
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    rec.notes.append("unscoped a step, largest ops: " + (", ".join(
        f"{labels.get(n, n)} {ns * per:.4f} s" for n, ns in top) or "none"))

    d = tr.idlest
    starts = [e.start_ns for e in d.leaves]
    step_starts = set(module_runs(rec).get(d.chip, {}).get("first_op_ns", []))
    idle: Dict[str, float] = {}
    for lo, hi in d.gaps:
        i = bisect.bisect_left(starts, hi - 0.5)
        if i >= len(starts) or starts[i] in step_starts:
            key = BETWEEN_STEPS
        else:
            key = table.get(d.leaves[i].name, UNATTRIBUTED).lstrip(INFERRED).split("/")[0]
        idle[key] = idle.get(key, 0.0) + (hi - lo)
    rec.notes.append(
        f"idle a step on chip {d.chip} ({len(d.gaps)} gaps in {d.periods} steps), by the scope of the op "
        "that follows: " + ", ".join(f"{k} {ns * 1e-9 / d.periods:.4f} s"
                                     for k, ns in sorted(idle.items(), key=lambda kv: -kv[1])))


# ------------------------------------------------------- the profiler's clock

def _profile(rec):
    def make():
        if rec.profile_dir is None:
            return None
        path = trace_reduce.find_xplane(rec.profile_dir)
        return trace_reduce.load(path) if path else None
    return _cached(rec, "profile", make)


def host_events(rec, name: str) -> List[Tuple[float, float]]:
    """``(start_ns, end_ns)`` of the host-plane events called ``name``, in
    start order — like ``trace_reduce.host_marks``, with the ends."""
    def make():
        profile = _profile(rec)
        out = []
        for plane in (profile.planes if profile is not None else ()):
            if plane.name.startswith("/host:"):
                for ln in plane.lines:
                    out.extend((float(e.start_ns), float(e.start_ns + e.duration_ns))
                               for e in ln.events if e.name == name)
        return sorted(out)
    return _cached(rec, f"host:{name}", make)


def module_runs(rec) -> Dict[int, Dict[str, List[float]]]:
    """Per chip, the executions of the step module (the module with most
    device time): ``start_ns``, ``end_ns`` and ``first_op_ns`` (the first op
    at or after each start)."""
    def make():
        profile = _profile(rec)
        out: Dict[int, Dict[str, List[float]]] = {}
        for chip, lines in (trace_reduce.device_lines(profile) if profile is not None else {}).items():
            modules = lines[trace_reduce.MODULES_LINE]
            name, _ = trace_reduce.step_boundaries(modules)
            runs = [e for e in modules if e.name == name]
            op_starts = [e.start_ns for e in lines[trace_reduce.OPS_LINE]]
            firsts = []
            for e in runs:
                i = bisect.bisect_left(op_starts, e.start_ns)
                if i < len(op_starts):
                    firsts.append(op_starts[i])
            out[chip] = {"start_ns": [e.start_ns for e in runs], "end_ns": [e.end_ns for e in runs],
                         "first_op_ns": firsts}
        return out
    return _cached(rec, "modules", make)


def step_edges(rec) -> List[Tuple[float, float]]:
    """Per traced execution of the step: (start on the first chip to start,
    end on the last chip to end), ns on the profiler's clock."""
    runs = list(module_runs(rec).values())
    if not runs:
        return []
    n = min(len(r["start_ns"]) for r in runs)
    return [(min(r["start_ns"][i] for r in runs), max(r["end_ns"][i] for r in runs)) for i in range(n)]


def _paired(rec, name: str) -> List[Tuple[Tuple[float, float], Tuple[float, float]]]:
    """(step edges, annotation) pairs, matched in order from the last traced
    step back: the profiler is stopped after a step's fetch, so the last
    annotation belongs to the last execution. (Not by which comes first: the
    profiler places device and host events on one clock only to about a
    millisecond — on the recorded toy fixture the device runs its module
    1.0 ms *before* the host enqueues it.)"""
    return list(zip(reversed(step_edges(rec)), reversed(host_events(rec, name))))[::-1]


def launch_latencies_ms(rec) -> List[float]:
    """Per traced step: start of the step module on the device minus the
    start of the ``enqueue`` annotation that launched it."""
    return [(t_dev - start) * 1e-6 for (t_dev, _), (start, _) in _paired(rec, "enqueue")]


def fetch_latencies_ms(rec) -> List[float]:
    """Per traced step: end of the ``fetch`` annotation that waited for it
    minus the end of the step module on the device."""
    return [(end - t_dev) * 1e-6 for (_, t_dev), (_, end) in _paired(rec, "fetch")]


def host_round_trip_note(rec) -> None:
    """Launch + fetch latency a step: enqueue start → fetch end, less the
    module's time on the device. The sum is free of the profiler's own
    host/device alignment error; each term alone carries it."""
    both = [a + b for a, b in zip(launch_latencies_ms(rec), fetch_latencies_ms(rec))]
    if both:
        rec.notes.append(f"launch + fetch latency a step (free of the profiler's host/device alignment, which each "
                         f"alone carries): median {statistics.median(both):.3f} ms")


def median_or_none(values: List[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def clock_offset_note(rec) -> None:
    """How well the harness's marks place the two clocks: the offset they
    give against the one implied by the program's own ``epoch`` annotations
    (the same spans in ``trace.jsonl`` and on the profiler's host plane)."""
    marks = rec.trace_clock_offset_s
    ann = host_events(rec, "epoch")
    spans = sorted(rec.spans_named("epoch"), key=lambda s: s["t0"])
    if marks is None or not ann or not spans:
        return
    t0s = [s["t0"] for s in spans]
    diffs = []
    for start_ns, _ in ann:
        guess = start_ns * 1e-9 + marks
        i = min(range(len(t0s)), key=lambda j: abs(t0s[j] - guess))
        diffs.append(t0s[i] - start_ns * 1e-9)
    implied = statistics.median(diffs)
    rec.notes.append(f"clock offset (harness clock - profiler clock): {marks:.6f} s by the harness's marks, "
                     f"{implied:.6f} s by the program's {len(diffs)} epoch annotations; "
                     f"they differ by {abs(implied - marks) * 1e3:.3f} ms")


# ---------------------------------------------------------------- build spans

def span_seconds(rec, *names: str, parent: Optional[str] = None) -> Optional[float]:
    """Sum of the durations of the ``trace.jsonl`` spans called one of
    ``names`` (under ``parent``, if given); None where there is none."""
    spans = [s for n in names for s in rec.spans_named(n) if parent is None or s.get("parent") == parent]
    return sum(s["dur_s"] for s in spans) if spans else None

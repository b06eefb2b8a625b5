"""What one run leaves behind, as the per-layer readers see it.

A driver fills a :class:`RunRecord`; the readers under ``layer_metrics/`` take
their numbers from it and from nothing else. Everything that costs time to
read (the run directory's files, the profiler trace) is read once, on first
use, after the measured window has closed.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import json
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from . import trace_reduce

# the harness's TraceAnnotation at every epoch end while the profiler runs:
# the same instant on the host's clock and on the trace's
MARK = "bench_epoch_end"


def read_jsonl(path: Path) -> List[Dict[str, Any]]:
    if not path.exists():
        return []
    rows = []
    for line in path.read_text().splitlines():
        line = line.strip()
        if line:
            rows.append(json.loads(line))
    return rows


@dataclasses.dataclass
class Job:
    """One invocation of ``run.py``: the cell's three files and the arguments."""

    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    out_dir: Path
    bench_dir: Path
    peaks: Optional[Dict[str, float]]  # None in a rehearsal: no device metric
    t_process_start: float           # on time.perf_counter(), like every t_* below
    clock_anchor: Tuple[float, float]  # (time.time(), time.perf_counter()) taken together


@dataclasses.dataclass
class RunRecord:
    job: Job
    run_dir: Path
    profile_dir: Optional[Path] = None
    flags: Dict[str, str] = dataclasses.field(default_factory=dict)  # the program's flags, by name
    t_entry: float = 0.0             # the program's entry point entered
    t_open: float = 0.0              # window opens: last warm-up epoch complete
    t_close: float = 0.0             # window closes
    t_trace_done: float = 0.0        # profiler stopped (traced runs)
    epoch_stamps: List[Tuple[int, float]] = dataclasses.field(default_factory=list)
    mark_stamps: List[Tuple[int, float]] = dataclasses.field(default_factory=list)
    work_per_step: int = 0           # images scored by one step
    first_epoch: int = 0             # first epoch inside the window
    last_epoch: int = -1
    peak_after_build: Optional[int] = None
    resident_bytes: List[int] = dataclasses.field(default_factory=list)  # most bytes in use between steps, a device
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    notes: List[str] = dataclasses.field(default_factory=list)

    def __getattr__(self, name: str) -> Any:
        # cell, config, traffic, chips, peaks, bench_dir, ...: the job's
        if name != "job" and name in Job.__dataclass_fields__:
            return getattr(self.job, name)
        raise AttributeError(name)

    # ---- the window
    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    @property
    def epochs(self) -> int:
        return self.last_epoch - self.first_epoch + 1

    def flag(self, name: str, default: Optional[str] = None) -> Optional[str]:
        return self.flags.get(name, default)

    # ---- the run directory
    @functools.cached_property
    def rows(self) -> List[Dict[str, Any]]:
        return read_jsonl(self.run_dir / "metrics.jsonl")

    @property
    def window_rows(self) -> List[Dict[str, Any]]:
        return [r for r in self.rows if self.first_epoch <= r.get("epoch", -1) <= self.last_epoch]

    @functools.cached_property
    def programs(self) -> List[Dict[str, Any]]:
        return read_jsonl(self.run_dir / "programs.jsonl")

    @property
    def step_programs(self) -> List[Dict[str, Any]]:
        return [p for p in self.programs if str(p.get("label", "")).startswith("es_step_")]

    @functools.cached_property
    def spans(self) -> List[Dict[str, Any]]:
        """Host spans of ``trace.jsonl`` with ``t0``/``t1`` on the harness's
        clock. The tracer stamps its origin with ``time.time()``; the harness
        took the same clock together with ``perf_counter``, which is the
        mapping (good to the resolution of ``time.time()``, well under a ms)."""
        events = read_jsonl(self.run_dir / "trace.jsonl")
        origin = None
        out = []
        for ev in events:
            if ev.get("meta") == "trace_start":
                wall, perf = self.job.clock_anchor
                origin = perf + (ev["wall_time"] - wall)
            elif origin is not None and "t0_s" in ev:
                t0 = origin + ev["t0_s"]
                out.append({**ev, "t0": t0, "t1": t0 + ev["dur_s"]})
        return out

    def spans_named(self, name: str, lo: Optional[float] = None, hi: Optional[float] = None):
        return [s for s in self.spans if s["name"] == name
                and (lo is None or s["t1"] > lo) and (hi is None or s["t0"] < hi)]

    # ---- the profiler trace
    @functools.cached_property
    def trace(self) -> Optional[trace_reduce.TraceReduction]:
        if self.profile_dir is None:
            return None
        path = trace_reduce.find_xplane(self.profile_dir)
        return trace_reduce.reduce_trace(path, mark=MARK) if path else None

    @functools.cached_property
    def trace_clock_offset_s(self) -> Optional[float]:
        """perf_counter seconds minus trace seconds, from the marks both clocks
        saw; None when they cannot be paired (then idle gaps stay
        ``unattributed``)."""
        tr = self.trace
        if tr is None or not tr.marks_ns or len(tr.marks_ns) != len(self.mark_stamps):
            return None
        diffs = sorted(t - ns * 1e-9 for (_, t), ns in zip(self.mark_stamps, tr.marks_ns))
        if diffs[-1] - diffs[0] > 5e-3:  # the marks disagree: not one clock offset
            return None
        return diffs[len(diffs) // 2]

    @functools.cached_property
    def _spans_by_depth(self):
        levels: Dict[int, List[Dict[str, Any]]] = {}
        for s in self.spans:
            levels.setdefault(s["depth"], []).append(s)
        out = []
        for depth in sorted(levels, reverse=True):
            level = sorted(levels[depth], key=lambda s: s["t0"])
            out.append(([s["t0"] for s in level], level))
        return out

    def host_span_at(self, t: float) -> str:
        """Innermost ``trace.jsonl`` span open at harness time ``t`` (the
        spans of one depth follow one another on the trainer's one thread)."""
        for starts, level in self._spans_by_depth:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t < level[i]["t1"]:
                return level[i]["name"]
        return "no_span"

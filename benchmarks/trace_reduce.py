"""From a profiler trace (``*.xplane.pb``) to device busy/idle, per-op time,
kernel time and collective exposure — the benchmark's own reduction, read
through ``jax.profiler.ProfileData`` and nothing of the program.

What a TPU trace holds (one plane per chip, ``/device:TPU:<n>``):

- line ``XLA Modules``: one event per execution of a compiled program;
- line ``XLA Ops``: one event per executed HLO op. Control-flow ops (``while``,
  ``conditional``, ``call``) span the ops of their bodies, gaps included, so
  events nest. Busy time is the union of the *leaf* intervals; an op's own
  time is its duration minus its children's; only leaves are "another op"
  when a collective's exposure is asked.

Everything below the loader works on plain ``Event`` tuples, so the arithmetic
is tested on a hand-written trace (``tests/fixtures/*.textproto``) as well as
on a recorded one.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# HLO collective opcodes, with their async -start/-done halves
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|ragged-all-to-all)(-start|-done)?(\.\d+)?$"
)
# stats whose text carries the op's source name (a Pallas call's ``name=``)
_TEXT_STATS = ("long_name", "tf_op", "hlo_op", "name", "source", "kernel_details")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str       # the op's own name: ``fusion.12``, ``fused_qlora.3``, ``all-reduce.4``
    start_ns: float
    end_ns: float
    text: str = ""  # the event's full name (on a TPU the op's whole HLO line) + its text stats

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


def find_xplane(profile_dir: Path) -> Optional[Path]:
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` output directory."""
    files = sorted(Path(profile_dir).glob("plugins/profile/*/*.xplane.pb"))
    return files[-1] if files else None


def load(path: Path):
    from jax.profiler import ProfileData

    path = Path(path)
    if path.suffix == ".textproto":
        return ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(path.read_text())
        )
    return ProfileData.from_file(str(path))


def own_name(event_name: str) -> str:
    """``%fused_qlora.3 = bf16[2,1024,2304]{...} custom-call(%copy.7, ...)`` ->
    ``fused_qlora.3``. The operands of an HLO line name *other* ops, so
    nothing is matched against the line as a whole."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def label(e: "Event") -> str:
    """Own name and result shape: what the breakdown prints for an op."""
    head, _, rest = e.text.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{e.name} {shape}".strip()[:96]


def is_kernel(e: "Event", kernel: str) -> bool:
    """A Pallas call carries its ``name=`` as its op name; failing that, a
    custom call's ``op_name`` metadata or source stats may."""
    if kernel in e.name:
        return True
    return "custom-call" in e.text and re.search(rf'(op_name="|name=)[^"\]]*{re.escape(kernel)}', e.text) is not None


def line_events(line) -> List[Event]:
    out = []
    for e in line.events:
        text = e.name
        for k, v in e.stats:
            if k in _TEXT_STATS and isinstance(v, str):
                text += " " + v
        out.append(Event(own_name(e.name), float(e.start_ns), float(e.start_ns + e.duration_ns), text))
    out.sort(key=lambda ev: (ev.start_ns, -ev.end_ns))
    return out


def device_lines(profile) -> Dict[int, Dict[str, List[Event]]]:
    """``{chip: {line name: events}}`` for the two lines the reduction reads."""
    out: Dict[int, Dict[str, List[Event]]] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            continue
        out[int(m.group(1))] = {
            OPS_LINE: line_events(lines[OPS_LINE]),
            MODULES_LINE: line_events(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
        }
    return out


def host_marks(profile, name: str) -> List[float]:
    """Start times (ns, the trace's clock) of the host ``TraceAnnotation``
    events called ``name`` — the harness's clock-alignment marks."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            out.extend(float(e.start_ns) for e in ln.events if e.name == name)
    return sorted(out)


# ---------------------------------------------------------------- arithmetic

def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(intervals: Iterable[Tuple[float, float]], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def total(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]):
    """Merged intervals of ``a`` minus merged intervals of ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def nesting(events: Sequence[Event]) -> Tuple[List[float], List[bool]]:
    """Per event (sorted by start, outermost first): its own time (duration
    minus its direct children's) and whether it is a leaf."""
    self_ns = [e.dur_ns for e in events]
    leaf = [True] * len(events)
    stack: List[int] = []
    for i, e in enumerate(events):
        # the nearest event still open that holds this one whole is its parent
        while stack and events[stack[-1]].end_ns < e.end_ns:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= e.dur_ns
            leaf[stack[-1]] = False
        stack.append(i)
    return self_ns, leaf


def step_boundaries(modules: Sequence[Event]) -> Tuple[Optional[str], List[float]]:
    """The step program is the module with the most device time; returns its
    name and the start of each of its executions."""
    by: Dict[str, float] = {}
    for e in modules:
        by[e.name] = by.get(e.name, 0.0) + e.dur_ns
    if not by:
        return None, []
    name = max(by, key=by.get)
    return name, [e.start_ns for e in modules if e.name == name]


@dataclasses.dataclass
class DeviceReduction:
    chip: int
    span_ns: Tuple[float, float]
    periods: int                   # whole step periods inside the span
    span_kind: str                 # "step_starts" | "first_to_last_op"
    busy_ns: float
    self_by_name: Dict[str, float]
    count_by_name: Dict[str, int]
    collective_ns: float
    collective_exposed_ns: float
    gaps: List[Tuple[float, float]]  # every idle interval of the span, longest first
    leaves: List[Event]
    label_by_name: Dict[str, str] = dataclasses.field(default_factory=dict)
    sample_text: Dict[str, str] = dataclasses.field(default_factory=dict)

    @property
    def span_s(self) -> float:
        return (self.span_ns[1] - self.span_ns[0]) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / (self.span_ns[1] - self.span_ns[0])


def reduce_device(chip: int, lines: Dict[str, List[Event]]) -> Optional[DeviceReduction]:
    ops = lines[OPS_LINE]
    if not ops:
        return None
    _, starts = step_boundaries(lines[MODULES_LINE])
    if len(starts) >= 2:
        # whole periods: from the start of the first traced step to the start
        # of the last, so each step is counted with the host gap that follows it
        span, periods, kind = (starts[0], starts[-1]), len(starts) - 1, "step_starts"
    else:
        span = (ops[0].start_ns, max(e.end_ns for e in ops))
        periods, kind = max(len(starts), 1), "first_to_last_op"
    lo, hi = span
    inside = [e for e in ops if e.end_ns > lo and e.start_ns < hi]
    self_ns, leaf = nesting(inside)
    self_by: Dict[str, float] = {}
    count_by: Dict[str, int] = {}
    for e, s in zip(inside, self_ns):
        self_by[e.name] = self_by.get(e.name, 0.0) + max(s, 0.0)
        count_by[e.name] = count_by.get(e.name, 0) + 1
    leaves = [e for e, is_leaf in zip(inside, leaf) if is_leaf]
    # a control-flow op spans the gaps between the ops of its body: only a
    # leaf is an operation running
    busy = merge(clip(((e.start_ns, e.end_ns) for e in leaves), lo, hi))
    coll = merge(clip(((e.start_ns, e.end_ns) for e in leaves if COLLECTIVE.match(e.name)), lo, hi))
    other = merge(clip(((e.start_ns, e.end_ns) for e in leaves if not COLLECTIVE.match(e.name)), lo, hi))
    gaps = sorted(subtract([(lo, hi)], busy), key=lambda g: g[0] - g[1])
    return DeviceReduction(
        chip=chip, span_ns=span, periods=periods, span_kind=kind,
        busy_ns=total(busy), self_by_name=self_by, count_by_name=count_by,
        collective_ns=total(coll), collective_exposed_ns=total(subtract(coll, other)),
        gaps=gaps, leaves=leaves,
        label_by_name={e.name: label(e) for e in inside},
        sample_text={e.name: e.text for e in inside},
    )


@dataclasses.dataclass
class TraceReduction:
    devices: List[DeviceReduction]
    marks_ns: List[float]
    lines_seen: Dict[str, List[str]] = dataclasses.field(default_factory=dict)  # plane -> its lines
    _matched: Dict[str, Tuple[float, float]] = dataclasses.field(default_factory=dict)

    def census(self, k: int = 40) -> List[List]:
        """First chip's ops by own time: ``[name, seconds, events, sample text]``."""
        d = self.devices[0]
        top = sorted(d.self_by_name.items(), key=lambda kv: -kv[1])[:k]
        return [[n, ns * 1e-9, d.count_by_name[n], d.sample_text.get(n, "")[:400]] for n, ns in top]

    @property
    def window_s(self) -> float:
        return max(d.span_s for d in self.devices)

    @property
    def idlest(self) -> DeviceReduction:
        return max(self.devices, key=lambda d: d.idle_share)

    @property
    def busy_s(self) -> float:
        """Mean over the chips of the time an op ran inside the span."""
        return sum(d.busy_ns for d in self.devices) / len(self.devices) * 1e-9

    @property
    def periods(self) -> int:
        return self.devices[0].periods

    def share_of_span(self, ns_of) -> float:
        """Mean over the chips of ``ns_of(chip)`` ÷ that chip's span."""
        return sum(ns_of(d) * 1e-9 / d.span_s for d in self.devices) / len(self.devices)

    def matching(self, kernel: str) -> Tuple[float, float]:
        """(seconds, events) per chip — mean over the chips — of the leaf ops
        that are calls of ``kernel`` (:func:`is_kernel`)."""
        if kernel in self._matched:
            return self._matched[kernel]
        ns = n = 0.0
        for d in self.devices:
            hit = [e for e in d.leaves if is_kernel(e, kernel)]
            lo, hi = d.span_ns
            ns += total(clip(((e.start_ns, e.end_ns) for e in hit), lo, hi))
            n += len(hit)
        k = len(self.devices)
        self._matched[kernel] = (ns / k * 1e-9, n / k)
        return self._matched[kernel]

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` ops with most own time, summed over executions, mean
        over the chips: ``[[name, seconds], ...]``."""
        acc: Dict[str, float] = {}
        for d in self.devices:
            for name, ns in d.self_by_name.items():
                acc[name] = acc.get(name, 0.0) + ns
        n = len(self.devices)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        labels = self.devices[0].label_by_name
        return [[labels.get(name, name), ns / n * 1e-9] for name, ns in top]


def reduce_trace(path: Path, mark: str = "") -> Optional[TraceReduction]:
    profile = load(path)
    devices = []
    for chip, lines in sorted(device_lines(profile).items()):
        d = reduce_device(chip, lines)
        if d is not None:
            devices.append(d)
    if not devices:
        return None
    return TraceReduction(
        devices=devices, marks_ns=host_marks(profile, mark) if mark else [],
        lines_seen={pl.name: [ln.name for ln in pl.lines] for pl in profile.planes},
    )

"""Nested-span tracer: the host side of one run, by name.

The spans exist for their readers. ``benchmarks/layer_metrics/`` takes the
build (``build_backend``, ``backend_setup``, ``quantize``, ``build_reward``),
the step builder (``compile`` → ``lower``) and the host loop
(``dispatch`` → ``enqueue``, ``fetch``) from ``trace.jsonl`` by span name;
``tools/trace_report.py`` / ``tools/run_report.py`` aggregate the same file
for an operator. PERF.md §3 lists every span with the metric that reads it.

- ``Tracer(path)`` records one JSON line per *completed* span (children close
  before parents, so child lines precede their parent's). Lines are held in
  memory and written through one file handle when a root span closes (the
  trainer's ``epoch``), on ``event()`` and on ``close()`` — a crash loses at
  most the epoch in flight. ``Tracer(None)`` is a zero-overhead no-op.
- ``Tracer(enabled=True)`` has no file yet: ``train.cli.main`` makes it on
  entry, so the build is under spans, and ``run_training`` gives it the run
  directory's file (``attach``) once the directory's name is known.
- Spans nest via a thread-local stack (``depth``/``parent`` are recorded per
  event) and are timed with the monotonic clock. The first line
  (``trace_start``) carries the wall time the tracer was made, which maps
  the offsets to any other clock.
- An enabled span also enters ``jax.profiler.TraceAnnotation(name)`` (when
  jax is already imported; a TraceMe costs nanoseconds with no profiler
  session open): under a profiler session every span is an event of the
  ``.xplane.pb`` host plane too, on the device events' own clock.
- ``to_chrome(events)`` converts the event list to Chrome trace-event JSON.
- ``startup`` is the one span that starts before the tracer does:
  ``record_startup`` back-dates it from the operating system's own stamp of
  the process's start (``process_start_monotonic``), so its ``t0_s`` is
  negative and a run's timeline begins where its ``setup_s`` does.
- ``scope(name)`` is how the step enters one of its top-level device scopes
  (``obs/xla_cost.TOP_SCOPES``): the ``jax.named_scope`` the device time is
  named by and, with the tracer on, a host span ``trace/<name>`` around the
  Python that traces the body — where ``lower``'s seconds go.

A process-global tracer (``set_tracer`` / ``get_tracer``) lets call sites in
other layers (``train/cli.py``, ``parallel/pop_eval.py``, backends) emit
spans without plumbing a handle; the module-level ``span(...)`` resolves it
at call time.

Device-side attribution is not this file's: the step program carries
``jax.named_scope`` names and ``obs/xla_cost.scope_table`` writes the
op → scope table that joins a profiler trace to them.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Union


class Tracer:
    """Thread-safe nested-span tracer writing JSON lines.

    ``Tracer(None)`` is disabled: ``span()`` yields immediately and records
    nothing (the tracing-off case). ``Tracer(enabled=True)`` records into
    memory until :meth:`attach` names its file.
    """

    def __init__(self, path: Optional[Union[str, Path]] = None, *, enabled: Optional[bool] = None):
        from .multihost import safe_process_index

        self.path = Path(path) if path is not None else None
        self._enabled = self.path is not None if enabled is None else bool(enabled)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._lines: List[str] = []  # recorded, not yet written
        self._file = None            # the one handle, opened on first flush
        # Wall epoch + monotonic origin recorded together so offsets in the
        # file can be mapped back to absolute time by readers that care.
        self._wall0 = time.time()
        self._mono0 = time.perf_counter()
        # Captured once: a process's rank never changes, and per-event lookup
        # would put a (cheap but nonzero) call on every span close.
        self._process_index = safe_process_index()
        if self._enabled:
            self._record({"meta": "trace_start", "wall_time": self._wall0,
                          "pid": os.getpid(),
                          "process_index": self._process_index})
            self.flush()

    @property
    def enabled(self) -> bool:
        return self._enabled

    def attach(self, path: Union[str, Path]) -> None:
        """Name the file of a tracer made without one; what it recorded so
        far (its ``trace_start`` line first) is written there."""
        self.path = Path(path)
        self.flush()

    def _record(self, obj: Dict[str, Any]) -> None:
        line = json.dumps(obj, default=str) + "\n"
        with self._lock:
            self._lines.append(line)

    def flush(self) -> None:
        """Write what was recorded since the last flush. Never raises:
        observability must not kill the run (e.g. run_dir removed underneath
        a long job) — the lines are dropped instead."""
        if self.path is None:
            return
        with self._lock:
            lines, self._lines = self._lines, []
            if not lines:
                return
            try:
                if self._file is None:
                    self.path.parent.mkdir(parents=True, exist_ok=True)
                    self._file = self.path.open("a")
                self._file.writelines(lines)
                self._file.flush()
            except OSError:
                pass

    def close(self) -> None:
        """Flush and release the file handle (the tracer stays usable: a
        later flush reopens the file in append mode)."""
        self.flush()
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None

    def _stack(self) -> List[str]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    @contextmanager
    def span(self, name: str, **attrs: Any):
        """Time a phase. Nesting is tracked per thread; the event line carries
        ``t0_s``/``dur_s`` (offsets from the tracer's monotonic origin),
        ``depth``, ``parent``, pid/tid, and any keyword attrs. The ``with``
        statement's target is the attrs dict itself: what is known only when
        the phase ends (a compile's cache verdict) is put there before the
        span closes.

        A registered span observer (``set_span_observer``) sees every
        completed span's ``(name, dur_s)`` even on a disabled tracer — the
        trainer's phase histograms must stream whether or not a trace file
        is being written. With neither file nor observer the disabled path
        stays allocation- and clock-free."""
        if not self.enabled and _OBSERVER is None:
            yield attrs
            return
        stack = self._stack()
        annotation = _trace_annotation(name) if self.enabled else None
        t0 = time.perf_counter() - self._mono0
        parent = stack[-1] if stack else None
        stack.append(name)
        if annotation is not None:
            annotation.__enter__()
        try:
            yield attrs
        finally:
            if annotation is not None:
                annotation.__exit__(None, None, None)
            stack.pop()
            t1 = time.perf_counter() - self._mono0
            if _OBSERVER is not None:
                try:
                    _OBSERVER(name, t1 - t0)
                except Exception:
                    pass  # a broken observer must not kill the traced phase
            if self.enabled:
                ev = {
                    "name": name,
                    "t0_s": round(t0, 6),
                    "dur_s": round(t1 - t0, 6),
                    "depth": len(stack),
                    "parent": parent,
                    "pid": os.getpid(),
                    "tid": threading.get_ident(),
                    "process_index": self._process_index,
                }
                if attrs:
                    ev["attrs"] = attrs
                self._record(ev)
                if not stack:
                    self.flush()  # a root span closed (the trainer's ``epoch``)

    def event(
        self,
        name: str,
        t0_monotonic: float,
        t1_monotonic: float,
        parent: Optional[str] = None,
        depth: int = 0,
        **attrs: Any,
    ) -> None:
        """Record a completed span retroactively from two ``perf_counter``
        stamps — for phases whose start and end live in different call
        frames (a serve request's submit→complete lifetime spans queueing,
        coalescing, and dispatch; no ``with`` block can wrap it) or are
        stamped by someone else (jax's own duration events under ``lower``:
        ``depth`` places those under their parent). The event
        line is shaped exactly like a ``span`` line, so every trace reader
        (trace_report, run_report, Chrome export) consumes it unchanged."""
        if not self.enabled:
            return
        ev = {
            "name": name,
            "t0_s": round(t0_monotonic - self._mono0, 6),
            "dur_s": round(max(t1_monotonic - t0_monotonic, 0.0), 6),
            "depth": int(depth),
            "parent": parent,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "process_index": self._process_index,
        }
        if attrs:
            ev["attrs"] = attrs
        self._record(ev)
        self.flush()

    def depth(self) -> int:
        """How many spans are open on the calling thread."""
        return len(self._stack())


def _trace_annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)`` where jax is already imported
    (this package stays importable, and usable, without it), else None. The
    bare name only: keyword arguments are encoded into the event's name, and
    readers of the profiler's host plane match names by equality."""
    if "jax" not in sys.modules:
        return None
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


_NULL = Tracer(None)
_GLOBAL: Tracer = _NULL
# span-close observer: (name, dur_s) -> None, or None (off). Process-global
# like the tracer itself, installed per run by run_training — it feeds the
# phase_* streaming histograms even when no trace file is being written.
_OBSERVER: Optional[Any] = None


def set_tracer(tracer: Optional[Tracer]) -> Tracer:
    """Install the process-global tracer (``None`` → disabled). Returns it."""
    global _GLOBAL
    _GLOBAL = tracer if tracer is not None else _NULL
    return _GLOBAL


def set_span_observer(observer: Optional[Any]) -> None:
    """Install the process-global span-close observer (``None`` → off)."""
    global _OBSERVER
    _OBSERVER = observer


def get_tracer() -> Tracer:
    return _GLOBAL


@contextmanager
def span(name: str, **attrs: Any):
    """Span on the process-global tracer (no-op until ``set_tracer``)."""
    with get_tracer().span(name, **attrs) as live_attrs:
        yield live_attrs


@contextmanager
def scope(name: str):
    """``jax.named_scope(name)`` and, on an enabled tracer, a host span
    ``trace/<name>`` around the same body. The device scope is the name the
    compiled step's ops carry (``obs/xla_cost.scope_table``) and is the same
    with the tracer on or off; the span times the Python that traces the
    body. A body traced twice gives two spans."""
    import jax

    with jax.named_scope(name), get_tracer().span(f"trace/{name}"):
        yield


def process_start_monotonic() -> Optional[float]:
    """When the operating system started this process, on the
    ``time.perf_counter`` clock (so: before every stamp the process took
    itself). From ``/proc/self/stat``'s start time, which the kernel counts
    in clock ticks since boot; None where there is no such file."""
    try:
        stat = Path("/proc/self/stat").read_text()
        # the command name (field 2) may hold spaces: count from its ")"
        start_ticks = int(stat.rsplit(")", 1)[1].split()[19])
        age_s = time.clock_gettime(time.CLOCK_BOOTTIME) - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.perf_counter() - age_s


def record_startup(t_entered: float, **attrs: Any) -> None:
    """The ``startup`` span of the process-global tracer: process start (the
    operating system's stamp) → ``t_entered``, the ``perf_counter`` stamp
    the entry point took first thing. Nothing where the system gives no
    start time."""
    t_start = process_start_monotonic()
    if t_start is not None:
        get_tracer().event("startup", t_start, t_entered, **attrs)


def block_if_tracing(tree: Any) -> Any:
    """``jax.block_until_ready(tree)`` when the process-global tracer is
    enabled, else nothing; returns ``tree``. A span around work the device
    runs asynchronously ends in this call, or its time would be charged to
    the next span that waits; with tracing off no sync is added."""
    if _GLOBAL.enabled and "jax" in sys.modules:
        import jax

        jax.block_until_ready(tree)
    return tree


def load_events(path: Union[str, Path]) -> List[Dict[str, Any]]:
    """Span events from ``trace.jsonl`` (or a run dir containing one), in file
    order. Unparseable lines are skipped, never fatal.

    A resumed run appends a NEW tracer session (fresh ``trace_start`` meta
    line, monotonic origin reset to ~0) to the same file; each event is
    annotated with its 0-based ``session`` index so consumers never mix the
    incompatible time bases (``t0_s`` restarts per session)."""
    p = Path(path)
    if p.is_dir():
        p = p / "trace.jsonl"
    events: List[Dict[str, Any]] = []
    session = -1
    for line in p.read_text().splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            ev = json.loads(line)
        except json.JSONDecodeError:
            continue
        if ev.get("meta") == "trace_start":
            session += 1
        elif "name" in ev and "dur_s" in ev and "t0_s" in ev:
            ev["session"] = max(session, 0)
            events.append(ev)
    return events


def to_chrome(events: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Chrome trace-event JSON (``chrome://tracing`` / Perfetto): one complete
    ``"ph": "X"`` event per span, microsecond units, attrs under ``args``."""
    trace_events = []
    for ev in sorted(events, key=lambda e: (e["t0_s"], -e["dur_s"])):
        trace_events.append({
            "name": ev["name"],
            "cat": ev.get("parent") or "root",
            "ph": "X",
            "ts": round(ev["t0_s"] * 1e6, 3),
            "dur": round(ev["dur_s"] * 1e6, 3),
            "pid": ev.get("pid", 0),
            "tid": ev.get("tid", 0),
            "args": ev.get("attrs", {}),
        })
    return {"traceEvents": trace_events, "displayTimeUnit": "ms"}

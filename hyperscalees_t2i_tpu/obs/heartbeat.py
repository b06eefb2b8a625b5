"""Phase heartbeat + stall watchdog, promoted from ``bench.py``.

A long blocking phase that says nothing looks exactly like a hang: a
flagship first compile runs for minutes, and a driver that hears no liveness
signal kills it healthy. This module makes the heartbeat a shared primitive
any long blocking phase can wrap.

Contract:

- **stderr only.** bench.py's driver-facing artifact is "the last JSON line
  on stdout"; a heartbeat firing mid-print from its daemon thread must never
  be able to interleave with that contract. Every emission here goes to
  ``stream`` (default: ``sys.stderr`` resolved at emit time, so pytest
  capture and redirection behave).
- One JSON object per line — ``{"hb": name, "phase": ..., "elapsed_s": ...}``
  plus ``device.memory_stats()`` gauges when the platform provides them —
  so parents/drivers can parse liveness without regexes.
- Optional **stall watchdog**: when the wrapped phase exceeds
  ``stall_cap_s``, ``on_stall(name, phase, elapsed_s)`` fires (once) from the
  heartbeat thread instead of the phase dying silently. The wait loop clamps
  its sleep to the remaining budget, so the callback fires within one
  interval of the cap even when ``interval_s`` is much larger. ``on_stall``
  is where escalation policy lives — the trainer's ``--stall_action
  checkpoint_exit`` uses it to latch a graceful preemption request
  (checkpoint + coordinated exit at the next epoch boundary) instead of only
  printing; ``stall_payload`` extra keys ride on the stalled heartbeat line
  so log scrapers see what the watchdog is about to do.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from contextlib import nullcontext
from typing import Any, Callable, Dict, Optional, TextIO


def device_memory_gauges() -> Dict[str, int]:
    """Best-effort memory gauges from ``device.memory_stats()``: the LARGEST
    value over this process's devices, so a mesh whose fullest chip is not
    chip 0 is still seen. ``{}`` on platforms without the API (CPU) or
    before a backend is up — never raises, and never *initializes* (or
    blocks on) a backend: during the very phase heartbeats exist to cover
    (first backend init), a ``jax.devices()`` call from the heartbeat thread
    would contend on the init lock and silence the heartbeat."""
    from .multihost import jax_backend_initialized

    try:
        # Only read devices once a backend already exists (shared probe in
        # multihost.jax_backend_initialized); otherwise degrade to no gauges
        # rather than risking a backend init from this thread.
        if not jax_backend_initialized():
            return {}
        import jax

        all_stats = [d.memory_stats() or {} for d in jax.local_devices()]
    except Exception:
        return {}
    out = {}
    for k in ("bytes_in_use", "peak_bytes_in_use"):
        vals = [s[k] for s in all_stats if isinstance(s.get(k), (int, float))]
        if vals:
            out[k] = int(max(vals))
    return out


def emit_heartbeat(name: str, phase: str, stream: Optional[TextIO] = None,
                   **extra: Any) -> None:
    """One liveness line — JSON, stderr by default, never stdout. Tagged
    with ``process_index`` so pod-level log aggregation can attribute hosts
    (``safe_process_index`` never initializes a backend — safe from the
    heartbeat daemon thread even mid backend-init)."""
    from .multihost import safe_process_index

    payload = {"hb": name, "phase": phase,
               "process_index": safe_process_index(), **extra}
    print(json.dumps(payload, default=str), file=stream or sys.stderr, flush=True)
    # mirror onto the /healthz blackboard (obs/exporter.py): liveness over
    # HTTP is exactly this stderr stream, re-exposed — best-effort, a broken
    # blackboard must never cost a heartbeat line
    try:
        from .exporter import note_heartbeat

        note_heartbeat(payload)
    except Exception:
        pass


class Heartbeat:
    """Context manager: periodic liveness lines while a blocking phase runs.

    >>> with Heartbeat("flagship", "compile", interval_s=20):
    ...     compiled = step.lower(...).compile()   # minutes at flagship size

    ``stall_cap_s > 0`` arms the watchdog: ``on_stall`` fires once when the
    phase exceeds the cap (and the heartbeat line gains ``"stalled": true``);
    the phase itself keeps running — deciding to kill it is the caller's
    policy, not this thread's.
    """

    def __init__(
        self,
        name: str,
        phase: str,
        interval_s: float = 20.0,
        stall_cap_s: float = 0.0,
        on_stall: Optional[Callable[[str, str, float], None]] = None,
        gauges: Optional[Callable[[], Dict[str, Any]]] = device_memory_gauges,
        stream: Optional[TextIO] = None,
        stall_payload: Optional[Dict[str, Any]] = None,
    ):
        self.name, self.phase = name, phase
        self.interval_s = float(interval_s)
        self.stall_cap_s = float(stall_cap_s or 0.0)
        self.on_stall = on_stall
        self.gauges = gauges
        self.stream = stream
        self.stall_payload = stall_payload
        self.stalled = False
        self._stop = threading.Event()
        self._t = threading.Thread(
            target=self._run, name=f"heartbeat:{name}:{phase}", daemon=True
        )

    def _run(self) -> None:
        t0 = time.perf_counter()
        while True:
            timeout = self.interval_s
            if self.stall_cap_s and not self.stalled:
                # wake for the watchdog even when the interval is far longer
                remaining = self.stall_cap_s - (time.perf_counter() - t0)
                timeout = min(timeout, max(remaining, 0.005))
            if self._stop.wait(timeout):
                return
            elapsed = time.perf_counter() - t0
            extra: Dict[str, Any] = {"elapsed_s": round(elapsed, 1)}
            if self.gauges is not None:
                try:
                    extra.update(self.gauges())
                except Exception:
                    pass
            if self.stall_cap_s and not self.stalled and elapsed >= self.stall_cap_s:
                self.stalled = True
                extra["stalled"] = True
                if self.stall_payload:
                    extra.update(self.stall_payload)
                try:  # /healthz flips to "stalled" while this phase hangs
                    from .exporter import note_stall

                    note_stall(True, {"hb": self.name, "phase": self.phase,
                                      "elapsed_s": round(elapsed, 1), **extra})
                except Exception:
                    pass
                if self.on_stall is not None:
                    try:
                        self.on_stall(self.name, self.phase, elapsed)
                    except Exception:
                        pass  # a broken callback must not kill liveness
            emit_heartbeat(self.name, self.phase, stream=self.stream, **extra)

    def __enter__(self) -> "Heartbeat":
        self._t.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._t.join(timeout=2)
        if self.stalled:
            try:  # the stalled phase has ended (however it ended): un-stall
                from .exporter import note_stall

                note_stall(False)
            except Exception:
                pass


def maybe_heartbeat(name: str, phase: str, interval_s: float, **kwargs):
    """``Heartbeat`` when ``interval_s > 0``, else a no-op context — call
    sites stay unconditional (`with maybe_heartbeat(...):`)."""
    if interval_s and interval_s > 0:
        return Heartbeat(name, phase, interval_s=interval_s, **kwargs)
    return nullcontext()

"""Unified observability: span tracing, heartbeats/watchdog, metrics registry.

One tracing system, three files a run leaves behind, each read by name by
the benchmark's per-layer readers (``benchmarks/layer_metrics/``; PERF.md §3
has the span/counter → metric table):

- ``trace``     — nested host-side spans → ``trace.jsonl`` per run (build,
  compile, dispatch → enqueue/fetch, logging); under a profiler session the
  same spans are events of the profiler's host plane, on the device's clock;
  Chrome-trace export, aggregated by ``tools/trace_report.py``;
- ``heartbeat`` — periodic liveness lines to **stderr** during long blocking
  phases (a flagship compile runs for minutes), with an optional
  stall watchdog that fires a callback instead of dying silently;
- ``metrics``   — process-wide counters/gauges (dispatches, compiles, cache
  entries, device-memory peaks) merged into ``metrics.jsonl`` payloads;
- ``xla_cost``  — per-compiled-program ledger (``programs.jsonl``: normalized
  cost/memory analysis, StableHLO stats, donation audit) + roofline
  classification of measured steps; stdlib-only at import like the rest.
  With tracing on it also writes ``scopes/<label>.json``, the instruction →
  ``jax.named_scope`` table of each compiled program: device time gets its
  name (generate / decode / reward / update) by joining a profiler trace's op
  names to that table, not through ``--profile_epochs``.

Plus two PR-2 layers on top of that plumbing:

- ``es_health``  — ES-semantic diagnostics (reward spread, update geometry,
  cap engagement, antithetic pair asymmetry) computed *inside* the jitted ES
  step and logged under the ``es/`` prefix, with a host-side degeneracy
  watchdog. NOT re-exported here: it imports jax at module level, and this
  package must stay importable jax-free (bench.py's ladder parent imports
  ``obs.heartbeat``/``obs.metrics`` and must never pay — or trigger — a jax
  import; import ``hyperscalees_t2i_tpu.obs.es_health`` directly);
- ``multihost``  — process-identity helpers making every obs writer safe on
  multi-host pods (process-0-only shared files, per-process trace segments,
  ``process_index`` tags on span/heartbeat payloads).

And the ISSUE-14 analysis layer above the raw streams:

- ``podtrace`` — pod flight recorder: merge per-host trace segments on the
  exact ``epoch_anchor`` barrier events, straggler/barrier-wait analytics,
  ``pod/*`` gauges;
- ``anomaly``  — ES-health anomaly watchdog: rolling robust-z/changepoint
  detection over the es/* streams → ``anomalies.jsonl`` + ``anomaly/*``
  gauges + loud stderr ALERT/CLEAR + /healthz;
- ``regress``  — cross-run regression engine behind ``tools/sentry.py``
  (robust baselines over run dirs/ledgers/bench artifacts, breach verdicts).

And the ISSUE-17 device-time attribution layer:

- ``xplane`` — stdlib-only protobuf wire-format reader for the
  ``.xplane.pb`` captures ``jax.profiler`` writes: per-XLA-op and
  per-program *device* durations, Pallas-kernel engagement evidence, and
  the join from device time back onto the ``programs.jsonl`` ledger;
- ``calib``  — measured-vs-model reconciliation: roofline-predicted step
  times against xplane-measured (or host-wall fallback) ones →
  ``CALIB_*.json`` prediction-error artifacts, ``calib/*`` gauges.
"""

from .anomaly import AnomalyWatchdog, load_anomalies
from .heartbeat import (
    Heartbeat,
    device_memory_gauges,
    emit_heartbeat,
    maybe_heartbeat,
)
from .exporter import (
    MetricsExporter,
    maybe_exporter,
    note_anomaly,
    note_health,
    parse_prometheus_text,
    render_prometheus,
    reset_health,
)
from .podtrace import (
    discover_trace_segments,
    load_pod_events,
    pod_gauges,
    pod_summary,
    write_pod_summary,
)
from .metrics import (
    DEFAULT_BUCKETS,
    Histogram,
    MetricsRegistry,
    compile_cache_entries,
    get_registry,
    is_histogram_payload,
    record_device_memory,
    set_registry,
)
from .calib import (
    calib_gauges,
    calibrate_run,
    load_calib,
    predicted_step_time_s,
    reconcile,
    write_calib,
)
from .multihost import (
    exporter_port,
    is_primary,
    profile_segment_path,
    safe_process_index,
    set_process_index_override,
    trace_segment_path,
)
from .xla_cost import (
    CompileProvenance,
    ProgramLedger,
    get_ledger,
    load_programs,
    note_program_geometry,
    program_record,
    record_compile,
    roofline,
    set_ledger,
)
from .trace import (
    Tracer,
    block_if_tracing,
    get_tracer,
    load_events,
    record_startup,
    scope,
    set_span_observer,
    set_tracer,
    span,
    to_chrome,
)
from .xplane import (
    build_xspace,
    device_planes,
    find_xplane_files,
    join_ledger,
    kernel_evidence,
    load_xspace,
    op_durations,
    parse_xspace,
    program_durations,
)

__all__ = [
    "AnomalyWatchdog",
    "CompileProvenance",
    "DEFAULT_BUCKETS",
    "Heartbeat",
    "Histogram",
    "MetricsExporter",
    "MetricsRegistry",
    "ProgramLedger",
    "Tracer",
    "block_if_tracing",
    "build_xspace",
    "calib_gauges",
    "calibrate_run",
    "compile_cache_entries",
    "device_memory_gauges",
    "device_planes",
    "discover_trace_segments",
    "emit_heartbeat",
    "exporter_port",
    "find_xplane_files",
    "get_ledger",
    "get_registry",
    "get_tracer",
    "is_histogram_payload",
    "is_primary",
    "join_ledger",
    "kernel_evidence",
    "load_anomalies",
    "load_calib",
    "load_events",
    "load_pod_events",
    "load_programs",
    "load_xspace",
    "maybe_exporter",
    "maybe_heartbeat",
    "note_anomaly",
    "note_health",
    "note_program_geometry",
    "op_durations",
    "parse_prometheus_text",
    "parse_xspace",
    "pod_gauges",
    "pod_summary",
    "predicted_step_time_s",
    "profile_segment_path",
    "program_durations",
    "program_record",
    "reconcile",
    "record_compile",
    "record_device_memory",
    "record_startup",
    "render_prometheus",
    "reset_health",
    "roofline",
    "safe_process_index",
    "scope",
    "set_ledger",
    "set_process_index_override",
    "set_registry",
    "set_span_observer",
    "set_tracer",
    "span",
    "to_chrome",
    "trace_segment_path",
    "write_calib",
    "write_pod_summary",
]

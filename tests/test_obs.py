"""MECHANICAL observability: the obs/ plumbing itself.

Scope of this file vs ``tests/test_observability.py``: this file covers the
obs/ *subsystem mechanics* — span nesting/ordering in trace.jsonl, Chrome
export validity, watchdog firing on an artificial stall, heartbeat
stderr-only discipline, metrics registry merging, MetricsLogger hardening,
multihost writer gating (faked process_index), and trace_report aggregation
over a real 2-epoch training run. ``test_observability.py`` covers the
*reference-parity observability payloads* (histograms, member strips, MFU
fields, profiler traces — what the reference logged to W&B). ES-semantic
telemetry has its own file (``test_es_health.py``), the HTML report too
(``test_run_report.py``). All CPU-fast."""

import io
import json
import re
import time

import numpy as np
import pytest

from hyperscalees_t2i_tpu.obs import (
    Heartbeat,
    MetricsRegistry,
    Tracer,
    get_registry,
    set_tracer,
    span,
    to_chrome,
)
from hyperscalees_t2i_tpu.obs.trace import load_events
from hyperscalees_t2i_tpu.tools import trace_report


@pytest.fixture(autouse=True)
def _reset_obs_state():
    # counters are process-global by design; tests need a known zero
    get_registry().reset()
    set_tracer(None)
    yield
    set_tracer(None)


# ---------------------------------------------------------------------------
# trace.py
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering(tmp_path):
    tracer = Tracer(tmp_path / "trace.jsonl")
    with tracer.span("outer", epoch=0):
        with tracer.span("inner"):
            time.sleep(0.01)
        with tracer.span("inner"):
            pass
    events = load_events(tmp_path)
    # children complete (and are written) before their parent
    assert [e["name"] for e in events] == ["inner", "inner", "outer"]
    outer = events[-1]
    assert outer["depth"] == 0 and outer["parent"] is None
    assert outer["attrs"] == {"epoch": 0}
    for inner in events[:2]:
        assert inner["depth"] == 1 and inner["parent"] == "outer"
        # temporal containment within the parent
        assert inner["t0_s"] >= outer["t0_s"] - 1e-9
        assert inner["t0_s"] + inner["dur_s"] <= outer["t0_s"] + outer["dur_s"] + 1e-9
    # the two inner spans are disjoint and ordered
    a, b = events[0], events[1]
    assert a["t0_s"] + a["dur_s"] <= b["t0_s"] + 1e-9
    assert a["dur_s"] >= 0.009  # the slept span measured its sleep


def test_disabled_tracer_is_noop_and_decorator_resolves_late(tmp_path):
    calls = []

    def f(x):  # the module-level span resolves the tracer at call time
        with span("fn"):
            calls.append(x)
            return x * 2

    set_tracer(None)  # global tracer disabled: no file, no error
    assert f(3) == 6
    set_tracer(Tracer(tmp_path / "t.jsonl"))
    assert f(4) == 8  # defined before any tracer existed, traced now
    assert [e["name"] for e in load_events(tmp_path / "t.jsonl")] == ["fn"]
    assert calls == [3, 4]


def test_chrome_export_is_loadable_trace_event_json(tmp_path):
    tracer = Tracer(tmp_path / "trace.jsonl")
    with tracer.span("a"):
        with tracer.span("b"):
            pass
    doc = json.loads(json.dumps(to_chrome(load_events(tmp_path))))
    evs = doc["traceEvents"]
    assert len(evs) == 2
    assert all(e["ph"] == "X" for e in evs)
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in evs)
    # sorted by start time: parent "a" starts before (or with) child "b"
    assert evs[0]["name"] == "a" and evs[1]["name"] == "b"
    assert evs[1]["cat"] == "a"  # child's category = parent name


def test_tracer_threadsafe_nesting(tmp_path):
    import threading

    tracer = Tracer(tmp_path / "trace.jsonl")

    def work(i):
        with tracer.span(f"t{i}"):
            with tracer.span("leaf"):
                time.sleep(0.01)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    events = load_events(tmp_path)
    assert len(events) == 8
    leaves = [e for e in events if e["name"] == "leaf"]
    # each thread's stack is independent: every leaf nests under its own root
    assert {e["parent"] for e in leaves} == {f"t{i}" for i in range(4)}
    assert all(e["depth"] == 1 for e in leaves)


# ---------------------------------------------------------------------------
# heartbeat.py
# ---------------------------------------------------------------------------

def test_heartbeat_emits_to_stderr_never_stdout(capfd):
    with Heartbeat("bench", "compile", interval_s=0.05, gauges=None):
        time.sleep(0.18)
    out, err = capfd.readouterr()
    assert out == ""  # the whole satellite: zero heartbeat bytes on stdout
    lines = [json.loads(l) for l in err.splitlines() if l.startswith("{")]
    assert len(lines) >= 2
    assert all(l["hb"] == "bench" and l["phase"] == "compile" for l in lines)
    assert all(l["elapsed_s"] >= 0 for l in lines)


def test_watchdog_fires_within_one_interval():
    fired = []
    sink = io.StringIO()
    t0 = time.perf_counter()
    # interval is 60s — the watchdog must NOT wait for it
    with Heartbeat("train", "dispatch", interval_s=60.0, stall_cap_s=0.1,
                   on_stall=lambda n, p, e: fired.append((n, p, e)),
                   gauges=None, stream=sink):
        while not fired and time.perf_counter() - t0 < 5.0:
            time.sleep(0.02)
    assert fired, "watchdog never fired on an artificial stall"
    name, phase, elapsed = fired[0]
    assert (name, phase) == ("train", "dispatch")
    assert 0.1 <= elapsed < 5.0
    hb_lines = [json.loads(l) for l in sink.getvalue().splitlines()]
    assert any(l.get("stalled") for l in hb_lines)
    assert len(fired) == 1  # once, not every interval


def test_heartbeat_survives_broken_gauges_and_callback(capfd):
    def bad_gauges():
        raise RuntimeError("boom")

    with Heartbeat("x", "y", interval_s=0.05, stall_cap_s=0.05,
                   on_stall=lambda *a: 1 / 0, gauges=bad_gauges):
        time.sleep(0.15)
    out, err = capfd.readouterr()
    assert out == ""
    assert any(l.startswith("{") for l in err.splitlines())  # still beating


def test_bench_uses_shared_heartbeat():
    import bench

    from hyperscalees_t2i_tpu.obs.heartbeat import Heartbeat as shared

    assert not hasattr(bench, "_phase_heartbeat")  # private class deleted
    assert bench.Heartbeat is shared


# ---------------------------------------------------------------------------
# multihost.py: writer gating under a faked process_index
# ---------------------------------------------------------------------------

def test_multihost_trace_segmentation_and_tags(tmp_path):
    from hyperscalees_t2i_tpu.obs.multihost import (
        is_primary,
        safe_process_index,
        set_process_index_override,
        trace_segment_path,
    )

    try:
        # process 0: canonical file, primary writer
        set_process_index_override(0)
        assert safe_process_index() == 0 and is_primary()
        assert trace_segment_path(tmp_path) == tmp_path / "trace.jsonl"

        # process 2: own segment, NOT the primary writer — on a shared
        # run_dir this is exactly what stops pods clobbering one trace file
        set_process_index_override(2)
        assert safe_process_index() == 2 and not is_primary()
        seg = trace_segment_path(tmp_path)
        assert seg == tmp_path / "trace.2.jsonl"

        tracer = Tracer(seg)
        with tracer.span("epoch", epoch=0):
            pass
        events = load_events(seg)
        assert [e["process_index"] for e in events] == [2]
        # the meta line is tagged too
        first = json.loads(seg.read_text().splitlines()[0])
        assert first["meta"] == "trace_start" and first["process_index"] == 2
    finally:
        set_process_index_override(None)


def test_multihost_heartbeat_payload_tagged(capfd):
    from hyperscalees_t2i_tpu.obs.heartbeat import emit_heartbeat
    from hyperscalees_t2i_tpu.obs.multihost import set_process_index_override

    try:
        set_process_index_override(3)
        emit_heartbeat("train", "compile", elapsed_s=1.0)
    finally:
        set_process_index_override(None)
    out, err = capfd.readouterr()
    assert out == ""  # stderr-only contract unchanged
    line = json.loads([l for l in err.splitlines() if l.startswith("{")][-1])
    assert line["process_index"] == 3
    assert (line["hb"], line["phase"]) == ("train", "compile")


def test_safe_process_index_runtime_beats_env(monkeypatch):
    """An initialized jax runtime is the authoritative identity — env vars
    are only the pre-init fallback. Initialize the backend FIRST so the test
    is order-independent (run alone, no earlier test has touched jax)."""
    from hyperscalees_t2i_tpu.obs import multihost

    import jax

    jax.devices()  # force backend init before the env var is set
    monkeypatch.setattr(multihost, "_OVERRIDE", None)
    monkeypatch.setenv("JAX_PROCESS_ID", "5")
    assert multihost.jax_backend_initialized()
    assert multihost.safe_process_index() == jax.process_index() == 0


def test_safe_process_index_env_fallback_without_jax():
    """Before any jax import (bench.py's jax-free ladder parent), the
    launcher env var is the identity source. Needs a jax-free interpreter —
    the in-process backend is already up here, so probe via subprocess."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from hyperscalees_t2i_tpu.obs.multihost import safe_process_index, "
        "jax_backend_initialized\n"
        "assert 'jax' not in sys.modules  # obs must stay importable jax-free\n"
        "assert not jax_backend_initialized()\n"
        "assert safe_process_index() == 5\n"
        "print('ok')\n"
    )
    env = {**__import__("os").environ, "JAX_PROCESS_ID": "5"}
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=60, env=env,
    )
    assert out.returncode == 0, out.stderr[-1500:]
    assert "ok" in out.stdout


# ---------------------------------------------------------------------------
# metrics.py + MetricsLogger hardening
# ---------------------------------------------------------------------------

def test_set_registry_installs_fresh():
    from hyperscalees_t2i_tpu.obs import set_registry

    reg1 = get_registry()
    reg1.inc("x")
    reg2 = set_registry(None)
    assert reg2 is get_registry() and reg2 is not reg1
    assert reg2.snapshot() == {}  # a new run starts from zero


def test_metrics_registry_counters_and_gauges():
    reg = MetricsRegistry()
    reg.inc("dispatches")
    reg.inc("dispatches", 2)
    reg.gauge("compile_cache_entries", 7)
    reg.gauge_max("peak", 10)
    reg.gauge_max("peak", 5)  # lower value must not regress the high-water
    snap = reg.snapshot()
    assert snap == {"obs/dispatches": 3, "obs/compile_cache_entries": 7, "obs/peak": 10}
    reg.reset()
    assert reg.snapshot() == {}


def test_metrics_logger_survives_non_numeric_payload(tmp_path, capsys):
    from hyperscalees_t2i_tpu.train.logging import MetricsLogger

    logger = MetricsLogger(tmp_path / "run", use_wandb=False)
    payload = {
        "opt_score_mean": "nan-sentinel",      # console brief used :.4f → crashed
        "theta_norm": 1.25,
        "weird": object(),                      # json default=float → crashed
        "arr": np.arange(3),                    # float(ndarray) → crashed
        "prompts": ["a", "b"],
    }
    logger.log(0, payload)  # must not raise
    line = json.loads((tmp_path / "run" / "metrics.jsonl").read_text().splitlines()[0])
    assert line["opt_score_mean"] == "nan-sentinel"
    assert line["theta_norm"] == 1.25
    assert isinstance(line["weird"], str)
    assert line["prompts"] == ["a", "b"]
    out = capsys.readouterr().out
    assert "opt_score_mean=nan-sentinel" in out and "theta_norm=1.2500" in out


def test_metrics_logger_info_goes_to_stderr(tmp_path, capsys):
    from hyperscalees_t2i_tpu.train.logging import MetricsLogger

    MetricsLogger(tmp_path / "run", use_wandb=False).info("compiling")
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "[train] compiling" in captured.err


# ---------------------------------------------------------------------------
# end-to-end: traced training run + trace_report aggregation
# ---------------------------------------------------------------------------

def test_traced_training_run_and_trace_report(tmp_path, capsys):
    from hyperscalees_t2i_tpu.train import TrainConfig, run_training
    from tests.test_trainer import brightness_reward, tiny_backend

    backend = tiny_backend(tmp_path)
    tc = TrainConfig(
        num_epochs=2, pop_size=4, sigma=0.05, egg_rank=2, promptnorm=False,
        prompts_per_gen=2, member_batch=4, run_dir=str(tmp_path / "runs"),
        save_every=2, log_hist_every=0, seed=3, trace=True,
    )
    run_training(backend, brightness_reward, tc)
    run_dir = next((tmp_path / "runs").iterdir())
    events = load_events(run_dir)
    names = {e["name"] for e in events}
    # the span timeline covers the trainer's phases end to end
    assert {"setup", "epoch", "plan", "compile", "dispatch", "log",
            "checkpoint", "trace/pop_eval"} <= names
    assert sum(1 for e in events if e["name"] == "epoch") == 2
    assert sum(1 for e in events if e["name"] == "dispatch") == 2
    # pop_eval's trace-time span nests inside the compile phase
    pe = next(e for e in events if e["name"] == "trace/pop_eval")
    assert pe["depth"] >= 1 and pe["attrs"]["pop"] == 4

    # acceptance: spans cover ≥ 90% of measured wall clock
    assert trace_report.coverage(events) >= 0.90

    # operational counters landed in metrics.jsonl
    lines = [json.loads(l) for l in (run_dir / "metrics.jsonl").read_text().splitlines()]
    assert lines[-1]["obs/dispatches"] == 2
    assert lines[-1]["obs/compiles"] >= 1
    assert lines[-1]["obs/pop_eval_traces"] >= 1

    capsys.readouterr()  # drop training output
    # the CLI prints the per-phase table + coverage and writes a Chrome trace
    assert trace_report.main([str(run_dir), "--chrome"]) == 0
    out = capsys.readouterr().out
    assert "| phase | count | total s" in out
    assert "| dispatch |" in out and "| epoch |" in out
    cov = float(re.search(r"coverage: +([0-9.]+)% of wall clock", out).group(1))
    assert cov >= 90.0
    chrome = json.loads((run_dir / "trace_chrome.json").read_text())
    assert chrome["traceEvents"] and all(e["ph"] == "X" for e in chrome["traceEvents"])


def test_trainer_heartbeat_stderr_only(tmp_path, capfd):
    from hyperscalees_t2i_tpu.train import TrainConfig, run_training
    from tests.test_trainer import brightness_reward, tiny_backend

    backend = tiny_backend(tmp_path)
    tc = TrainConfig(
        num_epochs=1, pop_size=2, sigma=0.05, egg_rank=2, promptnorm=False,
        prompts_per_gen=1, member_batch=2, run_dir=str(tmp_path / "runs"),
        save_every=0, log_hist_every=0, seed=4,
        heartbeat_interval_s=0.05,
    )
    run_training(backend, brightness_reward, tc)
    out, err = capfd.readouterr()
    hb_out = [l for l in out.splitlines() if l.startswith('{"hb"')]
    hb_err = [l for l in err.splitlines() if l.startswith('{"hb"')]
    assert hb_out == []  # stdout stays clean even with heartbeats firing
    assert hb_err, "no heartbeat lines despite heartbeat_interval_s"
    assert all(json.loads(l)["hb"] == "train" for l in hb_err)

    # a second same-process run gets a FRESH registry: its counters must not
    # include the first run's dispatches/compiles
    import dataclasses

    tc2 = dataclasses.replace(tc, heartbeat_interval_s=0.0, run_name="second")
    run_training(tiny_backend(tmp_path), brightness_reward, tc2)
    line = json.loads(
        (tmp_path / "runs" / "second" / "metrics.jsonl").read_text().splitlines()[-1]
    )
    assert line["obs/dispatches"] == 1 and line["obs/epochs_dispatched"] == 1


def test_trace_report_aggregation_math(tmp_path, capsys):
    trace = tmp_path / "trace.jsonl"
    evs = [
        {"name": "epoch", "t0_s": 0.0, "dur_s": 4.0, "depth": 0, "parent": None},
        {"name": "dispatch", "t0_s": 0.5, "dur_s": 3.0, "depth": 1, "parent": "epoch"},
        {"name": "epoch", "t0_s": 4.0, "dur_s": 4.0, "depth": 0, "parent": None},
        {"name": "dispatch", "t0_s": 4.5, "dur_s": 1.0, "depth": 1, "parent": "epoch"},
    ]
    trace.write_text("\n".join(json.dumps(e) for e in evs) + "\n")
    events = load_events(trace)
    assert trace_report.wall_clock_s(events) == 8.0
    assert trace_report.coverage(events) == 1.0
    rows = {r["phase"]: r for r in trace_report.aggregate(events)}
    assert rows["epoch"]["count"] == 2 and rows["epoch"]["total_s"] == 8.0
    d = rows["dispatch"]
    assert d["count"] == 2 and d["total_s"] == 4.0 and d["mean_s"] == 2.0
    assert d["max_s"] == 3.0 and d["p95_s"] == 3.0
    assert d["pct_wall"] == 50.0
    # rows sorted by total descending
    assert [r["phase"] for r in trace_report.aggregate(events)] == ["epoch", "dispatch"]

    assert trace_report.main([str(trace)]) == 0
    assert "100.0% of wall clock" in capsys.readouterr().out
    # missing / empty inputs are errors, not crashes
    assert trace_report.main([str(tmp_path / "nope")]) == 1
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert trace_report.main([str(empty)]) == 1


def test_trace_report_uses_only_latest_session_on_resume(tmp_path, capsys):
    # a resumed run appends a second tracer session whose t0_s offsets
    # restart at ~0 — mixing the time bases would corrupt every figure
    trace = tmp_path / "trace.jsonl"
    lines = [
        {"meta": "trace_start", "wall_time": 1.0, "pid": 1},
        {"name": "epoch", "t0_s": 0.0, "dur_s": 100.0, "depth": 0},
        {"meta": "trace_start", "wall_time": 2.0, "pid": 2},
        {"name": "epoch", "t0_s": 0.0, "dur_s": 2.0, "depth": 0},
        {"name": "epoch", "t0_s": 2.0, "dur_s": 2.0, "depth": 0},
    ]
    trace.write_text("\n".join(json.dumps(e) for e in lines) + "\n")
    events = load_events(trace)
    assert [e["session"] for e in events] == [0, 1, 1]
    assert trace_report.main([str(trace)]) == 0
    out = capsys.readouterr().out
    assert "1 spans from 1 earlier trace session(s)" in out
    # wall clock reflects the 4s resumed session, not the 100s ghost overlap
    assert "wall clock: 4.000s" in out


def test_p95_nearest_rank():
    from hyperscalees_t2i_tpu.tools.trace_report import _p95

    # n a multiple of 20 is the rounding edge: nearest-rank p95 of 1..20 is
    # the 19th value, NOT the max
    assert _p95([float(i) for i in range(1, 21)]) == 19.0
    assert _p95([1.0]) == 1.0
    assert _p95([1.0, 2.0]) == 2.0
    assert _p95([float(i) for i in range(1, 101)]) == 95.0


def test_trace_report_coverage_with_gaps():
    events = [
        {"name": "a", "t0_s": 0.0, "dur_s": 1.0, "depth": 0},
        {"name": "b", "t0_s": 3.0, "dur_s": 1.0, "depth": 0},
        # nested span inside the gap must NOT count toward coverage
        {"name": "c", "t0_s": 1.0, "dur_s": 2.0, "depth": 1},
    ]
    assert trace_report.wall_clock_s(events) == 4.0
    assert trace_report.coverage(events) == pytest.approx(0.5)

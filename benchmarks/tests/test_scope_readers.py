"""The readers of the program's own names (PR 25) against values worked out by
hand: on a hand-written two-chip trace (the per-chip mean, an async collective
under a kernel, annotations with known distances) and on a trace recorded on
the v5e from a toy step with scopes (``fixtures/record_scoped_trace.py``)."""

import importlib
import json
import shutil
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from benchmarks import trace_reduce
from benchmarks.layer_metrics import _scopes
from benchmarks.record import Job, RunRecord

FIXTURES = Path(__file__).resolve().parent / "fixtures"
NEW_METRICS = (
    "generate_device_s", "decode_device_s", "reward_device_s", "es_update_device_s", "unscoped_device_s",
    "launch_latency_ms", "fetch_latency_ms",
    "setup_model_build_s", "setup_quantize_s", "setup_reward_build_s", "setup_lowering_s",
)
SCOPE_METRICS = NEW_METRICS[:5]


def read(name, rec):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read(rec)


def make_record(tmp_path, xplane: Path, table=None, trace_jsonl: str = "", flags=None, wall0: float = 1000.0):
    """A run directory as the program leaves it, around one trace file."""
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    prog = {"site": "train", "label": "es_step_m4r1", "lowering_s": 1.25, "compile_s": 2.0}
    if table is not None:
        (run_dir / "scopes").mkdir()
        (run_dir / "scopes" / "es_step_m4r1.json").write_text(json.dumps(table))
        prog["scope_table"] = "scopes/es_step_m4r1.json"
    (run_dir / "programs.jsonl").write_text(json.dumps(prog) + "\n")
    if trace_jsonl:
        (run_dir / "trace.jsonl").write_text(trace_jsonl)
    profile_dir = tmp_path / "profile"
    pb = profile_dir / "plugins" / "profile" / "t" / "t.xplane.pb"
    pb.parent.mkdir(parents=True)
    if xplane.suffix == ".textproto":
        pb.write_bytes(ProfileData.text_proto_to_serialized_xspace(xplane.read_text()))
    else:
        shutil.copy(xplane, pb)
    job = Job(cell={}, config={}, traffic={}, chips=2, seed=0, seconds=1.0, trace=True, rehearse=False,
              out_dir=tmp_path, bench_dir=tmp_path, peaks=None, t_process_start=0.0,
              clock_anchor=(wall0, 0.0))  # the harness's clock reads 0 at wall time `wall0`
    return RunRecord(job=job, run_dir=run_dir, profile_dir=profile_dir, flags=dict(flags or {}))


# ---------------------------------------------------------------- hand-written

TWO_CHIP_TABLE = {
    "while.1": "unattributed", "fusion.2": "~generate", "fused_qlora.3": "generate/dit_ffn",
    "all-reduce.4": "es_update/update", "fusion.5": "reward/score",  # copy.9 is not in the table
}  # `~`: an entry the program inferred from the graph; it counts under its scope all the same


@pytest.fixture
def two_chip(tmp_path):
    return make_record(tmp_path, FIXTURES / "two_chip_annotated.textproto", TWO_CHIP_TABLE)


def test_scopes_are_the_mean_over_the_chips_and_add_up_to_the_busy_time(two_chip):
    # a step on chip 0: generate 30 + 20, the collective's 10 us that no kernel covers, reward 10,
    # copy.9 10 = 80 us busy; on chip 1 fusion.2 takes 40: 90 us busy
    want = {"generate_device_s": (50 + 60) / 2, "decode_device_s": 0.0, "reward_device_s": 10.0,
            "es_update_device_s": 10.0, "unscoped_device_s": 10.0}
    got = {name: read(name, two_chip) for name in SCOPE_METRICS}
    assert got == pytest.approx({k: v * 1e-6 for k, v in want.items()})
    step_device_s = importlib.import_module("benchmarks.layer_metrics.step_device_s").read(two_chip)
    assert step_device_s == pytest.approx(85e-6)
    assert sum(got.values()) == pytest.approx(step_device_s)  # the sum rule


def test_notes_name_inner_scopes_largest_unscoped_ops_and_idle_by_scope(two_chip):
    for name in SCOPE_METRICS:
        read(name, two_chip)
    notes = "\n".join(two_chip.notes)
    assert "generate a step: (own) 0.0000 s, dit_ffn 0.0000 s" in notes  # 35 and 20 us at four decimals
    by = _scopes.seconds_by_scope(two_chip)
    assert by["generate"] == pytest.approx(35e-6) and by["generate/dit_ffn"] == pytest.approx(20e-6)
    # what the program's metadata names and what it inferred are told apart, every run
    entries = _scopes._seconds_by_entry(two_chip)
    assert entries["~generate"] == pytest.approx(35e-6) and "generate" not in entries
    assert ("scope a step, named by the program's metadata + inferred from the graph: "
            "generate 0.0000 + 0.0000 s, es_update 0.0000 + 0.0000 s, reward 0.0000 + 0.0000 s") in notes
    assert "unscoped a step, largest ops: copy.9" in notes and "while.1" not in notes  # a leaf only
    # chip 0 is the idlest: 10 us a step in front of fusion.5 (reward), 110 us until the next step starts
    assert "idle a step on chip 0 (4 gaps in 2 steps)" in notes
    assert "between_steps 0.0001 s, reward 0.0000 s" in notes


def test_latencies_are_read_between_annotations_and_module_edges_of_one_trace(two_chip):
    # enqueue starts 20, 15, 30 us before the first chip starts the step; matched in order
    assert _scopes.launch_latencies_ms(two_chip) == pytest.approx([0.020, 0.015, 0.030])
    assert read("launch_latency_ms", two_chip) == pytest.approx(0.020)
    # fetch ends 25, 10, 35 us after the last chip (chip 1) ends it
    assert _scopes.fetch_latencies_ms(two_chip) == pytest.approx([0.025, 0.010, 0.035])
    assert read("fetch_latency_ms", two_chip) == pytest.approx(0.025)
    assert any(n.startswith("launch latency of each traced step, ms: 0.020, 0.015, 0.030") for n in two_chip.notes)


def test_a_program_without_the_names_reads_nothing_and_raises_nothing(tmp_path):
    """The parent of PR 25: no table, no annotation, no build span."""
    rec = make_record(tmp_path, FIXTURES / "two_chip_steps.textproto", table=None,
                      trace_jsonl='{"meta": "trace_start", "wall_time": 1000.0}\n'
                                  '{"name": "compile", "t0_s": 5.0, "dur_s": 3.0, "depth": 1, "parent": "epoch"}\n')
    assert rec.trace is not None
    assert {name: read(name, rec) for name in NEW_METRICS} == dict.fromkeys(NEW_METRICS)
    assert rec.notes == []
    rec.profile_dir = None  # and an untraced or rehearsed run
    rec.__dict__.pop("trace", None)
    rec.__dict__.pop("_scopes_cache", None)
    assert {name: read(name, rec) for name in NEW_METRICS} == dict.fromkeys(NEW_METRICS)


BUILD_SPANS = [
    ("build_backend", None, 0.1, 0.4), ("init_params", "backend_setup", 0.6, 2.0),
    ("load_prompts", "backend_setup", 3.6, 0.25),
    ("backend_setup", None, 0.5, 3.5), ("quantize", None, 4.0, 1.5),
    ("clip_h", "build_reward", 5.6, 0.5), ("text_tables", "build_reward", 6.1, 2.0), ("build_reward", None, 5.5, 3.0),
    ("lower", "compile", 9.0, 1.25), ("compile", "epoch", 9.0, 3.5),
    ("lower", "other", 20.0, 7.0),  # not the step's: another parent
]


def trace_lines(spans):
    lines = [{"meta": "trace_start", "wall_time": 1000.0}]
    lines += [{"name": n, "parent": p, "t0_s": t0, "dur_s": d, "depth": 0 if p is None else 1} for n, p, t0, d in spans]
    return "".join(json.dumps(l) + "\n" for l in lines)


def test_build_spans_by_name(tmp_path):
    rec = make_record(tmp_path, FIXTURES / "two_chip_annotated.textproto", TWO_CHIP_TABLE,
                      trace_lines(BUILD_SPANS), flags={"--base_quant": "int8"})
    assert read("setup_model_build_s", rec) == pytest.approx(0.4 + 3.5)
    assert read("setup_quantize_s", rec) == pytest.approx(1.5)
    assert read("setup_reward_build_s", rec) == pytest.approx(3.0)
    assert read("setup_lowering_s", rec) == pytest.approx(1.25)  # = programs.jsonl's lowering_s
    assert rec.step_programs[0]["lowering_s"] == pytest.approx(read("setup_lowering_s", rec))
    notes = "\n".join(rec.notes)
    assert "build span init_params: 2.000 s" in notes and "build span build_reward/text_tables: 2.000 s" in notes
    assert "clip_b" not in notes  # no such span in this run: nothing is said


@pytest.mark.parametrize("base_quant, want", [("off", 0.0), ("int8", None)])
def test_no_quantize_span_reads_zero_only_without_an_int8_base(tmp_path, base_quant, want):
    spans = [s for s in BUILD_SPANS if s[0] != "quantize"]
    rec = make_record(tmp_path, FIXTURES / "two_chip_annotated.textproto", TWO_CHIP_TABLE,
                      trace_lines(spans), flags={"--base_quant": base_quant})
    assert read("setup_quantize_s", rec) == want


def test_clock_offset_note_compares_the_marks_with_the_epoch_annotations(tmp_path):
    # the tracer's clock started at wall time 1000 = harness time 0; the profiler's clock runs
    # 2.5 s behind the harness's: an epoch annotation at 79 us on the trace is a span at 2.500079 s
    spans = [("epoch", None, 2.5 + t * 1e-6, 1e-4) for t in (79, 285, 470)]
    rec = make_record(tmp_path, FIXTURES / "two_chip_annotated.textproto", TWO_CHIP_TABLE, trace_lines(spans))
    rec.mark_stamps = [(i, 2.5 + t * 1e-6 + 0.00002) for i, t in enumerate((248, 433, 658))]  # the marks: 20 us off
    assert rec.trace_clock_offset_s == pytest.approx(2.50002)
    _scopes.clock_offset_note(rec)
    assert "they differ by 0.020 ms" in rec.notes[-1], rec.notes


# ------------------------------------------------------- recorded on the v5e

RECORDED = FIXTURES / "scoped_toy_steps.xplane.pb"


@pytest.fixture
def recorded(tmp_path):
    table = json.loads((FIXTURES / "scoped_toy_steps.scopes.json").read_text())
    rec = make_record(tmp_path, RECORDED, table, (FIXTURES / "scoped_toy_steps.trace.jsonl").read_text())
    rec.job.chips = 1
    return rec


def raw_events(line_name):
    """The recorded trace's events of one device line, straight from the file."""
    profile = ProfileData.from_file(str(RECORDED))
    plane = next(p for p in profile.planes if p.name == "/device:TPU:0")
    line = next(l for l in plane.lines if l.name == line_name)
    return [(trace_reduce.own_name(e.name), float(e.start_ns), float(e.start_ns + e.duration_ns)) for e in line.events]


def raw_host(name):
    profile = ProfileData.from_file(str(RECORDED))
    return sorted((float(e.start_ns), float(e.start_ns + e.duration_ns)) for p in profile.planes
                  if p.name.startswith("/host:") for l in p.lines for e in l.events if e.name == name)


def test_recorded_scope_split_against_a_sum_over_the_files_own_events(recorded):
    table = _scopes.scope_table(recorded)
    assert table["toy_kernel.3"] == "generate" and "generate/dit_ffn" in table.values()
    assert table["copy.7"] == "~generate" and table["add.7"] == "~reward/score"  # inferred, and marked
    modules = [m for m in raw_events("XLA Modules") if m[0].startswith("jit_toy_step")]
    assert len(modules) == 4
    lo, hi = modules[0][1], modules[-1][1]  # three whole periods
    ops = [e for e in raw_events("XLA Ops") if e[2] > lo and e[1] < hi]
    # by hand: an op is a leaf when no other op lies inside it; its time goes to its table entry
    leaves = [e for e in ops if not any(o is not e and e[1] <= o[1] and o[2] <= e[2] for o in ops)]
    want = {}
    for name, start, end in leaves:
        top = table.get(name, "unattributed").lstrip("~").split("/")[0]
        want[top] = want.get(top, 0.0) + (min(end, hi) - max(start, lo)) * 1e-9 / 3
    got = {name: read(name, recorded) for name in SCOPE_METRICS}
    assert got["generate_device_s"] == pytest.approx(want["generate"], rel=1e-9)
    assert got["reward_device_s"] == pytest.approx(want["reward"], rel=1e-9)
    assert got["unscoped_device_s"] == pytest.approx(want["unattributed"], rel=1e-9)
    assert got["decode_device_s"] == 0.0 and got["es_update_device_s"] == 0.0
    assert got["generate_device_s"] > got["unscoped_device_s"] > 0 and got["reward_device_s"] > 0
    # the sum rule, against the reduction's own busy time
    assert sum(got.values()) == pytest.approx(recorded.trace.busy_s / recorded.trace.periods, rel=1e-9)
    # the kernel keeps its name under a scope, and its time is inside `generate`
    kernel_s, kernel_events = recorded.trace.matching("toy_kernel")
    assert kernel_events == 12 and 0 < kernel_s / 3 < got["generate_device_s"]
    notes = "\n".join(recorded.notes)
    assert "generate a step: " in notes and "dit_ffn" in notes and "reward a step: score" in notes


def test_recorded_launch_and_fetch_latency_from_the_annotations(recorded):
    modules = [m for m in raw_events("XLA Modules") if m[0].startswith("jit_toy_step")]
    enqueue, fetch = raw_host("enqueue"), raw_host("fetch")
    assert len(enqueue) == len(fetch) == len(modules) == 4
    # by hand: the k-th annotation belongs to the k-th execution
    launch = [(m[1] - q[0]) * 1e-6 for m, q in zip(modules, enqueue)]
    wait = [(f[1] - m[2]) * 1e-6 for m, f in zip(modules, fetch)]
    assert _scopes.launch_latencies_ms(recorded) == pytest.approx(launch, abs=1e-9)
    assert _scopes.fetch_latencies_ms(recorded) == pytest.approx(wait, abs=1e-9)
    assert read("launch_latency_ms", recorded) == pytest.approx(sum(sorted(launch)[1:3]) / 2, abs=1e-9)
    assert read("fetch_latency_ms", recorded) == pytest.approx(sum(sorted(wait)[1:3]) / 2, abs=1e-9)
    # what this recording shows of the instrument: the profiler puts the device 0.9-1.1 ms EARLY against
    # the host (a 12 us module "starts" a millisecond before it is enqueued), so each latency alone is
    # off by that much, and only their sum (enqueue start -> fetch end, less the module) is exact
    assert all(-1.2 < v < -0.8 for v in launch) and all(1.5 < v < 3.0 for v in wait)
    assert all(0.5 < a + b < 2.0 for a, b in zip(launch, wait))
    assert any("launch + fetch latency a step" in n for n in recorded.notes)
    # every span of the program's tracer is an event of the host plane, name for name
    spans = [json.loads(l) for l in (FIXTURES / "scoped_toy_steps.trace.jsonl").read_text().splitlines()][1:]
    assert sorted(s["name"] for s in spans) == ["enqueue"] * 4 + ["epoch"] * 4 + ["fetch"] * 4
    for name in ("epoch", "enqueue", "fetch"):
        durations = sorted(s["dur_s"] for s in spans if s["name"] == name)
        annotated = sorted((e - s) * 1e-9 for s, e in raw_host(name))
        assert annotated == pytest.approx(durations, abs=5e-5)  # the same spans on two clocks


def test_recorded_epoch_annotations_place_the_clocks_like_the_marks(recorded):
    # harness time = the tracer's own clock here (clock_anchor maps wall 1000.0 -> 0): move the
    # anchor to the tracer's start, then the marks' offset and the annotations' must agree
    start = json.loads((FIXTURES / "scoped_toy_steps.trace.jsonl").read_text().splitlines()[0])
    recorded.job.clock_anchor = (start["wall_time"], 0.0)
    epochs = sorted(recorded.spans_named("epoch"), key=lambda s: s["t0"])
    marks = recorded.trace.marks_ns
    assert len(marks) == len(epochs) == 4
    # the recorder marked right after each epoch span closed: stamp the marks from the spans' ends
    implied = sorted(s["t0"] - a[0] * 1e-9 for s, a in zip(epochs, raw_host("epoch")))[1]
    recorded.mark_stamps = [(i, ns * 1e-9 + implied) for i, ns in enumerate(marks)]
    _scopes.clock_offset_note(recorded)
    assert "by the program's 4 epoch annotations" in recorded.notes[-1]
    assert float(recorded.notes[-1].rsplit("differ by ", 1)[1].split(" ms")[0]) < 0.1

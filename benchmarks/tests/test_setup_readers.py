"""The nine readers of the set-up waterfall (ISSUE 35) on a hand-written
``trace.jsonl`` / ``programs.jsonl`` (``fixtures/setup_waterfall.*``: round
numbers, one gap at each of the three places a gap can lie), and their silence
on a program that lacks the spans and fields — the parent of ISSUE 35, whose
trace has ``build_*``, ``setup``, ``epoch`` -> ``compile`` -> ``lower`` and no more."""

import importlib
import json
import shutil
from pathlib import Path

import pytest

from benchmarks.record import Job, RunRecord

FIXTURES = Path(__file__).resolve().parent / "fixtures"
MANIFEST = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())
NEW = ("setup_startup_s", "setup_theta_init_s", "setup_jaxpr_trace_s", "setup_to_stablehlo_s",
       "setup_executable_s", "compile_cache_hits", "setup_record_s", "setup_warmup_s", "setup_unspanned_s")
# the spans ISSUE 35 adds: a program without them is its parent
ADDED = ("startup", "parse_args", "imports", "devices", "mesh", "trainer_init", "loop_init", "make_step",
         "jaxpr_trace", "to_stablehlo", "backend_compile", "record", "scope_table")


def read(name, rec):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read(rec)


def make_record(tmp_path, keep=lambda ev: True, programs=None, trace=True):
    """The fixture as a run directory. The harness's clock reads 0 at wall time 990, the tracer was
    made at wall time 1000: a span's ``t0_s`` is 10 s behind the harness's clock. The harness stamped
    its own start at 0.5 (0.7 s after the operating system's) and opened the window at 74.3."""
    run_dir = tmp_path / "run"
    run_dir.mkdir(parents=True)
    if trace:
        lines = (FIXTURES / "setup_waterfall.trace.jsonl").read_text().splitlines()
        (run_dir / "trace.jsonl").write_text("".join(l + "\n" for l in lines if keep(json.loads(l))))
    if programs is None:
        shutil.copy(FIXTURES / "setup_waterfall.programs.jsonl", run_dir / "programs.jsonl")
    else:
        (run_dir / "programs.jsonl").write_text("".join(json.dumps(p) + "\n" for p in programs))
    job = Job(cell={}, config={}, traffic={}, chips=1, seed=0, seconds=1.0, trace=True, rehearse=False,
              out_dir=tmp_path, bench_dir=tmp_path, peaks=None, t_process_start=0.5, clock_anchor=(990.0, 0.0))
    return RunRecord(job=job, run_dir=run_dir, t_entry=9.99, t_open=74.3, first_epoch=2)


@pytest.fixture
def rec(tmp_path):
    return make_record(tmp_path)


def test_the_manifest_lists_the_nine_for_every_cell():
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert [m["name"] for m in MANIFEST["per_layer"]][-9:] == list(NEW)  # appended, in the issue's order
    for name in NEW:
        assert entries[name]["moves"] == "setup_s" and "workloads" not in entries[name]


@pytest.mark.parametrize("name, want", [
    ("setup_startup_s", 10.19),      # the OS's stamp -> cli.main entered
    ("setup_theta_init_s", 4.5),     # the trainer's `setup`
    ("setup_jaxpr_trace_s", 20.0),
    ("setup_to_stablehlo_s", 9.5),
    ("setup_executable_s", 8.0),     # `backend_compile`
    ("compile_cache_hits", 1.0),
    ("setup_record_s", 1.5),
    # epoch 0 is 46 s with a 40 s compile in it; epoch 1 ran 4.3 s when the window opened inside it
    ("setup_warmup_s", 46.0 - 40.0 + 4.3),
    # 0.25 s between backend_setup and build_reward; 0.4 s between epoch 0's dispatch and log;
    # 0.28 s between epoch 1's log and the window's opening
    ("setup_unspanned_s", 0.25 + 0.4 + 0.28),
])
def test_readings_by_hand(rec, name, want):
    assert read(name, rec) == pytest.approx(want, abs=1e-6)


def test_the_depth_0_spans_and_the_gaps_add_up_to_setup_s(rec):
    top = [s for s in rec.spans if s["depth"] == 0 and s["t0"] < rec.t_open]
    under = sum(min(s["t1"], rec.t_open) - max(s["t0"], rec.t_process_start) for s in top)
    setup_s = rec.t_open - rec.t_process_start
    assert under + 0.25 == pytest.approx(setup_s)  # the one top-level gap
    assert read("setup_unspanned_s", rec) - (0.4 + 0.28) == pytest.approx(setup_s - under)


def test_notes_name_the_gaps_the_trace_time_spans_and_the_cache_s_verdict(rec):
    for name in NEW:
        read(name, rec)
    notes = "\n".join(rec.notes)
    assert "under no span: 0.400 s between dispatch and log" in notes
    assert "under no span: 0.280 s between log and window open" in notes
    assert "under no span: 0.250 s between backend_setup and build_reward" in notes
    assert notes.index("0.400 s between") < notes.index("0.280 s between") < notes.index("0.250 s between")
    assert ("trace-time spans: trace/pop_eval 18.000 s, trace/generate 12.000 s, trace/reward 4.000 s, "
            "trace/decode 2.000 s, trace/es_update 1.000 s, trace/es_noise 0.200 s") in notes
    assert "lower 30.000 s = jaxpr_trace 20.000 + to_stablehlo 9.500 + 0.500 s beside them" in notes
    assert "backend_compile 8.000 s: cache hit, cache_read_s 7.500, key jit_step-0123456789ab" in notes
    assert "record 1.500 s = 0.500 s every run pays + scope_table 1.000 s only a traced run pays" in notes
    assert "startup 10.190 s" in notes and "the harness's clock reads 9.490 s before train.cli" in notes


@pytest.mark.parametrize("caches, want", [(["miss"], 0.0), (["hit", "miss"], 0.5), ([], None)])
def test_cache_hits_is_a_share_of_the_step_programs_that_say(tmp_path, caches, want):
    programs = [{"site": "train", "label": f"es_step_m{i}r1", "lowering_s": 1.0, "compile_s": 2.0, "cache": c}
                for i, c in enumerate(caches)]
    programs.append({"site": "train", "label": "es_chain_m1r1x4", "cache": "hit"})  # not a step program
    programs.append({"site": "train", "label": "es_step_m9r1", "lowering_s": 1.0, "compile_s": 2.0})  # says nothing
    assert read("compile_cache_hits", make_record(tmp_path, programs=programs)) == want


def test_a_body_traced_twice_is_summed(tmp_path):
    lines = (FIXTURES / "setup_waterfall.trace.jsonl").read_text().splitlines()
    again = next(l for l in lines if '"trace/generate"' in l)
    rec = make_record(tmp_path)
    (rec.run_dir / "trace.jsonl").write_text("".join(l + "\n" for l in lines + [again]))
    read("setup_jaxpr_trace_s", rec)
    assert "trace/generate 24.000 s" in "\n".join(rec.notes)


@pytest.mark.parametrize("name", NEW)
def test_silent_on_the_parent_s_program_and_without_a_trace(tmp_path, name):
    old_program = [{"site": "train", "label": "es_step_m4r1", "lowering_s": 30.0, "compile_s": 8.0}]
    parent = make_record(tmp_path, keep=lambda ev: ev.get("name") not in ADDED
                         and not str(ev.get("name", "")).startswith("trace/"), programs=old_program)
    got = read(name, parent)
    if name == "setup_theta_init_s":
        assert got == pytest.approx(4.5)  # `setup` is PR 25's span: the parent has it
    elif name == "setup_warmup_s":
        assert got == pytest.approx(10.3)
    elif name == "setup_unspanned_s":
        # what the parent leaves unnamed: the 9.5 s before build_backend on the harness's clock, 0.25,
        # build_reward -> setup 0.25, and inside epoch 0 nothing new (compile is one span there too)
        assert got == pytest.approx(9.5 + 0.25 + 0.25 + 0.4 + 0.28)
    else:
        assert got is None
    untraced = make_record(tmp_path / "untraced", trace=False, programs=old_program)
    assert read(name, untraced) is None

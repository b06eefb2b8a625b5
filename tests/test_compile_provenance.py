"""The compile's provenance and the set-up spans around it (ISSUE 35).

- ``train.trainer.lower_and_compile``: the one lower-and-compile function of the
  trainer's three compile sites — ``cache`` hit / miss / off with the key jax
  logs, the key's eight parts, jax's own seconds, and nothing left installed;
- ``obs.scope``: the device scope's name as ``jax.named_scope`` gives it, a
  ``trace/<scope>`` host span only on an enabled tracer;
- ``startup``: back-dated from the operating system's stamp.

All on the CPU: names, fields and counts, never a time.
"""

import json
import logging
import time

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring as jax_monitoring
from jax.experimental.compilation_cache import compilation_cache

from hyperscalees_t2i_tpu.obs import (
    CompileProvenance,
    ProgramLedger,
    Tracer,
    get_tracer,
    scope,
    set_ledger,
    set_tracer,
)
from hyperscalees_t2i_tpu.obs.trace import load_events, process_start_monotonic, record_startup
from hyperscalees_t2i_tpu.obs.xla_cost import CACHE_KEY_PARTS, scope_table
from hyperscalees_t2i_tpu.train.trainer import lower_and_compile


@pytest.fixture(autouse=True)
def _no_tracer_no_ledger():
    set_tracer(None)
    set_ledger(None)
    yield
    set_tracer(None)
    set_ledger(None)


@pytest.fixture
def cache_dir(tmp_path):
    """A persistent compile cache of this test's own (conftest's is shared by
    every run on the machine, so what it holds is not this test's to say)."""
    was = (jax.config.jax_compilation_cache_dir, jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    compilation_cache.reset_cache()
    yield tmp_path / "cache"
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_enable_compilation_cache", was[1])
    compilation_cache.reset_cache()


def _listeners():
    return (jax_monitoring.get_event_listeners(), jax_monitoring.get_event_duration_listeners(),
            jax_monitoring.get_event_time_span_listeners(), jax_monitoring.get_scalar_listeners())


def _compile(fn, label="toy"):
    # a fresh jitted function each time: nothing of jax's in-memory caches answers for the disk's
    return lower_and_compile(jax.jit(lambda x: fn(x)), (jnp.ones((8, 8)),), label=label, geometry={"m": 1}).record


def toy(x):
    return jnp.sin(x) @ x


def changed(x):
    return jnp.cos(x) @ x + 1.0


@pytest.mark.parametrize("traced", [False, True])
def test_second_lowering_of_one_program_is_a_hit_under_the_first_one_s_key(cache_dir, tmp_path, traced):
    if traced:
        set_tracer(Tracer(tmp_path / "trace.jsonl"))
    ledger = set_ledger(ProgramLedger(tmp_path / "programs.jsonl"))
    first, second, other = _compile(toy), _compile(toy), _compile(changed)
    assert (first["cache"], second["cache"], other["cache"]) == ("miss", "hit", "miss")
    assert second["cache_key"] == first["cache_key"] != other["cache_key"]
    assert first["cache_read_s"] is None and second["cache_read_s"] >= 0.0
    for rec in (first, second, other):
        assert rec["lowering_s"] >= rec["jaxpr_trace_s"] + rec["to_stablehlo_s"] - 1e-3 > 0
        assert rec["compile_s"] >= rec["backend_compile_s"] - 1e-3 > 0
    written = [json.loads(l) for l in ledger.path.read_text().splitlines()]
    assert [w["cache"] for w in written] == ["miss", "hit", "miss"]
    assert all(("cache_key_parts" in w) == traced for w in written)
    if traced:
        get_tracer().close()
        spans = load_events(tmp_path / "trace.jsonl")
        assert [s["attrs"]["cache"] for s in spans if s["name"] == "backend_compile"] == ["miss", "hit", "miss"]
        assert [s["name"] for s in spans].count("record") == 3


def test_no_cache_directory_reads_off(cache_dir):
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    rec = _compile(toy)
    assert (rec["cache"], rec["cache_key"], rec["cache_read_s"]) == ("off", None, None)
    assert rec["backend_compile_s"] > 0 and rec["jaxpr_trace_s"] > 0  # jax's seconds need no cache


def test_cache_key_parts_are_jax_s_eight_and_two_lowerings_of_one_program_agree(cache_dir, tmp_path):
    set_tracer(Tracer(tmp_path / "trace.jsonl"))
    first, second, other = _compile(toy), _compile(toy), _compile(changed)
    assert tuple(first["cache_key_parts"]) == CACHE_KEY_PARTS and len(CACHE_KEY_PARTS) == 8
    assert first["cache_key_parts"] == second["cache_key_parts"]
    differing = [k for k in CACHE_KEY_PARTS if first["cache_key_parts"][k] != other["cache_key_parts"][k]]
    assert differing == ["computation"]  # another program moves that part and no other


@pytest.mark.parametrize("traced", [False, True])
def test_nothing_installed_for_a_compile_outlives_it(cache_dir, tmp_path, traced):
    if traced:
        set_tracer(Tracer(tmp_path / "trace.jsonl"))
    loggers = [logging.getLogger(n) for n in ("jax._src.compiler", "jax._src.cache_key")]
    before = (_listeners(), [(lg.level, list(lg.filters)) for lg in loggers])
    _compile(toy)
    assert (_listeners(), [(lg.level, list(lg.filters)) for lg in loggers]) == before
    with pytest.raises(TypeError):  # and a compile that raises leaves nothing behind either
        lower_and_compile(jax.jit(toy), ("not an array",), label="bad", geometry={})
    assert (_listeners(), [(lg.level, list(lg.filters)) for lg in loggers]) == before


def test_the_compiler_s_debug_lines_are_read_and_not_passed_on(cache_dir, caplog):
    with caplog.at_level(logging.WARNING):
        with CompileProvenance(key_parts=True) as prov:
            jax.jit(lambda x: toy(x)).lower(jnp.ones((8, 8))).compile()
    assert prov.cache_key and len(prov.key_parts) == 8
    assert not [r for r in caplog.records if r.name.startswith("jax._src.c") and r.levelno < logging.WARNING]


# ------------------------------------------------------------------ obs.scope

def _body(x):
    return jnp.tanh(x @ x).sum()


def _under(enter):
    def f(x):
        with enter("generate"):
            y = _body(x)
        with enter("es_update"):
            return y * 2.0
    return jax.jit(f).lower(jnp.ones((16, 16))).compile()


def test_scope_leaves_every_op_name_as_named_scope_gives_it(tmp_path):
    plain = _under(jax.named_scope)
    assert scope_table(_under(scope)) == scope_table(plain) != {}
    set_tracer(Tracer(tmp_path / "trace.jsonl"))  # and the tracer does not reach the program
    assert scope_table(_under(scope)) == scope_table(plain)


def test_scope_opens_a_host_span_only_on_an_enabled_tracer(tmp_path):
    _under(scope)  # no tracer: nothing to write to, nothing raised
    tracer = set_tracer(Tracer(tmp_path / "trace.jsonl"))
    with tracer.span("lower"):
        _under(scope)
    tracer.close()
    spans = load_events(tmp_path / "trace.jsonl")
    assert [s["name"] for s in spans] == ["trace/generate", "trace/es_update", "lower"]
    assert {s["parent"] for s in spans[:2]} == {"lower"}


# -------------------------------------------------------------------- startup

def test_process_start_is_the_operating_system_s_and_lies_before_every_stamp_of_ours():
    started = process_start_monotonic()
    assert started is not None and 0.0 < time.perf_counter() - started < 24 * 3600


def test_startup_is_written_back_dated_with_what_the_entry_point_found(tmp_path):
    tracer = set_tracer(Tracer(tmp_path / "trace.jsonl"))
    entered = time.perf_counter()
    record_startup(entered, backend_initialized=True)
    tracer.event("jaxpr_trace", entered, entered + 0.25, parent="lower", depth=3)
    with tracer.span("backend_compile") as attrs:  # what is known only at the end goes in at the end
        attrs.update(cache="hit")
    tracer.close()
    startup, nested, compiled = load_events(tmp_path / "trace.jsonl")
    assert startup["name"] == "startup" and startup["depth"] == 0 and startup["t0_s"] < 0
    assert startup["t0_s"] + startup["dur_s"] == pytest.approx(entered - tracer._mono0, abs=1e-5)
    assert startup["attrs"] == {"backend_initialized": True}
    assert (nested["depth"], nested["parent"], nested["dur_s"]) == (3, "lower", 0.25)
    assert compiled["attrs"] == {"cache": "hit"}


# ------------------------------------------------- the trainer's compile sites

def test_host_sharded_programs_each_get_their_own_seconds_and_spans(tmp_path):
    """``es_update_*`` was recorded with ``lowering_s = compile_s = 0`` while one pair of stamps
    covered both programs; through the one function each has its own."""
    from hyperscalees_t2i_tpu.train import TrainConfig, run_training
    from tests.test_trainer import brightness_reward, tiny_backend

    tc = TrainConfig(
        num_epochs=1, pop_size=4, sigma=0.05, egg_rank=2, promptnorm=False, prompts_per_gen=2,
        member_batch=4, run_dir=str(tmp_path / "runs"), save_every=0, log_hist_every=0, seed=3,
        trace=True, pop_host_shard="on",
    )
    run_training(tiny_backend(tmp_path), brightness_reward, tc)
    run_dir = next((tmp_path / "runs").iterdir())
    programs = {p["label"].split("_m")[0]: p for p in map(json.loads, (run_dir / "programs.jsonl").read_text().splitlines())}
    assert set(programs) == {"es_eval_slice", "es_update"}
    for p in programs.values():
        assert p["lowering_s"] > 0 and p["compile_s"] > 0 and p["cache"] in ("hit", "miss")
    spans = load_events(run_dir)
    inside = [s["name"] for s in spans if s["parent"] == "compile"]
    assert inside == ["make_step", "lower", "backend_compile", "record", "lower", "backend_compile", "record"]
    assert [s["name"] for s in spans if s["depth"] == 0 and s["name"] != "epoch_anchor"] == [
        "trainer_init", "setup", "loop_init", "epoch"]

"""Names for the work, from inside the program (ISSUE 25).

- device time: ``jax.named_scope`` at the step's seams, carried to a profiler
  trace by ``obs/xla_cost.scope_table`` (instruction name -> scope);
- the build under spans from ``train.cli.main``, one tracer adopted by
  ``run_training``; ``compile`` -> ``lower``; ``dispatch`` ->
  ``enqueue``/``fetch``; spans as ``TraceAnnotation`` events of a profiler
  session; buffered writes;
- ``es/member_reward``: the per-member raw reward row of ``metrics.jsonl``.

All on the CPU: what is checked is names, files and counts, never a time.
"""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperscalees_t2i_tpu.obs import Tracer, get_registry, get_tracer, set_tracer
from hyperscalees_t2i_tpu.obs.trace import block_if_tracing, load_events, span
from hyperscalees_t2i_tpu.obs.xla_cost import (
    INFERRED,
    INNER_SCOPES,
    TOP_SCOPES,
    UNATTRIBUTED,
    scope_of,
    scope_table,
)


@pytest.fixture(autouse=True)
def _reset_obs_state():
    get_registry().reset()
    set_tracer(None)
    yield
    set_tracer(None)


# ---------------------------------------------------------------------------
# (a) the table on a toy program
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op_name, want", [
    ("jit(f)/while/body/closed_call/generate/dit_ffn/mul", "generate/dit_ffn"),
    ("jit(core)/while/body/closed_call/vmap(generate)/dit_ffn/dot_general", "generate/dit_ffn"),
    ("reward/reduce_sum", "reward"),  # only the tail of the path survived
    ("jit(core)/vmap(generate)/scale3/blocks/while/body/closed_call/fused_qlora", "generate/scale3/blocks"),
    ("jit(core)/vmap(decode)/checkpoint/stage2/conv_general_dilated", "decode/stage2"),
    ("jit(core)/reward/preprocess/jit(_resize)/scale/mul", "reward/preprocess"),  # `scale` needs its index
    ("jit(core)/es_update/update/es_update/health/sub", "es_update/health"),  # the innermost top scope wins
    ("jit(core)/while/body/add", UNATTRIBUTED),
    ("jit(core)/blocks/mul", UNATTRIBUTED),  # an inner name counts only under a top-level one
])
def test_scope_of_finds_the_innermost_vocabulary_path(op_name, want):
    assert scope_of(op_name) == want


def _toy_compiled():
    def member(x, k):
        with jax.named_scope("generate"):
            with jax.named_scope("dit_ffn"):
                y = jnp.tanh(x * k) @ x.T

            def body(i, c):
                with jax.named_scope("dit_self_attn"):
                    return jnp.sin(c) * 2.0 + i

            y = jax.lax.fori_loop(0, 3, body, y)
        with jax.named_scope("reward"), jax.named_scope("score"):
            return jnp.exp(y).sum()

    def f(x, ks):
        r = jax.lax.map(lambda k: member(x, k), ks, batch_size=2)
        with jax.named_scope("es_update"):
            return (r - r.mean()) / (r.std() + 1e-6)

    return jax.jit(f).lower(jnp.ones((16, 16)), jnp.arange(4.0)).compile()


def test_scope_table_maps_instructions_fusions_and_unscoped_ops():
    compiled = _toy_compiled()
    text = compiled.as_text()
    table = scope_table(compiled)
    assert {s.lstrip(INFERRED) for s in table.values()} <= {
        UNATTRIBUTED, "generate", "generate/dit_ffn", "generate/dit_self_attn",
        "reward/score", "es_update",
    }
    # every scope the program entered reached the optimized module
    assert {"generate/dit_ffn", "generate/dit_self_attn", "reward/score", "es_update"} <= set(table.values())
    # an instruction whose metadata names a scope has exactly that entry
    checked = 0
    for line in text.splitlines():
        m = re.match(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s", line)
        if m is None or m.group(1) not in table:
            continue
        meta = re.search(r'op_name="([^"]*)"', line)
        if meta and scope_of(meta.group(1)) != UNATTRIBUTED:
            assert table[m.group(1)] == scope_of(meta.group(1)), line
            checked += 1
    assert checked >= 10
    # a fusion takes its root's scope, and its interior is not listed
    fusions = {m.group(1): m.group(2) for m in re.finditer(
        r"%?([\w.\-]+) = [^\n]*?\bfusion\([^\n]*?calls=%?([\w.\-]+)", text)}
    assert fusions
    scoped_fusions = [n for n in fusions if table[n] not in (UNATTRIBUTED,) and not table[n].startswith(INFERRED)]
    assert scoped_fusions, "no fusion carried a scope"
    for name, callee in fusions.items():
        body = text.split(f"%{callee} ", 1)[1].split("\n}\n", 1)[0]
        root = re.search(r'ROOT [^\n]*?op_name="([^"]*)"', body)
        if root and scope_of(root.group(1)) != UNATTRIBUTED:
            assert table[name] == scope_of(root.group(1)), name
        interior = re.findall(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s", body, re.M)
        assert not set(interior) & set(table)
    # the member loop and its counter are nobody's: no scope names them, nothing scoped consumes them
    whiles = [n for n in table if n.startswith("while")]
    assert any(table[n] == UNATTRIBUTED for n in whiles)
    assert any(table[n] == "generate" for n in whiles)  # the fori_loop inside `generate`


HLO_WITH_COMPILER_MADE_OPS = """HloModule jit_f

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %inner = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/while/body/generate/dit_ffn/add"}
}

%body (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[4]{0} get-tuple-element(%arg), index=1
  %copy.1 = f32[4]{0:T(8,128)(2,1)} copy(%x)
  %fusion.1 = f32[4]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/while/body/generate/dit_ffn/add"}
  %copy-start.2 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%fusion.1)
  %copy-done.2 = f32[4]{0} copy-done(%copy-start.2)
  %one = s32[] constant(1)
  %next = s32[] add(%i, %one), metadata={op_name="jit(f)/while/body/add"}
  ROOT %tuple.3 = (s32[], f32[4]{0}) tuple(%next, %copy-done.2)
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %shared = f32[4]{0:T(8,128)} copy(%a)
  %u1 = f32[4]{0} tanh(%shared), metadata={op_name="jit(f)/generate/dit_ffn/tanh"}
  %u2 = f32[4]{0} sine(%shared), metadata={op_name="jit(f)/generate/dit_self_attn/sin"}
  %mixed = f32[4]{0:T(8,128)} copy(%a)
  %u3 = f32[4]{0} exponential(%mixed), metadata={op_name="jit(f)/reward/score/exp"}
  %u4 = f32[4]{0} negate(%mixed), metadata={op_name="jit(f)/generate/neg"}
  %rw = f32[4]{0} reduce-window(%u3, %u3), window={size=4}, to_apply=%fused_computation, metadata={op_name="reduce_window_sum"}
  ROOT %u5 = f32[4]{0} add(%rw, %u1), metadata={op_name="jit(f)/reward/score/add"}
}
"""


def test_scope_table_gives_compiler_made_ops_the_scope_they_serve():
    class Compiled:
        def as_text(self):
            return HLO_WITH_COMPILER_MADE_OPS

    table = scope_table(Compiled())
    assert "inner" not in table and "p" not in table  # a fusion's interior
    assert table["fusion.1"] == "generate/dit_ffn"      # the program's word: no mark
    # a guess from the graph is marked, so a reader can tell the two apart
    assert table["copy.1"] == "~generate/dit_ffn"       # no metadata: its one consumer's scope
    assert table["copy-done.2"] == "~generate/dit_ffn"  # nothing scoped consumes it: its operands'
    assert table["shared"] == "~generate"               # consumers in two inner scopes: what they share
    assert table["mixed"] == UNATTRIBUTED               # consumers share nothing, the operand is a parameter
    assert table["rw"] == "~reward/score"               # only the tail of the path survived the rewrite
    assert INFERRED == "~" and sum(v.startswith(INFERRED) for v in table.values()) == 5
    assert table["next"] == UNATTRIBUTED and table["one"] == UNATTRIBUTED  # loop control
    assert table["a"] == UNATTRIBUTED and table["arg"] == UNATTRIBUTED


# ---------------------------------------------------------------------------
# (b) every scope of the vocabulary is in the compiled step, per family
# ---------------------------------------------------------------------------

def _tiny_clip_reward(backend):
    from hyperscalees_t2i_tpu.models import clip as clip_mod
    from hyperscalees_t2i_tpu.rewards.suite import (
        clip_text_embed_table,
        make_clip_reward_fn,
        pickscore_text_embeds,
    )

    ccfg = clip_mod.CLIPConfig(
        vision=clip_mod.CLIPTowerConfig(16, 2, 2, 32),
        text=clip_mod.CLIPTowerConfig(16, 2, 2, 32),
        image_size=32, patch_size=16, vocab_size=64, max_positions=8,
        projection_dim=16,
    )
    n = len(backend.texts)
    ids = jnp.ones((n + 2, 8), jnp.int32)
    eot = jnp.full((n + 2,), 7, jnp.int32)
    mask = jnp.ones((n + 2, 8), bool)
    cparams = clip_mod.init_clip(jax.random.PRNGKey(11), ccfg)
    pparams = clip_mod.init_clip(jax.random.PRNGKey(12), ccfg)
    return make_clip_reward_fn(
        cparams, ccfg, clip_text_embed_table(cparams, ccfg, ids, eot, mask),
        pick_params=pparams, pick_cfg=ccfg,
        pick_text_embeds=pickscore_text_embeds(pparams, ccfg, ids[:n], eot[:n], mask[:n]),
    )


SANA_SCOPES = {
    "es_noise", "es_noise/perturb",
    "generate/dit_embed_out", "generate/dit_self_attn", "generate/dit_cross_attn", "generate/dit_ffn",
    "decode", "decode/stage0", "decode/stage1",
    "reward/preprocess", "reward/clip_b", "reward/clip_h", "reward/score",
    "es_update/fitness", "es_update/update", "es_update/health",
}
VAR_SCOPES = (
    {"es_noise", "es_noise/perturb", "generate", "decode",
     "reward/preprocess", "reward/clip_b", "reward/clip_h", "reward/score",
     "es_update/fitness", "es_update/update", "es_update/health"}
    | {f"generate/scale{k}/{inner}" for k in range(2)
       for inner in ("blocks", "head", "sample", "msvq_accumulate")}
)


LM_SCOPES = (
    {"es_noise", "es_noise/perturb", "decode",
     "reward/preprocess", "reward/clip_b", "reward/clip_h", "reward/score",
     "es_update/fitness", "es_update/update", "es_update/health"}
    | {f"generate/{phase}/{inner}" for phase in ("lm_prefill", "lm_decode_step")
       for inner in ("lm_mla", "lm_mla/attend", "lm_dense_ffn", "lm_moe/router", "lm_moe/experts", "lm_moe/shared")}
    | {"generate/lm_decode_step/lm_head", "generate/lm_decode_step/sample"}
)


LM_HYBRID_SCOPES = (
    (LM_SCOPES - {s for s in LM_SCOPES if "lm_mla" in s or "lm_dense_ffn" in s})
    | {f"generate/{phase}/{inner}" for phase in ("lm_prefill", "lm_decode_step")
       for inner in ("lm_gdn", "lm_gdn/conv", "lm_gdn/delta_rule", "lm_gdn/gdn_out", "lm_attn")}
    | {"generate/lm_decode_step/lm_attn/attend"}  # the toy is one period: its one attention layer's prefill stops at K and V
)


LM_MHC_SCOPES = LM_SCOPES | {f"generate/{phase}/lm_hc/{inner}" for phase in ("lm_prefill", "lm_decode_step")
                             for inner in ("hc_coeff", "hc_sinkhorn", "hc_mix")}


@pytest.mark.parametrize("family, want", [("sana_one_step", SANA_SCOPES), ("var", VAR_SCOPES),
                                          ("lm_ar", LM_SCOPES), ("lm_ar:qwen3_next", LM_HYBRID_SCOPES),
                                          ("lm_ar:xing4_0", LM_MHC_SCOPES)])
def test_compiled_step_carries_every_scope(family, want, tmp_path):
    """The guard against a refactor of the member loop silently dropping a
    scope: the tiny step of each family, compiled, names every top-level
    scope and every inner scope of its row in the vocabulary."""
    from hyperscalees_t2i_tpu.backends.base import make_frozen
    from hyperscalees_t2i_tpu.train import TrainConfig
    from hyperscalees_t2i_tpu.train.cli import build_backend, build_parser
    from hyperscalees_t2i_tpu.train.trainer import make_es_step

    prompts = tmp_path / "p.txt"
    prompts.write_text("a red square\na blue circle\n")
    family, _, model_type = family.partition(":")
    argv = ["--backend", family, "--model_scale", "tiny", "--prompts_txt", str(prompts),
            "--lora_r", "2", "--lora_alpha", "4"]
    if family == "lm_ar":
        if model_type == "xing4_0":
            from tests.test_lm_mhc import TOY
        elif model_type:
            from tests.test_lm_hybrid import TOY
        else:
            from tests.test_lm import TOY

        (tmp_path / "config.json").write_text(json.dumps(TOY))
        argv += ["--lm_config", str(tmp_path / "config.json")]
    args = build_parser().parse_args(argv)
    backend = build_backend(args)
    backend.setup()
    reward_fn = _tiny_clip_reward(backend)
    tc = TrainConfig(pop_size=4, sigma=0.05, egg_rank=2, prompts_per_gen=2, member_batch=2,
                     promptnorm=True, run_dir=str(tmp_path / "runs"))
    step = make_es_step(backend, reward_fn, tc, 2, 1, None, stateful_delta=True)
    theta = backend.init_theta(jax.random.PRNGKey(0))
    zeros = jax.tree_util.tree_map(jnp.zeros_like, theta)
    compiled = step.lower(
        make_frozen(backend, reward_fn), theta, zeros, jnp.zeros((2,), jnp.int32), jax.random.PRNGKey(1)
    ).compile()
    table = scope_table(compiled)
    # every scope is in the table by the program's own metadata, not by inference
    found = {s for s in table.values() if not s.startswith(INFERRED)}
    assert want <= found, sorted(want - found)
    if family == "lm_ar":  # the head on the image-id columns is still the decode step's own scope, with ops under it
        assert sum(s == "generate/lm_decode_step/lm_head" for s in table.values()) >= 2
    # the inference reads this jax's as_text(): operands printed as %names
    guessed = {s.lstrip(INFERRED) for s in table.values() if s.startswith(INFERRED)}
    assert guessed and {s.split("/")[0] for s in guessed} <= set(TOP_SCOPES), guessed
    assert {s.split("/")[0] for s in found - {UNATTRIBUTED}} == set(TOP_SCOPES)
    # nothing outside the vocabulary can be in a table
    for s in found - {UNATTRIBUTED}:
        top, *inner = s.split("/")
        assert top in TOP_SCOPES
        assert all(re.sub(r"^(scale|stage)\d+$", "", i) in INNER_SCOPES + ("",) for i in inner), s


# ---------------------------------------------------------------------------
# (c) train.cli with and without --trace
# ---------------------------------------------------------------------------

def _cli_run(out, trace: bool, monkeypatch):
    from hyperscalees_t2i_tpu.train import cli

    monkeypatch.setenv("HSES_BASE_QUANT_MIN_SIZE", "1")  # toy kernels still go int8
    cli.main([
        "--backend", "sana_one_step", "--model_scale", "tiny",
        "--base_quant", "int8",
        "--pop_size", "4", "--prompts_per_gen", "2", "--member_batch", "2",
        "--num_epochs", "2", "--allow_random_rewards", "true",
        "--run_dir", str(out), "--run_name", "run", "--resume", "false",
        "--save_every", "0", "--trace", "true" if trace else "false",
    ])
    return out / "run"


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    try:
        run = _cli_run(tmp_path_factory.mktemp("traced"), True, mp)
    finally:
        mp.undo()
    lines = [json.loads(l) for l in (run / "trace.jsonl").read_text().splitlines()]
    return run, lines


def test_traced_cli_run_has_one_adopted_tracer(traced_run):
    run, lines = traced_run
    starts = [l for l in lines if l.get("meta") == "trace_start"]
    assert len(starts) == 1 and lines[0] is starts[0]  # adopted, not replaced
    assert not get_tracer().enabled  # and uninstalled when main returned
    # the build's spans precede the trainer's on the one clock of the one tracer
    spans = [l for l in lines if "name" in l]
    t0 = {s["name"]: s["t0_s"] for s in reversed(spans)}  # first occurrence
    order = ["build_backend", "backend_setup", "quantize", "build_reward", "setup", "compile", "dispatch"]
    assert [t0[n] for n in order] == sorted(t0[n] for n in order)


@pytest.mark.parametrize("name, parent", [
    ("build_backend", None), ("backend_setup", None), ("quantize", None), ("build_reward", None),
    ("init_params", "backend_setup"), ("load_prompts", "backend_setup"),
    ("text_tables", "build_reward"),
    ("compile", "epoch"), ("lower", "compile"),
    ("dispatch", "epoch"), ("enqueue", "dispatch"), ("fetch", "dispatch"),
    # ISSUE 35: the set-up waterfall
    ("startup", None), ("parse_args", None), ("imports", None), ("devices", None), ("mesh", None),
    ("trainer_init", None), ("setup", None), ("loop_init", None),
    ("make_step", "compile"), ("backend_compile", "compile"), ("record", "compile"),
    ("jaxpr_trace", "lower"), ("to_stablehlo", "lower"), ("scope_table", "record"),
])
def test_traced_cli_run_span_parents(traced_run, name, parent):
    _, lines = traced_run
    found = [l for l in lines if l.get("name") == name]
    assert found, f"no span {name}"
    assert {l["parent"] for l in found} == {parent}
    if name in ("enqueue", "fetch", "dispatch"):
        assert len(found) == 2  # one an epoch
    if parent in ("compile", "lower", "record") or name == "compile" or parent is None and name != "epoch":
        assert len(found) == 1


def test_traced_cli_run_children_lie_inside_their_parents(traced_run):
    _, lines = traced_run
    spans = [l for l in lines if "name" in l]
    for parent, children in (("compile", ("make_step", "lower", "backend_compile", "record")),
                             ("lower", ("jaxpr_trace", "to_stablehlo")), ("dispatch", ("enqueue", "fetch"))):
        for p in (s for s in spans if s["name"] == parent):
            inside = [s for s in spans if s["name"] in children
                      and p["t0_s"] - 1e-6 <= s["t0_s"] and s["t0_s"] + s["dur_s"] <= p["t0_s"] + p["dur_s"] + 1e-6]
            assert sorted(s["name"] for s in inside) == sorted(children)
            assert sum(s["dur_s"] for s in inside) <= p["dur_s"] + 1e-6


@pytest.mark.parametrize("name, why", [
    ("xla_compile", "backend_compile is that span, and compile_s of programs.jsonl its seconds"),
    ("init_decoder", "params and decoder are seeded by one program, under init_params"),
])
def test_traced_cli_run_has_no_span_that_nothing_reads(traced_run, name, why):
    _, lines = traced_run
    assert name not in {l.get("name") for l in lines}, why


def test_traced_cli_run_writes_the_scope_table_programs_jsonl_names(traced_run):
    run, _ = traced_run
    programs = [json.loads(l) for l in (run / "programs.jsonl").read_text().splitlines()]
    steps = [p for p in programs if p["label"].startswith("es_step_")]
    assert len(steps) == 1
    assert steps[0]["scope_table"] == f"scopes/{steps[0]['label']}.json"
    table = json.loads((run / steps[0]["scope_table"]).read_text())
    assert {s.lstrip(INFERRED).split("/")[0] for s in table.values()} == set(TOP_SCOPES) | {UNATTRIBUTED}
    assert abs(steps[0]["lowering_s"] - next(
        json.loads(l)["dur_s"] for l in (run / "trace.jsonl").read_text().splitlines()
        if json.loads(l).get("name") == "lower")) < 0.05


def test_traced_cli_run_is_under_spans_from_process_start_to_the_end_of_the_second_epoch(traced_run):
    """The waterfall has no gap: from where ``startup`` begins (the operating system's stamp of the
    process's start) to the end of epoch 1, under 50 ms in all lie under no depth-0 or depth-1 span."""
    _, lines = traced_run
    spans = [l for l in lines if "name" in l]
    lo = next(s["t0_s"] for s in spans if s["name"] == "startup")
    assert lo < 0 < lo + next(s["dur_s"] for s in spans if s["name"] == "startup") + 0.1  # back-dated
    hi = max(s["t0_s"] + s["dur_s"] for s in spans if s["name"] == "epoch")
    assert sum(1 for s in spans if s["name"] == "epoch") == 2
    covered, at = 0.0, lo
    for s in sorted((s for s in spans if s["depth"] <= 1), key=lambda s: s["t0_s"]):
        t0, t1 = max(s["t0_s"], at), min(s["t0_s"] + s["dur_s"], hi)
        if t1 > t0:
            covered, at = covered + t1 - t0, t1
    assert (hi - lo) - covered < 0.05, f"{(hi - lo) - covered:.3f} s of {hi - lo:.1f} s under no span"
    # trace_report's coverage, the operator's form of the same figure, starts at startup too
    from hyperscalees_t2i_tpu.tools import trace_report

    assert trace_report.coverage(load_events(traced_run[0])) > 0.99


def test_traced_cli_run_times_the_tracing_of_every_top_scope(traced_run):
    _, lines = traced_run
    spans = [l for l in lines if "name" in l]
    lower = next(s for s in spans if s["name"] == "lower")
    for top in TOP_SCOPES:  # each a host span beside trace/pop_eval, inside lower
        found = [s for s in spans if s["name"] == f"trace/{top}"]
        assert found, top
        assert all(lower["t0_s"] <= s["t0_s"] and s["t0_s"] + s["dur_s"] <= lower["t0_s"] + lower["dur_s"] + 1e-6
                   for s in found)
    halves = sum(s["dur_s"] for s in spans if s["name"] in ("jaxpr_trace", "to_stablehlo"))
    assert 0.5 * lower["dur_s"] < halves <= lower["dur_s"] + 1e-3


PROVENANCE = ("cache", "cache_key", "cache_read_s", "backend_compile_s", "jaxpr_trace_s", "to_stablehlo_s")


def test_traced_cli_run_records_the_compile_s_provenance_and_the_key_s_parts(traced_run):
    from hyperscalees_t2i_tpu.obs.xla_cost import CACHE_KEY_PARTS

    run, lines = traced_run
    step = next(p for p in map(json.loads, (run / "programs.jsonl").read_text().splitlines())
                if p["label"].startswith("es_step_"))
    assert set(PROVENANCE) <= set(step) and step["cache"] in ("hit", "miss")  # conftest sets a cache directory
    assert step["cache_key"].startswith("jit_") and len(step["cache_key"].rsplit("-", 1)[1]) == 64
    assert (step["cache_read_s"] is not None) == (step["cache"] == "hit")
    assert tuple(step["cache_key_parts"]) == CACHE_KEY_PARTS
    assert all(re.fullmatch(r"[0-9a-f]{64}", h) for h in step["cache_key_parts"].values())
    span_attrs = next(l["attrs"] for l in lines if l.get("name") == "backend_compile")
    assert (span_attrs["cache"], span_attrs["cache_key"]) == (step["cache"], step["cache_key"])
    span_s = {l["name"]: l["dur_s"] for l in lines if l.get("name") in ("jaxpr_trace", "to_stablehlo", "backend_compile")}
    assert span_s["jaxpr_trace"] == pytest.approx(step["jaxpr_trace_s"], abs=1e-3)
    assert span_s["to_stablehlo"] == pytest.approx(step["to_stablehlo_s"], abs=1e-3)
    assert span_s["backend_compile"] == pytest.approx(step["compile_s"], abs=0.05)
    assert step["backend_compile_s"] <= step["compile_s"] + 1e-3


def test_untraced_cli_run_leaves_no_trace_and_no_table(tmp_path, monkeypatch):
    entered = []
    real = jax.profiler.TraceAnnotation

    class Spy(real):
        def __init__(self, name, **kw):
            entered.append(name)
            super().__init__(name, **kw)

    syncs = []
    real_block = jax.block_until_ready
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    monkeypatch.setattr(jax, "block_until_ready", lambda t: (syncs.append(1), real_block(t))[1])
    run = _cli_run(tmp_path, False, monkeypatch)
    assert (run / "metrics.jsonl").exists() and (run / "programs.jsonl").exists()
    assert not (run / "trace.jsonl").exists() and not (run / "scopes").exists()
    assert "scope_table" not in (run / "programs.jsonl").read_text()
    step = next(p for p in map(json.loads, (run / "programs.jsonl").read_text().splitlines())
                if p["label"].startswith("es_step_"))
    assert set(PROVENANCE) <= set(step) and "cache_key_parts" not in step  # always written; the parts traced only
    assert entered == [] and syncs == []  # no annotation entered, no sync added
    assert not get_tracer().enabled


# ---------------------------------------------------------------------------
# (d) spans on the profiler's clock
# ---------------------------------------------------------------------------

def _host_event_names(profile_dir):
    from jax.profiler import ProfileData

    files = sorted(profile_dir.glob("plugins/profile/*/*.xplane.pb"))
    assert files
    names = set()
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


@pytest.mark.parametrize("enabled", [True, False])
def test_spans_are_profiler_annotations_only_when_enabled(enabled, tmp_path):
    tracer = set_tracer(Tracer(tmp_path / "trace.jsonl") if enabled else None)
    jax.profiler.start_trace(str(tmp_path / "profile"))
    try:
        with span("epoch", epoch=3):  # attributes stay out of the event's name
            with span("enqueue"):
                x = jnp.ones((8, 8)) @ jnp.ones((8, 8))
            with span("fetch"):
                block_if_tracing(x)
    finally:
        jax.profiler.stop_trace()
        tracer.close()
    names = _host_event_names(tmp_path / "profile")
    if enabled:
        assert {"epoch", "enqueue", "fetch"} <= names
        assert [e["name"] for e in load_events(tmp_path)] == ["enqueue", "fetch", "epoch"]
    else:
        assert not {"epoch", "enqueue", "fetch"} & names
        assert not (tmp_path / "trace.jsonl").exists()


# ---------------------------------------------------------------------------
# (e) buffered writes
# ---------------------------------------------------------------------------

def test_buffered_spans_survive_a_loop_that_raises_mid_epoch(tmp_path):
    path = tmp_path / "trace.jsonl"
    tracer = Tracer(path)
    with pytest.raises(RuntimeError):
        with tracer.span("epoch", epoch=0):
            with tracer.span("dispatch"):
                with tracer.span("enqueue"):
                    pass
            # held in memory until the epoch closes: only the start line is on disk
            assert len(path.read_text().splitlines()) == 1
            raise RuntimeError("the step raised")
    assert [e["name"] for e in load_events(path)] == ["enqueue", "dispatch", "epoch"]
    tracer.close()
    assert len(path.read_text().splitlines()) == 4  # nothing written twice


def test_tracer_without_a_file_keeps_its_spans_until_attached(tmp_path):
    tracer = Tracer(enabled=True)
    assert tracer.enabled and tracer.path is None
    with tracer.span("build_backend"):
        pass
    tracer.flush()  # nowhere to write yet: nothing is lost
    tracer.attach(tmp_path / "run" / "trace.jsonl")
    with tracer.span("epoch"):
        pass
    tracer.close()
    lines = [json.loads(l) for l in (tmp_path / "run" / "trace.jsonl").read_text().splitlines()]
    assert [l.get("meta") or l["name"] for l in lines] == ["trace_start", "build_backend", "epoch"]
    assert lines[0]["wall_time"] <= lines[0]["wall_time"] + lines[1]["t0_s"]
    # a pending tracer that is never attached writes nothing and raises nothing
    orphan = Tracer(enabled=True)
    with orphan.span("build_backend"):
        pass
    orphan.close()


# ---------------------------------------------------------------------------
# (f) the per-member raw reward row
# ---------------------------------------------------------------------------

def test_member_reward_is_the_row_mean_of_the_reward_matrix(tmp_path):
    from hyperscalees_t2i_tpu.es import sample_noise
    from hyperscalees_t2i_tpu.train import TrainConfig
    from hyperscalees_t2i_tpu.train.trainer import _combine_and_update
    from tests.test_trainer import tiny_backend

    backend = tiny_backend(tmp_path)
    backend.setup()
    pop, m, r = 6, 3, 2
    tc = TrainConfig(pop_size=pop, sigma=0.05, egg_rank=2, promptnorm=True, prompts_per_gen=m)
    theta = backend.init_theta(jax.random.PRNGKey(0))
    noise = sample_noise(jax.random.PRNGKey(1), theta, pop, tc.es_config())
    combined = jax.random.uniform(jax.random.PRNGKey(2), (pop, r * m))
    _, _, metrics, _ = _combine_and_update(
        theta, jax.tree_util.tree_map(jnp.zeros_like, theta), noise, {"combined": combined},
        tc=tc, es_cfg=tc.es_config(), pop=pop, num_unique=m, repeats=r,
    )
    got = np.asarray(metrics["es/member_reward"])
    assert got.shape == (pop,)
    np.testing.assert_allclose(got, np.asarray(combined).mean(axis=1), rtol=1e-6)
    # raw, before promptnorm: promptnormed scores have mean 0, these do not
    assert abs(got.mean() - float(combined.mean())) < 1e-6 and got.mean() > 0.3


def test_member_reward_rides_the_one_dispatch_of_each_epoch(tmp_path):
    from hyperscalees_t2i_tpu.train import TrainConfig, run_training
    from tests.test_trainer import brightness_reward, tiny_backend

    tc = TrainConfig(
        num_epochs=3, pop_size=5, sigma=0.05, egg_rank=1, promptnorm=True,
        prompts_per_gen=2, member_batch=2, run_dir=str(tmp_path / "runs"), save_every=0,
    )
    run_training(tiny_backend(tmp_path), brightness_reward, tc)
    run_dir = next((tmp_path / "runs").iterdir())
    rows = [json.loads(l) for l in (run_dir / "metrics.jsonl").read_text().splitlines()]
    assert [r["obs/dispatches"] for r in rows] == [1, 2, 3]  # one an epoch, as before
    for row in rows:
        assert len(row["es/member_reward"]) == 5
        assert all(np.isfinite(row["es/member_reward"]))
        # the members' mean is the population's raw mean reward
        assert np.mean(row["es/member_reward"]) == pytest.approx(row["reward/combined_mean"], rel=1e-5)


def test_member_reward_row_stays_out_of_the_pod_scalar_gather(tmp_path):
    """A pod (``process_count > 1``) averages its ``es/`` scalars across hosts
    in one ``host_scalar_allgather`` an epoch, which carries one float a key:
    the ``[pop]`` row must not enter that payload (it did, and every pod run
    died at its first epoch)."""
    from hyperscalees_t2i_tpu.parallel.collectives import host_scalar_allgather
    from hyperscalees_t2i_tpu.train import TrainConfig, run_training
    from hyperscalees_t2i_tpu.train.trainer import host_reduce_keys
    from tests.test_trainer import brightness_reward, tiny_backend

    tc = TrainConfig(
        num_epochs=1, pop_size=4, sigma=0.05, egg_rank=1, promptnorm=True,
        prompts_per_gen=2, member_batch=2, run_dir=str(tmp_path / "runs"), save_every=0,
    )
    run_training(tiny_backend(tmp_path), brightness_reward, tc)
    run_dir = next((tmp_path / "runs").iterdir())
    # one epoch's row as the loop holds it: every es/ key of a real step
    scalars = json.loads((run_dir / "metrics.jsonl").read_text().splitlines()[0])
    assert isinstance(scalars["es/member_reward"], list)
    keys = host_reduce_keys(scalars)
    assert "es/member_reward" not in keys and "per_prompt_mean" not in keys
    assert {"step_time_s", "images_per_sec", "es/reward_std", "es/fitness_zero"} <= set(keys)
    assert not any(k.startswith("es/leaf_") for k in keys)
    # the lm_ar generator's rows: its probe arrays never reach a row (the
    # trainer writes them to a file), and no vector of its could enter the
    # gather, which carries one float a key
    from hyperscalees_t2i_tpu.train.trainer import _write_probe_once

    lm_row = _write_probe_once({**scalars, "moe/local_assignments": 12.0, "moe/max_expert_load": 2.0,
                                "moe/pair_route_flip": 0.1, "lm/hc_marginal_err": 2e-3, "lm/hc_row_err": 1e-6,
                                "lm/hc_offdiag_mass": 0.75, "probe/ids": np.zeros((4, 16), np.int32),
                                "probe/logits": np.zeros((4, 1, 16), np.float32)}, None, 0)
    assert not any(k.startswith("probe/") for k in lm_row)
    assert set(host_reduce_keys(lm_row)) == set(keys)
    # what a generator notes of its program (``lm_head_shape``, like ``kv_cache_shape``) is geometry of the next
    # ``programs.jsonl`` record, consumed there: it reaches no row, so it cannot widen the gather
    from hyperscalees_t2i_tpu.obs import note_program_geometry, xla_cost

    note_program_geometry(lm_head_shape=(32, 64))
    assert xla_cost.program_record(site="test", label="noted")["geometry"] == {"lm_head_shape": (32, 64)}
    assert set(host_reduce_keys(_write_probe_once(dict(lm_row), None, 0))) == set(keys)
    # the pc > 1 payload path of run_training, as it builds and reads it
    payload = {k: scalars[k] for k in keys}
    payload["_preempt_req"] = 0.0
    gathered = host_scalar_allgather(payload)
    reduced = {k: float(gathered[k].mean()) for k in keys}
    assert reduced["es/reward_std"] == pytest.approx(scalars["es/reward_std"], rel=1e-6)

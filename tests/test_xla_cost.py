"""obs/xla_cost: the per-compiled-program XLA ledger + roofline layer.

Covers the ISSUE-3 acceptance surface: ledger record shape from a real AOT
compile, graceful degradation when a backend lacks ``memory_analysis``, the
donation audit, roofline classification boundaries, and the gauges the
record surfaces into the metrics registry.
"""

import json

import pytest

import jax
import jax.numpy as jnp

from hyperscalees_t2i_tpu.obs import xla_cost


def _compiled_matmul(n=64, donate=()):
    def f(a, b):
        return a @ b + jnp.sin(a).sum()

    j = jax.jit(f, donate_argnums=donate)
    x = jax.ShapeDtypeStruct((n, n), jnp.float32)
    lowered = j.lower(x, x)
    return lowered, lowered.compile()


# -- normalization ----------------------------------------------------------


def test_normalize_cost_analysis_real_compile():
    _, compiled = _compiled_matmul()
    cost = xla_cost.normalize_cost_analysis(compiled)
    assert cost["flops"] and cost["flops"] >= 2 * 64**3 * 0.9
    assert cost["bytes_accessed"] and cost["bytes_accessed"] > 0
    assert cost["transcendentals"] and cost["transcendentals"] > 0  # sin


def test_normalize_cost_analysis_tolerates_broken_backends():
    class Broken:
        def cost_analysis(self):
            raise NotImplementedError

    assert xla_cost.normalize_cost_analysis(Broken()) == {
        "flops": None, "bytes_accessed": None, "transcendentals": None,
    }

    class ListShaped:
        def cost_analysis(self):
            return [{"flops": 7.0, "bytes accessed": 3.0}]

    cost = xla_cost.normalize_cost_analysis(ListShaped())
    assert cost["flops"] == 7.0 and cost["bytes_accessed"] == 3.0
    assert cost["transcendentals"] is None

    class NonPositive:
        def cost_analysis(self):
            return {"flops": 0.0}

    assert xla_cost.normalize_cost_analysis(NonPositive())["flops"] is None


def test_normalize_memory_analysis_and_peak():
    _, compiled = _compiled_matmul()
    mem = xla_cost.normalize_memory_analysis(compiled)
    assert mem is not None
    # two 64×64 f32 args; donation off → no aliasing
    assert mem["argument_bytes"] == 2 * 64 * 64 * 4
    assert mem["output_bytes"] == 64 * 64 * 4
    assert mem["peak_bytes"] == (
        mem["argument_bytes"] + mem["output_bytes"] + mem["temp_bytes"]
        + mem["generated_code_bytes"] - mem["alias_bytes"]
    )


def test_memory_analysis_absent_on_backend_falls_back():
    """A backend without memory_analysis still yields a record: peak_bytes
    degrades to the arguments-only floor, labeled as such."""

    class NoMem:
        donate_argnums = ()

        @property
        def in_avals(self):
            return ((jax.ShapeDtypeStruct((4, 4), jnp.float32),), {})

        def cost_analysis(self):
            return {"flops": 10.0, "bytes accessed": 5.0}

        def memory_analysis(self):
            raise NotImplementedError("not on this backend")

    assert xla_cost.normalize_memory_analysis(NoMem()) is None
    rec = xla_cost.program_record(site="test", label="nomem", compiled=NoMem())
    assert rec["peak_bytes"] == 4 * 4 * 4
    assert rec["peak_bytes_source"] == "arguments_only"
    assert rec["flops"] == 10.0
    assert rec["donation"]["honored"] is None


# -- donation audit ---------------------------------------------------------


def test_donation_audit_honored():
    _, compiled = _compiled_matmul(donate=(0,))
    audit = xla_cost.donation_audit(compiled)
    assert audit["donated_leaves"] == 1
    assert audit["donated_bytes"] == 64 * 64 * 4
    # NOTE: alias_bytes is 0 when the executable came from the persistent
    # compile cache (deserialized stats drop aliasing) — `honored` must be
    # True either way, via the memory stats or the HLO-config fallback.
    assert audit["alias_bytes"] is not None
    assert audit["honored"] is True


def test_donation_audit_nothing_donated():
    _, compiled = _compiled_matmul(donate=())
    audit = xla_cost.donation_audit(compiled)
    assert audit["donated_leaves"] == 0
    assert audit["donated_bytes"] == 0.0
    # nothing offered → honored is not a meaningful question
    assert audit["honored"] is None


# -- roofline classification ------------------------------------------------


def test_roofline_classification_boundaries():
    roof = xla_cost.roofline
    # compute-bound: compute floor 1.0 s dominates bandwidth floor 1 ms
    r = roof(1e12, 1e9, 1.5, peak_flops=1e12, hbm_bw=1e12)
    assert r["bound"] == "compute"
    assert r["t_compute_s"] == pytest.approx(1.0)
    assert r["t_bandwidth_s"] == pytest.approx(1e-3)
    assert r["t_roofline_s"] == pytest.approx(1.0)
    assert r["intensity"] == pytest.approx(1000.0)
    assert r["ridge_intensity"] == pytest.approx(1.0)
    # bandwidth-bound: bytes floor dominates
    r = roof(1e9, 1e12, 1.5, peak_flops=1e12, hbm_bw=1e12)
    assert r["bound"] == "bandwidth"
    # latency-bound: measured strictly above latency_factor × roofline ...
    r = roof(1e12, 1e9, 2.001, peak_flops=1e12, hbm_bw=1e12)
    assert r["bound"] == "latency"
    # ... while exactly AT the boundary stays with the resource verdict
    r = roof(1e12, 1e9, 2.0, peak_flops=1e12, hbm_bw=1e12)
    assert r["bound"] == "compute"
    # no measured time → resource verdict only, never latency
    r = roof(1e12, 1e9, None, peak_flops=1e12, hbm_bw=1e12)
    assert r["bound"] == "compute"
    # n_devices scales both floors
    r = roof(1e12, 1e9, 0.3, peak_flops=1e12, hbm_bw=1e12, n_devices=4)
    assert r["t_compute_s"] == pytest.approx(0.25)
    assert r["bound"] == "compute"


def test_roofline_unknown_peaks_degrade_to_none():
    r = xla_cost.roofline(1e12, 1e9, 0.5, peak_flops=None, hbm_bw=None)
    assert r["bound"] is None and r["t_roofline_s"] is None
    # one peak known is enough for a partial verdict
    r = xla_cost.roofline(1e12, None, 10.0, peak_flops=1e12, hbm_bw=None)
    assert r["bound"] == "latency"  # 10 s >> 1 s compute floor
    assert r["t_bandwidth_s"] is None


# -- ledger + record --------------------------------------------------------


def test_program_record_shape_from_real_compile():
    lowered, compiled = _compiled_matmul(donate=(0,))
    rec = xla_cost.program_record(
        site="test", label="matmul", lowered=lowered, compiled=compiled,
        geometry={"m": 2, "r": 1}, chain=4, lowering_s=0.1, compile_s=0.2,
    )
    assert rec["site"] == "test" and rec["label"] == "matmul"
    assert rec["chain"] == 4
    assert rec["geometry"]["m"] == 2
    assert rec["lowering_s"] == 0.1 and rec["compile_s"] == 0.2
    assert rec["stablehlo_lines"] > 0 and rec["stablehlo_bytes"] > 0
    assert len(rec["stablehlo_sha256"]) == 16
    assert rec["flops"] > 0 and rec["bytes_accessed"] > 0
    assert rec["peak_bytes"] > 0 and rec["peak_bytes_source"] == "memory_analysis"
    assert rec["intensity"] == rec["flops"] / rec["bytes_accessed"]
    assert rec["donation"]["honored"] is True
    assert rec["platform"] == "cpu"  # device identity stamped (backend is up)
    # the record must be JSON-serializable as-is (the ledger line contract)
    json.dumps(rec)


def test_ledger_write_load_and_gauges(tmp_path):
    from hyperscalees_t2i_tpu.obs.metrics import MetricsRegistry, set_registry

    registry = set_registry(MetricsRegistry())
    lowered, compiled = _compiled_matmul()
    ledger = xla_cost.set_ledger(xla_cost.ProgramLedger(tmp_path / "programs.jsonl"))
    try:
        rec = xla_cost.record_compile(
            site="test", label="m1", lowered=lowered, compiled=compiled,
        )
    finally:
        xla_cost.set_ledger(None)
        set_registry(None)
    assert rec["flops"] > 0
    loaded = xla_cost.load_programs(tmp_path)  # dir form resolves the file
    assert len(loaded) == 1 and loaded[0]["label"] == "m1"
    assert loaded[0]["flops"] == rec["flops"]
    # XLA's cost analysis (a loop body counted once) stays in the ledger
    # record; only the compiler's peak is surfaced as an obs/ gauge
    assert loaded[0]["intensity"] == pytest.approx(rec["flops"] / rec["bytes_accessed"])
    snap = registry.snapshot()
    assert snap["obs/program_peak_bytes"] == rec["peak_bytes"]
    assert not {"obs/program_flops", "obs/program_bytes_accessed", "obs/program_intensity"} & set(snap)
    # ledger uninstalled → further records go nowhere
    xla_cost.record_compile(site="test", label="m2", compiled=compiled)
    assert len(xla_cost.load_programs(tmp_path)) == 1


def test_record_compile_never_raises():
    # a completely alien object must yield an (empty-ish) dict, not a crash
    rec = xla_cost.record_compile(site="x", label="y", compiled=object())
    assert isinstance(rec, dict)


def test_note_program_geometry_merges_into_records():
    xla_cost.note_program_geometry(pop=32, n_pop=4)
    rec = xla_cost.program_record(site="test", label="g", geometry={"m": 2})
    assert rec["geometry"]["pop"] == 32 and rec["geometry"]["n_pop"] == 4
    assert rec["geometry"]["m"] == 2  # explicit keys win alongside context


def test_load_programs_skips_junk(tmp_path):
    p = tmp_path / "programs.jsonl"
    p.write_text('not json\n{"half": \n{"site": "s", "label": "ok"}\n')
    recs = xla_cost.load_programs(p)
    assert len(recs) == 1 and recs[0]["label"] == "ok"
    assert xla_cost.load_programs(tmp_path / "missing.jsonl") == []


# -- collective extraction (ISSUE 8) ---------------------------------------


def _compiled_collectives(n_shards=4):
    from jax.sharding import PartitionSpec as P

    from hyperscalees_t2i_tpu.parallel import POP_AXIS, make_mesh, shard_map

    mesh = make_mesh({"pop": n_shards})

    def body(x):
        return jax.lax.psum(x, POP_AXIS), jax.lax.all_gather(
            x, POP_AXIS, tiled=True
        )

    f = jax.jit(shard_map(
        body, mesh=mesh, in_specs=P(POP_AXIS), out_specs=(P(POP_AXIS), P()),
    ))
    return f.lower(jax.ShapeDtypeStruct((4 * n_shards,), jnp.float32)).compile()


def test_collective_stats_extracts_psum_and_gather():
    stats = xla_cost.collective_stats(_compiled_collectives())
    assert stats["collective_ops"] == 2
    # all-reduce result: the [4] f32 shard payload; all-gather result: the
    # full [16] f32 buffer — result-shape bytes, one rule for every op
    assert stats["collective_breakdown"]["all-reduce"]["bytes"] == 4 * 4
    assert stats["collective_breakdown"]["all-gather"]["bytes"] == 16 * 4
    assert stats["collective_bytes"] == 4 * 4 + 16 * 4


def test_collective_stats_zero_on_single_device_program():
    _, compiled = _compiled_matmul()
    stats = xla_cost.collective_stats(compiled)
    assert stats["collective_ops"] == 0
    assert stats["collective_bytes"] == 0.0
    # "no collectives" is a stated fact in every record, not a missing field
    rec = xla_cost.program_record(site="t", label="t", compiled=compiled)
    assert rec["collective_ops"] == 0 and rec["collective_bytes"] == 0.0


def test_collective_stats_merged_into_record():
    compiled = _compiled_collectives()
    rec = xla_cost.program_record(site="t", label="coll", compiled=compiled)
    assert rec["collective_ops"] == 2 and rec["collective_bytes"] == 80.0
    json.dumps(rec)  # ledger-line contract unchanged


def test_collective_stats_tolerates_backends_without_hlo_text():
    class NoText:
        def as_text(self):
            raise NotImplementedError

    assert xla_cost.collective_stats(NoText()) == {}
    assert xla_cost.collective_stats(object()) == {}


def test_hlo_shape_bytes():
    assert xla_cost._hlo_shape_bytes("f32[4,16]{1,0}") == 4 * 16 * 4
    assert xla_cost._hlo_shape_bytes("(f32[4]{0}, bf16[8,2]{1,0})") == 16 + 32
    assert xla_cost._hlo_shape_bytes("u32[]") == 4  # scalar
    assert xla_cost._hlo_shape_bytes("token[]") == 0  # unknown dtype → 0


def test_collective_stats_async_start_not_double_counted():
    """TPU XLA lowers collectives to async start/done pairs whose *start*
    result is a tuple carrying operand AND destination buffers — counting
    the whole tuple would inflate collective_bytes up to 2× (and with it
    t_comms_s / the comms verdict). Only the destination half counts, and
    context u32[] scalars are stripped (collective-permute-start)."""

    class Fake:
        def as_text(self):
            return "\n".join([
                "  %ars = (f32[128]{0}, f32[128]{0}) all-reduce-start(f32[128]{0} %x), replica_groups={{0,1}}",
                "  %ard = f32[128]{0} all-reduce-done((f32[128]{0}, f32[128]{0}) %ars)",
                "  %ags = (f32[1,128]{1,0}, f32[8,128]{1,0}) all-gather-start(f32[1,128]{1,0} %y), dimensions={0}",
                "  %agd = f32[8,128]{1,0} all-gather-done((f32[1,128]{1,0}, f32[8,128]{1,0}) %ags)",
                "  %cps = (f32[64]{0}, f32[64]{0}, u32[], u32[]) collective-permute-start(f32[64]{0} %z)",
            ])

    stats = xla_cost.collective_stats(Fake())
    # each -start counts once; the -done lines never match
    assert stats["collective_ops"] == 3
    assert stats["collective_breakdown"]["all-reduce"]["bytes"] == 128 * 4
    assert stats["collective_breakdown"]["all-gather"]["bytes"] == 8 * 128 * 4
    assert stats["collective_breakdown"]["collective-permute"]["bytes"] == 64 * 4
    assert stats["collective_bytes"] == (128 + 8 * 128 + 64) * 4


def test_roofline_comms_verdict():
    roof = xla_cost.roofline
    # comms floor (collective_bytes/ici_bw = 5 s) dominates compute (1 s)
    # and bandwidth (1 ms)
    r = roof(1e12, 1e9, 6.0, peak_flops=1e12, hbm_bw=1e12,
             collective_bytes=5e9, ici_bw=1e9)
    assert r["bound"] == "comms"
    assert r["t_comms_s"] == pytest.approx(5.0)
    assert r["t_roofline_s"] == pytest.approx(5.0)
    # measured far above even the comms floor → latency still wins
    r = roof(1e12, 1e9, 11.0, peak_flops=1e12, hbm_bw=1e12,
             collective_bytes=5e9, ici_bw=1e9)
    assert r["bound"] == "latency"
    # unknown ICI bandwidth: no comms claim, verdict falls back unchanged
    r = roof(1e12, 1e9, 1.5, peak_flops=1e12, hbm_bw=1e12,
             collective_bytes=5e9, ici_bw=None)
    assert r["bound"] == "compute" and r["t_comms_s"] is None
    # tiny collective traffic must not flip a compute verdict
    r = roof(1e12, 1e9, 1.5, peak_flops=1e12, hbm_bw=1e12,
             collective_bytes=10.0, ici_bw=1e9)
    assert r["bound"] == "compute"


def test_ici_bandwidth_table():
    from hyperscalees_t2i_tpu.utils.mfu import ici_bw_for_kind

    assert ici_bw_for_kind("TPU v5 lite") == 200e9
    assert ici_bw_for_kind("TPU v5p") == 600e9
    assert ici_bw_for_kind("cpu") is None
    assert ici_bw_for_kind("") is None


def test_trainer_run_writes_programs_ledger(tmp_path):
    """Acceptance: a (tiny) trainer run writes programs.jsonl with one record
    per AOT compile, and the run report renders the roofline panel table."""
    from hyperscalees_t2i_tpu.tools import run_report
    from hyperscalees_t2i_tpu.train import TrainConfig, run_training
    from tests.test_trainer import brightness_reward, tiny_backend

    backend = tiny_backend(tmp_path)
    tc = TrainConfig(
        num_epochs=2, pop_size=4, sigma=0.05, egg_rank=2, promptnorm=False,
        prompts_per_gen=2, member_batch=4, run_dir=str(tmp_path / "runs"),
        save_every=0, log_hist_every=0, seed=7,
    )
    run_training(backend, brightness_reward, tc)
    run_dir = next((tmp_path / "runs").iterdir())
    recs = xla_cost.load_programs(run_dir)
    assert len(recs) == 1  # one geometry → one AOT compile
    rec = recs[0]
    assert rec["site"] == "train" and rec["label"].startswith("es_step_")
    assert rec["geometry"]["pop"] == 4 and rec["geometry"]["m"] == 2
    assert rec["flops"] > 0 and rec["peak_bytes"] > 0
    assert rec["donation"]["donated_leaves"] > 0  # θ and Δθ donated
    assert rec["compile_s"] is not None and rec["lowering_s"] is not None
    # metrics.jsonl rows carry the compiler's peak of the program, nothing
    # from its cost analysis
    rows = run_report.load_metrics(run_dir / "metrics.jsonl")
    assert rows and rows[-1]["obs/program_peak_bytes"] == rec["peak_bytes"]
    assert "obs/program_flops" not in rows[-1]
    # the HTML report grows the per-program table
    assert run_report.main([str(run_dir)]) == 0
    html_text = (run_dir / "run_report.html").read_text()
    assert "Roofline" in html_text and "es_step_" in html_text


# ---------------------------------------------------------------------------
# kv_cache_whole_ops: ops of the optimized module as large as a KV cache
# ---------------------------------------------------------------------------

_KV_HLO = """HloModule m

%fused_dus (p0: bf16[4,16,8,680,16,64], p1: bf16[4,16,8,4,16,64]) -> bf16[4,16,8,680,16,64] {
  %p0 = bf16[4,16,8,680,16,64]{5,4,3,2,1,0} parameter(0)
  %p1 = bf16[4,16,8,4,16,64]{5,4,3,2,1,0} parameter(1)
  %c = s32[] constant(0)
  %inner = bf16[4,16,8,680,16,64]{5,4,3,2,1,0} copy(%p0)
  ROOT %dus = bf16[4,16,8,680,16,64]{5,4,3,2,1,0} dynamic-update-slice(%inner, %p1, %c, %c, %c, %c, %c, %c)
}

%body (t: (s32[], bf16[16,4,8,680,16,64])) -> (s32[], bf16[16,4,8,680,16,64]) {
  %t = (s32[], bf16[16,4,8,680,16,64]{5,4,3,2,1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %k = bf16[16,4,8,680,16,64]{5,4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%t), index=1
  %layer = bf16[4,8,680,16,64]{4,3,2,1,0:T(8,128)(2,1)} copy-done(%start)
  %start = (bf16[4,8,680,16,64]{4,3,2,1,0}, bf16[4,8,680,16,64]{4,3,2,1,0}, u32[]) copy-start(%view)
  %view = bf16[4,8,680,1024]{3,2,1,0} bitcast(%layer)
  %prefix = bf16[4,8,424,16,64]{4,3,2,1,0} slice(%layer), slice={[0:4], [0:8], [0:424], [0:16], [0:64]}
  ROOT %out = (s32[], bf16[16,4,8,680,16,64]{5,4,3,2,1,0}) tuple(%i, %k)
}

ENTRY %main (a: bf16[4,16,8,4,16,64]) -> bf16[8,680,16,64] {
  %a = bf16[4,16,8,4,16,64]{5,4,3,2,1,0} parameter(0)
  %zero = bf16[] constant(0)
  %fill = bf16[4,16,8,680,16,64]{5,4,3,2,1,0:T(8,128)(2,1)} broadcast(%zero), dimensions={}
  %fill2 = bf16[16,4,8,680,16,64]{5,4,3,2,1,0} broadcast(%zero), dimensions={}
  %i0 = s32[] constant(0)
  %init = (s32[], bf16[16,4,8,680,16,64]{5,4,3,2,1,0}) tuple(%i0, %fill2)
  %loop = (s32[], bf16[16,4,8,680,16,64]{5,4,3,2,1,0}) while(%init), condition=%cond, body=%body
  %write = bf16[4,16,8,680,16,64]{5,4,3,2,1,0} fusion(%fill, %a), kind=kLoop, calls=%fused_dus
  ROOT %one = bf16[8,680,16,64]{3,2,1,0} copy(%somewhere)
}
"""


@pytest.mark.parametrize("opcode, want, why", [
    ("broadcast", 2, "the stack with the member axis in front of the depth or behind it"),
    ("fusion(dynamic-update-slice)", 1, "a fusion goes by its root; what is inside it is no op of its own"),
    ("while", 1, "a tuple result that carries the stack"),
    ("copy-done", 1, "a layer of the stack under the member axis; its -start half is the same transfer"),
    ("copy", 1, "a layer without a member axis; the copy inside the fusion is not counted"),
    ("slice", 0, "a prefix is not the whole"),
    ("bitcast", 0, "moves nothing"), ("get-tuple-element", 0, "moves nothing"),
    ("parameter", 0, "moves nothing"), ("tuple", 0, "moves nothing"), ("copy-start", 0, "counted at its -done"),
])
def test_kv_cache_whole_ops_counts_by_opcode(opcode, want, why):
    class Fake:
        def as_text(self):
            return _KV_HLO

    counts = xla_cost.kv_cache_whole_ops(Fake(), (16, 8, 680, 16, 64))
    assert counts.get(opcode, 0) == want, (why, counts)
    assert sum(counts.values()) == 6


def test_kv_cache_whole_ops_tolerates_backends_without_hlo_text():
    assert xla_cost.kv_cache_whole_ops(object(), (16, 8, 680, 16, 64)) == {}

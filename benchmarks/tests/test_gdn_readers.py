"""The readers and the work functions the hybrid cell adds
(``qwen3next80b-ep4-train-pop8x8``), against values worked out by hand: the
flops file at the published widths (the figures ISSUE 31 counts) and at a tiny
shape, the scope readers on the hand-written two-chip trace with a hybrid
scope table, the roofline shares from known work, and silence on a program
that lacks the scopes and counters."""

import importlib
import json
from pathlib import Path

import pytest

from benchmarks.flops import common as c
from benchmarks.flops import gdn_moe
from benchmarks.tests.test_scope_readers import FIXTURES, make_record, read

ROOT = Path(__file__).resolve().parents[2]
CELL = "qwen3next80b-ep4-train-pop8x8"
CONFIG = json.loads((ROOT / "benchmarks/configs/qwen3-next-80b-a3b-ep4.json").read_text())
MODEL = CONFIG["model"]
NEW = ("lm_gdn_device_s", "gdn_update_roofline", "lm_gated_attn_device_s", "lm_state_carried_gb",
       "lm_moe_hybrid_device_s", "moe_experts_hybrid_roofline", "moe_max_expert_load_hybrid")


def test_parameter_counts_of_one_layer_at_the_published_widths():
    lm = MODEL["lm"]
    # Wqkvz 2048 x 12288 + Wba 2048 x 64 + Wout 4096 x 2048 + the conv's 4 x 8192
    assert gdn_moe.gdn_params(lm) == 25_165_824 + 131_072 + 8_388_608 + 32_768 == 33_718_272
    # Wq 2048 x 8192 + Wk, Wv 2048 x 512 each + Wo 4096 x 2048
    assert gdn_moe.attn_params(lm) == 16_777_216 + 2 * 1_048_576 + 8_388_608 == 27_262_976
    assert gdn_moe.expert_params(lm) == 3 * 2048 * 512 == 3_145_728
    assert gdn_moe.shared_params(lm) == 3 * 2048 * 512 + 2048
    assert gdn_moe.held_experts_per_token(lm) == 10 * 128 / 512 == 2.5
    assert gdn_moe.layer_kinds(lm) == (9, 3)
    assert gdn_moe.state_elements(lm) == 32 * 128 * 128
    # the configuration file states the cut's bytes: held experts 4.83 G, a layer's whole experts 1.61 G
    assert 12 * 128 * gdn_moe.expert_params(lm) == 4_831_838_208 and 512 * gdn_moe.expert_params(lm) == 1_610_612_736


def test_every_published_key_of_the_catalog_row_is_in_the_file_unchanged():
    lm = MODEL["lm"]
    same = [k for k in lm if k in CONFIG and k not in ("num_hidden_layers", "vocab_rows_held")]
    assert len(same) >= 21 and all(lm[k] == CONFIG[k] for k in same)
    assert (CONFIG["num_hidden_layers"], lm["num_hidden_layers"], CONFIG["layers"]) == (48, 12, 12)
    assert (CONFIG["num_experts"], lm["experts_held"], CONFIG["routed_experts_held"]) == (512, 128, 128)
    assert (CONFIG["vocab_size"], lm["vocab_rows_held"], CONFIG["vocab_rows_held"]) == (151936, 37984, 37984)
    img = lm["image_tokens"]
    assert img["image_id_offset"] + img["image_vocab"] == lm["vocab_rows_held"]
    assert CONFIG["rehearse"]["inputs"]["lm"]["num_hidden_layers"] == 4 and CONFIG["rehearse"]["inputs"]["lm"]["experts_held"] == 8


def test_work_by_hand_at_a_tiny_shape():
    lm = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 2,
          "full_attention_interval": 2, "linear_conv_kernel_dim": 2, "linear_key_head_dim": 2,
          "linear_num_key_heads": 1, "linear_num_value_heads": 2, "linear_value_head_dim": 3,
          "moe_intermediate_size": 3, "shared_expert_intermediate_size": 5, "num_experts": 8,
          "num_experts_per_tok": 2, "experts_held": 4, "num_hidden_layers": 4, "vocab_rows_held": 10,
          "image_tokens": {"grid": 2}}
    m = {"lm": lm, "prompt_tokens_mean": 3}
    gdn = 4 * (2 * 2 + 2 * 6) + 4 * 4 + 6 * 4 + 2 * (2 * 2 + 6)
    attn = 4 * 2 * 4 + 2 * 4 * 2 + 4 * 4
    expert, shared, state = 3 * 4 * 3, 3 * 4 * 5 + 4, 2 * 2 * 3
    assert (gdn_moe.gdn_params(lm), gdn_moe.attn_params(lm), gdn_moe.state_elements(lm)) == (gdn, attn, state)
    T = 3 + 4
    per_token = 2 * (gdn + 3 * state) + 2 * attn + 4 * (4 * 8 + shared + (2 * 4 / 8) * expert)
    assert gdn_moe.transformer(m) == T * per_token + 2 * 2 * 2 * 2 * (T * (T + 1) // 2) + 4 * 4 * 10
    # state update, 5 sequences, 2 DeltaNet layers: 4 sampled positions read + write the f32 state, the prefill writes it once;
    # 7 FLOPs a state element a position (three multiply-adds and the decay); q, k, v, o in f32 a position
    io = 2 * (2 * 2 + 2 * 3) * 4
    assert gdn_moe.state_update_work(m, 5) == (2 * 5 * 7 * 7 * state, 2 * 5 * (4 * (2 * state * 4 + io) + state * 4 + 3 * io))
    assert gdn_moe.experts_work(m, 10, 3) == (2.0 * 10 * expert, 3 * 4 * expert + 10 * 2 * (2 * 4 + 3 * 3))
    assert gdn_moe.expert_calls_per_step(m, 4, 8) == 2 * (4 * 4 + 3)  # the last layer's prefill routes nothing
    seen = 4 + 5 + 6 + 7
    assert gdn_moe.attend_work(m, 5, 5) == (2.0 * 2 * 2 * 2 * 2 * (6 + seen) * 5,
                                            2 * ((3 + seen) * 2 * 1 * 2 * 2 + 7 * 2 * 2 * 2 * 2) * 5)


def test_flops_per_image_and_the_floors_issue_31_counts():
    parts = gdn_moe.flops_per_image(MODEL)
    assert parts["total"] == pytest.approx(parts["generator"] + parts["decoder"] + parts["rewards"])
    assert parts["rewards"] == 2.0 * c.reward_towers(MODEL["reward_towers"])
    assert 0.3e12 < parts["generator"] < 0.4e12  # 3 B active of 80 B: a fifth of a dense layer's FLOPs a token
    # 64 sequences: the state's read + write a step 0.77 s at 819 GB/s; the held experts' bases 1.52 s
    flops, bytes_ = gdn_moe.state_update_work(MODEL, 64)
    assert bytes_ / 819e9 == pytest.approx(0.769, abs=2e-3) and flops / 197e12 < 0.01
    calls = gdn_moe.expert_calls_per_step(MODEL, 64, 64)
    assert calls == 12 * 256 + 11
    assert gdn_moe.experts_work(MODEL, 0, calls)[1] / 819e9 == pytest.approx(1.516, abs=2e-3)


HYBRID_TABLE = {
    "while.1": "unattributed", "fusion.2": "~generate/lm_decode_step/lm_gdn/delta_rule",
    "fused_qlora.3": "generate/lm_decode_step/lm_gdn", "all-reduce.4": "generate/lm_prefill/lm_attn/attend",
    "fusion.5": "generate/lm_decode_step/lm_moe/experts",
}


@pytest.fixture
def hybrid_run(tmp_path):
    flags = {"--pop_size": "8", "--prompts_per_gen": "8", "--member_batch": "8"}
    rec = make_record(tmp_path, FIXTURES / "two_chip_annotated.textproto", HYBRID_TABLE, flags=flags)
    rec.job.config, rec.job.chips = CONFIG, 1
    rec.job.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    rows = [{"epoch": e, "moe/local_assignments": 1000.0 + e, "moe/max_expert_load": 3.0 + e,
             "lm/state_bytes": 1.25e9, "lm/kv_cache_bytes": 0.125e9} for e in range(4)]
    (rec.run_dir / "metrics.jsonl").write_text("".join(json.dumps(r) + "\n" for r in rows))
    rec.first_epoch, rec.last_epoch = 1, 2  # the window: epochs 1 and 2
    return rec


def test_scope_seconds_and_counters_of_the_hybrid_cell(hybrid_run):
    # a step: fusion.2 30 us on chip 0 and 40 on chip 1 (delta_rule), fused_qlora.3 20 on both (lm_gdn's own),
    # the collective's uncovered 10 us (lm_attn/attend) and fusion.5 10 us (experts) on both: means over two chips
    assert read("lm_gdn_device_s", hybrid_run) == pytest.approx(35e-6 + 20e-6)
    assert any(n.startswith("lm_gdn a step: conv 0.0000 s, delta_rule 0.0000 s, gdn_out 0.0000 s, projections")
               for n in hybrid_run.notes)
    assert read("lm_gated_attn_device_s", hybrid_run) == pytest.approx(10e-6)
    assert any(n.startswith("lm_attn a step: attend 0.0000 s of 0.0000 s; attend: floor") for n in hybrid_run.notes)
    assert read("lm_moe_hybrid_device_s", hybrid_run) == pytest.approx(10e-6)
    assert read("moe_max_expert_load_hybrid", hybrid_run) == 5.0
    assert read("lm_state_carried_gb", hybrid_run) == pytest.approx(1.375)
    assert any("recurrent state + conv windows 1.2500 GB, KV cache 0.1250 GB" in n for n in hybrid_run.notes)


def test_roofline_shares_are_the_floor_over_the_scopes_seconds(hybrid_run):
    flops, bytes_ = gdn_moe.state_update_work(MODEL, 64)
    floor = max(flops / 197e12, bytes_ / 819e9)
    assert read("gdn_update_roofline", hybrid_run) == pytest.approx(100 * floor / 35e-6)
    assert any("delta_rule (64 sequences a step): floor" in n and "memory-bound" in n for n in hybrid_run.notes)
    flops, bytes_ = gdn_moe.experts_work(MODEL, 1001.5, 12 * 256 + 11)
    assert read("moe_experts_hybrid_roofline", hybrid_run) == pytest.approx(
        100 * max(flops / 197e12, bytes_ / 819e9) / 10e-6)


def test_a_program_without_the_scopes_or_counters_reads_nothing(tmp_path):
    """The parent of this PR traced with this PR's benchmark files: on an old
    cell, and on this configuration's own files."""
    from benchmarks.tests.test_scope_readers import TWO_CHIP_TABLE

    rec = make_record(tmp_path, FIXTURES / "two_chip_annotated.textproto", TWO_CHIP_TABLE,
                      flags={"--pop_size": "8", "--prompts_per_gen": "4", "--member_batch": "2"})
    rec.job.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for config in ("var-d16", "openpangu-ultra-moe-718b-ep16", "qwen3-next-80b-a3b-ep4"):
        rec.job.config = json.loads((ROOT / f"benchmarks/configs/{config}.json").read_text())
        assert {name: read(name, rec) for name in NEW} == dict.fromkeys(NEW)
    assert rec.notes == []


@pytest.mark.parametrize("name", NEW)
def test_new_readers_state_what_the_manifest_states(name):
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert entry["workloads"] == [CELL]


def test_the_driver_refuses_a_program_that_does_not_know_the_family(tmp_path, monkeypatch):
    """``parsed_as_stated`` with the program's parser, and with a parser that
    copies the keys it knows and ignores the rest (the parent's)."""
    from benchmarks.drivers import es_train_ref_family as drv
    from benchmarks.record import Job
    from hyperscalees_t2i_tpu.models import lm

    traffic = json.loads((ROOT / "benchmarks/traffic/train-lm-hybrid-pop8x8.json").read_text())
    job = Job(cell={}, config=CONFIG, traffic=traffic, chips=1, seed=2**31 + 7, seconds=1.0, trace=False,
              rehearse=False, out_dir=tmp_path, bench_dir=ROOT / "benchmarks", peaks=None, t_process_start=0.0,
              clock_anchor=(0.0, 0.0))
    assert drv.parsed_as_stated(job) == ""
    job.traffic = dict(traffic, layer_types=["linear_attention", "full_attention"])
    assert "does not know this family" in drv.parsed_as_stated(job)  # another period than the file's
    job.traffic = traffic
    monkeypatch.setattr(lm, "config_from_json", lambda path: lm.LMConfig())
    assert "does not know this family" in drv.parsed_as_stated(job)


def test_carried_state_bytes_by_hand_at_the_published_widths():
    lm = MODEL["lm"]
    # 64 sequences x 9 DeltaNet layers x (32 x 128 x 128 state numbers at 4 B + 3 conv inputs of 8192 channels in bf16):
    # what every step of the chip runs counted (lm/state_bytes 1236271104.0, my chip runs, PR 31)
    assert gdn_moe.carried_state_bytes(lm, 64) == 64 * 9 * (524_288 * 4 + 3 * 8192 * 2) == 1_236_271_104
    toy = CONFIG["rehearse"]["inputs"]["lm"]     # float32 activations: the conv window is 4 B a number
    assert gdn_moe.carried_state_bytes(toy, 64) == 64 * 3 * (4 * 8 * 8 * 4 + 3 * 64 * 4) == 344_064


@pytest.mark.parametrize("state_bytes,ok", [(1_236_271_104.0, True), (632_291_328.0, False), (None, False)],
                         ids=["float32-state", "bfloat16-state", "not-counted"])
def test_correct_holds_the_recurrent_state_to_float32_by_its_byte_count(hybrid_run, state_bytes, ok):
    """``recurrent_state_is_float32``: a step that carries its state in
    bfloat16 counts 9 x 64 x (2 MB -> 1 MB) less, and one that does not count
    it at all fails too; the two reference figures cannot tell either."""
    from benchmarks.drivers import es_train_ref_family as drv

    rows = [{"epoch": e, **({} if state_bytes is None else {"lm/state_bytes": state_bytes})} for e in range(4)]
    (hybrid_run.run_dir / "metrics.jsonl").write_text("".join(json.dumps(r) + "\n" for r in rows))
    name, passed, detail = drv.state_is_float32(hybrid_run.job, hybrid_run, gdn_moe)
    assert (name, passed) == ("recurrent_state_is_float32", ok)
    assert "limit: 1236271104 exactly" in detail and "over 2 steps" in detail
    assert gdn_moe.carried_state_bytes(MODEL["lm"], 64) - 9 * 64 * gdn_moe.state_elements(MODEL["lm"]) * 2 == 632_291_328


def test_kernel_sites_list_every_fused_qlora_site_of_the_hybrid_step():
    """66 sites a sampled position and 61 in the prefill (the last layer, an
    attention layer, stops at its K and V): the 127 kernels programs.jsonl counts."""
    lm, sites = MODEL["lm"], CONFIG["kernel_sites"]["fused_qlora"]
    decode = [s for s in sites if s["rows_per_image"][0] == 1]
    prefill = [s for s in sites if s["rows_per_image"] == [64]]
    assert len(decode) + len(prefill) == len(sites) and all(len(s["rows_per_image"]) == 256 for s in decode)
    assert sum(s["calls_per_image"] for s in decode) == 9 * 2 + 3 * 4 + 12 * 3 == 66
    assert sum(s["calls_per_image"] for s in prefill) == 9 * 2 + 2 * 4 + 2 + 11 * 3 == 61
    assert sum(len(s["rows_per_image"]) * s["calls_per_image"] for s in sites) == 66 * 256 + 61 == 16957
    d, nq, nv = lm["hidden_size"], lm["linear_num_key_heads"] * lm["linear_key_head_dim"], \
        lm["linear_num_value_heads"] * lm["linear_value_head_dim"]
    H, Hkv, dh, f = lm["num_attention_heads"], lm["num_key_value_heads"], lm["head_dim"], lm["shared_expert_intermediate_size"]
    shapes = {"layers/gdn/wqkvz": (d, 2 * nq + 2 * nv), "layers/gdn/wout": (nv, d), "layers/attn/wq": (d, 2 * H * dh),
              "layers/attn/wk": (d, Hkv * dh), "layers/attn/wv": (d, Hkv * dh), "layers/attn/wo": (H * dh, d),
              "layers/moe/shared/gate": (d, f), "layers/moe/shared/up": (d, f), "layers/moe/shared/down": (f, d)}
    assert {(s["site"].split(",")[0], s["din"], s["dout"]) for s in sites} == {(k, *v) for k, v in shapes.items()}


def test_the_bf16_state_control_ends_not_correct_by_the_state_check_alone(tmp_path, monkeypatch):
    """``BENCH_BF16_STATE`` through ``run.py`` itself, rehearsed: the program
    carries its recurrent state in bfloat16, every other check passes, and the
    line says ``correct: false``."""
    from benchmarks.tests.test_run import last_line, run

    monkeypatch.setenv("BENCH_BF16_STATE", "1")
    proc = run(ROOT, "--workload", CELL, "--seed", "3", "--seconds", "1", "--trace", "0", "--rehearse",
               "--out", str(tmp_path / "out"))
    assert last_line(proc)["correct"] is False and "CONTROL (BENCH_BF16_STATE)" in proc.stdout
    checks = json.loads((tmp_path / "out" / "result.json").read_text())["driver"]["checks"]
    assert [name for name, ok, _ in checks if not ok] == ["recurrent_state_is_float32"]
    toy = CONFIG["rehearse"]["inputs"]["lm"]
    halved = gdn_moe.carried_state_bytes(toy, 64) - 64 * 3 * gdn_moe.state_elements(toy) * 2
    assert f"lm/state_bytes [{float(halved)}]" in next(d for name, _, d in checks if name == "recurrent_state_is_float32")

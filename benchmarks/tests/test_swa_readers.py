"""The readers, the work functions and the driver the ``mimo_v2_flash`` cell
adds (``mimo309b-ep32-train-pop16x8``), against values worked out by hand: the
flops file at the published widths (the figures the configuration file
states) and at a tiny shape, the three scope readers on the hand-written
two-chip trace with a window-attention scope table, silence on a program that
lacks the scopes, ``window_cache_is_bounded`` on fixture records, the driver's
refusal of a program whose parser does not know the family (the parent's
raises on the ``model_type``), and the cell rehearsed through ``run.py``."""

import importlib
import json
from pathlib import Path

import pytest

from benchmarks.flops import common as c
from benchmarks.flops import gqa_swa_moe
from benchmarks.tests.test_scope_readers import FIXTURES, make_record, read

ROOT = Path(__file__).resolve().parents[2]
CELL = "mimo309b-ep32-train-pop16x8"
CONFIG = json.loads((ROOT / "benchmarks/configs/mimo-v2-flash-ep32.json").read_text())
TRAFFIC = json.loads((ROOT / "benchmarks/traffic/train-lm-swa-pop16x8.json").read_text())
MODEL = CONFIG["model"]
NEW = ("lm_swa_device_s", "swa_attend_roofline", "full_attend_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_parameter_counts_caches_and_reads_at_the_published_widths():
    lm = MODEL["lm"]
    # Wq 4096 x 64 x 192, Wk 4096 x 4 x 192, Wv 4096 x 4 x 128, Wo 64 x 128 x 4096; the window layers' 8 KV heads
    assert gqa_swa_moe.attn_params(lm, False) == 50_331_648 + 3_145_728 + 2_097_152 + 33_554_432 == 89_128_960
    assert gqa_swa_moe.attn_params(lm, True) == 50_331_648 + 6_291_456 + 4_194_304 + 33_554_432 == 94_371_840
    assert gqa_swa_moe.expert_params(lm) == 25_165_824 and gqa_swa_moe.dense_ffn_params(lm) == 201_326_592
    assert gqa_swa_moe.kinds(lm) == (3, 9) and gqa_swa_moe.moe_layers(lm) == 11
    # 128 sequences: full layers over 640 slots, the rings over 128, the limit over 64 + 128, full-length windows
    assert gqa_swa_moe.full_cache_bytes(lm, 128) == 3 * 128 * 640 * 4 * 320 * 2 == 629_145_600
    assert gqa_swa_moe.window_cache_bytes_max(lm, 128) == 9 * 128 * 192 * 8 * 320 * 2 == 1_132_462_080
    assert gqa_swa_moe.window_cache_bytes_ring(lm, 128) == 754_974_720
    assert 9 * 128 * 640 * 8 * 320 * 2 == 3_774_873_600
    reads = gqa_swa_moe.read_bytes_per_position(MODEL, 128)
    assert reads["total"] == pytest.approx(4.98e9, abs=0.005e9)
    assert {k: round(v / 1e9, 2) for k, v in reads.items() if k != "total"} == {
        "experts": 2.21, "dense_ffn": 0.2, "attention_projections": 1.12, "full_kv": 0.63, "window_kv": 0.75,
        "routers_and_head": 0.06}
    assert 3.80e9 < gqa_swa_moe.weight_bytes(lm) < 3.82e9   # about 3.8 GB of weights


def test_every_key_of_the_catalog_row_is_in_the_file_and_the_model_unchanged():
    lm = MODEL["lm"]
    same = [k for k in lm if k in CONFIG and k not in ("hybrid_layer_pattern", "moe_layer_freq",
                                                          "num_hidden_layers", "vocab_rows_held")]
    assert len(same) >= 30 and all(lm[k] == CONFIG[k] for k in same)
    assert (CONFIG["num_hidden_layers"], CONFIG["vocab_size"], CONFIG["n_routed_experts"]) == (48, 152576, 256)
    assert lm["hybrid_layer_pattern"] == CONFIG["hybrid_layer_pattern"][:12] == [
        0 if t == "full_attention" else 1 for t in TRAFFIC["layer_types"]]
    assert lm["moe_layer_freq"] == CONFIG["moe_layer_freq"][:12] == [int(t == "moe") for t in TRAFFIC["ffn_types"]]
    assert (lm["num_hidden_layers"], lm["experts_held"], lm["vocab_rows_held"]) == (12, 8, 152576 // 8)
    img = lm["image_tokens"]
    assert img["image_id_offset"] + img["image_vocab"] == lm["vocab_rows_held"] and img["grid"] == 24
    toy = CONFIG["rehearse"]["inputs"]["lm"]
    assert toy["hybrid_layer_pattern"] == lm["hybrid_layer_pattern"] and toy["moe_layer_freq"] == lm["moe_layer_freq"]
    assert toy["sliding_window"] < toy["image_tokens"]["max_prompt_len"] + toy["image_tokens"]["grid"] ** 2


def test_work_by_hand_at_a_tiny_shape():
    lm = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 3, "v_head_dim": 2,
          "swa_num_attention_heads": 2, "swa_num_key_value_heads": 2, "swa_head_dim": 3, "swa_v_head_dim": 2,
          "sliding_window": 2, "hybrid_layer_pattern": [0, 1, 1], "moe_layer_freq": [0, 1, 1],
          "num_hidden_layers": 3, "intermediate_size": 5, "moe_intermediate_size": 3, "n_routed_experts": 4,
          "num_experts_per_tok": 2, "experts_held": 2, "vocab_rows_held": 10, "torch_dtype": "bfloat16",
          "image_tokens": {"grid": 2, "image_vocab": 3, "max_prompt_len": 3}}
    m = {"lm": lm, "prompt_tokens_mean": 2}
    full = 4 * 2 * 3 + 4 * 1 * 5 + 2 * 2 * 4
    window = 4 * 2 * 3 + 4 * 2 * 5 + 2 * 2 * 4
    assert (gqa_swa_moe.attn_params(lm, False), gqa_swa_moe.attn_params(lm, True)) == (full, window)
    T = 2 + 4
    per_token = full + 2 * window + 3 * 4 * 5 + 2 * (4 * 4 + 2 * 2 / 4 * 3 * 4 * 3)
    attn = 2 * 5 * (T * (T + 1) // 2) + 2 * 2 * 5 * (1 + 2 * (T - 1))    # the window: 1, then 2 keys a query
    assert gqa_swa_moe.transformer(m) == pytest.approx(T * per_token + attn + 4 * 4 * 3)
    # 5 sequences: two window layers, a query sees 1, 2, 2, ... slots; the prompt's 2 positions, 4 sampled seeing 2
    flops, bytes_ = gqa_swa_moe.window_attend_work(m, 5)
    assert flops == 2.0 * 2 * 5 * 2 * 5 * ((1 + 2) + 4 * 2)
    assert bytes_ == 2 * 5 * ((2 + 4 * 2) * 2 * 5 * 2 + 6 * 2 * 5 * 2)
    flops, bytes_ = gqa_swa_moe.full_attend_work(m, 5)
    assert flops == 2.0 * 2 * 5 * 1 * 5 * ((1 + 2) + (3 + 4 + 5 + 6))
    assert bytes_ == 1 * 5 * ((2 + 18) * 1 * 5 * 2 + 6 * 2 * 5 * 2)
    assert gqa_swa_moe.full_cache_bytes(lm, 5) == 1 * 5 * 7 * 1 * 5 * 2
    assert gqa_swa_moe.window_cache_bytes_max(lm, 5) == 2 * 5 * (3 + 2) * 2 * 5 * 2


def test_flops_per_image_and_the_attend_floors():
    parts = gqa_swa_moe.flops_per_image(MODEL)
    assert parts["total"] == pytest.approx(parts["generator"] + parts["decoder"] + parts["rewards"])
    assert parts["rewards"] == 2.0 * c.reward_towers(MODEL["reward_towers"])
    assert 1.6e12 < parts["generator"] < 1.9e12   # 1.3 G parameters a token x 2 FLOPs x 594 positions, and the rest
    # both memory-bound: the rings' 128 slots a position cost more than the full layers' seen slots
    for work, lo, hi in ((gqa_swa_moe.window_attend_work, 0.50, 0.55), (gqa_swa_moe.full_attend_work, 0.20, 0.25)):
        flops, bytes_ = work(MODEL, 128)
        assert flops / 197e12 < bytes_ / 819e9 and lo < bytes_ / 819e9 < hi


SWA_TABLE = {
    "while.1": "unattributed", "fusion.2": "~generate/lm_decode_step/lm_swa/attend",
    "fused_qlora.3": "generate/lm_decode_step/lm_attn/attend", "all-reduce.4": "generate/lm_prefill/lm_swa",
    "fusion.5": "generate/lm_decode_step/lm_moe/experts",
}


@pytest.fixture
def swa_run(tmp_path):
    flags = {"--pop_size": "16", "--prompts_per_gen": "8", "--member_batch": "16"}
    rec = make_record(tmp_path, FIXTURES / "two_chip_annotated.textproto", SWA_TABLE, flags=flags)
    rec.job.config, rec.job.chips, rec.job.peaks = CONFIG, 1, PEAKS
    return rec


def test_lm_swa_device_s_and_its_notes(swa_run):
    # a step: fusion.2 30 us on chip 0 and 40 on chip 1 (attend under lm_swa), the collective's uncovered 10 us
    # (lm_swa's own, in the prefill): means over two chips
    assert read("lm_swa_device_s", swa_run) == pytest.approx(35e-6 + 10e-6)
    assert any(n.startswith("lm_swa a step: attend 0.0000 s of 0.0000 s; lm_attn 0.0000 s (attend 0.0000 s); "
                            "lm_moe ") for n in swa_run.notes)


@pytest.mark.parametrize("name,work,seconds", [("swa_attend_roofline", "window_attend_work", 35e-6),
                                               ("full_attend_roofline", "full_attend_work", 20e-6)])
def test_attend_rooflines_are_the_floor_over_the_attend_scope(swa_run, name, work, seconds):
    flops, bytes_ = getattr(gqa_swa_moe, work)(MODEL, 128)
    assert read(name, swa_run) == pytest.approx(100 * max(flops / 197e12, bytes_ / 819e9) / seconds)
    assert any("/attend (128 sequences a step): floor 0." in n and "memory-bound" in n for n in swa_run.notes)


def test_a_program_without_the_scopes_reads_nothing(tmp_path):
    """The parent of this PR traced with this PR's benchmark files: on an old
    cell's table, for every configuration."""
    from benchmarks.tests.test_scope_readers import TWO_CHIP_TABLE

    rec = make_record(tmp_path, FIXTURES / "two_chip_annotated.textproto", TWO_CHIP_TABLE,
                      flags={"--pop_size": "8", "--prompts_per_gen": "4", "--member_batch": "2"})
    rec.job.peaks = PEAKS
    for path in sorted((ROOT / "benchmarks/configs").glob("*.json")):
        rec.job.config = json.loads(path.read_text())
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
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("mimo-v2-flash-ep32", "train-lm-swa-pop16x8", 1)


@pytest.mark.parametrize("window,kv,ok", [
    (754_974_720.0, 629_145_600.0, True),             # the program's rings
    (1_132_462_080.0, 629_145_600.0, True),           # prompt slots and a window's: the limit
    (3_774_873_600.0, 629_145_600.0, False),          # the window layers over cache_len slots
    (754_974_720.0, 1_258_291_200.0, False),          # the full layers' cache miscounted
    (None, 629_145_600.0, False),                     # not counted
], ids=["ring", "at-the-limit", "full-length-window", "kv-miscounted", "not-counted"])
def test_window_cache_is_bounded_on_fixture_records(swa_run, window, kv, ok):
    from benchmarks.drivers import es_train_ref_swa as drv

    rows = [{"epoch": e, "lm/kv_cache_bytes": kv, **({} if window is None else {"lm/window_cache_bytes": window})}
            for e in range(4)]
    (swa_run.run_dir / "metrics.jsonl").write_text("".join(json.dumps(r) + "\n" for r in rows))
    swa_run.first_epoch, swa_run.last_epoch = 1, 2
    name, passed, detail = drv.window_cache_is_bounded(swa_run.job, swa_run, gqa_swa_moe)
    assert (name, passed) == ("window_cache_is_bounded", ok)
    assert "<= 1132462080" in detail and "629145600 exactly" in detail and "over 2 steps" in detail


def job(tmp_path, **over):
    from benchmarks.record import Job

    return Job(cell={}, config=CONFIG, traffic=dict(TRAFFIC, **over), chips=1, seed=2**31 + 7, seconds=1.0,
               trace=False, rehearse=False, out_dir=tmp_path, bench_dir=ROOT / "benchmarks", peaks=None,
               t_process_start=0.0, clock_anchor=(0.0, 0.0))


def test_the_driver_refuses_a_program_that_does_not_know_the_family(tmp_path, monkeypatch):
    """With the program's parser the family is known; with other layer kinds
    it is not; with the parent's parser — which raises on the ``model_type`` —
    the driver exits 3 with one line, before anything is built."""
    from benchmarks.drivers import es_train_ref_swa as drv
    from hyperscalees_t2i_tpu.models import lm

    assert drv.parsed_as_stated(job(tmp_path)) == ""
    swapped = ["sliding_attention"] + TRAFFIC["layer_types"][1:]
    assert "does not know this family" in drv.parsed_as_stated(job(tmp_path, layer_types=swapped))
    assert "does not know this family" in drv.parsed_as_stated(job(tmp_path, ffn_types=["moe"] * 12))

    def parent_parser(path):
        raise ValueError(f"{path}: model_type 'mimo_v2_flash' is not a family this model code writes down "
                         "([deepseek_v3, pangu_ultra_moe, xing4_0, qwen3_next, granitemoehybrid])")

    monkeypatch.setattr(lm, "config_from_json", parent_parser)
    why = drv.parsed_as_stated(job(tmp_path))
    assert why.startswith("the program's parser refused") and "not a family" in why and "\n" not in why
    with pytest.raises(SystemExit) as e:
        drv.run(job(tmp_path))
    assert e.value.code == 3


def test_kernel_sites_list_every_fused_qlora_site():
    """At a sampled position: attention's four projections in 3 full and 9
    window layers and the dense FFN's three in layer 0; the prefill's last
    layer stops at its K and V."""
    sites = CONFIG["kernel_sites"]["fused_qlora"]
    decode = [s for s in sites if len(s["rows_per_image"]) == 576]
    assert len(decode) == 11 and sum(s["calls_per_image"] for s in decode) == 12 * 4 + 3
    prefill = [s for s in sites if s not in decode]
    assert sum(s["calls_per_image"] for s in prefill) == 12 * 4 - 2 + 3


def test_the_cell_rehearsed_through_run_py(tmp_path):
    """``--rehearse`` at toy widths (window 8 under sequences of up to 32
    positions): correct, with the reference's comparison and the window
    cache's count among its checks."""
    from benchmarks.tests.test_run import last_line, run

    proc = run(ROOT, "--workload", CELL, "--seed", "2147480001", "--seconds", "1", "--trace", "0", "--rehearse",
               "--out", str(tmp_path / "out"))
    assert last_line(proc)["correct"] is True
    checks = json.loads((tmp_path / "out" / "result.json").read_text())["driver"]["checks"]
    assert [name for name, ok, _ in checks if not ok] == []
    names = [name for name, _, _ in checks]
    assert {"routing_agrees_with_reference", "logits_agree_with_reference", "window_cache_is_bounded"} <= set(names)

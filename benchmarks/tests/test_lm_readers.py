"""The readers and the work functions the ``lm_ar`` cell adds, against values
worked out by hand: the flops file at the published widths (the figures
ISSUE 27 counts), the scope readers on the hand-written two-chip trace with a
language-model scope table, and the two roofline shares from known work."""

import importlib
import json
from pathlib import Path

import pytest

from benchmarks.flops import common as c
from benchmarks.flops import mla_moe
from benchmarks.tests.test_scope_readers import FIXTURES, make_record, read

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "benchmarks/configs/openpangu-ultra-moe-718b-ep16.json").read_text())
MODEL = CONFIG["model"]
NEW = ("lm_mla_device_s", "lm_moe_device_s", "moe_experts_roofline", "mla_attend_roofline", "moe_max_expert_load")


def test_parameter_counts_of_one_layer_at_the_published_widths():
    lm = MODEL["lm"]
    # 7680 x 1536 + 1536 x 128 x 192 + 7680 x 576 + 512 x 128 x 256 + 16384 x 7680
    assert mla_moe.mla_params(lm) == 11_796_480 + 37_748_736 + 4_423_680 + 16_777_216 + 125_829_120 == 196_575_232
    assert mla_moe.expert_params(lm) == 3 * 7680 * 2048 == 47_185_920
    assert mla_moe.dense_ffn_params(lm) == 3 * 7680 * 18432 == 424_673_280
    assert mla_moe.held_experts_per_token(lm) == 8 * 16 / 256 == 0.5


def test_flops_per_image_by_hand_at_a_tiny_shape():
    lm = {"hidden_size": 4, "num_attention_heads": 2, "q_lora_rank": 3, "kv_lora_rank": 2,
          "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 2, "intermediate_size": 6,
          "moe_intermediate_size": 3, "n_routed_experts": 8, "num_experts_per_tok": 2, "experts_held": 4,
          "num_hidden_layers": 2, "first_k_dense_replace": 1, "vocab_rows_held": 10,
          "image_tokens": {"grid": 2}}
    m = {"lm": lm, "prompt_tokens_mean": 3}
    mla = 4 * 3 + 3 * 2 * 4 + 4 * 4 + 2 * 2 * 4 + 2 * 2 * 4
    dense, expert = 3 * 4 * 6, 3 * 4 * 3
    T = 3 + 4
    per_token = 2 * mla + dense + (4 * 8 + (1 + 2 * 4 / 8) * expert)
    attn = 2 * 2 * (4 + 2) * (T * (T + 1) // 2)
    assert mla_moe.transformer(m) == T * per_token + attn + 4 * 4 * 10
    # experts: 10 pairs, 3 calls: FLOPs 2 x pairs x 3 matrices; bytes: 4 held experts' int8 base a call + activations
    assert mla_moe.experts_work(m, 10, 3) == (2.0 * 10 * expert, 3 * 4 * expert + 10 * 2 * (2 * 4 + 3 * 3))
    assert mla_moe.expert_calls_per_step(m, 4, 8) == 2 * 1 * (1 + 4)


def test_flops_per_image_of_the_cell_is_mostly_the_generator():
    parts = mla_moe.flops_per_image(MODEL)
    assert parts["total"] == pytest.approx(parts["generator"] + parts["decoder"] + parts["rewards"])
    assert parts["rewards"] == 2.0 * c.reward_towers(MODEL["reward_towers"])
    assert 0.9e12 < parts["generator"] < 1.2e12 and parts["generator"] > parts["decoder"] > parts["rewards"]


LM_TABLE = {
    "while.1": "unattributed", "fusion.2": "~generate/lm_decode_step/lm_mla/attend",
    "fused_qlora.3": "generate/lm_decode_step/lm_mla", "all-reduce.4": "generate/lm_prefill/lm_moe/router",
    "fusion.5": "generate/lm_decode_step/lm_moe/experts",
}


@pytest.fixture
def lm_run(tmp_path):
    flags = {"--pop_size": "8", "--prompts_per_gen": "8", "--member_batch": "8"}
    rec = make_record(tmp_path, FIXTURES / "two_chip_annotated.textproto", LM_TABLE, flags=flags)
    rec.job.config, rec.job.chips = CONFIG, 1
    rec.job.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    rows = [{"epoch": e, "moe/local_assignments": 1000.0 + e, "moe/max_expert_load": 3.0 + e} for e in range(4)]
    (rec.run_dir / "metrics.jsonl").write_text("".join(json.dumps(r) + "\n" for r in rows))
    rec.first_epoch, rec.last_epoch = 1, 2  # the window: epochs 1 and 2
    return rec


def test_scope_seconds_by_a_name_anywhere_in_the_path(lm_run):
    # a step: fusion.2 30 us on chip 0 and 40 on chip 1 (attend), fused_qlora.3 20 on both (lm_mla),
    # the collective's uncovered 10 us (router) and fusion.5 10 us (experts) on both: means over two chips
    assert read("lm_mla_device_s", lm_run) == pytest.approx((30 + 40) / 2 * 1e-6 + 20e-6)
    assert read("lm_moe_device_s", lm_run) == pytest.approx((10 + 10) * 1e-6)
    assert any(n.startswith("lm_moe a step: router 0.0000 s, experts 0.0000 s, shared 0.0000 s") for n in lm_run.notes)
    assert read("moe_max_expert_load", lm_run) == 5.0  # the largest of the window's rows (epochs 1, 2)


def test_roofline_shares_are_the_floor_over_the_scopes_seconds(lm_run):
    pairs, calls = 1001.5, 4 * 257  # the window's mean; 4 MoE layers x (prefill + 256 positions), one chunk
    flops, bytes_ = mla_moe.experts_work(MODEL, pairs, calls)
    floor = max(flops / 197e12, bytes_ / 819e9)
    assert read("moe_experts_roofline", lm_run) == pytest.approx(100 * floor / 10e-6)  # experts: fusion.5, 10 us a step
    flops, bytes_ = mla_moe.attend_work(MODEL, 64, 64)
    assert read("mla_attend_roofline", lm_run) == pytest.approx(100 * max(flops / 197e12, bytes_ / 819e9) / 35e-6)
    assert any("experts (1002 pairs, 1028 calls a step): floor" in n and "memory-bound" in n for n in lm_run.notes)


def test_a_program_without_the_scopes_or_counters_reads_nothing(tmp_path):
    """The parent of this PR on an old cell, traced with this PR's benchmark files."""
    from benchmarks.tests.test_scope_readers import TWO_CHIP_TABLE

    rec = make_record(tmp_path, FIXTURES / "two_chip_annotated.textproto", TWO_CHIP_TABLE,
                      flags={"--pop_size": "8", "--prompts_per_gen": "4", "--member_batch": "2"})
    rec.job.config = json.loads((ROOT / "benchmarks/configs/var-d16.json").read_text())
    rec.job.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert {name: read(name, rec) for name in NEW} == dict.fromkeys(NEW)
    assert rec.notes == []


@pytest.mark.parametrize("name", NEW)
def test_new_readers_state_what_the_manifest_states(name):
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert entry["workloads"] == ["pangu718b-ep16-train-pop8x8"]


def test_input_generator_writes_the_config_and_ids_below_the_image_range(tmp_path):
    from benchmarks.inputs import lm_config_and_prompt_ids as gen

    flags = gen.make(CONFIG["inputs"], MODEL, 2**31 + 5, tmp_path, ROOT / "benchmarks")
    assert flags[0::2] == ["--lm_config", "--prompt_token_ids"]
    written = json.loads(Path(flags[1]).read_text())
    assert {k: written[k] for k in MODEL["lm"]} == MODEL["lm"] and written["vq"]["ch"] == 160
    data = json.loads(Path(flags[3]).read_text())
    assert len(data["prompts"]) == len(data["ids"]) == 12
    assert all(1 <= len(r) <= 64 and all(2 <= t < 15104 for t in r) for r in data["ids"])
    again = json.loads(Path(gen.make(CONFIG["inputs"], MODEL, 2**31 + 5, tmp_path, ROOT / "benchmarks")[3]).read_text())
    assert again == data  # the same seed, the same inputs


def test_kernel_sites_list_the_sites_the_kernel_takes():
    """257 calls an image (prompt, then one row a step) at the dense sites
    whose contraction axis fits VMEM; the two it declines are named."""
    sites = CONFIG["kernel_sites"]["fused_qlora"]
    per_image = sum(len(s["rows_per_image"]) * s["calls_per_image"] for s in sites)
    assert per_image == 256 * (5 + 5 + 5 + 1 + 1 + 4 + 4 + 4) + (4 + 4 + 5 + 4 + 1 + 1 + 3 + 3 + 3) == 7452
    assert {s["rows_per_image"][0] for s in sites} == {1, 64}
    assert "wo" in CONFIG["kernel_sites"]["fused_qlora_not_listed"]

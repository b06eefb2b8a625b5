"""The readers, the work functions and the driver the ``granitemoehybrid``
cell adds (``granite4h-micro-train-pop8x8``), against values worked out by
hand: the flops file at the published widths (the figures the configuration file states)
and at a tiny shape, the two scope readers on the hand-written two-chip trace
with a Mamba-2 scope table, silence on a program that lacks the scopes, the
driver's refusal of a program whose parser does not know the family (the
parent's raises on the ``model_type``), and the cell rehearsed through
``run.py`` — with its float32 state, and with the bf16-state control."""

import importlib
import json
from pathlib import Path

import pytest

from benchmarks.flops import common as c
from benchmarks.flops import mamba2_gqa
from benchmarks.tests.test_scope_readers import FIXTURES, make_record, read

ROOT = Path(__file__).resolve().parents[2]
CELL = "granite4h-micro-train-pop8x8"
CONFIG = json.loads((ROOT / "benchmarks/configs/granite-4.0-h-micro.json").read_text())
TRAFFIC = json.loads((ROOT / "benchmarks/traffic/train-lm-ssm-pop8x8.json").read_text())
MODEL = CONFIG["model"]
NEW = ("lm_ssm_device_s", "ssd_update_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_parameter_counts_and_bytes_at_the_published_widths():
    lm = MODEL["lm"]
    # W_in 2048 x (4096 + 4352 + 64) + W_out 4096 x 2048 + the conv's 4 taps and bias over 4352 channels
    assert mamba2_gqa.mamba_params(lm) == 17_432_576 + 8_388_608 + 5 * 4352 == 25_842_944
    # Wq, Wo 2048 x 2048 each + Wk, Wv 2048 x 512 each; a SwiGLU of 8192
    assert mamba2_gqa.attn_params(lm) == 2 * 4_194_304 + 2 * 1_048_576 == 10_485_760
    assert mamba2_gqa.ffn_params(lm) == 3 * 2048 * 8192 == 50_331_648
    assert mamba2_gqa.layer_kinds(lm) == (36, 4)
    assert mamba2_gqa.state_elements(lm) == 64 * 64 * 128 == 524_288
    # 2.99 G of base at 1 B + 100352 x 2048 embedding rows in bf16: 3.40 GB of weights, the whole model on one chip
    assert mamba2_gqa.weight_bytes(lm) == 36 * 25_842_944 + 4 * 10_485_760 + 40 * 50_331_648 + 100_352 * 2048 * 2
    assert 3.39e9 < mamba2_gqa.weight_bytes(lm) < 3.41e9


def test_every_key_of_the_catalog_row_is_in_the_file_and_the_model_unchanged():
    lm = MODEL["lm"]
    same = [k for k in lm if k in CONFIG]
    assert len(same) >= 27 and all(lm[k] == CONFIG[k] for k in same)
    assert (CONFIG["num_hidden_layers"], CONFIG["vocab_size"], CONFIG["num_local_experts"]) == (40, 100352, 0)
    assert CONFIG["layer_types"] == TRAFFIC["layer_types"] * 4 and CONFIG["reduced"] == ["pop_size"]
    img = lm["image_tokens"]
    assert img["image_id_offset"] + img["image_vocab"] == lm["vocab_size"] == 100352   # the last 4096 ids
    toy = CONFIG["rehearse"]["inputs"]["lm"]
    assert toy["layer_types"] == TRAFFIC["layer_types"] and toy["num_hidden_layers"] == 10   # one period


def test_work_by_hand_at_a_tiny_shape():
    lm = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1, "shared_intermediate_size": 3,
          "layer_types": ["mamba", "attention", "mamba"], "mamba_n_heads": 2, "mamba_d_head": 4, "mamba_d_state": 3,
          "mamba_n_groups": 1, "mamba_d_conv": 2, "vocab_size": 10, "torch_dtype": "bfloat16",
          "image_tokens": {"grid": 2, "image_vocab": 5}}
    m = {"lm": lm, "prompt_tokens_mean": 3}
    I, C = 8, 8 + 6
    mamba = 4 * (I + C + 2) + I * 4 + 3 * C
    attn = 2 * 4 * 4 + 2 * 4 * 2
    state = 2 * 4 * 3
    assert (mamba2_gqa.mamba_params(lm), mamba2_gqa.attn_params(lm), mamba2_gqa.state_elements(lm)) == (mamba, attn, state)
    T = 3 + 4
    per_token = 2 * (mamba + 5 * state / 2) + attn + 3 * (3 * 4 * 3)
    assert mamba2_gqa.transformer(m) == T * per_token + 1 * 2 * 2 * 2 * (T * (T + 1) // 2) + 4 * 4 * 5
    # state update, 5 sequences, 2 Mamba-2 layers: 4 sampled positions read + write the f32 state, the prefill writes
    # it once; 5 FLOPs a state element a position, prompt and sampled
    assert mamba2_gqa.state_update_work(m, 5) == (2 * 5 * 7 * 5 * state, 2 * 5 * (4 * 2 * state * 4 + state * 4))
    # carried: the f32 state and one conv input (K - 1 = 1) of 14 channels in bf16, a layer
    assert mamba2_gqa.carried_state_bytes(lm, 5) == 5 * 2 * (state * 4 + 1 * C * 2)


def test_flops_per_image_and_the_figures_the_configuration_states():
    parts = mamba2_gqa.flops_per_image(MODEL)
    assert parts["total"] == pytest.approx(parts["generator"] + parts["decoder"] + parts["rewards"])
    assert parts["rewards"] == 2.0 * c.reward_towers(MODEL["reward_towers"])
    assert 1.6e12 < parts["generator"] < 1.8e12   # 2.99 G parameters x 2 FLOPs x 274 positions, and the rest
    lm = MODEL["lm"]
    # lm/state_bytes at 64 sequences: 64 x 36 x (2097152 + 26112)
    assert mamba2_gqa.carried_state_bytes(lm, 64) == 64 * 36 * (2_097_152 + 26_112) == 4_892_000_256
    # the state read and written at 256 positions, and the prefill's one write: 2.47 TB a step, 3.0 s at 819 GB/s
    flops, bytes_ = mamba2_gqa.state_update_work(MODEL, 64)
    assert bytes_ == 36 * 64 * (256 * 2 + 1) * 2_097_152 and 2.47e12 < bytes_ < 2.48e12
    assert bytes_ / 819e9 == pytest.approx(3.03, abs=0.01) and flops / 197e12 < 0.1


SSM_TABLE = {
    "while.1": "unattributed", "fusion.2": "~generate/lm_decode_step/lm_ssm/ssd",
    "fused_qlora.3": "generate/lm_decode_step/lm_ssm", "all-reduce.4": "generate/lm_prefill/lm_ssm/conv",
    "fusion.5": "generate/lm_decode_step/lm_dense_ffn",
}


@pytest.fixture
def ssm_run(tmp_path):
    flags = {"--pop_size": "8", "--prompts_per_gen": "8", "--member_batch": "8"}
    rec = make_record(tmp_path, FIXTURES / "two_chip_annotated.textproto", SSM_TABLE, flags=flags)
    rec.job.config, rec.job.chips, rec.job.peaks = CONFIG, 1, PEAKS
    return rec


def test_lm_ssm_device_s_and_its_children(ssm_run):
    # a step: fusion.2 30 us on chip 0 and 40 on chip 1 (ssd), fused_qlora.3 20 on both (lm_ssm's own: the
    # input projection), the collective's uncovered 10 us (conv, in the prefill): means over two chips
    assert read("lm_ssm_device_s", ssm_run) == pytest.approx(35e-6 + 20e-6 + 10e-6)
    assert any(n.startswith("lm_ssm a step: conv 0.0000 s, ssd 0.0000 s, ssm_out 0.0000 s, the input projection")
               for n in ssm_run.notes)


def test_ssd_update_roofline_is_the_floor_over_the_ssd_scope(ssm_run):
    flops, bytes_ = mamba2_gqa.state_update_work(MODEL, 64)
    assert read("ssd_update_roofline", ssm_run) == pytest.approx(100 * max(flops / 197e12, bytes_ / 819e9) / 35e-6)
    assert any("ssd (64 sequences a step): floor 3.0" in n and "memory-bound" in n for n in ssm_run.notes)


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
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("granite-4.0-h-micro", "train-lm-ssm-pop8x8", 1)


def job(tmp_path, **over):
    from benchmarks.record import Job

    return Job(cell={}, config=CONFIG, traffic=dict(TRAFFIC, **over), chips=1, seed=2**31 + 7, seconds=1.0,
               trace=False, rehearse=False, out_dir=tmp_path, bench_dir=ROOT / "benchmarks", peaks=None,
               t_process_start=0.0, clock_anchor=(0.0, 0.0))


def test_the_driver_refuses_a_program_that_does_not_know_the_family(tmp_path, monkeypatch):
    """With the program's parser the family is known; with another period it
    is not; with the parent's parser — which raises on the ``model_type`` — the
    driver exits 3 with one line, before anything is built."""
    from benchmarks.drivers import es_train_ref_ssm as drv
    from hyperscalees_t2i_tpu.models import lm

    assert drv.refusal(job(tmp_path)) == ""
    assert "does not know this family" in drv.refusal(job(tmp_path, layer_types=["mamba", "attention"]))

    def parent_parser(path):
        raise ValueError(f"{path}: model_type 'granitemoehybrid' is not a family this model code writes down "
                         "([deepseek_v3, pangu_ultra_moe, xing4_0, qwen3_next])")

    monkeypatch.setattr(lm, "config_from_json", parent_parser)
    why = drv.refusal(job(tmp_path))
    assert why.startswith("the program's parser refused") and "not a family" in why and "\n" not in why
    with pytest.raises(SystemExit) as e:
        drv.run(job(tmp_path))
    assert e.value.code == 3


@pytest.mark.parametrize("state_bytes,ok", [(4_892_000_256.0, True), (2_476_081_152.0, False), (None, False)],
                         ids=["float32-state", "bfloat16-state", "not-counted"])
def test_correct_holds_the_ssm_state_to_float32_by_its_byte_count(ssm_run, state_bytes, ok):
    """``recurrent_state_is_float32`` through the hybrid cell's check: a step
    that carries its state in bfloat16 counts 36 x 64 x (2 MB -> 1 MB) less,
    and one that does not count it at all fails too."""
    from benchmarks.drivers import es_train_ref_family as drv

    rows = [{"epoch": e, **({} if state_bytes is None else {"lm/state_bytes": state_bytes})} for e in range(4)]
    (ssm_run.run_dir / "metrics.jsonl").write_text("".join(json.dumps(r) + "\n" for r in rows))
    ssm_run.first_epoch, ssm_run.last_epoch = 1, 2
    name, passed, detail = drv.state_is_float32(ssm_run.job, ssm_run, mamba2_gqa)
    assert (name, passed) == ("recurrent_state_is_float32", ok)
    assert "limit: 4892000256 exactly" in detail and "over 2 steps" in detail
    assert 4_892_000_256 - 36 * 64 * mamba2_gqa.state_elements(MODEL["lm"]) * 2 == 2_476_081_152


def test_kernel_sites_list_every_fused_qlora_site():
    """Nine sites, at a sampled position and in the prefill: the Mamba-2
    projections in 36 layers, attention's four in 4, the SwiGLU's three in 40."""
    sites = CONFIG["kernel_sites"]["fused_qlora"]
    decode = [s for s in sites if len(s["rows_per_image"]) == 256]
    assert len(decode) == 9 and sum(s["calls_per_image"] for s in decode) == 36 * 2 + 4 * 4 + 40 * 3
    assert {s["site"].split(",")[0] for s in sites} == {
        f"layers/{k}" for k in ("mamba/in_proj", "mamba/out_proj", "attn/wq", "attn/wk", "attn/wv", "attn/wo",
                                "ffn/gate", "ffn/up", "ffn/down")}


@pytest.mark.parametrize("control", [False, True], ids=["float32-state", "bf16-state-control"])
def test_the_cell_rehearsed_through_run_py(tmp_path, monkeypatch, control):
    """``--rehearse`` at toy widths: correct with the state as stated; with
    ``BENCH_BF16_STATE`` the line says ``correct: false`` by the state check
    alone."""
    from benchmarks.tests.test_run import last_line, run

    if control:
        monkeypatch.setenv("BENCH_BF16_STATE", "1")
    proc = run(ROOT, "--workload", CELL, "--seed", "2147480001", "--seconds", "1", "--trace", "0", "--rehearse",
               "--out", str(tmp_path / "out"))
    assert last_line(proc)["correct"] is (not control)
    checks = json.loads((tmp_path / "out" / "result.json").read_text())["driver"]["checks"]
    assert [name for name, ok, _ in checks if not ok] == (["recurrent_state_is_float32"] if control else [])
    assert "logits_agree_with_reference" in [name for name, _, _ in checks]

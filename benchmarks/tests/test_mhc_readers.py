"""The readers and the work functions the ``xing4_0`` cell adds
(``xing29b-ep1-train-pop8x8``), against values worked out by hand: the flops
file at the published widths (the figures ISSUE 33 counts: 7.52 GB of weights,
1449 GB of expert reads a step) and at a tiny shape, the scope readers on the
hand-written two-chip trace with a hyper-connection scope table, silence on a
program that lacks the scopes, the driver's refusal of a program that does not
know the family, and the check that holds the coefficient path to float32."""

import importlib
import json
from pathlib import Path

import pytest

from benchmarks.flops import common as c
from benchmarks.flops import mhc_moe, mla_moe
from benchmarks.tests.test_scope_readers import FIXTURES, make_record, read

ROOT = Path(__file__).resolve().parents[2]
CELL = "xing29b-ep1-train-pop8x8"
CONFIG = json.loads((ROOT / "benchmarks/configs/xing4.0-29b-a4b-ep1.json").read_text())
TRAFFIC = json.loads((ROOT / "benchmarks/traffic/train-lm-mhc-pop8x8.json").read_text())
MODEL = CONFIG["model"]
NEW = ("lm_hc_device_s", "lm_hc_ops_per_call", "lm_mla_mhc_device_s", "lm_moe_mhc_device_s",
       "moe_experts_mhc_roofline", "mla_attend_mhc_roofline", "moe_max_expert_load_mhc")


def test_parameter_counts_and_bytes_at_the_published_widths():
    lm = MODEL["lm"]
    # wdq 3584 x 768 + wuq 768 x 32 x 192 + wdkv 3584 x 576 + wukv 512 x 32 x 256 + wo 4096 x 3584
    assert mhc_moe.mla_params(lm) == 2_752_512 + 4_718_592 + 2_064_384 + 4_194_304 + 14_680_064 == 28_409_856
    assert mhc_moe.dense_ffn_params(lm) == 3 * 3584 * 9216 == 99_090_432
    assert mhc_moe.expert_params(lm) == 3 * 3584 * 1024 == 11_010_048
    assert 64 * mhc_moe.expert_params(lm) == 704_643_072            # a whole layer's routed experts: 0.705 GB in int8
    # one sub-layer's hyper-connection: phi [14336, 24], b [24], three alphas
    assert mhc_moe.hc_params(lm) == 14336 * 24 + 24 + 3 == 344_091 and mhc_moe.sublayers(lm) == 18
    w = mhc_moe.weight_bytes(MODEL)
    int8 = 9 * 28_409_856 + 99_090_432 + 8 * 65 * 11_010_048 + 131072 * 3584
    assert w["int8_base"] == int8 == 6_549_766_144 and w["embedding_bf16"] == 2 * 131072 * 3584 == 939_524_096
    assert w["float32_parts"] == 4 * (8 * (3584 * 64 + 64) + 18 * 344_091) == 32_116_632
    assert w["total"] == 7_521_406_872                               # 7.52 GB: 47 % of a 16 GB chip by weights alone
    assert w["total"] / 16e9 > 0.25


def test_every_published_key_is_in_the_file_unchanged_and_the_cut_is_depth_alone():
    lm = MODEL["lm"]
    cut = ("num_hidden_layers", "first_k_dense_replace", "num_nextn_predict_layers")
    same = [k for k in lm if k in CONFIG and k not in cut + ("vocab_rows_held",)]
    assert len(same) >= 27 and all(lm[k] == CONFIG[k] for k in same)
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"], CONFIG["q_lora_rank"], CONFIG["kv_lora_rank"],
            CONFIG["qk_nope_head_dim"], CONFIG["qk_rope_head_dim"], CONFIG["v_head_dim"]) == (3584, 32, 768, 512, 128, 64, 128)
    assert (CONFIG["intermediate_size"], CONFIG["moe_intermediate_size"], CONFIG["n_routed_experts"],
            CONFIG["num_experts_per_tok"], CONFIG["routed_scaling_factor"]) == (9216, 1024, 64, 4, 2)
    assert (CONFIG["hc_mult"], CONFIG["hc_sinkhorn_iters"], CONFIG["vocab_size"]) == (4, 20, 131072)
    assert (CONFIG["num_hidden_layers"], lm["num_hidden_layers"], CONFIG["layers"]) == (40, 9, 9)
    assert (CONFIG["first_k_dense_replace"], lm["first_k_dense_replace"], CONFIG["leading_dense_layers"]) == (2, 1, 1)
    assert (CONFIG["num_nextn_predict_layers"], lm["num_nextn_predict_layers"], CONFIG["mtp_modules"]) == (1, 0, 0)
    assert CONFIG["reduced"] == ["layers", "leading_dense_layers", "moe_layers", "mtp_modules", "pop_size"]
    # experts and vocabulary are held whole, and the file says so
    assert (lm["experts_held"], CONFIG["routed_experts_held"], lm["vocab_rows_held"], CONFIG["vocab_rows_held"]) == (
        64, 64, 131072, 131072) and set(CONFIG["not_reduced"]) == {"routed_experts_held", "vocab_rows_held"}
    img = lm["image_tokens"]
    assert img["image_id_offset"] + img["image_vocab"] == lm["vocab_rows_held"]   # the last 4096 ids
    assert (TRAFFIC["model_type"], TRAFFIC["hc_mult"]) == (lm["model_type"], lm["hc_mult"]) == ("xing4_0", 4)
    toy = CONFIG["rehearse"]["inputs"]["lm"]
    assert (toy["model_type"], toy["hc_mult"], toy["topk_method"]) == ("xing4_0", 4, "noaux_tc")


def test_hc_work_by_hand_at_a_tiny_shape():
    lm = {"hidden_size": 5, "hc_mult": 2, "hc_sinkhorn_iters": 3, "num_hidden_layers": 2, "first_k_dense_replace": 1,
          "image_tokens": {"grid": 2}, "torch_dtype": "float32"}
    m = {"lm": lm, "prompt_tokens_mean": 3}
    # a token, a sub-layer: norm 10, product 10 x 8, Sinkhorn 3 x 2 x 4, mixes 10 + 20 + 10
    per_token = 10 + 80 + 24 + 40
    assert mhc_moe.hc_macs_per_token(lm) == per_token and mhc_moe.hc_params(lm) == 10 * 8 + 8 + 3
    # 6 sequences in chunks of 3: (3 + 4) positions x 4 sub-layers each; calls: 2 chunks x 4 sub-layers x (1 + 4)
    tokens, calls = 6 * 7 * 4, 2 * 4 * 5
    assert mhc_moe.hc_calls_per_step(m, 3, 6) == calls
    assert mhc_moe.hc_work(m, 6, 3) == (2.0 * tokens * per_token, tokens * (2 * 10 + 2 * 5) * 4 + calls * 91 * 4)


def test_flops_per_image_and_the_floors_issue_33_counts():
    parts = mhc_moe.flops_per_image(MODEL)
    assert parts["total"] == pytest.approx(parts["generator"] + parts["decoder"] + parts["rewards"])
    assert parts["rewards"] == 2.0 * c.reward_towers(MODEL["reward_towers"])
    chain = 2.0 * (18 + 256) * 18 * mhc_moe.hc_macs_per_token(MODEL["lm"])
    assert parts["generator"] == pytest.approx(2.0 * mla_moe.transformer(MODEL) + chain)
    assert 0.001 < chain / parts["generator"] < 0.01                 # the chain is latency, not arithmetic
    # 8 routed layers x 257 calls, each reading 64 experts' int8 bases: 1449 GB a step, 1.77 s at 819 GB/s
    calls = mhc_moe.expert_calls_per_step(MODEL, 64, 64)
    assert calls == 8 * 257 == 2056
    assert mhc_moe.experts_work(MODEL, 0, calls)[1] == 2056 * 704_643_072 == pytest.approx(1448.7e9, rel=1e-4)
    assert mhc_moe.experts_work(MODEL, 0, calls)[1] / 819e9 == pytest.approx(1.769, abs=1e-3)
    # every pair of a call is computed here: 4 rows an expert a call
    assert mla_moe.held_experts_per_token(MODEL["lm"]) == 4 and 64 * 4 / 64 == 4
    # the chain's floor, one pass over the streams: 64 sequences x 274 positions x 18 sub-layers
    flops, bytes_ = mhc_moe.hc_work(MODEL, 64, 64)
    assert mhc_moe.hc_calls_per_step(MODEL, 64, 64) == 18 * 257 == 4626
    assert bytes_ / 819e9 == pytest.approx(0.0354, abs=5e-4) and flops / 197e12 < 0.002


MHC_TABLE = {
    "while.1": "unattributed", "fusion.2": "~generate/lm_decode_step/lm_hc/hc_sinkhorn",
    "fused_qlora.3": "generate/lm_decode_step/lm_mla", "all-reduce.4": "generate/lm_prefill/lm_hc/hc_mix",
    "fusion.5": "generate/lm_decode_step/lm_moe/experts",
}


@pytest.fixture
def mhc_run(tmp_path):
    flags = {"--pop_size": "8", "--prompts_per_gen": "8", "--member_batch": "8"}
    rec = make_record(tmp_path, FIXTURES / "two_chip_annotated.textproto", MHC_TABLE, flags=flags)
    rec.job.config, rec.job.traffic, rec.job.chips = CONFIG, TRAFFIC, 1
    rec.job.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    rows = [{"epoch": e, "moe/local_assignments": 1000.0 + e, "moe/max_expert_load": 3.0 + e,
             "lm/hc_row_err": 1.2e-6, "lm/hc_marginal_err": 2e-3, "lm/hc_offdiag_mass": 0.75} for e in range(4)]
    (rec.run_dir / "metrics.jsonl").write_text("".join(json.dumps(r) + "\n" for r in rows))
    rec.first_epoch, rec.last_epoch = 1, 2  # the window: epochs 1 and 2
    return rec


def test_scope_seconds_and_the_op_count_of_the_chain(mhc_run):
    # a step: fusion.2 30 us on chip 0 and 40 on chip 1 (hc_sinkhorn), the collective's uncovered 10 us (hc_mix),
    # fused_qlora.3 20 us (lm_mla) and fusion.5 10 us (experts) on both: means over two chips
    assert read("lm_hc_device_s", mhc_run) == pytest.approx(35e-6 + 10e-6)
    assert any(n.startswith("lm_hc a step: hc_coeff 0.0000 s, hc_sinkhorn 0.0000 s, hc_mix 0.0000 s, own")
               for n in mhc_run.notes)
    assert any(n.startswith("lm_hc (one pass over the streams): floor 0.0354 s a step (memory-bound") for n in mhc_run.notes)
    # two leaf ops under lm_hc a step on either chip, over the 18 x 257 calls the configuration makes a step
    assert read("lm_hc_ops_per_call", mhc_run) == pytest.approx(2 / 4626)
    assert any(n.startswith("lm_hc: 2 leaf ops a traced step over 4626 calls") for n in mhc_run.notes)
    assert read("lm_mla_mhc_device_s", mhc_run) == pytest.approx(20e-6)
    assert read("lm_moe_mhc_device_s", mhc_run) == pytest.approx(10e-6)
    assert read("moe_max_expert_load_mhc", mhc_run) == 5.0
    flops, bytes_ = mhc_moe.experts_work(MODEL, 1001.5, 2056)
    assert read("moe_experts_mhc_roofline", mhc_run) == pytest.approx(100 * max(flops / 197e12, bytes_ / 819e9) / 10e-6)
    assert read("mla_attend_mhc_roofline", mhc_run) is None          # no op of the fixture lies under `attend`


def test_a_program_without_the_scopes_reads_nothing(tmp_path):
    """The parent of this PR traced with this PR's benchmark files: an old
    cell's table under each configuration's files, this one's included."""
    from benchmarks.tests.test_scope_readers import TWO_CHIP_TABLE

    rec = make_record(tmp_path, FIXTURES / "two_chip_annotated.textproto", TWO_CHIP_TABLE,
                      flags={"--pop_size": "8", "--prompts_per_gen": "4", "--member_batch": "2"})
    rec.job.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for config in ("var-d16", "openpangu-ultra-moe-718b-ep16", "qwen3-next-80b-a3b-ep4", "xing4.0-29b-a4b-ep1"):
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


def _job(tmp_path):
    from benchmarks.record import Job

    return Job(cell={}, config=CONFIG, traffic=TRAFFIC, chips=1, seed=2**31 + 7, seconds=1.0, trace=False,
               rehearse=False, out_dir=tmp_path, bench_dir=ROOT / "benchmarks", peaks=None, t_process_start=0.0,
               clock_anchor=(0.0, 0.0))


def test_the_driver_refuses_a_program_that_does_not_know_the_family(tmp_path, monkeypatch):
    """``parsed_as_stated`` with the program's parser; with the parent's, which
    raises on the ``model_type``; and with one that copies the keys it knows."""
    from benchmarks.drivers import es_train_ref_mhc as drv
    from hyperscalees_t2i_tpu.models import lm

    job = _job(tmp_path)
    assert drv.parsed_as_stated(job) == ""
    job.traffic = dict(TRAFFIC, hc_mult=2)
    assert "does not know this family" in drv.parsed_as_stated(job)  # another number of streams than the file's
    job.traffic = TRAFFIC

    def parent(path):
        raise ValueError(f"{path}: model_type 'xing4_0' is not a family this model code writes down")

    monkeypatch.setattr(lm, "config_from_json", parent)
    assert "the program's parser refused config.json (model_type 'xing4_0')" in drv.parsed_as_stated(job)
    monkeypatch.setattr(lm, "config_from_json", lambda path: lm.LMConfig())
    assert "does not know this family" in drv.parsed_as_stated(job)
    with pytest.raises(SystemExit) as e:
        drv.run(job)
    assert e.value.code == 3


@pytest.mark.parametrize("row_err,ok", [(1.2e-6, True), (3.9e-3, False), (None, False)],
                         ids=["float32-path", "bfloat16-path", "not-counted"])
def test_correct_holds_the_coefficient_path_to_float32_by_its_row_sums(mhc_run, row_err, ok):
    from benchmarks.drivers import es_train_ref_mhc as drv

    rows = [{"epoch": e, "lm/hc_marginal_err": 3e-3, "lm/hc_offdiag_mass": 0.75,
             **({} if row_err is None else {"lm/hc_row_err": row_err})} for e in range(4)]
    (mhc_run.run_dir / "metrics.jsonl").write_text("".join(json.dumps(r) + "\n" for r in rows))
    name, passed, detail = drv.coefficients_are_float32(mhc_run.job, mhc_run)
    assert (name, passed) == ("hc_coefficients_are_float32", ok)
    assert f"limit <= {TRAFFIC['reference']['hc_row_err_max']}" in detail and "over 2 steps" in detail
    assert 1.2e-6 < TRAFFIC["reference"]["hc_row_err_max"] < 3.9e-3 / 10


def test_the_bf16_coefficient_control_ends_not_correct_by_its_check_alone(tmp_path, monkeypatch):
    """``BENCH_BF16_HC`` through ``run.py`` itself, rehearsed: the program
    computes its hyper-connection coefficients in bfloat16, every other check
    passes, and the line says ``correct: false``."""
    from benchmarks.tests.test_run import last_line, run

    monkeypatch.setenv("BENCH_BF16_HC", "1")
    proc = run(ROOT, "--workload", CELL, "--seed", "3", "--seconds", "1", "--trace", "0", "--rehearse",
               "--out", str(tmp_path / "out"))
    assert last_line(proc)["correct"] is False and "CONTROL (BENCH_BF16_HC)" in proc.stdout
    checks = json.loads((tmp_path / "out" / "result.json").read_text())["driver"]["checks"]
    assert [name for name, ok, _ in checks if not ok] == ["hc_coefficients_are_float32"]

"""bench.py ladder end-to-end on CPU (slow tier): the driver-facing artifact
must keep printing one valid JSON line with per-rung results and the MFU
honesty fields, whatever else refactors touch."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.mark.slow
def test_bench_tiny_ladder_cpu(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["BENCH_TINY"] = "1"
    env["BENCH_BUDGET_S"] = "400"
    env["JAX_COMPILATION_CACHE_DIR"] = os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", "/tmp/jax_test_cache"
    )
    env["BENCH_PROGRAMS_JSONL"] = str(tmp_path / "programs.jsonl")
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
    d = json.loads(line)
    assert d["metric"].startswith("population-evals/sec")
    assert d["value"] and d["value"] > 0
    assert d["unit"] == "imgs/sec"
    assert "mfu_gate_armed" in d and "baseline_estimated" in d
    tiny = d["rungs"]["tiny"]
    assert tiny["sync"] == "device_get" and tiny["prompts"] == 4
    # vs_baseline is only ever claimed at flagship geometry
    assert d["vs_baseline"] is None
    assert d["platform"] == "cpu" and not any("fallback" in k for k in d)
    # provenance stamp: artifact and rung records both comparable across PRs
    # (tools/bench_report.py --trend); schema 3 adds the XLA-ledger fields
    for rec in (d, tiny):
        assert rec["schema_version"] >= 3
        assert rec["jax_version"]
        assert "git_sha" in rec
    assert tiny["bytes_accessed"] and tiny["bytes_accessed"] > 0
    assert tiny["peak_bytes_est"] and tiny["peak_bytes_est"] > 0
    assert tiny["lowering_s"] > 0 and tiny["stablehlo_lines"] > 0
    assert len(tiny["stablehlo_sha256"]) == 16
    # roofline verdict is None on CPU (no peak table entry) but present
    assert "roofline_bound" in tiny and "predicted_step_time_s" in tiny
    assert tiny["mesh_shape"] == {"pop": 4, "data": 2}  # 8 virtual CPU devices
    # every AOT compile in the child appended a ledger record (plain program
    # + the 16-step chained program for the tiny rung)
    from hyperscalees_t2i_tpu.obs.xla_cost import load_programs

    progs = load_programs(tmp_path / "programs.jsonl")
    assert len(progs) >= 2
    assert {p["site"] for p in progs} == {"bench"}
    assert any(p["chain"] > 1 for p in progs)


def test_bench_without_a_tpu_exits_nonzero_and_publishes_no_value(tmp_path):
    """The ladder at its real sizes on a machine with no TPU: rc != 0 and no
    rate anywhere in the output — a CPU run is never published under the
    headline metric (the path that once wrote 2.74 imgs/s, "platform": "cpu",
    exit code 0 into a driver record)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("BENCH_TINY", None)
    env.pop("BENCH_RUNGS", None)
    env["BENCH_BUDGET_S"] = "200"
    env["BENCH_PROGRAMS_JSONL"] = str(tmp_path / "programs.jsonl")
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO,
    )
    assert proc.returncode != 0, proc.stdout[-2000:]
    d = json.loads([l for l in proc.stdout.splitlines() if l.startswith("{")][-1])
    assert d["value"] is None and d["vs_baseline"] is None
    assert "imgs_per_sec" not in proc.stdout
    # every rung was refused for the stated reason — including tiny: the
    # default ladder is a real-size measurement, not the CPU smoke
    assert set(d["rungs"]) == {"tiny", "small", "popscale", "mid", "flagship"}
    assert all("no TPU" in r["error"] for r in d["rungs"].values()), d["rungs"]
    # and a direct single-rung run says the same on stderr
    one = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--rung", "small"],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO,
    )
    assert one.returncode != 0 and "no TPU" in one.stderr
    assert not one.stdout.strip()


def test_physical_floor_check():
    import bench

    # plausible: 1 TFLOP step, 197 TFLOP/s peak → floor ≈ 5 ms
    assert bench.physical_floor_check(0.01, 1e12, 197e12, 1) is None
    # impossible: the measured time undercuts the floor
    err = bench.physical_floor_check(0.001, 1e12, 197e12, 1)
    assert err is not None and "IMPOSSIBLE" in err
    # multichip raises the floor's denominator
    assert bench.physical_floor_check(0.001, 1e12, 197e12, 8) is None
    # the gate cannot arm without a peak figure or a flop count
    assert bench.physical_floor_check(1e-9, 1e12, None, 1) is None
    assert bench.physical_floor_check(1e-9, 0.0, 197e12, 1) is None
    assert bench.physical_floor_check(1e-9, None, 197e12, 1) is None


def test_analytic_floor_flops():
    import numpy as np

    import bench

    frozen = {"w": np.zeros((10, 10), np.float32), "ids": np.zeros((5,), np.int32)}
    theta = {"a": np.zeros((7,), np.float32)}
    # 107 float params × 2 FLOPs × 3 images; int leaves don't count
    assert bench.analytic_floor_flops(frozen, theta, 3) == 2.0 * 107 * 3
    assert bench.analytic_floor_flops(frozen, theta, 0) == 2.0 * 107


def test_pallas_kernel_parity_helper(monkeypatch):
    """Off the TPU the parity probe reports None — no kernel ran, nothing to
    compare; the comparison itself only ever executes where the kernel
    does."""
    import bench

    monkeypatch.delenv("HSES_USE_PALLAS", raising=False)
    assert bench.pallas_kernel_parity() is None  # CPU test tier: XLA path


def test_bench_report_renders_from_artifact_and_log(tmp_path, capsys):
    from hyperscalees_t2i_tpu.tools import bench_report as br

    art = tmp_path / "BENCH_r99.json"
    art.write_text(json.dumps({
        "value": 5.0,
        "rungs": {
            "flagship": {"rung": "flagship", "geometry": "flagship", "pop": 4,
                         "imgs_per_sec": 5.0, "step_time_s": 0.8,
                         "step_time_single_dispatch_s": 0.9, "chain": 4,
                         "mfu": 0.12, "step_tflops": 16.2, "platform": "tpu",
                         "physical_floor_s": 0.08},
            "mid": {"rung": "mid", "error": "stalled"},
        },
    }))
    log = tmp_path / "rungs.log"
    log.write_text("\n".join([
        '{"hb": "ar", "phase": "build"}',
        "[bench +  1.0s] noise line",
        json.dumps({"rung": "ar", "geometry": "ar_small", "pop": 16,
                    "imgs_per_sec": 40.0, "step_time_s": 1.6, "chain": 0,
                    "platform": "tpu", "kernel_parity_maxdiff": 0.0078}),
    ]))
    assert br.main([str(art), "--log", str(log)]) == 0
    out = capsys.readouterr().out
    # knobs column: "—" for a pre-knob (schema < 3) record — schema-additive
    assert "| flagship | flagship | 4 | — | 5.0 | 0.8 | 0.9 | 4 | 0.12 |" in out
    assert "| ar |" in out and "max |Δ| = 0.0078" in out
    assert "mid" not in out  # errored rung: not a table row
    # floor column flags an impossible published pair loudly
    art.write_text(json.dumps({"rungs": {"flagship": {
        "rung": "flagship", "geometry": "flagship", "imgs_per_sec": 5.0,
        "step_time_s": 0.01, "physical_floor_s": 0.08, "platform": "tpu"}}}))
    br.main([str(art)])
    assert "| NO |" in capsys.readouterr().out


def test_bench_report_empty_inputs(tmp_path):
    from hyperscalees_t2i_tpu.tools import bench_report as br

    art = tmp_path / "empty.json"
    art.write_text(json.dumps({"rungs": {"tiny": {"rung": "tiny", "error": "x"}}}))
    assert br.main([str(art)]) == 1


def test_bench_report_trend_mode(tmp_path, capsys):
    """--trend: one row per artifact in the given order, stamp columns, and
    per-rung imgs/sec side by side; unstamped (schema-1) artifacts render
    with '—' instead of crashing."""
    from hyperscalees_t2i_tpu.tools import bench_report as br

    old = tmp_path / "BENCH_r01.json"  # pre-stamp artifact
    old.write_text(json.dumps({
        "value": 3.0, "platform": "cpu",
        "rungs": {"tiny": {"rung": "tiny", "imgs_per_sec": 3.0}},
    }))
    new = tmp_path / "BENCH_r06.json"  # schema-2 stamped artifact
    new.write_text(json.dumps({
        "value": 7.5, "platform": "tpu", "schema_version": 2,
        "git_sha": "abc1234", "jax_version": "0.4.37",
        "rungs": {
            "tiny": {"rung": "tiny", "imgs_per_sec": 6.0},
            "mid": {"rung": "mid", "imgs_per_sec": 7.5},
            "broken": {"rung": "broken", "error": "stalled"},
        },
    }))
    assert br.main(["--trend", str(old), str(new)]) == 0
    out = capsys.readouterr().out
    lines = out.splitlines()
    assert lines[0].startswith("| artifact | schema | git sha | jax | platform |")
    assert "tiny" in lines[0] and "mid" in lines[0]
    assert "broken" not in lines[0]  # errored rungs never become columns
    # ordered as given: r01 row before r06
    r01 = next(l for l in lines if "BENCH_r01" in l)
    r06 = next(l for l in lines if "BENCH_r06" in l)
    assert lines.index(r01) < lines.index(r06)
    assert "| — | — | — | cpu | 3.0 | 3.0 | — |" in r01
    assert "| 2 | abc1234 | 0.4.37 | tpu | 7.5 | 6.0 | 7.5 |" in r06
    # no artifacts at all is an error, not a crash
    assert br.main(["--trend"]) == 1

    # knob/kernel markers (schema-additive, ISSUE 10 + 11): an int8
    # rung is marked in its trend cell — its throughput only compares to
    # rows with the same marks — the Pallas env flags active at measurement
    # time render as P:<short names> (kernel-on vs kernel-off artifacts
    # were previously indistinguishable), and the per-rung table carries
    # the knobs column
    q8 = tmp_path / "BENCH_r07.json"
    q8.write_text(json.dumps({
        "value": 9.0, "platform": "tpu", "schema_version": 4,
        "rungs": {"mid": {"rung": "mid", "imgs_per_sec": 9.0,
                          "remat": "blocks", "reward_tile": 2,
                          "noise_dtype": "bfloat16", "tower_dtype": "bfloat16",
                          "base_quant": "int8"}},
    }))
    assert br.main(["--trend", str(new), str(q8)]) == 0
    out = capsys.readouterr().out
    assert "9.0 (q8)" in out
    assert "| 7.5 |" in out  # unmarked cell stays unmarked
    assert br.main([str(q8)]) == 0
    assert "blocks/t2/n-bf16/w-bf16/q8" in capsys.readouterr().out

    kern = tmp_path / "BENCH_r08.json"
    kern.write_text(json.dumps({
        "value": 9.5, "platform": "tpu", "schema_version": 4,
        "rungs": {"mid": {"rung": "mid", "imgs_per_sec": 9.5,
                          "remat": "blocks", "reward_tile": 2,
                          "noise_dtype": "bfloat16", "tower_dtype": "bfloat16",
                          "base_quant": "int8",
                          "pallas_env": {"HSES_FUSED_QLORA_PALLAS": "1",
                                         "HSES_USE_PALLAS": "0"}}},
    }))
    assert br.main(["--trend", str(q8), str(kern)]) == 0
    out = capsys.readouterr().out
    assert "9.5 (q8,P:flash-,qlora)" in out
    assert "9.0 (q8)" in out  # flag-free row unchanged beside it
    # the per-rung knobs column renders the same provenance
    assert br.main([str(kern)]) == 0
    assert "blocks/t2/n-bf16/w-bf16/q8/P:flash-,qlora" in capsys.readouterr().out


def _scaling_doc():
    """A synthetic SCALING artifact the shape bench.run_scaling emits."""
    rows = {
        "1": {"rung": "tiny", "imgs_per_sec": 100.0, "step_time_s": 0.16,
              "mesh_shape": None, "collective_bytes": 0.0, "collective_ops": 0,
              "opt_scores_digest": "aa" * 8, "t_comms_s": None},
        "2": {"rung": "tiny", "imgs_per_sec": 180.0, "step_time_s": 0.089,
              "mesh_shape": {"pop": 2, "data": 1}, "collective_bytes": 67520.0,
              "collective_ops": 37, "opt_scores_digest": "aa" * 8,
              "t_comms_s": 0.0089},
        "4": {"rung": "tiny", "error": "timeout after 600s at 4 device(s)"},
    }
    import bench

    return {
        "metric": "scaling-efficiency (imgs scored/sec/chip)",
        "rung": "tiny", "device_counts": [1, 2, 4],
        "platform_forced": "cpu", "rows": rows,
        "summary": bench.scaling_summary(rows),
        "schema_version": bench.BENCH_SCHEMA_VERSION,
    }


def test_scaling_summary_math():
    """imgs/sec/chip, efficiency vs the 1-device baseline, collective share
    — the artifact math, exercised without spawning bench children."""
    doc = _scaling_doc()
    by_n = {s["devices"]: s for s in doc["summary"]}
    assert by_n[1]["imgs_per_sec_per_chip"] == 100.0
    assert by_n[1]["efficiency"] == 1.0
    assert by_n[2]["imgs_per_sec_per_chip"] == 90.0
    assert by_n[2]["efficiency"] == 0.9
    # collective share = t_comms / step_time when both are known
    assert by_n[2]["collective_time_share_est"] == 0.1
    assert by_n[1]["collective_time_share_est"] is None
    # an errored count keeps its row (with the error) instead of vanishing
    assert by_n[4]["efficiency"] is None and by_n[4]["error"]
    # digests travel into the summary — the CI parity assert reads them
    assert by_n[1]["opt_scores_digest"] == by_n[2]["opt_scores_digest"]


def test_scaling_main_rejects_bad_args(capsys):
    import bench

    assert bench.scaling_main(["--scaling", "--rungs", "nonesuch"]) == 2
    assert "unknown rung" in capsys.readouterr().err
    # the 1-device row is the baseline: lists not starting at 1 are refused
    assert bench.scaling_main(["--scaling", "--devices", "2,4"]) == 2
    assert "starting at 1" in capsys.readouterr().err
    # an empty list is the same usage error, not an IndexError traceback
    assert bench.scaling_main(["--scaling", "--devices", ","]) == 2
    assert "starting at 1" in capsys.readouterr().err


def test_bench_report_trend_renders_scaling_artifact(tmp_path, capsys):
    """--trend with a SCALING artifact: its rows render as the dedicated
    per-device-count table (efficiency column) AFTER the rung trend, and
    plain v2/v3 bench artifacts keep parsing unchanged beside it."""
    from hyperscalees_t2i_tpu.tools import bench_report as br

    plain = tmp_path / "BENCH_r05.json"
    plain.write_text(json.dumps({
        "value": 7.5, "platform": "cpu", "schema_version": 3,
        "rungs": {"tiny": {"rung": "tiny", "imgs_per_sec": 7.5}},
    }))
    scaling = tmp_path / "SCALING_r01.json"
    scaling.write_text(json.dumps(_scaling_doc()))
    assert br.main(["--trend", str(plain), str(scaling)]) == 0
    out = capsys.readouterr().out
    assert "| artifact | schema |" in out  # the rung trend table survives
    assert "efficiency" in out  # the scaling table rendered
    assert "pop2×data1" in out
    assert "| 0.9 |" in out
    assert "timeout after 600s" in out  # errored counts stay visible
    # scaling-only invocation renders just the scaling table
    assert br.main(["--trend", str(scaling)]) == 0
    out = capsys.readouterr().out
    assert "efficiency" in out and "| artifact | schema |" not in out


def test_artifact_stamp_fields():
    import bench

    stamp = bench.artifact_stamp()
    assert stamp["schema_version"] == bench.BENCH_SCHEMA_VERSION >= 2
    assert stamp["jax_version"]  # jax is installed in the test env
    # in a git checkout the sha resolves; the field must exist either way
    assert "git_sha" in stamp


def test_rung_tables_consistent():
    """Every rung has a budget estimate; the default ladder only names real
    rungs; the flaggen decomposition rung must mirror flagship's pop/prompts/
    member_batch exactly or the (flagship − flaggen) subtraction is void."""
    import bench

    assert set(bench.RUNG_PLAN) == set(bench.RUNG_EST_S)
    assert all(r in bench.RUNG_PLAN for r in bench.RUNG_ORDER)
    assert bench.RUNG_PLAN["flaggen"][1:] == bench.RUNG_PLAN["flagship"][1:]
    assert all(r in bench.RUNG_PLAN for r in bench.RUNG_CHAIN)

"""``run.py`` end to end on the CPU: the last line's shape in a rehearsal, the
refusals, and the proof that a later PR adds a cell with new files and new
entries only. Each run is a process of its own, as the driver's are."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


def run(root: Path, *args: str, timeout: int = 900):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"))
    return subprocess.run([sys.executable, "benchmarks/run.py", *args], cwd=root, env=env,
                          capture_output=True, text=True, timeout=timeout)


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def metric_names(section: str, cell: str):
    return {m["name"] for m in MANIFEST[section] if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line(cell, trace, tmp_path):
    line = last_line(run(ROOT, "--workload", cell, "--seed", "3", "--seconds", "1",
                         "--trace", str(trace), "--rehearse", "--out", str(tmp_path / "out")))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    chips = next(w["chips"] for w in MANIFEST["workloads"] if w["name"] == cell)
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == chips
    assert line["rehearsal"] is True
    units = {m["name"]: m["unit"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]}
    assert all(set(v) == {"value", "unit"} and v["unit"] == units[k] for k, v in line["metrics"].items())
    if trace == 0:
        assert set(line["metrics"]) == metric_names("end_to_end", cell)
        assert line["metrics"]["setup_s"]["value"] > 0
        assert line["metrics"]["images_per_s_per_chip"]["value"] > 0
    else:
        # what the host's files give exists on the CPU; what the device trace
        # gives is left out of the line, never faked
        got = set(line["metrics"])
        assert {"setup_build_s", "setup_compile_s", "dispatches_per_step", "host_gap_share"} <= got
        assert got <= metric_names("per_layer", cell)
        assert not got & {"device_idle_share", "step_device_s", "model_flops_util"}
        assert line["metrics"]["dispatches_per_step"]["value"] == 1.0


def test_real_size_refuses_to_run_off_the_tpu(tmp_path):
    proc = run(ROOT, "--workload", MANIFEST["workloads"][0]["name"], "--seed", "0",
               "--seconds", "1", "--trace", "0", "--out", str(tmp_path / "out"))
    assert proc.returncode == 2 and "REFUSED" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_rehearsal_refuses_a_wrong_device_count(tmp_path):
    env_flags = "--xla_force_host_platform_device_count=2"
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=env_flags)
    proc = subprocess.run([sys.executable, "benchmarks/run.py", "--workload",
                           MANIFEST["workloads"][0]["name"], "--seed", "0", "--seconds", "1",
                           "--rehearse", "--out", str(tmp_path / "out")],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2 and not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    proc = run(tmp_path, "--workload", MANIFEST["workloads"][0]["name"], "--seed", "0",
               "--seconds", "1", "--trace", "0", "--rehearse")
    assert proc.returncode != 0 and not any(ln.startswith("{") for ln in proc.stdout.splitlines())


DUMMY_DRIVER = '''
"""A later PR's driver: new file, no edit of any file that was there."""
import time
from benchmarks.record import RunRecord

def peak_bytes(rec):
    return None

def run(job):
    import jax, jax.numpy as jnp
    rec = RunRecord(job=job, run_dir=job.out_dir / "run", work_per_step=job.traffic["items"])
    f = jax.jit(lambda x: (x * job.config["model"]["scale"]).sum())
    f(jnp.ones(8)).block_until_ready()
    rec.t_entry = rec.t_open = time.perf_counter()
    n = 0
    while time.perf_counter() - rec.t_open < job.seconds:
        f(jnp.ones(8)).block_until_ready(); n += 1
    rec.t_close, rec.first_epoch, rec.last_epoch = time.perf_counter(), 0, n - 1
    rec.end_to_end["dummy_items_per_s"] = n * rec.work_per_step / rec.window_s
    # every cell of this system yields images, so the metrics without a
    # `workloads` list are every driver's to report
    rec.end_to_end["images_per_s_per_chip"] = rec.end_to_end["dummy_items_per_s"] / job.chips
    return rec, {"correct": True, "attempted": n, "failed": 0}
'''
DUMMY_READER = '''
LAYER, UNIT, SOURCE, MOVES = "dummy layer", "count", "program_counter", "dummy_items_per_s"

def read(rec):
    return float(rec.epochs)
'''


def test_a_later_pr_adds_a_cell_with_new_files_and_entries_only(tmp_path):
    """Dummy configuration, traffic mix, driver, reader and end-to-end metric
    in a copy of the checkout: every file that was there keeps its bytes."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "hyperscalees_t2i_tpu", tmp_path / "hyperscalees_t2i_tpu")
    before = {p: p.read_bytes() for p in (tmp_path / "benchmarks").rglob("*") if p.is_file()}
    b = tmp_path / "benchmarks"
    (b / "configs" / "dummy.json").write_text(json.dumps(
        {"name": "dummy", "family": "dummy", "source": "none", "model": {"scale": 2.0},
         "reduced": [], "inputs": {"kind": "none"}}))
    (b / "traffic" / "dummy-mix.json").write_text(json.dumps({"kind": "dummy_loop", "items": 3}))
    (b / "drivers" / "dummy_loop.py").write_text(DUMMY_DRIVER)
    (b / "layer_metrics" / "dummy_epochs.py").write_text(DUMMY_READER)
    manifest = json.loads((tmp_path / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "dummy", "source": "none", "file": "benchmarks/configs/dummy.json",
                                "reduced": [], "why": "proof"})
    manifest["workloads"].append({"name": "dummy-cell", "config": "dummy", "traffic": "dummy-mix",
                                  "chips": 1, "why": "proof"})
    manifest["end_to_end"].append({"name": "dummy_items_per_s", "unit": "items/s", "better": "higher",
                                   "bound": 0.05, "source": "host_clock", "workloads": ["dummy-cell"]})
    manifest["per_layer"].append({"name": "dummy_epochs", "unit": "count", "better": "higher",
                                  "source": "program_counter", "layer": "dummy layer",
                                  "moves": "dummy_items_per_s", "workloads": ["dummy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    e2e = last_line(run(tmp_path, "--workload", "dummy-cell", "--seed", "0", "--seconds", "0.2",
                        "--trace", "0", "--rehearse"))
    assert set(e2e["metrics"]) == {"dummy_items_per_s", "images_per_s_per_chip", "setup_s", "peak_hbm_gb"}
    assert e2e["metrics"]["dummy_items_per_s"]["value"] > 0
    traced = last_line(run(tmp_path, "--workload", "dummy-cell", "--seed", "0", "--seconds", "0.2",
                           "--trace", "1", "--rehearse"))
    assert traced["metrics"]["dummy_epochs"] == {"value": float(traced["attempted"]), "unit": "count"}
    assert all(p.read_bytes() == data for p, data in before.items())

"""BENCHMARK.json against the files it names and the contract's own rules."""

import importlib
import json
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|head_dim|d_model|d_mlp|"
                   r"ff_ratio|expansion|experts_per_tok)")
ALL_METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmarks"]
    assert MANIFEST["command"][:2] == ["python3", "benchmarks/run.py"]
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 2 <= len(MANIFEST["workloads"]) <= 24 and 1 <= len(MANIFEST["configs"]) <= 24


@pytest.mark.parametrize("entry", MANIFEST["configs"] + MANIFEST["workloads"] + ALL_METRICS,
                         ids=lambda e: e["name"])
def test_names_units_and_keys(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic", "layer"):
        if key in entry and key != "layer":
            assert NAME.match(entry[key])
    for text in (entry.get("why"), entry.get("layer"), entry.get("source")):
        if text is not None:
            assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    if "unit" in entry:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
        allowed = {"name", "unit", "better", "source", "workloads"}
        allowed |= {"bound"} if entry in MANIFEST["end_to_end"] else {"layer", "moves"}
        assert set(entry) <= allowed


def test_no_two_entries_share_a_name():
    for section in (MANIFEST["configs"], MANIFEST["workloads"], ALL_METRICS):
        names = [e["name"] for e in section]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_cells_chips_and_configs():
    cells = MANIFEST["workloads"]
    assert [w["chips"] for w in cells].count(4) == 1
    assert all(w["chips"] in (1, 4) for w in cells)
    assert {w["config"] for w in cells} == {c["name"] for c in MANIFEST["configs"]}


@pytest.mark.parametrize("cfg", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    assert cfg["file"].startswith("benchmarks/configs/")
    data = json.loads((ROOT / cfg["file"]).read_text())
    assert data["name"] == cfg["name"] and data["reduced"] == cfg["reduced"]
    assert len(cfg["reduced"]) <= 16 and not any(WIDTH.search(k) for k in cfg["reduced"])
    assert data["source"].startswith(cfg["source"])
    assert (BENCH / "flops" / f"{data['family']}.py").exists()
    assert (BENCH / "inputs" / f"{data['inputs']['kind']}.py").exists()
    files = [c["file"] for c in MANIFEST["configs"]]
    assert files.count(cfg["file"]) == 1


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_traffic_files_and_drivers(cell):
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (BENCH / "drivers" / f"{traffic['kind']}.py").exists()
    assert traffic["warmup_epochs"] >= 2  # compile, then one warm step
    assert all(k.startswith("--") for k in traffic["flags"])
    # the kernels' roofline readers count calls from data, not from the program's flags
    config = json.loads((ROOT / next(c["file"] for c in MANIFEST["configs"]
                                     if c["name"] == cell["config"])).read_text())
    if config.get("kernel_sites"):
        assert isinstance(traffic["images_per_kernel_call"], int) and traffic["images_per_kernel_call"] >= 1


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_reader_of_each_per_layer_metric(metric):
    reader = importlib.import_module(f"benchmarks.layer_metrics.{metric['name']}")
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
        metric["layer"], metric["unit"], metric["source"], metric["moves"])
    assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    assert callable(reader.read)


def test_every_cell_reports_a_per_layer_metric_and_setup():
    for w in MANIFEST["workloads"]:
        assert any("workloads" not in m or w["name"] in m["workloads"] for m in MANIFEST["per_layer"])
    assert all("workloads" not in m for m in MANIFEST["end_to_end"])


def test_file_names_under_paths_use_name_characters():
    for p in BENCH.rglob("*"):
        if "__pycache__" in p.parts or p.suffix == ".pyc":
            continue
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", str(p.relative_to(ROOT))), p


def test_expected_records_parse():
    for p in (BENCH / "expected").glob("*/seed*.json"):
        data = json.loads(p.read_text())
        assert p.parent.name in {w["name"] for w in MANIFEST["workloads"]}
        assert all(isinstance(v, float) for v in data["epoch0_reward_means"].values())


def test_peaks_table_names_its_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "source" in peaks and list(peaks["device_kinds"]) == ["TPU v5 lite"]  # as a chip reported it
    v5e = peaks["device_kinds"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9

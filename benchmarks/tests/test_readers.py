"""Readers and the driver's memory arithmetic on hand-made records: the
hand-written two-chip trace, counters a TPU would report, and a step program's
record as ``programs.jsonl`` holds it."""

import types
from pathlib import Path

import pytest

from benchmarks import trace_reduce
from benchmarks.drivers import es_train
from benchmarks.layer_metrics import _shared, step_compiled_peak_gb
from benchmarks.record import MARK

FIXTURES = Path(__file__).resolve().parent / "fixtures"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SITE = {"site": "toy", "rows_per_image": 8, "din": 128, "dout": 128, "calls_per_image": 1}


def record(calls_per_image, images_per_kernel_call=1):
    """Two traced steps on two chips, each with one fused_qlora event a chip."""
    return types.SimpleNamespace(
        trace=trace_reduce.reduce_trace(FIXTURES / "two_chip_steps.textproto", mark=MARK),
        peaks=PEAKS, chips=2, work_per_step=2, notes=[],
        traffic={"images_per_kernel_call": images_per_kernel_call},
        config={"kernel_sites": {"fused_qlora": [dict(SITE, calls_per_image=calls_per_image)]},
                "model": {"lora": {"rank": 2, "es_rank": 1}}})


def test_roofline_share_when_the_trace_shows_the_calls_the_sites_list():
    rec = record(calls_per_image=1)  # 2 steps x 1 image a chip x 1 call = the 2 events seen
    share = _shared.kernel_roofline(rec, "fused_qlora")
    assert 0 < share < 100 and "2 events a chip" in rec.notes[0] and "2 calls expected" in rec.notes[0]


@pytest.mark.parametrize("calls_per_image", [2, 0.5])
def test_no_roofline_share_when_the_step_makes_other_calls(calls_per_image):
    rec = record(calls_per_image)
    assert _shared.kernel_roofline(rec, "fused_qlora") is None
    assert "no roofline share reported" in rec.notes[0]


def test_no_roofline_share_without_the_traffic_files_images_per_call():
    rec = record(calls_per_image=1, images_per_kernel_call=None)
    assert _shared.kernel_roofline(rec, "fused_qlora") is None and not rec.notes


def test_a_kernel_absent_from_the_trace_has_time_share_zero_and_no_roofline():
    rec = record(calls_per_image=1)
    assert _shared.kernel_time_share(rec, "no_such_kernel") == 0.0
    rec.config["kernel_sites"]["no_such_kernel"] = [SITE]
    assert _shared.kernel_roofline(rec, "no_such_kernel") is None


STATS = {  # four chips as the four-chip cell left them (GB): the build on chip 0
    "peak_bytes_in_use": [8_772, 3_385, 3_385, 3_385],
    "peak_bytes_reserved": [5_342, 5_342, 5_342, 5_342],
}


def memory_record(resident, peak_bytes=8_606):
    return types.SimpleNamespace(resident_bytes=resident, peak_after_build=8_772,
                                 step_programs=[{"label": "es_step_m4r1", "peak_bytes": peak_bytes}])


def test_peak_is_the_fullest_chip_by_the_runtimes_counters(monkeypatch, capsys):
    monkeypatch.setattr(es_train, "device_stat", lambda stat: STATS.get(stat, []))
    # chip 0: the build's 8772 against 3386 + 5342 = 8728; chips 1-3: 3385 + 5342 = 8727
    assert es_train.peak_bytes(memory_record([3_386, 3_385, 3_385, 3_385])) == 8_772
    # a step that reserved more than the build held: in use between steps + reserved
    assert es_train.peak_bytes(memory_record([3_386, 3_600, 3_385, 3_385])) == 3_600 + 5_342
    # the compiler's figure is printed beside the counters and enters nothing
    assert es_train.peak_bytes(memory_record([3_386] * 4, peak_bytes=99_999)) == 8_772
    assert "compiler's peak" in capsys.readouterr().out


def test_peak_is_none_where_the_backend_reports_no_memory(monkeypatch):
    monkeypatch.setattr(es_train, "device_stat", lambda stat: [])
    assert es_train.peak_bytes(memory_record([])) is None


def test_compiled_peak_reads_the_step_programs_record():
    assert step_compiled_peak_gb.read(memory_record([], peak_bytes=8.42e9)) == pytest.approx(8.42)
    assert step_compiled_peak_gb.read(types.SimpleNamespace(step_programs=[])) is None

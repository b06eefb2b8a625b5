"""chip_smoke.py — the quickest proof that the system still starts on the
chip — rehearsed on the CPU. What only a chip can show (Mosaic's verdict on
the kernels, device memory, the real widths) is the chip run's; what is under
test here is the script's own contract:

- the explicit tiny rehearsal drives ``train.cli.main`` end to end, over the
  CLI's own mesh logic (the test rig's 8 virtual devices → a 4×2 mesh, the
  four-chip shape), and passes its own assertions;
- asked for the real size where there is no TPU it exits non-zero and says
  why — it never chooses the CPU itself;
- a run directory left by an earlier call is never resumed: the second call
  executes its steps again;
- alone in a directory, without the rest of the repo, it fails.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


@pytest.fixture
def smoke_env(monkeypatch):
    # what chip_smoke would setdefault into the process: pin them here so
    # they are restored after the test (the int8 size floor especially must
    # not leak into later tests)
    monkeypatch.setenv("HF_HUB_OFFLINE", "1")
    monkeypatch.setenv("TRANSFORMERS_OFFLINE", "1")
    monkeypatch.setenv("HSES_BASE_QUANT_MIN_SIZE", "1")


def _last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def test_tiny_rehearsal_runs_the_trainer_and_a_rerun_executes_its_steps_again(
    smoke_env, tmp_path, capsys
):
    out = tmp_path / "smoke"
    assert chip_smoke.main(["--tiny", "--out", str(out)]) == 0
    last = _last_json(capsys.readouterr().out)
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 8}}
    report = json.loads((out / "smoke.json").read_text())
    assert report["rehearsal"] is True and report["epochs"] == 3
    assert report["mesh"] == {"pop": 4, "data": 2}  # every local device used
    assert report["peak_bytes_in_use"] == "not measured"  # a CPU has no HBM
    assert report["kernels_in_step"] == {}  # no Mosaic off the TPU
    assert len(report["pop_scores"]) == 3 and len(report["pop_scores"][0]) == 4
    # the toy widths still took the int8 + fused-LoRA route the real size takes
    rec = json.loads((out / "run" / "programs.jsonl").read_text().splitlines()[0])
    assert rec["geometry"]["base_quant"] == "int8"
    assert len((out / "run" / "metrics.jsonl").read_text().splitlines()) == 3

    # the directory now holds a finished 3-epoch run: with the trainer's
    # default (--resume auto) a second call would execute zero steps and pass
    assert chip_smoke.main(["--tiny", "--out", str(out)]) == 0
    assert _last_json(capsys.readouterr().out)["ok"] is True
    rows = [json.loads(l) for l in (out / "run" / "metrics.jsonl").read_text().splitlines()]
    assert [r["epoch"] for r in rows] == [0, 1, 2, 0, 1, 2]
    assert rows[-1]["obs/dispatches"] == 3


def test_real_size_without_a_tpu_refuses_and_names_the_reason(smoke_env, tmp_path, capsys):
    assert chip_smoke.main(["--out", str(tmp_path / "smoke")]) == 2
    cap = capsys.readouterr()
    assert "TPU only" in cap.err and "cpu" in cap.err
    assert '"ok"' not in cap.out  # no result line
    assert not (tmp_path / "smoke").exists()  # and nothing was started


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--tiny"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path, env=env,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "hyperscalees_t2i_tpu" in proc.stderr

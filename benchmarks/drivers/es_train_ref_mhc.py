"""``es_train_ref`` for the ``xing4_0`` configuration (pre-norm MLA and routed
experts inside hyper-connection streams): the closed loop, the probe, the two
figures and their limits are ``drivers/es_train_ref.py``'s and the comparison
is ``drivers/es_train_ref_family.compare_with_reference`` over
``benchmarks/reference/<family>_reference.py`` — both by import. What this
file adds:

- **before anything is built**, the generated ``config.json`` is parsed with
  the program's own parser and must come back saying the ``model_type`` and
  the ``hc_mult`` the traffic file states. A program that does not know the
  family either raises on the ``model_type`` or copies the keys it knows and
  would build and time another model: here it exits non-zero with one line,
  in seconds;
- with ``BENCH_LOWER_PRECISION`` set (a builder's aid, no argument) the two
  figures are also read for the reference with float8 (e4m3) sub-layer inputs
  and for the reference with its **hyper-connection coefficient path rounded
  to bfloat16** (the imported comparison calls its second hook ``bf16_state``;
  this reference's eighth argument is ``coeff_round`` and the reading is
  reported as ``bf16_hc_coefficients``). Nothing is judged by them;
- **the coefficient path is held to float32 by a counter**: every step of the
  window has to report ``lm/hc_row_err`` — the largest distance of a row sum
  of any ``H_res`` of the step from 1 — at or under the traffic file's
  ``hc_row_err_max``. Rows are normalized last, so float32 leaves ``hc_eps``
  and one rounding (1e-6), bfloat16 its own spacing (4e-3): no reading of the
  two figures separates the two as cleanly. A step that does not count it is
  not correct. ``BENCH_BF16_HC`` (a builder's aid, no argument) runs the
  control: the program computes the path in bfloat16 (``models/lm.HC_DTYPE``)
  and the run has to end ``correct: false``.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import tempfile
from pathlib import Path
from typing import Any, Dict, Tuple

from . import es_train_ref, es_train_ref_family

peak_bytes = es_train_ref.peak_bytes


def parsed_as_stated(job) -> str:
    """'' when the program's parser gives the model_type and the number of
    streams the traffic file states, else the one line to exit with."""
    from hyperscalees_t2i_tpu.models import lm

    spec = dict(job.config["inputs"])
    if job.rehearse:
        spec.update(job.config["rehearse"].get("inputs", {}))
    gen = importlib.import_module(f"benchmarks.inputs.{spec['kind']}")
    want_type, want_streams = job.traffic["model_type"], int(job.traffic["hc_mult"])
    with tempfile.TemporaryDirectory() as tmp:
        flags = gen.make(spec, job.config["model"], job.seed, Path(tmp), job.bench_dir)
        path = flags[flags.index("--lm_config") + 1]
        stated = json.loads(Path(path).read_text()).get("model_type")
        parse = getattr(lm, "config_from_json", None) or lm.LMConfig.from_json
        try:
            cfg = parse(path)
        except ValueError as e:
            return f"the program's parser refused {Path(path).name} (model_type {stated!r}): {e}"
    got = (getattr(cfg, "model_type", None), getattr(cfg, "hc_mult", None))
    if stated != want_type or got != (want_type, want_streams):
        return (f"the program parsed {Path(path).name} (model_type {stated!r}) as {type(cfg).__name__} with "
                f"model_type {got[0]!r} and hc_mult {got[1]!r}: it does not know this family")
    return ""


def run(job) -> Tuple[Any, Dict[str, Any]]:
    why = parsed_as_stated(job)
    if why:
        print(f"[bench] REFUSED: {why}", file=sys.stderr, flush=True)
        raise SystemExit(3)
    from hyperscalees_t2i_tpu.models import lm

    family = job.config["family"]
    real, stated = es_train_ref.compare_with_reference, lm.HC_DTYPE
    es_train_ref.compare_with_reference = lambda rec, seen, n: compare_with_reference(rec, seen, n, family)
    if os.environ.get("BENCH_BF16_HC"):
        import jax.numpy as jnp

        print("[bench] CONTROL (BENCH_BF16_HC): the program computes its hyper-connection coefficients in "
              "bfloat16; this run has to end correct: false", flush=True)
        lm.HC_DTYPE = jnp.bfloat16
    try:
        rec, verdict = es_train_ref.run(job)
    finally:
        es_train_ref.compare_with_reference, lm.HC_DTYPE = real, stated
    name, ok, detail = coefficients_are_float32(job, rec)
    print(f"[bench] check {name}: {'ok' if ok else 'FAILED'} {detail}", flush=True)
    verdict["correct"] = verdict["correct"] and ok
    verdict["report"]["checks"].append([name, ok, detail])
    return rec, verdict


def coefficients_are_float32(job, rec) -> Tuple[str, bool, str]:
    """Every step of the window counted its ``H_res`` row sums, and none lies
    further from 1 than a float32 normalisation leaves it."""
    limit = float(job.traffic["reference"]["hc_row_err_max"])
    got = [r.get("lm/hc_row_err") for r in rec.window_rows]
    ok = bool(got) and all(isinstance(v, (int, float)) and 0.0 <= v <= limit for v in got)
    beside = [(r.get("lm/hc_marginal_err"), r.get("lm/hc_offdiag_mass")) for r in rec.window_rows][:1]
    return ("hc_coefficients_are_float32", ok,
            f"lm/hc_row_err {got} over {len(rec.window_rows)} steps (limit <= {limit}); beside it "
            f"(lm/hc_marginal_err, lm/hc_offdiag_mass) {beside}, not judged")


def compare_with_reference(rec, seen: Dict[str, Any], sequences: int, family: str) -> Dict[str, Any]:
    result = es_train_ref_family.compare_with_reference(rec, seen, sequences, family)
    if "bf16_state" in result:  # the imported comparison's name for its second hook
        result["bf16_hc_coefficients"] = result.pop("bf16_state")
    return result

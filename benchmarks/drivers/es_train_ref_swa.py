"""``es_train_ref`` for the ``mimo_v2_flash`` configuration (sliding-window
attention with a sink beside full attention, routed experts with no shared
one): the closed loop, the probe, the two figures and their limits are
``drivers/es_train_ref.py``'s and the comparison is
``drivers/es_train_ref_family.compare_with_reference`` over
``benchmarks/reference/<family>_reference.py`` — both by import. What this
file adds:

- **before anything is built**, the generated ``config.json`` is parsed with
  the program's own parser and must come back saying the ``model_type`` and,
  layer by layer, the attention kind and FFN kind the traffic file states
  (``layer_types``, ``ffn_types``; the pattern is not periodic from layer 0,
  so it is compared whole). A program that does not know the family raises on
  the ``model_type`` or would build and time another model: here it exits 3
  with one line, in seconds;
- **the window layers' cache is held to the window by a count**
  (``window_cache_is_bounded``): every step of the window has to report
  ``lm/window_cache_bytes`` — counted by the program from the carried arrays
  — above 0 and at or under ``flops/<family>.window_cache_bytes_max`` (the
  prompt's slots and a window's a sequence, whatever ``cache_len`` is), and
  ``lm/kv_cache_bytes`` equal to ``flops/<family>.full_cache_bytes``, to the
  byte. A step whose window layers carry ``cache_len`` slots, or that counts
  neither, is not correct;
- with ``BENCH_LOWER_PRECISION`` set (an aid for setting the limits, no
  argument) the two figures are also read for the reference with float8
  (e4m3) sub-layer inputs and with the K and V a decode carries rounded to
  bfloat16 (the imported comparison calls its second hook ``bf16_state``;
  this reference's eighth argument is ``kv_round`` and the reading is
  reported as ``bf16_kv``), and the logits' figure for the reference with
  the window widened to the whole sequence (``window_removed``) and with the
  sinks removed (``sink_removed``), each against the reference proper.
  Nothing is judged by them: they say whether the limits can see the
  mechanism.
"""

from __future__ import annotations

import importlib
import json
import math
import sys
import tempfile
from pathlib import Path
from typing import Any, Dict, Tuple

from ..layer_metrics import _lm
from . import es_train_ref, es_train_ref_family

peak_bytes = es_train_ref.peak_bytes


def parsed_as_stated(job) -> str:
    """'' when the program's parser gives the model_type and the per-layer
    kinds the traffic file states, else the one line to exit with."""
    from hyperscalees_t2i_tpu.models import lm

    spec = dict(job.config["inputs"])
    if job.rehearse:
        spec.update(job.config["rehearse"].get("inputs", {}))
    gen = importlib.import_module(f"benchmarks.inputs.{spec['kind']}")
    want = (job.traffic["model_type"], list(job.traffic["layer_types"]), list(job.traffic["ffn_types"]))
    with tempfile.TemporaryDirectory() as tmp:
        flags = gen.make(spec, job.config["model"], job.seed, Path(tmp), job.bench_dir)
        path = flags[flags.index("--lm_config") + 1]
        stated = json.loads(Path(path).read_text()).get("model_type")
        parse = getattr(lm, "config_from_json", None) or lm.LMConfig.from_json
        try:
            cfg = parse(path)
        except ValueError as e:
            return f"the program's parser refused {Path(path).name} (model_type {stated!r}): {e}"
    got = (getattr(cfg, "model_type", None), list(getattr(cfg, "layer_types", ())),
           list(getattr(cfg, "ffn_types", ())))
    if stated != want[0] or got != want:
        return (f"the program parsed {Path(path).name} (model_type {stated!r}) as {type(cfg).__name__} with "
                f"model_type {got[0]!r}, layer types {got[1] or None} and FFN types {got[2] or None}: it does "
                "not know this family")
    return ""


def run(job) -> Tuple[Any, Dict[str, Any]]:
    why = parsed_as_stated(job)
    if why:
        print(f"[bench] REFUSED: {why}", file=sys.stderr, flush=True)
        raise SystemExit(3)
    family = job.config["family"]
    real = es_train_ref.compare_with_reference
    es_train_ref.compare_with_reference = lambda rec, seen, n: compare_with_reference(rec, seen, n, family)
    try:
        rec, verdict = es_train_ref.run(job)
    finally:
        es_train_ref.compare_with_reference = real
    name, ok, detail = window_cache_is_bounded(job, rec, importlib.import_module(f"benchmarks.flops.{family}"))
    print(f"[bench] check {name}: {'ok' if ok else 'FAILED'} {detail}", flush=True)
    verdict["correct"] = verdict["correct"] and ok
    verdict["report"]["checks"].append([name, ok, detail])
    return rec, verdict


def window_cache_is_bounded(job, rec, flops) -> Tuple[str, bool, str]:
    """Every step of the window counted a window cache above 0 and within the
    prompt's and a window's slots a sequence, and the full layers' cache to
    the byte."""
    lm = job.config["model"]["lm"]
    if job.rehearse:
        lm = job.config["rehearse"].get("inputs", {}).get("lm", lm)
    sequences = _lm.sequences(rec)[0]
    most, full = flops.window_cache_bytes_max(lm, sequences), flops.full_cache_bytes(lm, sequences)
    window = [r.get("lm/window_cache_bytes") for r in rec.window_rows]
    kv = [r.get("lm/kv_cache_bytes") for r in rec.window_rows]
    ok = bool(window) and all(isinstance(v, (int, float)) and 0 < v <= most for v in window) \
        and all(v == full for v in kv)
    return ("window_cache_is_bounded", ok,
            f"lm/window_cache_bytes {sorted(set(window), key=str)} over {len(window)} steps (limit: above 0, "
            f"<= {most}); lm/kv_cache_bytes {sorted(set(kv), key=str)} (limit: {full} exactly)")


def compare_with_reference(rec, seen: Dict[str, Any], sequences: int, family: str) -> Dict[str, Any]:
    ref = importlib.import_module(f"benchmarks.reference.{family}_reference")
    real, calls = ref.forward_batch, []

    def recording(*args, **kw):
        out = real(*args, **kw)
        if not calls:  # the reference proper
            calls.append((args, out))
        return out

    ref.forward_batch = recording
    try:
        result = es_train_ref_family.compare_with_reference(rec, seen, sequences, family)
    finally:
        ref.forward_batch = real
    if "bf16_state" in result:  # the imported comparison's name for its second hook
        result["bf16_kv"] = result.pop("bf16_state")
        result.update(mechanism_readings(rec, seen, real, calls[0]))
    return result


def mechanism_readings(rec, seen: Dict[str, Any], forward_batch, proper) -> Dict[str, Any]:
    """The logits' figure of the reference with its window widened to the
    whole sequence, and with its sinks removed, against the reference proper
    (the imported comparison's first call, whose arguments are reused)."""
    import jax.numpy as jnp
    import numpy as np

    (weights, n_layers, top, raw, ids, forced, *_), out = proper
    cfg = seen["backend"].cfg.model
    probe = np.load(rec.run_dir / "probe_epoch0.npz")
    lo, k = cfg.image_id_offset, probe["logits"].shape[1]
    every = cfg.image_tokens // k

    def probed(logits):
        return np.stack([np.asarray(logits[s, int(probe["prompt_len"][s]) + every * np.arange(k), lo: lo + cfg.image_vocab],
                                    np.float64) for s in range(ids.shape[0])])

    def without_sink(i):
        w = weights(i)
        return dict(w, sink=jnp.full_like(w["sink"], -jnp.inf)) if "sink" in w else w

    want = probed(out["logits"])
    variants = {"window_removed": (weights, {**raw, "sliding_window": ids.shape[1]}), "sink_removed": (without_sink, raw)}
    readings = {}
    for name, (w, r) in variants.items():
        got = probed(forward_batch(w, n_layers, top, r, ids, forced)["logits"])
        rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        readings[name] = {"logits_rel_l2": rel if math.isfinite(rel) else str(rel)}
    return readings

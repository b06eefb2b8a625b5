"""``es_train`` with a plain reference behind it, for a configuration without
a router (``granitemoehybrid`` with no experts: Mamba-2 mixers beside
attention, a dense SwiGLU): the closed loop is ``drivers/es_train.py``'s,
``run_training`` is captured as ``drivers/es_train_ref.py`` captures it, and
after the window what **epoch 0 of the timed program at the timed sizes**
produced is held to ``benchmarks/reference/<family>_reference.py``. What
differs from the routed families' drivers:

- **before anything is built**, the generated ``config.json`` is parsed with
  the program's own parser (``es_train_ref_family.parsed_as_stated``) and must
  come back saying the ``model_type`` and repeating the ``layer_types`` period
  the traffic file states. A parser that does not know the family raises on
  the ``model_type`` — that is caught here — or would build and time another
  model. Either way the run exits 3 with one line, in seconds;
- **one figure, the logits**: a dense model routes nothing, so the relative
  L2 of the probed logits (over the image-id range at every 16th sampled
  position of member 0's first sequences) against the reference's full causal
  forward over ``[prompt ‖ begin-of-image ‖ sampled ids]`` is the comparison,
  at or under the traffic file's ``logits_rel_l2_max``;
- with ``BENCH_LOWER_PRECISION`` set (an aid for setting the limit, no
  argument) the figure is also read for the reference with float8 (e4m3)
  sub-layer inputs and for the reference with its SSM state rounded to
  bfloat16 after every update, each against the reference proper. Nothing is
  judged by them;
- **the SSM state is held to float32 by a count, not by a reading**
  (``es_train_ref_family.state_is_float32``): every step of the window has to
  report ``lm/state_bytes`` — counted by the program from the carried arrays'
  own dtype — equal to ``flops/<family>.carried_state_bytes``, to the byte.
  ``BENCH_BF16_STATE`` (no argument) runs the control: the program carries
  its state in bfloat16 (``models/lm_hybrid.STATE_DTYPE``, the one constant
  the family reads), and the run has to end ``correct: false`` by this check.

``peak_bytes`` is read the moment the loop returns: the reference's float32
weights must not enter ``peak_hbm_gb``.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
import os
import sys
import time
from typing import Any, Dict, Tuple

from . import es_train, es_train_ref_family


def peak_bytes(rec):
    return rec.peak_bytes_at_close


def refusal(job) -> str:
    """'' when the program's parser gives the family the traffic file states,
    else the one line to exit with."""
    try:
        return es_train_ref_family.parsed_as_stated(job)
    except ValueError as e:
        return f"the program's parser refused the generated config.json (model_type {job.traffic['model_type']!r}): {e}"


def run(job) -> Tuple[Any, Dict[str, Any]]:
    why = refusal(job)
    if why:
        print(f"[bench] REFUSED: {why}", file=sys.stderr, flush=True)
        raise SystemExit(3)
    from hyperscalees_t2i_tpu.models import lm_hybrid
    from hyperscalees_t2i_tpu.train import cli

    trainer = importlib.import_module(cli.__package__ + ".trainer")
    seen: Dict[str, Any] = {}
    real, stated = trainer.run_training, lm_hybrid.STATE_DTYPE

    def capture(backend, reward_fn, tc, *a, **kw):
        seen.update(backend=backend, tc=tc)
        return real(backend, reward_fn, tc, *a, **kw)

    if os.environ.get("BENCH_BF16_STATE"):
        import jax.numpy as jnp

        print("[bench] CONTROL (BENCH_BF16_STATE): the program carries its SSM state in bfloat16; "
              "this run has to end correct: false", flush=True)
        lm_hybrid.STATE_DTYPE = jnp.bfloat16
    trainer.run_training = capture  # es_train wraps whatever the name holds, and restores it
    try:
        rec, verdict = es_train.run(job)
    finally:
        trainer.run_training, lm_hybrid.STATE_DTYPE = real, stated
    rec.peak_bytes_at_close = es_train.peak_bytes(rec)  # before the reference puts anything on the device

    family = job.config["family"]
    limits = job.traffic["reference"]
    t0 = time.perf_counter()
    try:
        figures = compare_with_reference(rec, seen, int(limits["sequences"]), family)
    except Exception as e:  # a comparison that cannot be made is a failed check, reported as one
        import traceback

        traceback.print_exc()
        figures = {"error": f"{type(e).__name__}: {e}"}
    if "error" in figures:
        checks = [("reference_comparison_made", False, figures["error"])]
    else:
        hi = None if job.rehearse else limits["logits_rel_l2_max"]  # toy float32 widths: printed, not judged
        checks = [("logits_agree_with_reference", hi is None or figures["logits_rel_l2"] <= hi,
                   f"{figures['logits_rel_l2']:.6f} (limit <= {hi})")]
    print(f"[bench] reference comparison took {time.perf_counter() - t0:.1f} s after the window: {figures}", flush=True)
    checks.append(es_train_ref_family.state_is_float32(job, rec, importlib.import_module(f"benchmarks.flops.{family}")))
    for name, ok, detail in checks:
        print(f"[bench] check {name}: {'ok' if ok else 'FAILED'} {detail}", flush=True)
    verdict["correct"] = verdict["correct"] and all(ok for _, ok, _ in checks)
    verdict["report"]["checks"] += [[n, ok, d] for n, ok, d in checks]
    verdict["report"]["reference"] = figures
    return rec, verdict


def compare_with_reference(rec, seen: Dict[str, Any], sequences: int, family: str) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hyperscalees_t2i_tpu.es import perturb_member, sample_noise
    from hyperscalees_t2i_tpu.es.sampling import epoch_key

    ref = importlib.import_module(f"benchmarks.reference.{family}_reference")
    files = sorted(rec.run_dir.glob("probe_epoch*.npz"))
    if not files or "backend" not in seen:
        raise RuntimeError("the run left no probe_epoch*.npz (or run_training was never reached)")
    if files[0].stem != "probe_epoch0":
        raise RuntimeError(f"the probe is {files[0].stem}: θ is only known at epoch 0")
    probe = np.load(files[0])
    backend, tc = seen["backend"], seen["tc"]
    cfg, params = backend.cfg.model, backend.params
    raw = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
           if isinstance(getattr(cfg, f.name), (int, float, bool))}

    # member 0's adapter, by the program's own common-random-numbers contract (es_train_ref says where it comes from)
    es_cfg = tc.es_config()
    theta = backend.init_theta(jax.random.fold_in(jax.random.PRNGKey(tc.seed), 17))
    k_noise, _ = jax.random.split(epoch_key(tc.seed, 0))
    noise = sample_noise(k_noise, theta, tc.pop_size, es_cfg)
    lora = jax.device_get(perturb_member(theta, noise, 0, tc.pop_size, es_cfg))
    del theta, noise

    n = min(sequences, probe["ids"].shape[0])
    off, k_img = cfg.image_id_offset, cfg.image_tokens
    T = cfg.max_prompt_len + 1 + k_img  # every sequence right-padded to one length: padding behind a causal model is unseen
    ids = np.zeros((n, T), np.int32)
    for s in range(n):
        L = int(probe["prompt_len"][s])
        seq = np.concatenate([probe["prompt_ids"][s, :L], [cfg.boi_id], probe["ids"][s, :-1] + off])
        ids[s, : len(seq)] = seq
    every = k_img // probe["logits"].shape[1]

    def reference(act=None, state_round=None):
        out = ref.forward_batch(lambda i: ref.block_weights(params, i, lora, backend.lora_scale),
                                cfg.num_hidden_layers, ref.top_weights(params), raw, jnp.asarray(ids), act, state_round)
        logits = out["logits"]
        return np.stack([np.asarray(logits[s, int(probe["prompt_len"][s]) + every * np.arange(probe["logits"].shape[1]),
                                           off: off + cfg.image_vocab], np.float64) for s in range(n)])

    def rel_l2(got, want):
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    want = reference()
    rel = rel_l2(np.asarray(probe["logits"][:n], np.float64), want)
    if not math.isfinite(rel):
        raise RuntimeError(f"non-finite figure: logits {rel}")
    result = {"logits_rel_l2": rel, "sequences": n, "probed_positions": int(probe["logits"].shape[1]), "epoch": 0}
    if os.environ.get("BENCH_LOWER_PRECISION"):
        lower = {"float8_activations": dict(act=lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype)),
                 # reduce_precision: XLA may drop a float32 -> bfloat16 -> float32 round trip as excess precision
                 "bf16_state": dict(state_round=lambda s: jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7))}
        for name, hooks in lower.items():
            result[name] = {"logits_rel_l2": rel_l2(reference(**hooks), want)}
    return result

"""``es_train_ref`` for a configuration whose plain reference is named by its
``family``: the closed loop, the probe, the two figures and their limits are
``drivers/es_train_ref.py``'s (run through it, by import); what differs is

- **before anything is built**, the generated ``config.json`` is parsed with
  the program's own parser and must come back saying the ``model_type`` and
  repeating the ``layer_types`` period the traffic file states. A program that
  does not know the family copies the keys it knows and fills the rest from
  another model's defaults: it would build and time that model and only then
  fail the comparison. Here it exits non-zero with one line, in seconds;
- the reference is ``benchmarks/reference/<family>_reference.py`` (the same
  four names: ``forward_batch``, ``block_weights``, ``top_weights``; every
  layer routes);
- with ``BENCH_LOWER_PRECISION`` set (a builder's aid, no argument) the two
  figures are also read for the reference with float8 (e4m3) sub-layer inputs
  *and* for the reference with its recurrent state rounded to bfloat16 after
  every update, each against the reference proper. The limits lie between the
  step's reading and the float8 one; nothing is judged by them;
- **the recurrent state is held to float32 by a count, not by a reading**: the
  two figures cannot tell a bfloat16 state from a float32 one (rounding the
  state moves the logits by a quarter of what the stated bf16 activations do),
  so every step of the window has to report ``lm/state_bytes`` - which the
  program counts from the carried arrays' own dtype - equal to
  ``flops/<family>.carried_state_bytes``, the hand count at 4 B an element
  of state, to the byte. A step whose state is narrower, or that does not
  count it, is not correct. ``BENCH_BF16_STATE`` (a builder's aid, no
  argument) runs the control: the program carries its state in bfloat16
  (``models/lm_hybrid.STATE_DTYPE``), and the run has to end ``correct:
  false`` by this check and no other.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import math
import os
import sys
import tempfile
from pathlib import Path
from typing import Any, Dict, Tuple

from ..layer_metrics import _lm
from . import es_train_ref

peak_bytes = es_train_ref.peak_bytes


def parsed_as_stated(job) -> str:
    """'' when the program's parser gives the family the traffic file states,
    else the one line to exit with."""
    from hyperscalees_t2i_tpu.models import lm

    spec = dict(job.config["inputs"])
    if job.rehearse:
        spec.update(job.config["rehearse"].get("inputs", {}))
    gen = importlib.import_module(f"benchmarks.inputs.{spec['kind']}")
    with tempfile.TemporaryDirectory() as tmp:
        flags = gen.make(spec, job.config["model"], job.seed, Path(tmp), job.bench_dir)
        path = flags[flags.index("--lm_config") + 1]
        want = json.loads(Path(path).read_text())["model_type"]
        parse = getattr(lm, "config_from_json", None) or lm.LMConfig.from_json
        cfg = parse(path)
    period = list(job.traffic["layer_types"])
    got_type = getattr(cfg, "model_type", None)
    got_layers = list(getattr(cfg, "layer_types", ()))
    n = len(got_layers)
    if want != job.traffic["model_type"] or got_type != want or not n or got_layers != (period * n)[:n]:
        return (f"the program parsed {Path(path).name} (model_type {want!r}) as {type(cfg).__name__} with model_type "
                f"{got_type!r} and layer types {got_layers[:len(period)] or None}: it does not know this family")
    return ""


def run(job) -> Tuple[Any, Dict[str, Any]]:
    why = parsed_as_stated(job)
    if why:
        print(f"[bench] REFUSED: {why}", file=sys.stderr, flush=True)
        raise SystemExit(3)
    from hyperscalees_t2i_tpu.models import lm_hybrid

    family = job.config["family"]
    real, stated = es_train_ref.compare_with_reference, lm_hybrid.STATE_DTYPE
    es_train_ref.compare_with_reference = lambda rec, seen, n: compare_with_reference(rec, seen, n, family)
    if os.environ.get("BENCH_BF16_STATE"):
        import jax.numpy as jnp

        print("[bench] CONTROL (BENCH_BF16_STATE): the program carries its recurrent state in bfloat16; "
              "this run has to end correct: false", flush=True)
        lm_hybrid.STATE_DTYPE = jnp.bfloat16
    try:
        rec, verdict = es_train_ref.run(job)
    finally:
        es_train_ref.compare_with_reference, lm_hybrid.STATE_DTYPE = real, stated
    name, ok, detail = state_is_float32(job, rec, importlib.import_module(f"benchmarks.flops.{family}"))
    print(f"[bench] check {name}: {'ok' if ok else 'FAILED'} {detail}", flush=True)
    verdict["correct"] = verdict["correct"] and ok
    verdict["report"]["checks"].append([name, ok, detail])
    return rec, verdict


def state_is_float32(job, rec, flops) -> Tuple[str, bool, str]:
    """Every step of the window counted, from its carried arrays' own dtype,
    the bytes the configuration's float32 state and conv windows come to."""
    lm = job.config["model"]["lm"]
    if job.rehearse:
        lm = job.config["rehearse"].get("inputs", {}).get("lm", lm)
    want = flops.carried_state_bytes(lm, _lm.sequences(rec)[0])
    got = sorted({r.get("lm/state_bytes") for r in rec.window_rows}, key=str)
    return ("recurrent_state_is_float32", got == [want],
            f"lm/state_bytes {got} over {len(rec.window_rows)} steps (limit: {want} exactly, "
            f"{flops.STATE_BYTES} B an element of state)")


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

    # member 0's adapter, by the program's own CRN contract (es_train_ref says where it comes from)
    es_cfg = tc.es_config()
    theta = backend.init_theta(jax.random.fold_in(jax.random.PRNGKey(tc.seed), 17))
    k_noise, _ = jax.random.split(epoch_key(tc.seed, 0))
    noise = sample_noise(k_noise, theta, tc.pop_size, es_cfg)
    lora = jax.device_get(perturb_member(theta, noise, 0, tc.pop_size, es_cfg))
    del theta, noise

    n = min(sequences, probe["ids"].shape[0])
    P, off, k_img = cfg.max_prompt_len, cfg.image_id_offset, cfg.image_tokens
    T = P + 1 + k_img  # every sequence right-padded to one length: padding behind a causal model is unseen
    ids = np.zeros((n, T), np.int32)
    mine = np.full((n, T) + probe["topk"].shape[2:], -1, np.int32)
    for s in range(n):
        L = int(probe["prompt_len"][s])
        seq = np.concatenate([probe["prompt_ids"][s, :L], [cfg.boi_id], probe["ids"][s, :-1] + off])
        ids[s, : len(seq)] = seq
        mine[s, : len(seq)] = np.concatenate([probe["topk"][s, :L], probe["topk"][s, P:]])  # prompt slots, then sampled
    # where the step routed nothing (padding; the last layer's prompt rows, whose prefill stops at what the layer
    # carries on) nothing probed reads the block's output: expert 0 is forced there, and the set is not compared
    real = (mine >= 0).all(-1)
    forced = jnp.asarray(np.where(real[..., None], mine, 0))

    def reference(act=None, state_round=None):
        out = ref.forward_batch(
            lambda i: ref.block_weights(params["layers"][i], f"layers/{i}", lora, backend.lora_scale),
            len(params["layers"]), ref.top_weights(params), raw, jnp.asarray(ids), forced, act, state_round)
        return np.sort(np.asarray(out["topk"]), axis=-1), out["logits"]

    every = k_img // probe["logits"].shape[1]

    def probed(logits):
        return np.stack([np.asarray(logits[s, int(probe["prompt_len"][s]) + every * np.arange(probe["logits"].shape[1]),
                                           off: off + cfg.image_vocab], np.float64) for s in range(n)])

    def figures(sets, logits, want_sets, want_logits):
        same = (sets[..., :, None] == want_sets[..., None, :]).any(-1).sum(-1) / sets.shape[-1]
        return (float(((sets == want_sets).all(-1) & real).sum() / real.sum()),
                float((same * real).sum() / real.sum()),
                float(np.linalg.norm(logits - want_logits) / np.linalg.norm(want_logits)))

    own, logits = reference()
    want = probed(logits)
    agree, overlap, rel = figures(mine, np.asarray(probe["logits"][:n], np.float64), own, want)
    if not (math.isfinite(rel) and math.isfinite(agree)):
        raise RuntimeError(f"non-finite figures: routing {agree}, logits {rel}")
    result = {"routing_agreement": agree, "routing_overlap": overlap, "logits_rel_l2": rel, "sequences": n,
              "token_layer_sets": int(real.sum()), "probed_positions": int(probe["logits"].shape[1]), "epoch": 0}
    if os.environ.get("BENCH_LOWER_PRECISION"):
        lower = {"float8_activations": dict(act=lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype)),
                 # reduce_precision: XLA may drop a float32 -> bfloat16 -> float32 round trip as excess precision
                 "bf16_state": dict(state_round=lambda s: jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7))}
        for name, hooks in lower.items():
            sets, low = reference(**hooks)
            a, o, r = figures(sets, probed(low), own, want)
            result[name] = {"routing_agreement": a, "routing_overlap": o, "logits_rel_l2": r}
    return result

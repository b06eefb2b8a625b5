"""``es_train`` with a plain reference behind it: the closed loop of ES steps
is ``drivers/es_train.py``'s, unchanged, and after the window what **epoch 0 of
the timed program at the timed sizes** produced is held to the float32
reference under ``benchmarks/reference/``.

What is compared (``--backend lm_ar``; the traffic file's ``reference`` group
holds the limits):

- the program's step leaves ``probe_epoch0.npz`` in its run directory: for
  member 0's first sequences, the prompt ids, the ids it sampled, the router's
  top-k at every cache slot of every MoE layer, and the logits over the
  image-id range at every 16th sampled position;
- the harness takes the frozen base from the ``backend`` object
  ``run_training`` is called with (the hook point ``es_train`` already uses)
  and member 0's adapter from the program's own common-random-numbers contract
  (``train.trainer.regenerate_member_images``: θ from ``init_theta`` under the
  run's seed, the perturbation from ``(seed, epoch, member)``) — the step does
  not emit 80 MB of factors an epoch;
- the reference's full causal forward over ``[prompt ‖ begin-of-image ‖ sampled
  ids]``, float32 at ``highest``, one layer's dequantized weights resident at
  a time, no cache, the absent experts absent as in the program.

Two figures, each printed beside its limit: **routing agreement**, the share
of (token, MoE layer) top-k sets that the program and the reference choose
alike, and the **relative L2 of the probed logits** with the reference's
routing forced to the step's (a departure from the plain forward, made so that
one flipped near-tie does not decide the logits' figure; the reference's own
choice is what the first figure reads).

With ``BENCH_LOWER_PRECISION`` set in the environment (a builder's aid like
``BENCH_KEEP_TRACE``, no argument) the same two figures are also read for the
reference itself with every sub-layer's input rounded to float8 (e4m3), the
nearest precision below the bf16 the configuration states, against the
reference proper: what an 8-bit activation path would read. The limits have
to lie between the two readings; nothing is judged by the second.

``peak_bytes`` is read the moment ``es_train.run`` returns: the reference's
float32 weights must not enter ``peak_hbm_gb``.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
import os
import time
from typing import Any, Dict, Tuple

from . import es_train


def run(job) -> Tuple[Any, Dict[str, Any]]:
    from hyperscalees_t2i_tpu.train import cli

    trainer = importlib.import_module(cli.__package__ + ".trainer")
    seen: Dict[str, Any] = {}
    real = trainer.run_training

    def capture(backend, reward_fn, tc, *a, **kw):
        seen.update(backend=backend, tc=tc)
        return real(backend, reward_fn, tc, *a, **kw)

    trainer.run_training = capture  # es_train wraps whatever the name holds, and restores it
    try:
        rec, verdict = es_train.run(job)
    finally:
        trainer.run_training = real
    rec.peak_bytes_at_close = es_train.peak_bytes(rec)  # before the reference puts anything on the device

    limits = job.traffic["reference"]
    t0 = time.perf_counter()
    try:
        figures = compare_with_reference(rec, seen, int(limits["sequences"]))
    except Exception as e:  # a comparison that cannot be made is a failed check, reported as one
        import traceback

        traceback.print_exc()
        figures = {"error": f"{type(e).__name__}: {e}"}
    checks = []
    if "error" in figures:
        checks.append(("reference_comparison_made", False, figures["error"]))
    else:
        lo, hi = limits["routing_agreement_min"], limits["logits_rel_l2_max"]
        if job.rehearse:
            # toy float32 widths: the figures are printed, the chip's limits do not apply
            lo, hi = None, None
        checks.append(("routing_agrees_with_reference", lo is None or figures["routing_agreement"] >= lo,
                       f"{figures['routing_agreement']:.6f} (limit >= {lo})"))
        checks.append(("logits_agree_with_reference", hi is None or figures["logits_rel_l2"] <= hi,
                       f"{figures['logits_rel_l2']:.6f} (limit <= {hi})"))
    for name, ok, detail in checks:
        print(f"[bench] check {name}: {'ok' if ok else 'FAILED'} {detail}", flush=True)
    print(f"[bench] reference comparison took {time.perf_counter() - t0:.1f} s after the window: {figures}", flush=True)
    verdict["correct"] = verdict["correct"] and all(ok for _, ok, _ in checks)
    verdict["report"]["checks"] += [[n, ok, d] for n, ok, d in checks]
    verdict["report"]["reference"] = figures
    return rec, verdict


def peak_bytes(rec):
    return rec.peak_bytes_at_close


def compare_with_reference(rec, seen: Dict[str, Any], sequences: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import lm_reference as ref
    from hyperscalees_t2i_tpu.es import perturb_member, sample_noise
    from hyperscalees_t2i_tpu.es.sampling import epoch_key

    files = sorted(rec.run_dir.glob("probe_epoch*.npz"))
    if not files or "backend" not in seen:
        raise RuntimeError("the run left no probe_epoch*.npz (or run_training was never reached)")
    epoch = int(files[0].stem[len("probe_epoch"):])
    probe = np.load(files[0])
    backend, tc = seen["backend"], seen["tc"]
    cfg, params = backend.cfg.model, backend.params
    # the model's config.json keys and the share, as the reference reads them
    raw = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
           if isinstance(getattr(cfg, f.name), (int, float, bool))}

    # member 0's adapter, by the program's own CRN contract
    es_cfg = tc.es_config()
    theta = backend.init_theta(jax.random.fold_in(jax.random.PRNGKey(tc.seed), 17))
    if epoch != 0:
        raise RuntimeError(f"the probe is of epoch {epoch}: θ is only known at epoch 0")
    k_noise, _ = jax.random.split(epoch_key(tc.seed, epoch))
    noise = sample_noise(k_noise, theta, tc.pop_size, es_cfg)
    lora = jax.device_get(perturb_member(theta, noise, 0, tc.pop_size, es_cfg))
    del theta, noise

    n = min(sequences, probe["ids"].shape[0])
    P, off, k_img = cfg.max_prompt_len, cfg.image_id_offset, cfg.image_tokens
    T = P + 1 + k_img  # every sequence right-padded to one length: padding behind a causal model is unseen
    ids = np.zeros((n, T), np.int32)
    forced = np.zeros((n, T, probe["topk"].shape[2], probe["topk"].shape[3]), np.int32)
    mine = np.full(forced.shape, -1, np.int32)
    for s in range(n):
        L = int(probe["prompt_len"][s])
        seq = np.concatenate([probe["prompt_ids"][s, :L], [cfg.boi_id], probe["ids"][s, :-1] + off])
        ids[s, : len(seq)] = seq
        slots = np.concatenate([probe["topk"][s, :L], probe["topk"][s, P:]])  # prompt slots, then sampled ones
        mine[s, : len(seq)] = slots
        forced[s, : len(seq)] = slots
    def reference(act=None):
        return ref.forward_batch(
            lambda i: ref.block_weights(params["layers"][i], f"layers/{i}", lora, backend.lora_scale),
            len(params["layers"]), ref.top_weights(params), raw, jnp.asarray(ids), jnp.asarray(forced), act)

    real = (mine >= 0).all(-1)
    every = k_img // probe["logits"].shape[1]

    def probed(logits):
        return np.stack([np.asarray(logits[s, int(probe["prompt_len"][s]) + every * np.arange(probe["logits"].shape[1]),
                                           off: off + cfg.image_vocab], np.float64) for s in range(n)])

    def figures(sets, logits, want_sets, want_logits):
        """(share of real (token, layer) top-k sets equal, mean share of a set's experts in common,
        relative L2 of the probed logits)."""
        same = (sets[..., :, None] == want_sets[..., None, :]).any(-1).sum(-1) / sets.shape[-1]
        return (float(((sets == want_sets).all(-1) & real).sum() / real.sum()),
                float((same * real).sum() / real.sum()),
                float(np.linalg.norm(logits - want_logits) / np.linalg.norm(want_logits)))

    out = reference()
    own, want = np.sort(np.asarray(out["topk"]), axis=-1), probed(out["logits"])
    agree, overlap, rel = figures(mine, np.asarray(probe["logits"][:n], np.float64), own, want)
    if not (math.isfinite(rel) and math.isfinite(agree)):
        raise RuntimeError(f"non-finite figures: routing {agree}, logits {rel}")
    result = {"routing_agreement": agree, "routing_overlap": overlap, "logits_rel_l2": rel, "sequences": n,
              "token_layer_sets": int(real.sum()), "probed_positions": int(probe["logits"].shape[1]), "epoch": epoch}
    if os.environ.get("BENCH_LOWER_PRECISION"):
        low = reference(lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype))
        a8, o8, r8 = figures(np.sort(np.asarray(low["topk"]), axis=-1), probed(low["logits"]), own, want)
        result["float8_activations"] = {"routing_agreement": a8, "routing_overlap": o8, "logits_rel_l2": r8}
    return result

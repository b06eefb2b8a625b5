"""``models/lm.generate`` hands its decode scan the head's image-id columns
(every leaf of ``params["head"]`` cut to ``[image_id_offset, image_id_offset +
image_vocab)`` once, outside the scan) where it used to ask the family for the
whole vocabulary's logits and slice them: XLA sinks that slice through the dot
but not through an int8 node's dequantization, and wrote the whole head to
HBM at every sampled position.

Two oracles are kept here, for the MLA family without and with
hyper-connection streams and for the hybrid family, over an int8 base and a
float one (both in float32: XLA:CPU has no bfloat16 dot for the toy models),
at an image range that starts off any multiple of 128 and at one that ends at
the last row held:

- the parent's step as the TPU compiler built it (compile-only, PR 34): the
  whole head dequantized, the image columns of *that* taken, the dot on those.
  The same dot on the same columns, so ids and probed logits are equal **bit
  for bit**: cutting ``q8`` and ``scale`` apart gives the product's columns.
- the parent's source, ``fam.head(params, cfg, x)[:, lo:hi]``. XLA:CPU runs
  that dot on every row held and sums a row of 300 columns in another order
  than a row of 16, so here its float32 logits stand a last bit apart (on the
  TPU the two are one convolution, and the chip's readings are the parent's to
  the last digit): the ids are equal and the logits equal to that bit.

The callers that read every column (``forward_logits``, ``mtp_logits``) keep
``vocab_rows_held`` columns and their references' tolerance.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperscalees_t2i_tpu.models import lm, lm_hybrid as hy
from hyperscalees_t2i_tpu.ops.quant import resolve_kernel
from tests import test_lm, test_lm_hybrid, test_lm_mhc

FAMILIES = {"mla": test_lm, "mla-streams": test_lm_mhc, "hybrid": test_lm_hybrid}
ROWS_HELD, IMAGE_VOCAB = 300, 16
OFFSETS = {"off-128s": 131, "ends-at-last-row": ROWS_HELD - IMAGE_VOCAB}
BASES = ("int8", "float")


def toy(tmp_path, monkeypatch, family, offset, base="float", **keys):
    quant = "int8" if base == "int8" else None
    mod = FAMILIES[family]
    raw = {**mod.TOY, "vocab_size": ROWS_HELD, "vocab_rows_held": ROWS_HELD, **keys,
           "image_tokens": {**mod.TOY["image_tokens"], "image_vocab": IMAGE_VOCAB, "image_id_offset": offset}}
    (tmp_path / "config.json").write_text(json.dumps(raw))
    cfg = lm.config_from_json(str(tmp_path / "config.json"))
    monkeypatch.setenv("HSES_BASE_QUANT_MIN_SIZE", "1")  # toy kernels still go int8
    params = lm.init_lm(jax.random.PRNGKey(0), cfg, quant)
    assert ("kernel_q8" in params["head"]) == (quant == "int8")
    return cfg, raw, params


def image_range(cfg):
    return cfg.image_id_offset, cfg.image_id_offset + cfg.image_vocab


def parents_source(fam, cfg, whole):
    """The head ``generate`` asked for before this change: every column of
    the whole node, then the image range of the logits."""
    lo, hi = image_range(cfg)
    return lambda p, c, x: fam.head({**p, "head": whole}, c, x)[:, lo:hi]


def parents_compiled_step(fam, cfg, whole):
    """What the TPU compiler made of it: the whole kernel dequantized, its
    image columns, the dot on those."""
    lo, hi = image_range(cfg)
    rest = {k: v[lo:hi] for k, v in whole.items() if k not in ("kernel", "kernel_q8")}
    return lambda p, c, x: fam.head(
        {**p, "head": {"kernel": resolve_kernel(whole, c.compute_dtype)[:, lo:hi], **rest}}, c, x)


def generate(cfg, params, key=5):
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, cfg.max_prompt_len), 2, 48)
    fn = jax.jit(lambda params, prompt, lens, key: lm.generate(params, cfg, prompt, lens, key, decode=False))
    ids, rows = fn(params, prompt, jnp.array([6, 3]), jax.random.PRNGKey(key))
    return np.asarray(ids), np.asarray(rows["logits"])


def generate_with(monkeypatch, cfg, params, oracle):
    """``generate`` with ``cfg.family()``'s head swapped for ``oracle``'s over
    the whole node, whatever narrowed node it is handed."""
    fam = cfg.family()
    swapped = fam._replace(head=oracle(fam, cfg, params["head"]))
    with monkeypatch.context() as m:
        m.setattr(type(cfg), "family", lambda self: swapped)
        return generate(cfg, params)


# (a) the sampler and the probe read the parent's bits ---------------------------

@pytest.mark.parametrize("offset", list(OFFSETS))
@pytest.mark.parametrize("base", BASES)
@pytest.mark.parametrize("family", list(FAMILIES))
def test_generate_on_the_image_columns_is_the_parents_form_bit_for_bit(tmp_path, monkeypatch, family, base, offset):
    cfg, _, params = toy(tmp_path, monkeypatch, family, OFFSETS[offset], base)
    ids, logits = generate(cfg, params)
    assert logits.shape == (2, cfg.image_tokens // lm.PROBE_EVERY, IMAGE_VOCAB)
    assert logits.dtype == np.float32 and np.abs(logits).max() > 0
    assert len(np.unique(ids)) > 1 and 0 <= ids.min() and ids.max() < IMAGE_VOCAB
    want_ids, want_logits = generate_with(monkeypatch, cfg, params, parents_compiled_step)
    assert np.array_equal(ids, want_ids) and np.array_equal(logits, want_logits)
    want_ids, want_logits = generate_with(monkeypatch, cfg, params, parents_source)
    assert np.array_equal(ids, want_ids) and test_lm.rel(logits, want_logits) < 1e-6


def test_a_bias_on_the_head_is_cut_with_its_kernel(tmp_path, monkeypatch):
    """No family's head has a bias today; a node that had one (``nn.dense``
    adds it) keeps the image range's entries, as the oracle's slice would."""
    cfg, _, params = toy(tmp_path, monkeypatch, "mla", OFFSETS["off-128s"])
    biased = {**params, "head": {**params["head"], "bias": jax.random.normal(jax.random.PRNGKey(8), (ROWS_HELD,))}}
    ids, logits = generate(cfg, biased)
    want_ids, want_logits = generate_with(monkeypatch, cfg, biased, parents_compiled_step)
    assert np.array_equal(ids, want_ids) and np.array_equal(logits, want_logits)
    assert not np.array_equal(logits, generate(cfg, params)[1])  # the bias reaches the logits


# (b) the callers that read every column -----------------------------------------

@pytest.mark.parametrize("family", list(FAMILIES))
def test_teacher_forced_logits_keep_every_row_held_and_their_reference(tmp_path, monkeypatch, family):
    cfg, raw, params = toy(tmp_path, monkeypatch, family, OFFSETS["off-128s"])
    mod = FAMILIES[family]
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0, ROWS_HELD)
    lens = jnp.array([6, 4])
    got = (hy if family == "hybrid" else lm).forward_logits(params, cfg, ids, lens)
    assert got.shape == (2, 6, ROWS_HELD) and got.dtype == jnp.float32
    for s in range(2):
        n = int(lens[s])
        assert mod.rel(got[s, :n], mod.ref_forward(params, raw, ids[s, :n])["logits"]) < mod.TOL


@pytest.mark.parametrize("family", ["mla", "mla-streams"])
def test_mtp_logits_keep_every_row_held_and_their_reference(tmp_path, monkeypatch, family):
    cfg, raw, params = toy(tmp_path, monkeypatch, family, OFFSETS["ends-at-last-row"])
    mod = FAMILIES[family]
    ids = jax.random.randint(jax.random.PRNGKey(6), (1, 6), 0, ROWS_HELD)
    lens = jnp.array([5])
    hidden, _, _ = lm.prefill(params, cfg, ids[:, :5], lens)
    got = lm.mtp_logits(params, cfg, hidden, ids[:, 1:6], lens)
    assert got.shape == (1, 5, ROWS_HELD) and got.dtype == jnp.float32
    main = mod.ref_forward(params, raw, ids[0, :5])
    want = mod.ref.mtp(mod.ref.mtp_weights(params["mtp"][0]), mod.ref.top_weights(params), raw, main["hidden"],
                       ids[0, 1:6])
    assert mod.rel(got[0], want) < mod.TOL

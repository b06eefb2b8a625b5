"""The step programs of the ``lm_ar`` families that came before the
``granitemoehybrid`` one are pinned: what a member runs — ``lm.generate`` with
a member's factored adapter over an int8 base, two members of an antithetic
pair under ``vmap``, the VQ decode, then ``LMArBackend.step_metrics`` over
their rows — lowered at the toy widths of each family's tier-1 tests, its
StableHLO text hashed (XLA:CPU; no debug information, so source lines do not
enter). A change to the code these families share with another
(``models/lm.py``, ``models/lm_hybrid.py``, ``backends/lm_backend.py``) that
moves any of their programs shows here; a change meant to move one updates
its hash with the reason."""

import hashlib
import json

import jax
import jax.numpy as jnp
import pytest

from hyperscalees_t2i_tpu.backends.lm_backend import LMArBackend
from hyperscalees_t2i_tpu.es import EggRollConfig, factored_member_theta, sample_noise
from hyperscalees_t2i_tpu.lora import init_lora
from hyperscalees_t2i_tpu.models import lm
from tests import test_lm, test_lm_hybrid, test_lm_mhc

TOYS = {"mla": test_lm.TOY, "xing4_0": test_lm_mhc.TOY, "qwen3_next": test_lm_hybrid.TOY}

# sha256 of each family's step text, first 16 hex digits
PINNED = {
    "mla": "02ddf9fd13a7e796",
    "xing4_0": "380d1aa2587dbac1",
    "qwen3_next": "0743401b84ad04bc",
}


def step_text(cfg, params) -> str:
    """StableHLO of two members' generation and the step's generator metrics."""
    theta = init_lora(jax.random.PRNGKey(1), params, cfg.lora_spec(rank=2, alpha=4.0))
    es = EggRollConfig(sigma=0.05, rank=2, antithetic=True)
    noise = sample_noise(jax.random.PRNGKey(2), theta, 2, es)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, cfg.max_prompt_len), 2, cfg.image_id_offset)
    lens = jnp.array([cfg.max_prompt_len, 3])

    def step(params, theta, noise, prompt, lens, key):
        def member(k):  # as LMArBackend.generate_p
            images, rows = lm.generate(params, cfg, prompt, lens, key,
                                       lora=factored_member_theta(theta, noise, k, 2, es), lora_scale=2.0)
            return images, dict(rows, prompt_ids=prompt, prompt_len=lens)

        images, rows = jax.vmap(member)(jnp.arange(2))
        return images, LMArBackend.step_metrics(None, rows, 2, True)

    return jax.jit(step).lower(params, theta, noise, prompt, lens, jax.random.PRNGKey(4)).as_text()


def family_sha(name: str, tmp_path) -> str:
    path = tmp_path / "config.json"
    path.write_text(json.dumps(TOYS[name]))
    cfg = lm.config_from_json(str(path))
    params = lm.init_lm(jax.random.PRNGKey(0), cfg, "int8")
    return hashlib.sha256(step_text(cfg, params).encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_step_program_of_an_earlier_family_is_as_pinned(name, tmp_path, monkeypatch):
    monkeypatch.setenv("HSES_BASE_QUANT_MIN_SIZE", "1")  # toy kernels go int8, as the cells' do
    assert family_sha(name, tmp_path) == PINNED[name]

"""Golden-output regression guards: tiny fixed-seed generations per family,
compared against checked-in arrays (tests/golden/*.npz).

The torch-parity tests pin converter semantics; these pin the *generation
semantics themselves* across refactors — a silent change to noise keying,
sampler math, or attention would show up here even when shapes stay right.
CPU-tier only (conftest forces the platform), loose f32 tolerance so benign
XLA version drift doesn't flake. Regenerate after an INTENTIONAL semantic
change:

    env JAX_PLATFORMS=cpu PYTHONPATH=. \
        python tests/test_golden.py --regen
"""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

GOLDEN = Path(__file__).resolve().parent / "golden"
RTOL, ATOL = 3e-4, 3e-4


def _sana_out():
    from hyperscalees_t2i_tpu.models import sana

    cfg = sana.SanaConfig(
        in_channels=4, out_channels=4, d_model=32, n_layers=2, n_heads=4,
        cross_n_heads=4, caption_dim=16, ff_ratio=2.0, compute_dtype=jnp.float32,
    )
    p = sana.init_sana(jax.random.PRNGKey(11), cfg)
    emb = jax.random.normal(jax.random.PRNGKey(12), (2, 6, 16))
    return sana.one_step_generate(
        p, cfg, emb, jnp.ones((2, 6), bool), jax.random.PRNGKey(13), latent_hw=(4, 4)
    )


def _zimage_out():
    from hyperscalees_t2i_tpu.models import zimage

    cfg = zimage.ZImageConfig(
        in_channels=4, patch_size=2, d_model=24, n_layers=2, n_heads=2,
        caption_dim=12, ff_ratio=2.0, num_steps=2, compute_dtype=jnp.float32,
    )
    p = zimage.init_zimage(jax.random.PRNGKey(21), cfg)
    emb = jax.random.normal(jax.random.PRNGKey(22), (2, 5, 12))
    return zimage.generate_latents(
        p, cfg, emb, jnp.ones((2, 5), bool), jax.random.PRNGKey(23), latent_hw=(4, 4)
    )


def _var_out():
    from hyperscalees_t2i_tpu.models import msvq, var as var_mod

    vq = msvq.MSVQConfig(vocab_size=64, c_vae=8, patch_nums=(1, 2, 4), phi_partial=2,
                         ch=8, ch_mult=(1, 1), num_res_blocks=1,
                         compute_dtype=jnp.float32)
    cfg = var_mod.VARConfig(vq=vq, num_classes=10, depth=2, d_model=32, n_heads=4,
                            ff_ratio=2.0, patch_nums=(1, 2, 4),
                            compute_dtype=jnp.float32, top_k=0, top_p=0.0)
    p = var_mod.init_var(jax.random.PRNGKey(31), cfg)
    return var_mod.generate(p, cfg, jnp.asarray([1, 7]), jax.random.PRNGKey(32))


def _infinity_out():
    from hyperscalees_t2i_tpu.models import bsq, infinity as inf_mod

    cfg = inf_mod.InfinityConfig(
        depth=2, d_model=16, n_heads=2, ff_ratio=2.0, text_dim=12,
        patch_nums=(1, 2, 4),
        vq=bsq.BSQConfig(bits=4, patch_nums=(1, 2, 4), phi_partial=2,
                         dec_ch=(8, 8), dec_blocks=1, compute_dtype=jnp.float32),
        compute_dtype=jnp.float32,
    )
    p = inf_mod.init_infinity(jax.random.PRNGKey(41), cfg)
    emb = jax.random.normal(jax.random.PRNGKey(42), (2, 5, 12))
    return inf_mod.generate(p, cfg, emb, jnp.ones((2, 5), bool), jax.random.PRNGKey(43))


def _infinity_rope_l2_out():
    """Released-checkpoint attention variants: 2D pyramid RoPE + self/cross
    QK-l2 with learned per-head scales (round-5 fidelity additions)."""
    from hyperscalees_t2i_tpu.models import bsq, infinity as inf_mod

    cfg = inf_mod.InfinityConfig(
        depth=2, d_model=16, n_heads=2, ff_ratio=2.0, text_dim=12,
        patch_nums=(1, 2, 4),
        vq=bsq.BSQConfig(bits=4, patch_nums=(1, 2, 4), phi_partial=2,
                         dec_ch=(8, 8), dec_blocks=1, compute_dtype=jnp.float32),
        compute_dtype=jnp.float32,
        attn_l2_norm=True, cross_attn_l2_norm=True, use_rope2d=True,
    )
    p = inf_mod.init_infinity(jax.random.PRNGKey(51), cfg)
    emb = jax.random.normal(jax.random.PRNGKey(52), (2, 5, 12))
    return inf_mod.generate(p, cfg, emb, jnp.ones((2, 5), bool), jax.random.PRNGKey(53))


FAMILIES = {
    "sana": _sana_out,
    "zimage": _zimage_out,
    "var": _var_out,
    "infinity": _infinity_out,
    "infinity_rope_l2": _infinity_rope_l2_out,
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_golden_outputs_stable(family):
    path = GOLDEN / f"{family}.npz"
    assert path.exists(), f"golden fixture missing — run: python {__file__} --regen"
    fixture = np.load(path)
    # Version gate: golden values are pinned to the jax/jaxlib that generated
    # them — XLA's RNG/fusion details shift between releases, so under a
    # different jax the numeric comparison measures version drift, not our
    # code (the pre-PR2 tier-1 failure mode: 6 red tests that meant nothing).
    # Skip loudly with the exact versions instead; regenerate under the new
    # jax (cheap, CPU-tiny) to re-arm the guard.
    gen_jax = str(fixture["gen_jax"]) if "gen_jax" in fixture else None
    if gen_jax is not None and gen_jax != jax.__version__:
        pytest.skip(
            f"golden {family}.npz was generated under jax {gen_jax}, running "
            f"jax {jax.__version__} — value drift is expected across jax "
            f"releases; regenerate with: python {__file__} --regen"
        )
    want = fixture["out"]
    got = np.asarray(FAMILIES[family]())
    assert got.shape == want.shape, (got.shape, want.shape)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


if __name__ == "__main__":
    import sys

    if "--regen" not in sys.argv:
        raise SystemExit("pass --regen to overwrite the golden fixtures")
    GOLDEN.mkdir(exist_ok=True)
    for family, fn in FAMILIES.items():
        out = np.asarray(fn())
        # gen_jax stamps the generating jax version — the skip gate above
        np.savez_compressed(GOLDEN / f"{family}.npz", out=out, gen_jax=jax.__version__)
        print(f"wrote {family}: {out.shape} mean {out.mean():.5f} (jax {jax.__version__})")

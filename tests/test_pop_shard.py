"""Pop-sharded EGGROLL update parity (ISSUE 8 tentpole).

The contract under test: ``--pop_shard_update on`` computes each pop shard's
fitness-weighted noise sum over its contiguous base slice only and one psum
over the pop axis rebuilds the full Δθ — the θ trajectory matches the
replicated update within tight f32 tolerance on a 2×2 pop×data mesh
(under either ``noise_dtype``), ``auto`` falls back to replicated exactly when
the base-sample count does not tile the pop axis, and ``off`` keeps lowering
the replicated program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperscalees_t2i_tpu.es import (
    EggRollConfig,
    apply_es_delta,
    epoch_key,
    es_partial_delta,
    es_update,
    fitness_coeffs,
    sample_noise,
)
from hyperscalees_t2i_tpu.parallel import (
    make_mesh,
    make_sharded_es_update,
    pop_shard_update_plan,
)
from hyperscalees_t2i_tpu.train.config import TrainConfig
from hyperscalees_t2i_tpu.train.trainer import make_es_step

# toy fixtures mirror tests/test_parallel.py (tests/ is not a package, so
# the helpers are duplicated rather than imported): one leaf per noise
# geometry — 2D low-rank, 1D dense, stacked-3D low-rank — and an
# item_index-folding generator (the data-axis sharding contract)
_EMPTY_FROZEN = {"gen": {}, "reward": {}}


def _toy_theta():
    k = jax.random.PRNGKey(0)
    return {
        "w1": jax.random.normal(jax.random.fold_in(k, 1), (6, 4)),
        "b": jnp.zeros((4,)),
        "stack": jax.random.normal(jax.random.fold_in(k, 2), (2, 4, 3)),
    }


def _mat(leaf):
    """A member's adapter arrives as FactoredDelta leaves; materialize like
    the real consumers (lora.effective_factor) do. Raw leaves pass through."""
    from hyperscalees_t2i_tpu.lora import FactoredDelta, effective_factor

    return (
        effective_factor(leaf, jnp.float32)
        if isinstance(leaf, FactoredDelta) else leaf
    )


def _toy_generate(theta, flat_ids, key, item_index=None):
    idx = jnp.arange(flat_ids.shape[0]) if item_index is None else item_index
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)
    noise = jax.vmap(lambda k: jax.random.normal(k, (4,)))(keys)
    feat = jnp.tanh(noise @ _mat(theta["w1"])[:4, :] + _mat(theta["b"]))
    return feat * (1.0 + flat_ids[:, None].astype(jnp.float32))


def _toy_reward(images, flat_ids):
    combined = -jnp.mean((images - 0.5) ** 2, axis=-1)
    return {"combined": combined, "aux": combined * 2.0}


class _ToyBackend:
    name = "toy"
    generate = staticmethod(_toy_generate)


# ---------------------------------------------------------------------------
# update-level parity: es_update vs the shard_map/psum variant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "pop,antithetic,noise_dtype,axes",
    [
        (8, True, "float32", {"pop": 2, "data": 2}),
        (8, True, "bfloat16", {"pop": 4}),
        (12, False, "float32", {"pop": 2, "data": 2}),
    ],
)
def test_sharded_update_matches_replicated(pop, antithetic, noise_dtype, axes):
    cfg = EggRollConfig(sigma=0.05, rank=2, antithetic=antithetic,
                        noise_dtype=noise_dtype)
    theta = _toy_theta()  # 2D + bias (dense-noised) + stacked-3D leaves
    noise = sample_noise(jax.random.PRNGKey(3), theta, pop, cfg)
    fitness = jax.random.normal(jax.random.PRNGKey(4), (pop,))
    ref = es_update(theta, noise, fitness, pop, cfg)
    mesh = make_mesh(axes)
    got = jax.jit(make_sharded_es_update(mesh, pop, cfg))(theta, noise, fitness)
    for k in ref:
        np.testing.assert_allclose(
            np.asarray(ref[k]), np.asarray(got[k]), rtol=2e-6, atol=1e-7,
        )


def test_partial_deltas_cover_the_update():
    """Summing disjoint slice contributions host-side reproduces es_update —
    the algebraic identity the psum relies on, checked without a mesh."""
    pop, cfg = 8, EggRollConfig(sigma=0.05, rank=2, antithetic=True)
    theta = _toy_theta()
    noise = sample_noise(jax.random.PRNGKey(5), theta, pop, cfg)
    fitness = jax.random.normal(jax.random.PRNGKey(6), (pop,))
    c = fitness_coeffs(fitness, pop, cfg)
    assert c.shape == (4,)  # base = pop/2 under antithetic pairing
    parts = [
        es_partial_delta(theta, noise, c, jnp.int32(lo), 2, pop, cfg)
        for lo in (0, 2)
    ]
    summed = jax.tree_util.tree_map(lambda a, b: a + b, *parts)
    got = apply_es_delta(theta, summed, noise, pop, cfg)
    ref = es_update(theta, noise, fitness, pop, cfg)
    for k in ref:
        np.testing.assert_allclose(
            np.asarray(ref[k]), np.asarray(got[k]), rtol=2e-6, atol=1e-7,
        )


# ---------------------------------------------------------------------------
# mode resolution: auto falls back, on raises, off is off
# ---------------------------------------------------------------------------

def test_plan_resolution():
    mesh22 = make_mesh({"pop": 2, "data": 2})
    # base 4 tiles a 2-way pop axis
    assert pop_shard_update_plan("auto", 8, True, mesh22)[0]
    assert pop_shard_update_plan("on", 8, True, mesh22)[0]
    # off always wins
    assert not pop_shard_update_plan("off", 8, True, mesh22)[0]
    # no mesh → replicated; "on" without a pop axis is a user error
    assert not pop_shard_update_plan("auto", 8, True, None)[0]
    with pytest.raises(ValueError, match="pop axis"):
        pop_shard_update_plan("on", 8, True, None)
    # base 5 (pop 9 antithetic) does not tile 2: auto falls back, on raises
    ok, reason = pop_shard_update_plan("auto", 9, True, mesh22)
    assert not ok and "5" in reason
    with pytest.raises(ValueError, match="divisible"):
        pop_shard_update_plan("on", 9, True, mesh22)
    with pytest.raises(ValueError, match="auto/on/off"):
        pop_shard_update_plan("always", 8, True, mesh22)


def test_sharded_update_rejects_nontiling_base():
    mesh = make_mesh({"pop": 4})
    with pytest.raises(ValueError, match="tile"):
        make_sharded_es_update(mesh, 9, EggRollConfig(antithetic=True))


# ---------------------------------------------------------------------------
# full-step trajectory: on vs off through make_es_step on a 2×2 mesh
# ---------------------------------------------------------------------------

def _run_steps(tc, mesh, epochs=3):
    step = make_es_step(_ToyBackend(), _toy_reward, tc, 3, 2, mesh)
    theta = jax.tree_util.tree_map(jnp.copy, _toy_theta())
    flat_ids = jnp.asarray([0, 1, 2, 0, 1, 2], jnp.int32)
    scores = None
    for e in range(epochs):
        theta, metrics, scores = step(
            _EMPTY_FROZEN, theta, flat_ids, epoch_key(0, e)
        )
    return theta, np.asarray(scores)


# the sharded update under both noise stores
@pytest.mark.parametrize("noise_dtype", ["float32", "bfloat16"])
def test_step_trajectory_parity_2x2(noise_dtype):
    mesh = make_mesh({"pop": 2, "data": 2})
    out = {}
    for mode in ("off", "on"):
        tc = TrainConfig(
            pop_size=8, sigma=0.05, egg_rank=2, prompts_per_gen=3,
            batches_per_gen=2, member_batch=4, promptnorm=True,
            noise_dtype=noise_dtype, pop_shard_update=mode,
        )
        out[mode] = _run_steps(tc, mesh)
    t_off, s_off = out["off"]
    t_on, s_on = out["on"]
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6,
        ),
        t_off, t_on,
    )
    np.testing.assert_allclose(s_off, s_on, rtol=1e-5, atol=1e-6)


def test_on_lowers_a_different_program_with_psum():
    """Sanity complement to the replicated pin: "on" is not a no-op — the
    lowered step differs from "off" and actually carries the psum (an
    all-reduce the collective extractor can see)."""
    from hyperscalees_t2i_tpu.obs.xla_cost import collective_stats

    mesh = make_mesh({"pop": 2, "data": 2})
    flat_ids = jnp.asarray([0, 1, 2, 0, 1, 2], jnp.int32)
    theta = _toy_theta()
    texts = {}
    compiled = {}
    for mode in ("off", "on"):
        tc = TrainConfig(
            pop_size=8, sigma=0.05, egg_rank=2, prompts_per_gen=3,
            batches_per_gen=2, member_batch=4, promptnorm=True,
            pop_shard_update=mode,
        )
        step = make_es_step(_ToyBackend(), _toy_reward, tc, 3, 2, mesh)
        lowered = step.lower(_EMPTY_FROZEN, theta, flat_ids, epoch_key(0, 0))
        texts[mode] = lowered.as_text()
        compiled[mode] = lowered.compile()
    assert texts["on"] != texts["off"]
    on_stats = collective_stats(compiled["on"])
    off_stats = collective_stats(compiled["off"])
    # the sharded update adds all-reduce traffic (the Δθ psum) on top of the
    # evaluator's score all-gathers
    assert on_stats["collective_bytes"] > off_stats["collective_bytes"]
    assert on_stats["collective_breakdown"].get("all-reduce", {}).get("ops", 0) > \
        off_stats["collective_breakdown"].get("all-reduce", {}).get("ops", 0)

"""Multi-device population-sharding tests on the 8-device virtual CPU mesh
(SURVEY.md §4(c)): the sharded ES step must be numerically identical to the
single-device step, and the collective helpers must match their specs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from hyperscalees_t2i_tpu.es import (
    EggRollConfig,
    epoch_key,
    perturb_member,
    sample_noise,
)
from hyperscalees_t2i_tpu.lora import effective_factor
from hyperscalees_t2i_tpu.parallel import (
    POP_AXIS,
    all_gather_ragged,
    local_pop,
    make_mesh,
    make_population_evaluator,
    ppermute_ring,
    psum_tree,
    shard_map,
)


def _toy_theta():
    k = jax.random.PRNGKey(0)
    return {
        "w1": jax.random.normal(jax.random.fold_in(k, 1), (6, 4)),
        "b": jnp.zeros((4,)),
        "stack": jax.random.normal(jax.random.fold_in(k, 2), (2, 4, 3)),
    }


def _toy_generate(theta, flat_ids, key, item_index=None):
    # Deterministic "generation": tiny function of theta + per-item noise.
    # Per-item keys fold in the *global* position so outputs are invariant to
    # chunking/data-sharding (the framework-wide item_index contract).
    # A member's matrix leaves arrive factored (lora.FactoredDelta); a
    # generator that reads a leaf itself builds it as nn.dense's helpers do.
    idx = jnp.arange(flat_ids.shape[0]) if item_index is None else item_index
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)
    noise = jax.vmap(lambda k: jax.random.normal(k, (4,)))(keys)
    feat = jnp.tanh(noise @ effective_factor(theta["w1"], jnp.float32)[:4, :] + theta["b"])
    return feat * (1.0 + flat_ids[:, None].astype(jnp.float32))


def _toy_generate_p(frozen, theta, flat_ids, key, item_index=None):
    return _toy_generate(theta, flat_ids, key, item_index)


def _toy_reward(images, flat_ids):
    combined = -jnp.mean((images - 0.5) ** 2, axis=-1)
    return {"combined": combined, "aux": combined * 2.0}


def _toy_reward_p(frozen, images, flat_ids):
    return _toy_reward(images, flat_ids)


_EMPTY_FROZEN = {"gen": {}, "reward": {}}


def test_make_mesh_shapes():
    mesh = make_mesh()
    assert mesh.shape[POP_AXIS] == 8
    mesh2 = make_mesh({"pop": 4, "tp": 2})
    assert mesh2.shape == {"pop": 4, "tp": 2}
    mesh3 = make_mesh({"pop": -1, "tp": 2})
    assert mesh3.shape == {"pop": 4, "tp": 2}
    with pytest.raises(ValueError):
        make_mesh({"pop": 16})
    assert local_pop(mesh, 16) == 2
    with pytest.raises(ValueError):
        local_pop(mesh, 12)


@pytest.mark.parametrize(
    "antithetic,pop,axes",
    [
        (True, 8, None),  # default 1-D pop mesh
        (False, 8, None),
        (True, 16, None),
        (True, 6, None),  # pop not divisible by 8 → padded pop axis
        (True, 4, {"pop": 4, "data": 2}),  # batch sharded over data axis
        (True, 2, {"pop": 2, "data": 4}),  # B=5 not divisible by 4 → padded
    ],
)
def test_sharded_eval_matches_single_device(antithetic, pop, axes):
    cfg = EggRollConfig(sigma=0.05, lr_scale=1.0, rank=2, antithetic=antithetic)
    theta = _toy_theta()
    key = epoch_key(0, 3)
    k_noise, k_gen = jax.random.split(key)
    noise = sample_noise(k_noise, theta, pop, cfg)
    flat_ids = jnp.arange(5, dtype=jnp.int32)

    ref_eval = make_population_evaluator(_toy_generate_p, _toy_reward_p, pop, cfg, 2, None)
    ref = jax.jit(ref_eval)(_EMPTY_FROZEN, theta, noise, flat_ids, k_gen)

    mesh = make_mesh(axes)
    sh_eval = make_population_evaluator(_toy_generate_p, _toy_reward_p, pop, cfg, 2, mesh)
    got = jax.jit(sh_eval)(_EMPTY_FROZEN, theta, noise, flat_ids, k_gen)

    for k in ref:
        np.testing.assert_allclose(np.asarray(ref[k]), np.asarray(got[k]), rtol=1e-5, atol=1e-6)


def test_sharded_full_step_matches(tmp_path):
    """The whole jitted epoch step (noise→eval→promptnorm→update) sharded vs not."""
    from hyperscalees_t2i_tpu.train.trainer import make_es_step
    from hyperscalees_t2i_tpu.train.config import TrainConfig

    class ToyBackend:
        name = "toy"
        generate = staticmethod(_toy_generate)

    tc = TrainConfig(pop_size=8, sigma=0.05, egg_rank=2, prompts_per_gen=3,
                     batches_per_gen=2, member_batch=4, promptnorm=True)
    theta = _toy_theta()
    flat_ids = jnp.asarray([0, 1, 2, 0, 1, 2], jnp.int32)
    key = epoch_key(0, 0)

    step_ref = make_es_step(ToyBackend(), _toy_reward, tc, 3, 2, None)
    step_sh = make_es_step(ToyBackend(), _toy_reward, tc, 3, 2, make_mesh())
    t_ref, m_ref, s_ref = step_ref(_EMPTY_FROZEN, jax.tree_util.tree_map(jnp.copy, theta), flat_ids, key)
    t_sh, m_sh, s_sh = step_sh(_EMPTY_FROZEN, jax.tree_util.tree_map(jnp.copy, theta), flat_ids, key)

    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        t_ref, t_sh)
    np.testing.assert_allclose(np.asarray(s_ref), np.asarray(s_sh), rtol=1e-5, atol=1e-6)
    assert float(m_sh["theta_norm"]) > 0.0


def test_psum_tree_and_ppermute():
    mesh = make_mesh()

    def body(x):
        s = psum_tree({"v": x}, POP_AXIS)["v"]
        nxt = ppermute_ring(x, POP_AXIS, shift=1)
        return s, nxt

    f = jax.jit(
        shard_map(body, mesh=mesh, in_specs=P(POP_AXIS), out_specs=(P(POP_AXIS), P(POP_AXIS)))
    )
    x = jnp.arange(8, dtype=jnp.float32)
    s, nxt = f(x)
    np.testing.assert_allclose(np.asarray(s), np.full(8, 28.0))
    # ring shift: source i goes to i+1
    np.testing.assert_allclose(np.asarray(nxt), np.roll(np.arange(8, dtype=np.float32), 1))


def test_all_gather_ragged():
    mesh = make_mesh()
    max_len = 4

    def body(x, n):
        # each shard holds a [max_len, feat] padded buffer + scalar true length
        data, lens = all_gather_ragged(x, n[0], max_len, POP_AXIS)
        return data, lens

    f = jax.jit(
        shard_map(
            body, mesh=mesh,
            in_specs=(P(POP_AXIS), P(POP_AXIS)),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )
    # global buffer: 8 shards × max_len rows × 3 features
    x = jnp.arange(8 * max_len * 3, dtype=jnp.float32).reshape(8 * max_len, 3)
    lens = jnp.asarray([(i % max_len) + 1 for i in range(8)], jnp.int32)
    data, got_lens = f(x, lens)
    assert data.shape == (8, max_len, 3)
    np.testing.assert_array_equal(np.asarray(got_lens), np.asarray(lens))
    for i in range(8):
        np.testing.assert_allclose(
            np.asarray(data[i]), np.asarray(x[i * max_len : (i + 1) * max_len])
        )

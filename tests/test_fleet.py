"""Fleet training (ISSUE 20): the (job, member)-batched ES step + scheduler.

The tentpole contract under test, at toy geometry:

- the member-axis slicing seam (``es.noiser.lane_slice``) is ONE helper
  shared by serving (``stacked_adapter_theta``) and the fleet path;
- ``job_lane_spans`` partitions the flat (job, member) lane axis exactly;
- ``jobwise_prompt_normalized_scores`` standardizes each job against its
  OWN statistics (never pooled across jobs);
- ONE ``make_fleet_step`` execution reproduces each job's solo reward rows
  within the written tolerance (``fleet.ROWS_TOL_ULPS``, per step, given
  identical θ) and the update outputs to rounding — XLA pins neither fusion
  nor reduction association across programs, so a cross-program hash is not
  a contract it can keep (it held under one jax and broke under the next);
- the ``FleetScheduler`` enforces cohort admission, interleaves fair-share
  ticks, fans per-job telemetry into ``job<j>/…`` streams, and keeps
  per-job checkpoint slots independently restorable;
- ``obs.regress.ingest_fleet`` turns a FLEET artifact into sentry
  observations with the right directions.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_trainer import brightness_reward, tiny_backend

from hyperscalees_t2i_tpu.backends.base import make_frozen
from hyperscalees_t2i_tpu.es import epoch_key, jobwise_prompt_normalized_scores
from hyperscalees_t2i_tpu.es.noiser import lane_slice, stacked_adapter_theta
from hyperscalees_t2i_tpu.es.scoring import prompt_normalized_scores
from hyperscalees_t2i_tpu.lora import stack_adapters
from hyperscalees_t2i_tpu.train import TrainConfig
from hyperscalees_t2i_tpu.train.fleet import (
    FleetAdmissionError,
    FleetJobSpec,
    FleetScheduler,
    cohort_mismatches,
    job_lane_spans,
    make_solo_reward_rows,
    reward_rows_close,
)
from hyperscalees_t2i_tpu.train.trainer import (
    fleet_scalar_args,
    make_es_step,
    make_fleet_step,
)


# ---------------------------------------------------------------------------
# the slicing seam + lane packing
# ---------------------------------------------------------------------------

def test_lane_slice_identity():
    stacked = {
        "a": jnp.arange(12.0).reshape(3, 4),
        "b": jnp.arange(6.0).reshape(3, 2),
    }
    for k in range(3):
        out = lane_slice(stacked, k)
        np.testing.assert_array_equal(out["a"], np.asarray(stacked["a"])[k])
        np.testing.assert_array_equal(out["b"], np.asarray(stacked["b"])[k])


def test_lane_slice_refuses_scalar_leaves():
    with pytest.raises(ValueError, match="leading adapter axis"):
        lane_slice({"a": jnp.float32(1.0)}, 0)


def test_stacked_adapter_theta_is_lane_slice():
    # the serving twin must be the SAME slicing seam, bit for bit
    stacked = {"w": jnp.arange(8.0).reshape(2, 4)}
    for k in range(2):
        a = stacked_adapter_theta(stacked, k)
        b = lane_slice(stacked, k)
        np.testing.assert_array_equal(np.asarray(a["w"]), np.asarray(b["w"]))


def test_job_lane_spans_cover_identity():
    # spans partition [0, W·pop) contiguously, one span of `pop` lanes per job
    for width, pop in ((1, 4), (2, 4), (3, 8)):
        spans = job_lane_spans(width, pop)
        assert len(spans) == width
        cursor = 0
        for start, count in spans:
            assert (start, count) == (cursor, pop)
            cursor += count
        assert cursor == width * pop


# ---------------------------------------------------------------------------
# jobwise fitness shaping
# ---------------------------------------------------------------------------

def test_jobwise_promptnorm_is_per_job_not_pooled():
    rng = np.random.default_rng(7)
    # job 1's rewards live on a 100× scale — pooling would crush job 0
    S = np.stack([
        rng.normal(0.0, 1.0, size=(6, 3)),
        rng.normal(50.0, 100.0, size=(6, 3)),
    ]).astype(np.float32)
    scores, mu_q, sigma_bar = jobwise_prompt_normalized_scores(jnp.asarray(S))
    assert scores.shape == (2, 6) and mu_q.shape == (2, 3)
    for j in range(2):
        s_solo, mu_solo, sb_solo = prompt_normalized_scores(jnp.asarray(S[j]))
        np.testing.assert_array_equal(np.asarray(scores[j]), np.asarray(s_solo))
        np.testing.assert_array_equal(np.asarray(mu_q[j]), np.asarray(mu_solo))
        np.testing.assert_array_equal(
            np.asarray(sigma_bar[j]), np.asarray(sb_solo)
        )
    # pooled normalization would NOT reproduce job 0's solo scores
    pooled, _, _ = prompt_normalized_scores(jnp.asarray(S.reshape(12, 3)))
    assert not np.allclose(np.asarray(pooled[:6]), np.asarray(scores[0]))


def test_jobwise_promptnorm_refuses_wrong_rank():
    with pytest.raises(ValueError, match="jobs"):
        jobwise_prompt_normalized_scores(jnp.zeros((4, 3)))


# ---------------------------------------------------------------------------
# the fused step vs solo: rows within tolerance, rounding-tight update
# ---------------------------------------------------------------------------

def _fleet_tc(sigma, lr_scale, seed, run_dir):
    return TrainConfig(
        num_epochs=1, pop_size=4, sigma=sigma, lr_scale=lr_scale, egg_rank=2,
        antithetic=True, promptnorm=True, prompts_per_gen=2, batches_per_gen=1,
        member_batch=4, run_dir=str(run_dir), save_every=0, seed=seed,
    )


def test_fleet_step_matches_solo_rows_bitwise_update_close(tmp_path):
    backend = tiny_backend(tmp_path)
    backend.setup()
    frozen = make_frozen(backend, brightness_reward)
    tcs = [_fleet_tc(0.05, 2.0, 3, tmp_path), _fleet_tc(0.08, 1.5, 9, tmp_path)]
    num_unique, repeats = 2, 1
    info = backend.step_info(0, num_unique, 1)
    flat_ids = jnp.asarray(np.asarray(info.flat_ids, np.int32))

    thetas = [
        backend.init_theta(jax.random.fold_in(jax.random.PRNGKey(t.seed), 17))
        for t in tcs
    ]
    keys = [epoch_key(t.seed, 0) for t in tcs]

    # solo references: reward rows (the parity surface) + stateful update
    solo_rows, solo_thetas = [], []
    for t, th, k in zip(tcs, thetas, keys):
        rows_fn = make_solo_reward_rows(backend, brightness_reward, t)
        solo_rows.append(np.asarray(jax.device_get(rows_fn(frozen, th, flat_ids, k))))
        step = make_es_step(backend, brightness_reward, t, num_unique, repeats,
                            stateful_delta=True, donate=False)
        zeros = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, x.dtype), th
        )
        th2, _, _, _ = step(frozen, th, zeros, flat_ids, k)
        solo_thetas.append(jax.device_get(th2))

    # ONE fused execution advancing both jobs
    stacked = jax.tree_util.tree_map(
        jnp.asarray, stack_adapters([jax.device_get(t) for t in thetas])
    )
    szeros = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype), stacked
    )
    sig, csc, lrs = fleet_scalar_args(tcs)
    fleet = make_fleet_step(backend, brightness_reward, tcs[0], num_unique,
                            repeats, 2, donate=False)
    theta_new, _delta, metrics, opt_scores = fleet(
        frozen, stacked, szeros, jnp.stack([flat_ids, flat_ids]),
        jnp.stack(keys), jnp.asarray(sig), jnp.asarray(csc), jnp.asarray(lrs),
    )
    rows = np.asarray(jax.device_get(metrics["fleet_reward_rows"]))
    assert rows.shape[0] == 2
    assert opt_scores.shape[0] == 2

    for j in range(2):
        # reward rows: within the written tolerance of the solo program's
        # (train/fleet.ROWS_TOL_ULPS — two XLA programs, rounding apart)
        ok, diff = reward_rows_close(rows[j], solo_rows[j])
        assert ok, f"job {j} reward rows diverged from solo: max|diff|={diff}"
        # ... and the bound still tells jobs apart: the OTHER job's rows
        # (its key, its noise, its σ) are far outside it
        assert not reward_rows_close(rows[j], solo_rows[1 - j])[0]
        # updated θ: rounding-tight, not bitwise (cross-program reduction
        # association is XLA's to choose — the documented boundary)
        got = jax.device_get(lane_slice(theta_new, j))
        flat_got = jax.tree_util.tree_leaves(got)
        flat_want = jax.tree_util.tree_leaves(solo_thetas[j])
        for a, b in zip(flat_got, flat_want):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2e-5, atol=2e-6,
            )


def test_fleet_step_refuses_zero_width(tmp_path):
    backend = tiny_backend(tmp_path)
    tc = _fleet_tc(0.05, 2.0, 3, tmp_path)
    with pytest.raises(ValueError, match="width"):
        make_fleet_step(backend, brightness_reward, tc, 2, 1, 0)


def test_fleet_scalar_args_single_rounding():
    import math

    tcs = [_fleet_tc(0.05, 2.0, 3, "."), _fleet_tc(0.08, 1.5, 9, ".")]
    sig, csc, lrs = fleet_scalar_args(tcs)
    assert sig.dtype == np.float32 and csc.dtype == np.float32
    for j, t in enumerate(tcs):
        cfg = t.es_config()
        # each value rounded ONCE from float64 — the solo traced-constant path
        assert sig[j] == np.float32(cfg.sigma)
        assert csc[j] == np.float32(cfg.sigma / math.sqrt(cfg.rank))
        assert lrs[j] == np.float32(cfg.lr)


# ---------------------------------------------------------------------------
# the scheduler: admission, fair-share, per-job slots, telemetry fan-out
# ---------------------------------------------------------------------------

def test_cohort_mismatches_names_fields(tmp_path):
    a = _fleet_tc(0.05, 2.0, 3, tmp_path)
    import dataclasses

    b = dataclasses.replace(a, pop_size=8, member_batch=8)
    mism = cohort_mismatches(b, a)
    joined = "; ".join(mism)
    assert "pop_size" in joined and "member_batch" in joined
    # σ/lr/seed are per-job freedoms, never cohort fields
    c = dataclasses.replace(a, sigma=0.5, lr_scale=9.0, seed=999)
    assert cohort_mismatches(c, a) == []


def test_fleet_scheduler_end_to_end(tmp_path):
    backend = tiny_backend(tmp_path)
    backend.setup()

    def make_tc(sigma, lr_scale, seed):
        return TrainConfig(
            num_epochs=2, pop_size=4, sigma=sigma, lr_scale=lr_scale,
            egg_rank=2, antithetic=True, promptnorm=True, prompts_per_gen=2,
            batches_per_gen=1, member_batch=4, run_dir=str(tmp_path / "runs"),
            save_every=1, seed=seed,
        )

    tc_a, tc_b = make_tc(0.05, 2.0, 3), make_tc(0.08, 1.5, 9)
    sched = FleetScheduler(backend, brightness_reward, tc_a,
                           tmp_path / "fleet", max_width=2)
    sched.submit(FleetJobSpec("job-a", tc_a))
    sched.submit(FleetJobSpec("job-b", tc_b))

    # admission: cohort mismatch refused BEFORE joining, named
    import dataclasses

    bad = dataclasses.replace(make_tc(0.05, 2.0, 5), pop_size=8)
    with pytest.raises(FleetAdmissionError, match="pop_size"):
        sched.submit(FleetJobSpec("job-bad", bad))
    # admission: duplicate id refused
    with pytest.raises(FleetAdmissionError, match="duplicate"):
        sched.submit(FleetJobSpec("job-a", tc_a))

    # fair-share: both jobs advance each tick; 2 epochs → 2 ticks and done
    assert sched.run() == 2
    sa, sb = sched.job_state("job-a"), sched.job_state("job-b")
    assert sa["done"] and sb["done"]
    assert sa["epoch"] == 2 and sb["epoch"] == 2

    # epoch-0 reward rows: within tolerance of each job's solo rows
    # (identical init θ — later epochs drift because θ drifted)
    frozen = make_frozen(backend, brightness_reward)
    info0 = backend.step_info(0, 2, 1)
    ids0 = jnp.asarray(np.asarray(info0.flat_ids, np.int32))
    for tc, jid in ((tc_a, "job-a"), (tc_b, "job-b")):
        rows_fn = make_solo_reward_rows(backend, brightness_reward, tc)
        theta0 = backend.init_theta(
            jax.random.fold_in(jax.random.PRNGKey(tc.seed), 17)
        )
        rows = rows_fn(frozen, theta0, ids0, epoch_key(tc.seed, 0))
        ok, diff = reward_rows_close(
            sched.job_state(jid)["first_rows"], np.asarray(jax.device_get(rows))
        )
        assert ok, (jid, diff)

    # per-job slots restore independently, no fleet state needed
    template = backend.init_theta(jax.random.PRNGKey(0))
    for jid in ("job-a", "job-b"):
        res = sched.restore_job(jid, template)
        assert res is not None and res.epoch == 2

    # ONE fused compile served both ticks at width 2 (flat retrace counter)
    from hyperscalees_t2i_tpu.obs import get_registry

    reg = get_registry()
    fleet_compiles = [
        v for k, v in reg.snapshot().items() if "fleet_compiles" in k
    ]
    assert fleet_compiles and all(v >= 1 for v in fleet_compiles)

    # telemetry fan-out: one metrics.jsonl line per tick, job<j>/ namespaced
    lines = [
        json.loads(ln)
        for ln in (tmp_path / "fleet" / "metrics.jsonl").read_text().splitlines()
        if ln.strip().startswith("{")
    ]
    assert any("job0/epoch" in ln for ln in lines)
    assert any("job1/reward_rows_sha256" in ln for ln in lines)
    assert any(ln.get("job0/job_id") == "job-a" for ln in lines)


# ---------------------------------------------------------------------------
# sentry ingestion of FLEET artifacts
# ---------------------------------------------------------------------------

def test_ingest_fleet_observations(tmp_path):
    from hyperscalees_t2i_tpu.obs.regress import (
        METRIC_POLICY,
        ingest,
        ingest_fleet,
    )

    doc = {
        "mode": "fleet", "rung": "tiny", "device_kind": "cpu",
        "widths": [
            {"width": 2, "fused_imgs_per_sec_chip": 350.0,
             "bytes_per_job": 9e6, "stablehlo_sha256": "ab12"},
            {"width": 4, "fused_imgs_per_sec_chip": 400.0,
             "bytes_per_job": 5e6, "stablehlo_sha256": "cd34"},
        ],
    }
    p = tmp_path / "FLEET_r01.json"
    p.write_text(json.dumps(doc))
    obs = ingest_fleet(p)
    by_key = {(o.metric, o.key): o for o in obs}
    assert by_key[("fleet_imgs_per_sec_chip", "fleet/tiny/j2")].value == 350.0
    assert by_key[("fleet_bytes_per_job", "fleet/tiny/j4")].value == 5e6
    assert by_key[("fleet_imgs_per_sec_chip", "fleet/tiny/j2")].chip == "cpu"
    assert by_key[("fleet_bytes_per_job", "fleet/tiny/j2")].sha == "ab12"
    # throughput gates DOWN-only, bytes/job UP-only
    assert METRIC_POLICY["fleet_imgs_per_sec_chip"]["direction"] == "lower"
    assert METRIC_POLICY["fleet_bytes_per_job"]["direction"] == "upper"
    # the .json dispatch routes FLEET docs here (not to bench)
    assert {o.metric for o in ingest(p)} == {
        "fleet_imgs_per_sec_chip", "fleet_bytes_per_job"
    }
    # non-fleet docs fall through empty
    q = tmp_path / "other.json"
    q.write_text(json.dumps({"mode": "capacity"}))
    assert ingest_fleet(q) == []

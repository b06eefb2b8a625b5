"""Dispatch-tax microbench: single-dispatch vs chained vs fleet.

Times variants of ONE rung's ES epoch step and emits a single JSON row, so
the per-step *dispatch overhead* (host→device round-trip + program launch)
is a measured number in the bench trend, not an inference from two
different artifacts::

    python -m hyperscalees_t2i_tpu.tools.dispatch_tax                 # tiny
    python -m hyperscalees_t2i_tpu.tools.dispatch_tax --rung small \\
        --steps 8 --chain 8 --out bench_runs/dispatch_tax.json

Variants (same geometry, same weights, same keys):

- ``single``  — one host dispatch per epoch step (the trainer's default).
- ``chained`` — ``--chain`` steps fused into one dispatched ``fori_loop``
  program; per-step time isolates everything that is NOT per-dispatch
  overhead. ``dispatch_tax_s = single − chained`` (per step) is the number
  bench r05 showed is worth 7–12% at small geometry.
- ``fused_qlora`` — one dispatch per step over an int8 base (min-size
  floor dropped so small rungs quantize): every adapted dense resolves
  through ops/fused_qlora.py — on CPU this times its XLA composition.
- ``fleet2`` — J=2 jobs advanced by ONE dispatched (job, member)-batched
  fleet step (``make_fleet_step``, ISSUE 20) vs the same two jobs stepped
  sequentially through the solo program: one launch + one sync for
  J jobs is the dispatch-side half of fleet amortization
  (``fleet2_amortization`` = sequential/fused per-round time).

Each row also stamps the active Pallas kernel env flags (``pallas_env``),
so kernel-on and kernel-off rows are distinguishable in the trend.

Timing honesty follows bench.py: every timed window ends in a
``jax.device_get`` of a scalar that data-depends on all timed steps (θ is
chained through), so the clock cannot stop at dispatch. Models are
random-init at the rung's geometry (throughput measurement, not quality).

Only the Sana-family rungs are supported (the ladder's hot path); the AR
rung has its own kernel-parity probe in bench.py.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Optional


def build_rung(rung: str, base_quant: Optional[str] = None):
    """Concrete backend + reward fn + step config at the rung's geometry —
    via ``bench.build`` itself (one builder, so the timed program here can
    never drift from the ladder's). bench.py lives at the repo root, the
    same way the test suite imports it. ``base_quant`` overrides the rung's
    shipped setting (the fused_qlora variant quantizes even on rungs that
    ship a float base)."""
    try:
        import bench
    except ImportError as e:
        raise SystemExit(
            "dispatch_tax drives bench.build and must run from the repo "
            f"root (where bench.py lives): {e}"
        ) from e

    from ..rungs import RUNG_PLAN, rung_opt

    scale, pop, m, member_batch = RUNG_PLAN[rung]
    opt = rung_opt(rung)
    if base_quant is not None:
        opt["base_quant"] = base_quant
    backend, reward_fn = bench.build(
        scale, remat=opt["remat"], tower_dtype=opt["tower_dtype"],
        base_quant=opt.get("base_quant", "off"),
    )
    return backend, reward_fn, (pop, m, member_batch, opt)


def _timed_steps(compiled, frozen, theta, flat_ids, steps: int):
    """Per-step wall time over ``steps`` exec-synced steps. θ chains through
    every call (it is donated into the step and data-feeds the fetched
    scalar, so the final ``device_get`` cannot complete early)."""
    import jax

    t0 = time.perf_counter()
    for e in range(steps):
        theta, metrics, _ = compiled(
            frozen, theta, flat_ids, jax.random.fold_in(jax.random.PRNGKey(3), e)
        )
    float(jax.device_get(metrics["opt_score_mean"]))
    return (time.perf_counter() - t0) / steps


def run(rung: str, steps: int, chain: int) -> dict:
    import jax
    import jax.numpy as jnp

    from ..backends.base import make_frozen
    from ..train.config import TrainConfig
    from ..train.trainer import make_es_step

    backend, reward_fn, (pop, m, member_batch, opt) = build_rung(rung)
    num_unique = min(m, backend.num_items)
    info = backend.step_info(0, num_unique, 1)
    flat_ids = jnp.asarray(info.flat_ids, jnp.int32)
    frozen = make_frozen(backend, reward_fn)
    # θ is DONATED into the step — keep a host copy and give every timed
    # variant its own fresh device tree (a reused donated buffer raises)
    theta_host = jax.device_get(backend.init_theta(jax.random.PRNGKey(1)))

    def fresh_theta():
        return jax.tree_util.tree_map(jnp.array, theta_host)

    theta = fresh_theta()

    tc = TrainConfig(
        pop_size=pop, sigma=0.01, egg_rank=4, prompts_per_gen=num_unique,
        batches_per_gen=1, member_batch=member_batch, promptnorm=True,
        remat=opt["remat"], reward_tile=opt["reward_tile"],
        noise_dtype=opt["noise_dtype"],
        base_quant=opt.get("base_quant", "off"),
        quality=opt.get("quality", False),
    )
    step = make_es_step(backend, reward_fn, tc, num_unique, 1, None)
    compiled = step.lower(frozen, theta, flat_ids, jax.random.PRNGKey(2)).compile()

    rec: dict = {
        "metric": "dispatch_tax", "rung": rung, "pop": pop,
        "prompts": num_unique, "member_batch": member_batch,
        "base_quant": opt.get("base_quant", "off"),
        "steps_timed": steps, "chain": chain,
        "platform": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        "sync": "device_get",
    }

    # -- single dispatch per step ------------------------------------------
    th, metrics, _ = compiled(frozen, fresh_theta(), flat_ids, jax.random.PRNGKey(2))
    float(jax.device_get(metrics["opt_score_mean"]))  # warmup, exec-synced
    rec["step_time_single_s"] = round(
        _timed_steps(compiled, frozen, th, flat_ids, steps), 6
    )

    # -- chained: `chain` steps per dispatched program ---------------------
    if chain > 1:
        m0 = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, x.dtype), metrics)

        def multi(fz, th_, ids, k):
            def body(e, carry):
                th2, _ = carry
                th3, mm, _ = step(fz, th2, ids, jax.random.fold_in(k, e))
                return (th3, mm)

            return jax.lax.fori_loop(0, chain, body, (th_, m0))

        cchain = jax.jit(multi).lower(frozen, theta, flat_ids, jax.random.PRNGKey(2)).compile()
        th2, m2 = cchain(frozen, fresh_theta(), flat_ids, jax.random.PRNGKey(2))
        float(jax.device_get(m2["opt_score_mean"]))  # warmup
        t0 = time.perf_counter()
        th2, m2 = cchain(frozen, th2, flat_ids, jax.random.PRNGKey(5))
        float(jax.device_get(m2["opt_score_mean"]))
        rec["step_time_chained_s"] = round((time.perf_counter() - t0) / chain, 6)
        rec["dispatch_tax_s"] = round(
            rec["step_time_single_s"] - rec["step_time_chained_s"], 6
        )

    # -- fleet: TWO jobs per dispatch (ISSUE 20) vs the same two jobs
    # stepped sequentially through the solo program. This row isolates
    # the *dispatch-side* half of fleet amortization (one launch + one sync
    # for J jobs); the byte-side half is preflight --fleet's claim. Both
    # jobs share the cohort geometry (admission contract), so the sequential
    # baseline legitimately reuses one compiled solo program.
    import numpy as np

    from ..lora import stack_adapters
    from ..train.trainer import fleet_scalar_args, make_fleet_step

    # donate=False: microbench re-executes one program many times in-process
    # (XLA:CPU donation clobbers reused inputs under that pattern)
    fleet2 = make_fleet_step(backend, reward_fn, tc, num_unique, 1, 2,
                             donate=False)
    stacked = jax.tree_util.tree_map(
        jnp.asarray, stack_adapters([theta_host, theta_host])
    )
    szeros = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype), stacked
    )
    ids2 = jnp.stack([flat_ids, flat_ids])
    keys2 = jnp.stack([jax.random.PRNGKey(2), jax.random.PRNGKey(4)])
    sig, csc, lrs = fleet_scalar_args([tc, tc])
    fargs = (frozen, stacked, szeros, ids2, keys2,
             jnp.asarray(sig), jnp.asarray(csc), jnp.asarray(lrs))
    cfleet = fleet2.lower(*fargs).compile()
    _, _, mm2, _ = cfleet(*fargs)
    float(np.asarray(jax.device_get(mm2["opt_score_mean"])).sum())  # warmup
    t0 = time.perf_counter()
    for _ in range(steps):
        _, _, mm2, _ = cfleet(*fargs)
    float(np.asarray(jax.device_get(mm2["opt_score_mean"])).sum())
    rec["step_time_fleet2_fused_s"] = round(
        (time.perf_counter() - t0) / steps, 6
    )
    # sequential baseline: two chained solo steps per round (θ chains
    # per job, so the final fetch data-depends on every timed step)
    th_a, th_b = fresh_theta(), fresh_theta()
    th_a, ma, _ = compiled(frozen, th_a, flat_ids, jax.random.PRNGKey(2))
    th_b, mb, _ = compiled(frozen, th_b, flat_ids, jax.random.PRNGKey(4))
    float(jax.device_get(ma["opt_score_mean"]))
    float(jax.device_get(mb["opt_score_mean"]))  # warmup
    t0 = time.perf_counter()
    for e in range(steps):
        th_a, ma, _ = compiled(
            frozen, th_a, flat_ids, jax.random.fold_in(jax.random.PRNGKey(2), e)
        )
        th_b, mb, _ = compiled(
            frozen, th_b, flat_ids, jax.random.fold_in(jax.random.PRNGKey(4), e)
        )
    float(jax.device_get(ma["opt_score_mean"]))
    float(jax.device_get(mb["opt_score_mean"]))
    rec["step_time_fleet2_sequential_s"] = round(
        (time.perf_counter() - t0) / steps, 6
    )
    if rec["step_time_fleet2_fused_s"] > 0:
        rec["fleet2_amortization"] = round(
            rec["step_time_fleet2_sequential_s"]
            / rec["step_time_fleet2_fused_s"], 4
        )

    # -- fused_qlora: int8 base + factored members (ops/fused_qlora.py — its
    # XLA composition on CPU). The base is quantized with the min-size floor
    # dropped so small-geometry rungs exercise the PATH (the byte win is the
    # ledger's claim, not this microbench's); the row measures what the
    # dequant+delta composition does to the same dispatch cadence.
    import os

    from ..ops.quant import MIN_SIZE_ENV

    old_floor = os.environ.get(MIN_SIZE_ENV)
    os.environ[MIN_SIZE_ENV] = "1"
    try:
        backend_q, reward_q, _ = build_rung(rung, base_quant="int8")
        frozen_q = make_frozen(backend_q, reward_q)
        theta_q_host = jax.device_get(backend_q.init_theta(jax.random.PRNGKey(1)))
        tc_q = TrainConfig(
            pop_size=pop, sigma=0.01, egg_rank=4, prompts_per_gen=num_unique,
            batches_per_gen=1, member_batch=member_batch, promptnorm=True,
            remat=opt["remat"], reward_tile=opt["reward_tile"],
            noise_dtype=opt["noise_dtype"], base_quant="int8",
            quality=opt.get("quality", False),
        )
        step_q = make_es_step(backend_q, reward_q, tc_q, num_unique, 1, None)
        theta_q = jax.tree_util.tree_map(jnp.array, theta_q_host)
        compiled_q = step_q.lower(
            frozen_q, theta_q, flat_ids, jax.random.PRNGKey(2)
        ).compile()
        thq, mq, _ = compiled_q(
            frozen_q, jax.tree_util.tree_map(jnp.array, theta_q_host),
            flat_ids, jax.random.PRNGKey(2),
        )
        float(jax.device_get(mq["opt_score_mean"]))  # warmup, exec-synced
        rec["step_time_fused_qlora_s"] = round(
            _timed_steps(compiled_q, frozen_q, thq, flat_ids, steps), 6
        )
    finally:
        if old_floor is None:
            os.environ.pop(MIN_SIZE_ENV, None)
        else:
            os.environ[MIN_SIZE_ENV] = old_floor

    # kernel provenance: the Pallas env flags set when this row was measured
    from ..ops.pallas_gate import active_pallas_flags, selected_kernels

    rec["pallas_env"] = active_pallas_flags()
    rec["pallas_selected"] = selected_kernels()
    return rec


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rung", default="tiny",
                    help="sana-family rung to time (default: tiny)")
    ap.add_argument("--steps", type=int, default=5,
                    help="timed single-dispatch steps per variant")
    ap.add_argument("--chain", type=int, default=None,
                    help="steps per chained program (default: the rung's "
                         "RUNG_CHAIN entry, min 2)")
    ap.add_argument("--out", default=None,
                    help="also append the JSON row to this file")
    args = ap.parse_args(argv)

    from ..rungs import RUNG_CHAIN, RUNG_PLAN

    if args.rung not in RUNG_PLAN or args.rung == "ar":
        print(f"unsupported rung {args.rung!r} (sana-family rungs only: "
              f"{sorted(set(RUNG_PLAN) - {'ar'})})", file=sys.stderr)
        return 2
    chain = args.chain if args.chain is not None else max(RUNG_CHAIN.get(args.rung, 0), 2)

    # provenance stamp without importing bench (repo-root module): schema
    # fields mirror bench artifacts so bench_report --trend can line rows up
    try:
        from importlib.metadata import version

        jax_version = version("jax")
    except Exception:
        jax_version = None
    rec = run(args.rung, args.steps, chain)
    rec["jax_version"] = jax_version
    line = json.dumps(rec)
    print(line)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Fleet training: N independent ES jobs through ONE compiled step (ISSUE 20).

The production dual of ``serve/``: serving proved adapters-as-program-
arguments amortizes the resident base across tenants (PR 12); here the same
argument-batching amortizes it across *training jobs*. The member axis
generalizes to a flat (job, member) lane axis — ``W`` jobs × ``pop`` members
advance through one ``lax.map`` against one frozen base — and this module
owns everything around that program:

- **admission** — a job joins the fleet only if it shares the *cohort
  geometry* (every compile-relevant TrainConfig field; per-job σ/lr_scale/
  seed are free, they enter as argument values) and, when the HBM budget is
  resolvable, only if the fused step's compiled peak fits
  (:func:`serve.admission.check_fit` generalized — same typed refusal,
  same unarmed-gate convention on CPU rigs). ``tools/preflight --fleet``
  renders the offline verdict from :func:`analyze_fleet_geometry`.
- **per-job checkpoint slots** — one PR-4 ``CheckpointStore`` per job id at
  ``run_dir/jobs/<job_id>/``, each independently restorable; the serve
  ``AdapterStore`` layout doubles as the in-memory job registry (structural
  admission against the cohort template, per-job content digests).
- **fair-share interleaving** — when more jobs are active than one step
  takes, each tick advances the ``max_width`` lowest-epoch jobs (ties by
  join order), so epochs stay within one of each other across the fleet.
- **join/leave at epoch boundaries** — ``submit()``/``leave()`` queue; the
  membership change lands at the next tick boundary, riding the same
  due-boundary discipline as the trainer's checkpoint/rollback machinery.

Parity contract (README runbook): per-job REWARD ROWS agree with the job's
solo run within :data:`ROWS_TOL_ULPS` float32 ulps of the largest row value
(:func:`reward_rows_close`). Every per-job input is exactly the solo one —
the key split, the noise draw (counter-based, no cross-job reduction), σ as
a one-rounding f32 argument (``trainer.fleet_scalar_args``) — but the fused
step and the solo step are different XLA programs, and XLA pins neither
fusion nor reduction association across programs: the rows differ by
rounding (measured 1–2 ulps on XLA:CPU, jax 0.9.0, PR 21; an earlier jax
happened to compile the lane body identically in both and they hashed
equal). A real divergence — another job's key, another member's noise —
moves a row by ~1e-2, five orders above the bound. The θ-update outputs are
rounding-tight for the same reason — the documented boundary of
``reward_tile`` and the pod eval split.
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

Pytree = Any

# TrainConfig fields every job in one fused step must share: they are baked
# into the compiled program (shapes, lax.map structure, knob routing) or
# into traced constants the per-job scalar rows do NOT override. Per-job
# freedom is exactly {sigma, lr_scale, seed, num_epochs, run_dir, save_every}.
COHORT_FIELDS: Tuple[str, ...] = (
    "pop_size", "egg_rank", "antithetic", "member_batch", "promptnorm",
    "prompts_per_gen", "batches_per_gen", "reward_tile", "noise_dtype",
    "base_quant", "remat", "max_step_norm", "theta_max_norm",
    "quality",
)


class FleetAdmissionError(RuntimeError):
    """A job refused at fleet admission — cohort-geometry mismatch or a
    compiled-memory no-fit. Carries structured detail so CLIs/CI can exit
    nonzero naming the offending field and both values."""

    def __init__(self, job_id: str, reason: str, detail: str = ""):
        self.job_id = job_id
        self.reason = reason
        super().__init__(
            f"fleet admission REFUSED for job {job_id!r} ({reason})"
            + (f": {detail}" if detail else "")
        )


def cohort_mismatches(job_tc, cohort_tc) -> List[str]:
    """Human-readable list of cohort-field divergences (empty = compatible),
    each naming the field and BOTH values — the refusal must tell the
    operator exactly which knob to align."""
    out = []
    for f in COHORT_FIELDS:
        a, b = getattr(job_tc, f, None), getattr(cohort_tc, f, None)
        if a != b:
            out.append(f"{f}: job={a!r} cohort={b!r}")
    return out


def job_lane_spans(width: int, pop_size: int) -> List[Tuple[int, int]]:
    """Job → lane-span packing for the flat (job, member) axis: job j owns
    lanes ``[j·pop, (j+1)·pop)``. This IS ``parallel.mesh.host_slices`` —
    the fleet reuses the reshard-plan math (contiguous, disjoint, covering)
    rather than growing a third copy of slice arithmetic; the cover identity
    is unit-tested in tests/test_fleet.py."""
    from ..parallel.mesh import host_slices

    return host_slices(width * pop_size, width)


def reward_rows_digest(rows) -> str:
    """Canonical content digest of one job's ``[pop, B]`` combined reward
    rows — an identifier for logs and artifacts (same program, same rows →
    same digest). NOT the fleet-vs-solo parity test: that is
    :func:`reward_rows_close`. f32 little-endian bytes in C order, sha256."""
    a = np.ascontiguousarray(np.asarray(rows, np.float32))
    return hashlib.sha256(a.astype("<f4", copy=False).tobytes()).hexdigest()


# Fleet-vs-solo reward rows: the bound, in float32 ulps (2^-24 relative) of
# the largest solo row value. Two XLA programs computing the same rows may
# associate and fuse differently, each choice moving a result by an ulp or
# two; 16 leaves room over the 1–2 measured, and is ~10^5 below what any
# mix-up of keys, noise slabs or σ produces (module docstring).
ROWS_TOL_ULPS = 16


def reward_rows_close(rows, solo_rows) -> Tuple[bool, float]:
    """(within the contract?, max |rows − solo_rows|) for one job's
    ``[pop, B]`` combined reward rows against its solo run's."""
    a = np.asarray(rows, np.float32)
    b = np.asarray(solo_rows, np.float32)
    if a.shape != b.shape:
        return False, float("inf")
    diff = float(np.max(np.abs(a - b))) if a.size else 0.0
    atol = ROWS_TOL_ULPS * 2.0 ** -24 * float(np.max(np.abs(b))) if b.size else 0.0
    return bool(np.all(np.isfinite(a)) and diff <= atol), diff


def make_solo_reward_rows(backend, reward_fn, tc) -> Callable:
    """The canonical solo-side parity recipe: a jitted
    ``rows(frozen, theta, flat_ids, key) → [pop, B]`` program that computes
    exactly the solo step's front half (same key split, same noise draw,
    same population evaluator) and returns the raw combined reward rows.

    The full solo step never exposes its rows (its outputs are the update
    products), so parity checks run THIS program for the solo side and
    hold the fused fleet step's ``fleet_reward_rows`` to it with
    :func:`reward_rows_close`.
    """
    import jax

    from ..backends.base import generate_parts, reward_parts
    from ..es import sample_noise
    from ..parallel.pop_eval import make_population_evaluator

    es_cfg = tc.es_config()
    pop = tc.pop_size
    gen_p, _ = generate_parts(backend)
    rew_p, _ = reward_parts(reward_fn)
    eval_pop = make_population_evaluator(
        gen_p, rew_p, pop, es_cfg, tc.member_batch,
        reward_tile=tc.reward_tile,
    )

    def rows(frozen, theta, flat_ids, key):
        k_noise, k_gen = jax.random.split(key)
        noise = sample_noise(k_noise, theta, pop, es_cfg)
        return eval_pop(frozen, theta, noise, flat_ids, k_gen)["combined"]

    return jax.jit(rows)


# ---------------------------------------------------------------------------
# Offline analysis (tools/preflight --fleet) — the serve/admission pattern
# ---------------------------------------------------------------------------


def parse_fleet_geometry(spec: str) -> Tuple[str, int]:
    """``RUNG:J`` → (rung, width). The preflight ``--fleet`` argument."""
    parts = [p.strip() for p in spec.split(":") if p.strip()]
    if len(parts) != 2:
        raise ValueError(f"fleet geometry must be RUNG:J, got {spec!r}")
    try:
        width = int(parts[1])
    except ValueError:
        raise ValueError(f"fleet geometry J must be an integer, got {spec!r}") from None
    if width < 1:
        raise ValueError(f"fleet geometry J must be >= 1, got {spec!r}")
    return parts[0], width


def analyze_fleet_geometry(
    rung: str,
    width: int,
    ledger: Any = None,
    opt_override: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Abstract-lower + CPU-compile the fused ``width``-job fleet step at a
    rung's geometry; return (and optionally ledger-append) its
    ``site="fleet"`` program record — zero weights allocated, the offline
    half of the admission gate (``tools/preflight --fleet RUNG:J``)."""
    import jax
    import jax.numpy as jnp

    from ..obs.xla_cost import program_record
    from ..rungs import RUNG_PLAN, rung_opt
    from ..tools.preflight import _add_chip_true_estimates, abstract_step_inputs
    from .trainer import make_fleet_step

    if rung not in RUNG_PLAN:
        raise ValueError(f"unknown rung {rung!r} (have: {sorted(RUNG_PLAN)})")
    scale, pop, m, member_batch = RUNG_PLAN[rung]
    opt = rung_opt(rung)
    opt.update({k: v for k, v in (opt_override or {}).items() if v is not None})
    (backend, reward_fn, tc, frozen, theta, _ids, key_s,
     num_unique) = abstract_step_inputs(scale, pop, m, member_batch, opt)
    W = int(width)
    stacked = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((W,) + tuple(l.shape), l.dtype), theta
    )
    ids = jax.ShapeDtypeStruct((W, num_unique), jnp.int32)
    keys = jax.ShapeDtypeStruct((W,) + tuple(key_s.shape), key_s.dtype)
    row = jax.ShapeDtypeStruct((W,), jnp.float32)
    step = make_fleet_step(backend, reward_fn, tc, num_unique, 1, W)
    t0 = time.perf_counter()
    lowered = step.lower(frozen, stacked, stacked, ids, keys, row, row, row)
    lowering_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    rec = program_record(
        site="fleet", label=f"fleet-{rung}-j{W}",
        lowered=lowered, compiled=compiled,
        lowering_s=lowering_s, compile_s=compile_s,
        geometry={"scale": scale, "pop": pop, "m": num_unique, "r": 1,
                  "member_batch": member_batch, "fleet_width": W, **opt},
        extra={"rung": rung, "fleet_width": W,
               "imgs_per_step": W * pop * num_unique},
    )
    _add_chip_true_estimates(rec, (frozen, stacked), compiled)
    if ledger is not None:
        ledger.write(rec)
    return rec


def fleet_fit_verdict(
    rec: Dict[str, Any], hbm_budget_bytes: Optional[float] = None
) -> Dict[str, Any]:
    """Fit verdict for one fleet program record — the serve admission gate
    verbatim: ``admitted`` / ``REFUSED`` / ``unverdicted`` (budget or peak
    unknown; the gate records itself unarmed rather than guessing)."""
    from ..serve.admission import ServeAdmissionError, check_fit, resolve_hbm_budget

    budget, source = resolve_hbm_budget(hbm_budget_bytes)
    peak = rec.get("peak_bytes_chip_est")
    if peak is None:
        peak = rec.get("peak_bytes")
    try:
        armed = check_fit(rec.get("label", "fleet"), peak, budget, source)
        verdict = "admitted" if armed else "unverdicted"
    except ServeAdmissionError as e:
        return {"verdict": "REFUSED", "peak_bytes": float(peak),
                "budget_bytes": float(budget), "budget_source": source,
                "detail": str(e)}
    return {"verdict": verdict,
            "peak_bytes": float(peak) if peak is not None else None,
            "budget_bytes": float(budget) if budget is not None else None,
            "budget_source": source}


# ---------------------------------------------------------------------------
# The scheduler
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FleetJobSpec:
    """One job's identity + config. ``tc`` must match the scheduler's cohort
    on every :data:`COHORT_FIELDS` entry; σ/lr_scale/seed/num_epochs/
    save_every are the per-job degrees of freedom."""

    job_id: str
    tc: Any  # TrainConfig
    num_epochs: Optional[int] = None  # default: tc.num_epochs


class _Job:
    __slots__ = ("spec", "index", "theta", "prev_delta", "epoch", "end_epoch",
                 "store", "done", "leave_requested", "last_scalars",
                 "rows_digest", "rows_digests", "first_rows", "admission")

    def __init__(self, spec: FleetJobSpec, index: int, theta, store, epoch: int,
                 prev_delta, admission: Dict[str, Any]):
        self.spec = spec
        self.index = index
        self.theta = theta
        self.prev_delta = prev_delta
        self.epoch = int(epoch)
        self.end_epoch = int(spec.num_epochs if spec.num_epochs is not None
                             else spec.tc.num_epochs)
        self.store = store
        self.done = False
        self.leave_requested = False
        self.last_scalars: Dict[str, Any] = {}
        self.rows_digest: Optional[str] = None
        # digest per ADVANCED epoch (index e = the rows that produced the
        # e→e+1 update) — content identifiers for the logs
        self.rows_digests: List[str] = []
        # the rows of the first epoch this process advanced: from a fresh
        # start the fleet-vs-solo parity surface (init θ is identical, so
        # the rows must be reward_rows_close to the solo program's; later
        # epochs run from rounding-tight θ and drift with it)
        self.first_rows: Optional[np.ndarray] = None
        self.admission = admission


class FleetScheduler:
    """Own the fleet: admission, fair-share ticks, per-job slots, telemetry.

    One scheduler per (backend, reward_fn, cohort) — the backend must already
    be ``setup()`` (the bench/CLI discipline). Thetas live host-side between
    ticks; each tick stacks the selected jobs' trees (``lora.stack_adapters``
    — the dispatch-time host→device transfer, exactly serving's), runs the
    fused step, and unstacks the results. One compiled program per active
    width: any job mix at that width is an argument change, never a compile
    (``fleet_compiles`` counts programs, ``fleet_traces`` retraces — CI
    asserts both flat across job joins/leaves at constant width).
    """

    def __init__(
        self,
        backend,
        reward_fn,
        cohort_tc,
        run_dir,
        max_width: int = 4,
        hbm_budget_bytes: Optional[float] = None,
        peak_bytes_hint: Optional[float] = None,
    ):
        from ..serve.adapter_store import AdapterStore
        from .logging import MetricsLogger

        if max_width < 1:
            raise ValueError(f"max_width must be >= 1, got {max_width}")
        self.backend = backend
        self.reward_fn = reward_fn
        self.cohort_tc = cohort_tc
        self.run_dir = Path(run_dir)
        self.run_dir.mkdir(parents=True, exist_ok=True)
        self.max_width = int(max_width)
        self.hbm_budget_bytes = hbm_budget_bytes
        # offline peak (tools/preflight --fleet) arms the submit-time gate
        # before the first runtime compile has produced a measured one
        self.peak_bytes_hint = peak_bytes_hint
        self.logger = MetricsLogger(self.run_dir)
        # the serve AdapterStore layout AS the job registry: structural
        # admission against the cohort template, content digest + residency
        # accounting per job (budget 0 = no eviction; jobs are not tenants
        # to thrash, the store is the canonical "who is registered" map)
        self.registry_store = AdapterStore(budget_bytes=0)
        self._jobs: Dict[str, _Job] = {}
        self._pending: List[_Job] = []
        self._next_index = 0
        self._frozen = None
        self._compiled: Dict[Tuple[int, int, int], Any] = {}
        self._peaks: Dict[int, float] = {}
        self._tick = 0
        self._geom: Optional[Tuple[int, int]] = None  # (num_unique, repeats)

    # -- admission -----------------------------------------------------------

    def _admission_gate(self, job_id: str, prospective_width: int) -> Dict[str, Any]:
        """The compiled-memory gate (serve/admission.check_fit generalized):
        armed by a measured peak for the prospective width (runtime compile)
        or the preflight hint; unarmed (recorded, not refused) when neither
        the peak nor the budget is known — the CPU-rig convention."""
        from ..serve.admission import check_fit, resolve_hbm_budget

        budget, source = resolve_hbm_budget(self.hbm_budget_bytes)
        peak = self._peaks.get(prospective_width, self.peak_bytes_hint)
        try:
            armed = check_fit(
                f"fleet:{job_id}@w{prospective_width}", peak, budget, source
            )
        except Exception as e:  # ServeAdmissionError → typed fleet refusal
            raise FleetAdmissionError(job_id, "memory no-fit", str(e)) from e
        return {"armed": bool(armed), "peak_bytes": peak,
                "budget_bytes": budget, "budget_source": source,
                "width": prospective_width}

    def submit(self, spec: FleetJobSpec, theta=None, resume: bool = False) -> Dict[str, Any]:
        """Queue a job for admission at the next tick boundary. Validation is
        immediate (duplicate id, cohort mismatch, memory no-fit raise NOW —
        a refused job never half-joins); the membership change itself lands
        at the boundary. Returns the admission record."""
        import jax
        import jax.numpy as jnp

        from ..obs import get_registry
        from ..resilience.checkpoints import CheckpointStore

        if spec.job_id in self._jobs or any(
            p.spec.job_id == spec.job_id for p in self._pending
        ):
            raise FleetAdmissionError(spec.job_id, "duplicate job id")
        mism = cohort_mismatches(spec.tc, self.cohort_tc)
        if mism:
            raise FleetAdmissionError(
                spec.job_id, "cohort geometry mismatch", "; ".join(mism)
            )
        n_after = sum(1 for j in self._jobs.values() if not j.done) + len(self._pending) + 1
        admission = self._admission_gate(spec.job_id, min(self.max_width, n_after))
        store = CheckpointStore(self.run_dir / "jobs" / spec.job_id,
                                keep=max(1, getattr(spec.tc, "ckpt_keep", 3)))
        epoch = 0
        prev_delta = None
        if resume:
            template = theta if theta is not None else self.backend.init_theta(
                jax.random.fold_in(jax.random.PRNGKey(spec.tc.seed), 17)
            )
            res = store.restore(template, with_delta=True)
            if res is not None:
                theta, epoch, prev_delta = res.theta, res.epoch, res.prev_delta
        if theta is None:
            # the trainer's init discipline: θ from (seed, 17) fold-in, so a
            # fleet job's trajectory is the solo run_training trajectory
            theta = self.backend.init_theta(
                jax.random.fold_in(jax.random.PRNGKey(spec.tc.seed), 17)
            )
        theta = jax.tree_util.tree_map(lambda x: np.asarray(x), theta)
        if prev_delta is None:
            prev_delta = jax.tree_util.tree_map(
                lambda x: np.zeros(x.shape, x.dtype), theta
            )
        else:
            prev_delta = jax.tree_util.tree_map(np.asarray, prev_delta)
        job = _Job(spec, self._next_index, theta, store, epoch, prev_delta,
                   admission)
        self._next_index += 1
        self._pending.append(job)
        if self.registry_store.template is None:
            self.registry_store.template = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, jnp.asarray(x).dtype), theta
            )
        get_registry().inc("fleet_submits")
        if not admission["armed"]:
            get_registry().inc("fleet_admission_unarmed")
        self.logger.info(
            f"fleet: job {spec.job_id!r} admitted (index {job.index}, "
            f"epoch {epoch}, gate "
            f"{'armed' if admission['armed'] else 'unarmed'}) — joins at the "
            "next tick boundary"
        )
        return admission

    def leave(self, job_id: str) -> None:
        """Request a leave; effective at the next tick boundary (the job's
        current epoch completes, a final slot commits, then it exits)."""
        if job_id not in self._jobs:
            raise KeyError(f"unknown fleet job {job_id!r}")
        self._jobs[job_id].leave_requested = True

    # -- the tick ------------------------------------------------------------

    def _ensure_frozen(self):
        if self._frozen is None:
            from ..backends.base import make_frozen

            self._frozen = make_frozen(self.backend, self.reward_fn)
        return self._frozen

    def _boundary(self) -> None:
        """Membership changes land here: admit pending joins, retire done/
        leaving jobs (final checkpoint slot + registry update)."""
        from ..obs import get_registry

        for job in self._pending:
            self._jobs[job.spec.job_id] = job
            self.registry_store.put(job.spec.job_id, job.theta, source="fleet-join")
        self._pending.clear()
        for job in self._jobs.values():
            if job.done:
                continue
            if job.epoch >= job.end_epoch or job.leave_requested:
                self._save_job(job, final=True)
                job.done = True
                get_registry().inc("fleet_leaves")
                self.logger.info(
                    f"fleet: job {job.spec.job_id!r} left at epoch boundary "
                    f"{job.epoch} ({'finished' if job.epoch >= job.end_epoch else 'requested'})"
                )

    def _save_job(self, job: _Job, final: bool = False) -> None:
        job.store.save(
            job.theta, job.epoch,
            prev_delta=job.prev_delta,
            summary_reward=float(job.last_scalars.get("reward/combined_mean", 0.0) or 0.0),
            backend_name=self.backend.name,
            config=dataclasses.asdict(job.spec.tc),
            topology={"fleet_width": self.max_width, "fleet_job": job.spec.job_id,
                      "pop_size": job.spec.tc.pop_size},
        )

    def _step_for(self, W: int, num_unique: int, repeats: int, args):
        """Compile-once per (width, m, r): AOT lower + compile with a
        site="fleet" ledger record; later ticks reuse the executable, so a
        changed job mix can never retrace."""
        import time as _time

        from ..obs import get_registry, record_compile
        from .trainer import make_fleet_step

        key = (W, num_unique, repeats)
        if key in self._compiled:
            return self._compiled[key]
        step = make_fleet_step(self.backend, self.reward_fn, self.cohort_tc,
                               num_unique, repeats, W)
        t0 = _time.perf_counter()
        lowered = step.lower(*args)
        lowering_s = _time.perf_counter() - t0
        t0 = _time.perf_counter()
        compiled = lowered.compile()
        compile_s = _time.perf_counter() - t0
        rec = record_compile(
            site="fleet", label=f"fleet_step_w{W}m{num_unique}r{repeats}",
            lowered=lowered, compiled=compiled,
            lowering_s=lowering_s, compile_s=compile_s,
            geometry={"fleet_width": W, "m": num_unique, "r": repeats,
                      "pop": self.cohort_tc.pop_size,
                      "member_batch": self.cohort_tc.member_batch},
        )
        if rec.get("peak_bytes"):
            self._peaks[W] = float(rec["peak_bytes"])
        self._compiled[key] = compiled
        get_registry().inc("fleet_compiles")
        return compiled

    def tick(self) -> bool:
        """One fair-share fleet step: admit/retire at the boundary, select
        the ``max_width`` lowest-epoch active jobs, advance them one epoch
        through the fused program, fan out per-job telemetry and due
        checkpoints. Returns False when no job is active (fleet drained)."""
        import jax
        import jax.numpy as jnp

        from ..es import epoch_key
        from ..lora import stack_adapters
        from ..obs import get_registry
        from .trainer import fleet_scalar_args

        self._boundary()
        active = [j for j in self._jobs.values() if not j.done]
        if not active:
            return False
        selected = sorted(active, key=lambda j: (j.epoch, j.index))[: self.max_width]
        W = len(selected)

        infos = [
            self.backend.step_info(
                j.epoch, j.spec.tc.prompts_per_gen, j.spec.tc.batches_per_gen
            )
            for j in selected
        ]
        geoms = {(len(i.unique_ids), i.repeats) for i in infos}
        if len(geoms) != 1:
            raise RuntimeError(
                f"fleet cohort produced divergent step geometries {geoms} — "
                "prompts_per_gen/batches_per_gen must be cohort-uniform"
            )
        (num_unique, repeats), = geoms
        self._geom = (num_unique, repeats)

        frozen = self._ensure_frozen()
        stacked = stack_adapters([j.theta for j in selected])
        sdelta = stack_adapters([j.prev_delta for j in selected])
        ids = jnp.asarray(np.stack([np.asarray(i.flat_ids, np.int32) for i in infos]))
        keys = jnp.stack([epoch_key(j.spec.tc.seed, j.epoch) for j in selected])
        sig, csc, lrs = fleet_scalar_args([j.spec.tc for j in selected])
        args = (frozen, stacked, sdelta, ids, keys,
                jnp.asarray(sig), jnp.asarray(csc), jnp.asarray(lrs))
        compiled = self._step_for(W, num_unique, repeats, args)
        theta_new, delta, metrics, opt_scores = compiled(*args)
        metrics = jax.device_get(metrics)
        rows = np.asarray(metrics.pop("fleet_reward_rows"))  # [W, pop, B]
        theta_new = jax.device_get(theta_new)
        delta = jax.device_get(delta)

        reg = get_registry()
        reg.gauge("fleet_width", W)
        reg.gauge("fleet_active_jobs", len(active))
        # "epoch" = the tick number: run_report's row loader keys every
        # series on it (the solo trainer writes it in its scalars; the
        # fleet's per-JOB epochs live under job<j>/epoch instead)
        line: Dict[str, Any] = {"epoch": self._tick, "fleet_tick": self._tick,
                                "fleet_width": W}
        for j, job in enumerate(selected):
            job.theta = jax.tree_util.tree_map(lambda l, _j=j: np.asarray(l[_j]), theta_new)
            job.prev_delta = jax.tree_util.tree_map(lambda l, _j=j: np.asarray(l[_j]), delta)
            job.epoch += 1
            job.rows_digest = reward_rows_digest(rows[j])
            job.rows_digests.append(job.rows_digest)
            if job.first_rows is None:
                job.first_rows = np.array(rows[j], np.float32)
            prefix = f"job{job.index}"
            scalars: Dict[str, Any] = {}
            for k, v in metrics.items():
                leaf = np.asarray(v)
                if leaf.ndim >= 1 and leaf.shape[0] == W:
                    vj = leaf[j]
                    if vj.ndim == 0:
                        scalars[k] = float(vj)
            job.last_scalars = scalars
            # per-job streams through the PR-13 surfaces: namespaced rows in
            # metrics.jsonl (one line per tick, all jobs) + exporter gauges
            for k, v in scalars.items():
                line[f"{prefix}/{k}"] = v
            line[f"{prefix}/epoch"] = job.epoch
            line[f"{prefix}/job_id"] = job.spec.job_id
            line[f"{prefix}/reward_rows_sha256"] = job.rows_digest
            reg.gauge(f"{prefix}/epoch", job.epoch)
            if "opt_score_mean" in scalars:
                reg.gauge(f"{prefix}/opt_score_mean", scalars["opt_score_mean"])
            self.registry_store.put(job.spec.job_id, job.theta, source="fleet-tick")
            every = getattr(job.spec.tc, "save_every", 0)
            if every and job.epoch % every == 0:
                self._save_job(job)
        self.logger.log(self._tick, line)
        self._tick += 1
        return True

    def run(self, max_ticks: Optional[int] = None) -> int:
        """Tick until the fleet drains (or ``max_ticks``); returns ticks run."""
        n = 0
        while (max_ticks is None or n < max_ticks) and self.tick():
            n += 1
        return n

    # -- introspection -------------------------------------------------------

    def job_state(self, job_id: str) -> Dict[str, Any]:
        j = self._jobs[job_id]
        return {"job_id": job_id, "index": j.index, "epoch": j.epoch,
                "end_epoch": j.end_epoch, "done": j.done,
                "rows_digest": j.rows_digest, "rows_digests": list(j.rows_digests),
                "first_rows": j.first_rows,
                "admission": j.admission,
                "scalars": dict(j.last_scalars)}

    def restore_job(self, job_id: str, theta_template) -> Any:
        """Independently restore a job's newest slot (the per-job-slot
        contract CI asserts): a job's checkpoints are a plain PR-4 store at
        ``run_dir/jobs/<job_id>`` — no fleet state needed to read them."""
        from ..resilience.checkpoints import CheckpointStore

        store = CheckpointStore(self.run_dir / "jobs" / job_id)
        return store.restore(theta_template, with_delta=True)

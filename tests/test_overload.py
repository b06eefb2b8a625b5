"""Overload-robustness tests (ISSUE 19, serve/overload.py + satellites).

The load-bearing assertions:

- **hysteretic brownout ladder**: escalate only after N consecutive
  pressured evaluations, recover only after M calm ones, and the band in
  between FREEZES the ladder (no flapping);
- **per-adapter circuit breaker**: closed → open on consecutive dispatch
  faults, half-open after cooldown admitting exactly ONE probe, closed on
  probe success / re-open on probe fault — and an un-dispatched probe
  returns its slot (no wedged breaker);
- **deadline + doomed shedding**: a request whose deadline expires in the
  queue is shed before occupying a batch lane, its censored wait stays in
  the queue-wait histogram, and the EWMA predictor sheds requests whose
  remaining budget cannot cover their geometry's measured dispatch time;
- **residency leases**: eviction skips leased adapters, so the PR-16
  "admitted at submit, not resident at dispatch" refusal count is exactly
  ZERO with the layer armed (and reproducibly nonzero without it);
- **exactly-once finalize**: the abandon/shed race releases the lease and
  backdates the censored wait once — the duplicate-finalize counter is the
  proof;
- the chaos faults (``store_io*N`` feeding the breaker, ``slow_dispatch*N``
  feeding the EWMA), the shed-path SLO availability burn, the /healthz
  pressure view, the harness-side shed/expiry accounting, and the
  ``DEGRADE_*.json`` → ``ingest_degrade`` → sentry-trip artifact chain.
"""

import json
import time
import types

import pytest

from hyperscalees_t2i_tpu.obs import MetricsRegistry, get_registry, set_registry
from hyperscalees_t2i_tpu.serve.overload import (
    BREAKER_CLOSED,
    BREAKER_HALF_OPEN,
    BREAKER_OPEN,
    BROWNOUT_LADDER,
    AdapterBreaker,
    DispatchEwma,
    OverloadConfig,
    OverloadGovernor,
    PressureController,
)


# ---------------------------------------------------------------------------
# pressure controller (pure logic, no jax)
# ---------------------------------------------------------------------------

def test_ladder_hysteresis_escalate_band_recover():
    cfg = OverloadConfig(escalate_after=2, recover_after=3, recover_below=0.5)
    pc = PressureController(cfg)
    assert pc.rung == 0 and pc.rung_name == BROWNOUT_LADDER[0]
    # one hot evaluation is NOT enough (escalate_after=2)
    pc.update(queue_frac=0.9, burn=None, thrash=0)
    assert pc.rung == 0
    pc.update(queue_frac=0.9, burn=None, thrash=0)
    assert pc.rung == 1 and pc.escalations == 1
    # the band (0.5 <= worst < 1.0) freezes BOTH streaks: neither three
    # band samples nor a band sample between calm ones moves the ladder
    for _ in range(5):
        pc.update(queue_frac=0.3, burn=None, thrash=0)  # score 0.6: band
    assert pc.rung == 1 and pc._calm_streak == 0 and pc._hot_streak == 0
    # calm streak interrupted by a band sample restarts from zero
    pc.update(queue_frac=0.1, burn=None, thrash=0)
    pc.update(queue_frac=0.1, burn=None, thrash=0)
    pc.update(queue_frac=0.3, burn=None, thrash=0)  # band: reset
    pc.update(queue_frac=0.1, burn=None, thrash=0)
    pc.update(queue_frac=0.1, burn=None, thrash=0)
    assert pc.rung == 1  # still only 2 consecutive calm evals
    pc.update(queue_frac=0.1, burn=None, thrash=0)
    assert pc.rung == 0 and pc.recoveries == 1
    # any single saturated signal is enough to count as pressured
    pc.update(queue_frac=0.0, burn=20.0, thrash=0)
    pc.update(queue_frac=0.0, burn=20.0, thrash=0)
    assert pc.rung == 1
    assert pc.last["worst"] == pytest.approx(20.0 / cfg.burn_high)


def test_ladder_tops_out_and_signals_normalized():
    cfg = OverloadConfig(escalate_after=1)
    pc = PressureController(cfg)
    for _ in range(10):
        pc.update(queue_frac=1.0, burn=100.0, thrash=100.0)
    assert pc.rung == len(BROWNOUT_LADDER) - 1  # clamped at the top
    assert pc.last["queue"] == pytest.approx(1.0 / cfg.queue_high_frac)
    assert pc.last["thrash"] == pytest.approx(100.0 / cfg.thrash_high)


# ---------------------------------------------------------------------------
# circuit breaker (injectable clock)
# ---------------------------------------------------------------------------

def test_breaker_open_halfopen_close_cycle():
    clock = types.SimpleNamespace(t=0.0)
    cfg = OverloadConfig(breaker_faults=3, breaker_cooldown_s=5.0)
    br = AdapterBreaker(cfg, clock=lambda: clock.t)
    assert br.allow("a") and br.state("a") == BREAKER_CLOSED
    assert not br.record_fault("a")
    assert not br.record_fault("a")
    assert br.record_fault("a")  # third consecutive fault: open
    assert br.state("a") == BREAKER_OPEN and br.opens == 1
    assert not br.allow("a")  # quarantined
    clock.t += 5.0  # cooldown elapsed: next allow IS the probe
    assert br.allow("a")
    assert br.state("a") == BREAKER_HALF_OPEN
    assert not br.allow("a")  # exactly one probe in flight
    br.record_ok("a")  # probe succeeded: closed AND forgotten
    assert br.state("a") == BREAKER_CLOSED and br.closes == 1
    assert "a" not in br._st


def test_breaker_probe_fault_reopens_and_abort_returns_slot():
    clock = types.SimpleNamespace(t=0.0)
    br = AdapterBreaker(OverloadConfig(breaker_faults=1, breaker_cooldown_s=2.0),
                        clock=lambda: clock.t)
    br.record_fault("a")
    clock.t += 2.0
    assert br.allow("a")  # probe
    br.record_fault("a")  # probe failed: re-open, fresh cooldown
    assert br.state("a") == BREAKER_OPEN and br.opens == 2
    assert not br.allow("a")
    clock.t += 2.0
    assert br.allow("a")  # new probe
    assert not br.allow("a")
    # the probe request was shed before dispatch: without abort_probe the
    # half-open breaker would refuse forever
    br.abort_probe("a")
    assert br.allow("a")


def test_breaker_tracking_bounded():
    br = AdapterBreaker(OverloadConfig(breaker_faults=1, breaker_max_tracked=4))
    for i in range(10):
        br.record_fault(f"a{i}")
    assert len(br._st) <= 4
    assert len(br.non_closed()) <= 4  # bounded labeled-series cardinality


# ---------------------------------------------------------------------------
# EWMA + doom predicate
# ---------------------------------------------------------------------------

def test_ewma_per_geometry_and_doom_reasons():
    gov = OverloadGovernor(OverloadConfig(ewma_alpha=0.5))
    gov.ewma.observe(("g1",), 1.0)
    gov.ewma.observe(("g1",), 3.0)
    assert gov.ewma.get(("g1",)) == pytest.approx(2.0)
    assert gov.ewma.get(("g2",)) is None  # unprimed: never predicts

    req = types.SimpleNamespace(t_deadline=None, geometry_key=("g1",))
    assert gov.doom_reason(req, now=100.0) is None  # no deadline: never doomed
    req = types.SimpleNamespace(t_deadline=50.0, geometry_key=("g1",))
    assert gov.doom_reason(req, now=50.0) == "deadline"  # expired
    assert gov.doom_reason(req, now=49.0) == "doomed"  # 1s budget < 2s EWMA
    assert gov.doom_reason(req, now=40.0) is None  # 10s budget covers it
    # unprimed geometry with live deadline: no prediction, no shed
    req2 = types.SimpleNamespace(t_deadline=50.0, geometry_key=("g2",))
    assert gov.doom_reason(req2, now=49.9) is None
    # shed_doomed=False: only hard expiry sheds
    gov2 = OverloadGovernor(OverloadConfig(shed_doomed=False))
    gov2.ewma.observe(("g1",), 5.0)
    assert gov2.doom_reason(req, now=49.0) is None


# ---------------------------------------------------------------------------
# fault-injection grammar (resilience/faultinject.py serve faults)
# ---------------------------------------------------------------------------

def test_serve_fault_tokens_parse_and_consume():
    from hyperscalees_t2i_tpu.resilience.faultinject import (
        FaultPlan, maybe_serve_fault, set_fault_plan,
    )

    plan = FaultPlan.parse("slow_dispatch*2;store_io")
    assert plan.serve_faults == {"slow_dispatch": 2, "store_io": 1}
    # host-scoped to another process: not armed here
    assert FaultPlan.parse("store_io*3:host7").serve_faults == {}
    set_fault_plan(plan)
    try:
        assert maybe_serve_fault("slow_dispatch")
        assert maybe_serve_fault("slow_dispatch")
        assert not maybe_serve_fault("slow_dispatch")  # exhausted
        assert maybe_serve_fault("store_io")
        assert not maybe_serve_fault("store_io")
    finally:
        set_fault_plan(None)
    with pytest.raises(ValueError, match="unknown fault token"):
        FaultPlan.parse("bogus_fault*2")


# ---------------------------------------------------------------------------
# residency leases on the store
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def backend():
    from hyperscalees_t2i_tpu.backends.sana_backend import SanaBackend
    from hyperscalees_t2i_tpu.rungs import sana_rung_model

    b = SanaBackend(sana_rung_model("tiny")["bcfg"])
    b.setup()
    return b


@pytest.fixture(scope="module")
def template(backend):
    import jax

    return backend.init_theta(jax.random.PRNGKey(0))


def test_lease_blocks_budget_eviction(backend, template):
    from hyperscalees_t2i_tpu.serve import AdapterStore, adapter_bytes

    set_registry(MetricsRegistry())
    one = adapter_bytes(template)
    store = AdapterStore(budget_bytes=int(2.5 * one), template=template)
    store.put("a", template)
    store.put("b", template)
    store.lease("a")  # a is LRU *and* leased
    store.put("c", template)  # must evict b, never leased a
    assert set(store.ids()) == {"a", "c"}
    # everything leased + admit over budget: nothing evictable — the store
    # runs over budget and counts the tension instead of dropping a pin
    store.lease("c")
    store.put("d", template)
    assert set(store.ids()) == {"a", "c", "d"}
    assert store.resident_bytes > store.budget_bytes
    assert store.lease_blocked >= 1
    assert store.stats()["lease_blocked_evictions"] == store.lease_blocked
    # release re-enables eviction: the next admit evicts the unleased LRU
    store.release("a")
    store.put("e", template)
    assert "a" not in store.ids() and store.leased("c")
    snap = get_registry().snapshot()
    assert snap["obs/serve_lease_blocked_evictions"] >= 1
    assert snap["obs/serve_lease_acquired"] == 2


def test_lease_refcount_release_and_explicit_evict(backend, template):
    from hyperscalees_t2i_tpu.serve import AdapterStore

    set_registry(MetricsRegistry())
    store = AdapterStore(template=template)
    with pytest.raises(KeyError, match="cannot lease"):
        store.lease("ghost")  # leasing a non-resident id would hide thrash
    store.put("a", template)
    store.lease("a")
    store.lease("a")
    assert store.leases_active == 2
    store.release("a")
    assert store.leased("a")
    # explicit eviction refuses a leased tenant unless forced
    assert not store.evict("a")
    assert "a" in store.ids() and store.lease_blocked == 1
    assert store.evict("a", force=True)
    assert not store.leased("a") and store.leases_active == 0
    # releasing past zero is a counted no-op, never an error
    store.release("a")
    assert get_registry().snapshot()["obs/serve_lease_release_orphaned"] == 1


# ---------------------------------------------------------------------------
# engine: deadlines, doomed shedding, exactly-once finalize
# ---------------------------------------------------------------------------

def _engine(backend, template, **cfg_kw):
    from hyperscalees_t2i_tpu.serve import ServeConfig, ServeEngine

    cfg_kw.setdefault("adapter_batch", 2)
    eng = ServeEngine(backend, ServeConfig(**cfg_kw), theta_template=template)
    eng.put_adapter("a", template)
    return eng


def test_submit_expired_deadline_sheds_with_censored_wait(backend, template):
    from hyperscalees_t2i_tpu.serve import ServeShedError

    set_registry(MetricsRegistry())
    eng = _engine(backend, template, overload=OverloadConfig())
    with pytest.raises(ServeShedError) as ei:
        eng.submit("a", [0], seed=1, deadline_s=0.5,
                   t_submit=time.perf_counter() - 2.0)
    assert ei.value.reason == "deadline"
    snap = get_registry().snapshot()
    assert snap["obs/serve_shed_total"] == 1
    assert snap["obs/serve_request_errors"] == 1
    # the shed request's backdated (~2 s) wait stays in the histogram
    h = snap["obs/serve_queue_wait_seconds"]
    assert h["count"] == 1 and h["sum"] > 1.5
    assert eng.store.leases_active == 0  # never leased: shed pre-queue
    assert eng._governor.shed == {"deadline": 1}


def test_deadline_expires_in_queue_sheds_before_dispatch(backend, template):
    set_registry(MetricsRegistry())
    eng = _engine(backend, template, overload=OverloadConfig())
    req = eng.submit("a", [0], seed=1, deadline_s=0.05)
    assert eng.store.leases_active == 1  # pinned from accepted submit
    time.sleep(0.08)
    results = eng.flush()
    assert len(results) == 1 and results[0].shed_reason == "deadline"
    assert not results[0].ok and results[0].batch_size == 0
    assert eng.store.leases_active == 0  # released by the shed finalize
    assert req.finalized
    snap = get_registry().snapshot()
    assert snap["obs/serve_shed_total"] == 1
    assert snap["obs/serve_queue_wait_seconds"]["count"] == 1
    # the lane was never occupied: no dispatch happened
    assert "obs/serve_dispatches" not in snap


def test_doomed_ewma_shed_and_default_deadline(backend, template):
    set_registry(MetricsRegistry())
    # default deadline stamped by config; EWMA primed way above the budget
    eng = _engine(backend, template,
                  overload=OverloadConfig(deadline_default_s=0.5))
    req = eng.submit("a", [0], seed=1)  # no explicit deadline
    assert req.t_deadline == pytest.approx(req.t_submit + 0.5)
    eng._governor.ewma.observe(req.geometry_key, 100.0)
    results = eng.flush()
    assert [r.shed_reason for r in results] == ["doomed"]
    assert eng._governor.shed == {"doomed": 1}
    # a request with NO deadline rides through untouched by the predictor
    eng2 = _engine(backend, template, overload=OverloadConfig())
    eng2._governor.ewma.observe((1, None), 100.0)
    eng2.submit("a", [0], seed=2)
    out = eng2.flush()
    assert len(out) == 1 and out[0].ok


def test_exactly_once_finalize_shed_then_abandon(backend, template):
    set_registry(MetricsRegistry())
    eng = _engine(backend, template, overload=OverloadConfig())
    req = eng.submit("a", [0], seed=1, deadline_s=0.01)
    time.sleep(0.03)
    results = eng.flush()
    assert results[0].shed_reason == "deadline"
    wait_count = get_registry().snapshot()["obs/serve_queue_wait_seconds"]["count"]
    # the race partner arrives late: a second finalize (abandon sweep) must
    # be a counted no-op — no double lease release, no double wait sample
    assert eng._finalize_request(req, reason="abandon", censored_wait=True) is False
    snap = get_registry().snapshot()
    assert snap["obs/serve_finalize_duplicates"] == 1
    assert snap["obs/serve_queue_wait_seconds"]["count"] == wait_count
    # released ONCE: the orphaned-release counter never ticked
    assert "obs/serve_lease_release_orphaned" not in snap
    # and a clean abandon path still finalizes exactly once
    eng.submit("a", [0], seed=2)
    abandoned = eng.abandon_queued()
    assert len(abandoned) == 1 and abandoned[0].finalized
    assert eng.store.leases_active == 0


# ---------------------------------------------------------------------------
# engine: brownout ladder actions + breaker quarantine
# ---------------------------------------------------------------------------

def test_brownout_priority_shed_and_degrade(backend, template):
    from hyperscalees_t2i_tpu.serve import ServeShedError

    set_registry(MetricsRegistry())
    eng = _engine(backend, template, overload=OverloadConfig())
    gov = eng._governor
    gov.controller.rung = 1
    with pytest.raises(ServeShedError) as ei:
        eng.submit("a", [0], seed=1, priority=0)  # below the bar at rung 1
    assert ei.value.reason == "brownout_priority"
    eng.submit("a", [0], seed=2, priority=1)  # default priority rides
    gov.controller.rung = 2
    req = eng.submit("a", [0, 1], seed=3)  # rung 2: truncated + flagged
    assert req.degraded and len(req.prompt_ids) == 1
    results = eng.flush()
    by_seed = {r.request.seed: r for r in results}
    assert by_seed[3].degraded and by_seed[3].ok
    assert not by_seed[2].degraded
    assert gov.degraded_total == 1
    snap = get_registry().snapshot()
    assert snap["obs/serve_degraded_total"] == 1
    assert snap["obs/serve_shed_total"] == 1


def test_pressure_escalation_from_real_queue_depth(backend, template):
    set_registry(MetricsRegistry())
    eng = _engine(backend, template, max_queue=4,
                  overload=OverloadConfig(escalate_after=1))
    for s in range(3):
        eng.submit("a", [0], seed=s)
    results = eng.flush()  # first iteration: queue_frac 0.75 -> escalate
    assert all(r.ok for r in results)
    assert eng._governor.controller.escalations >= 1
    snap = get_registry().snapshot()
    assert snap["obs/serve_brownout_transitions"] >= 1
    assert "obs/serve/pressure_rung" in snap


def test_breaker_quarantines_store_io_faults_then_recovers(backend, template):
    from hyperscalees_t2i_tpu.resilience.faultinject import (
        FaultPlan, set_fault_plan,
    )
    from hyperscalees_t2i_tpu.serve import ServeShedError

    set_registry(MetricsRegistry())
    eng = _engine(backend, template,
                  overload=OverloadConfig(breaker_faults=2,
                                          breaker_cooldown_s=60.0))
    gov = eng._governor
    set_fault_plan(FaultPlan.parse("store_io*2"))
    try:
        for s in range(2):
            eng.submit("a", [0], seed=s)
            out = eng.flush()
            assert len(out) == 1 and not out[0].ok
            assert out[0].shed_reason is None  # a fault, not a shed
        assert gov.breaker.state("a") == BREAKER_OPEN
        assert eng.store.leases_active == 0  # fault finalize released them
        with pytest.raises(ServeShedError) as ei:
            eng.submit("a", [0], seed=9)
        assert ei.value.reason == "breaker_open"
        # cooldown elapses (rewound manually — the governor clock is real
        # monotonic here): ONE probe is admitted and its success closes
        gov.breaker._st["a"]["t_open"] -= 120.0
        eng.submit("a", [0], seed=10)
        out = eng.flush()
        assert len(out) == 1 and out[0].ok
        assert gov.breaker.state("a") == BREAKER_CLOSED
    finally:
        set_fault_plan(None)
    snap = get_registry().snapshot()
    assert snap["obs/serve_shed_total"] == 1
    assert snap["obs/serve_request_errors"] == 3  # 2 faults + 1 shed


def test_slow_dispatch_fault_inflates_ewma(backend, template, monkeypatch):
    from hyperscalees_t2i_tpu.resilience.faultinject import (
        SLOW_FAULT_ENV, FaultPlan, set_fault_plan,
    )
    from hyperscalees_t2i_tpu.resilience.telemetry import (
        get_resilience_registry, set_resilience_registry,
    )

    set_registry(MetricsRegistry())
    set_resilience_registry(None)
    eng = _engine(backend, template, overload=OverloadConfig())
    eng.submit("a", [0], seed=1)
    eng.flush()
    baseline = eng._governor.ewma.get((1, None))
    assert baseline is not None
    # No wall-clock margin: the straggle is sized from the baseline itself.
    # time.sleep never returns early, so the faulted dispatch lasts at least
    # the straggle, which exceeds the baseline — the EWMA (a convex mix of
    # the two) must rise, however slow or fast this machine is.
    monkeypatch.setenv(SLOW_FAULT_ENV, repr(2.0 * baseline + 0.01))
    plan = FaultPlan.parse("slow_dispatch*1")
    set_fault_plan(plan)
    try:
        eng.submit("a", [0], seed=2)
        eng.flush()
        inflated = eng._governor.ewma.get((1, None))
        eng.submit("a", [0], seed=3)  # the plan is spent: no second straggle
        eng.flush()
    finally:
        set_fault_plan(None)
    assert inflated > baseline
    # exactly one injected fault was observed over the two dispatches
    assert plan.serve_faults["slow_dispatch"] == 0
    assert get_resilience_registry().snapshot()["resilience/faults_injected"] == 1


# ---------------------------------------------------------------------------
# engine: leases eliminate admit-then-thrash (the acceptance bar)
# ---------------------------------------------------------------------------

def _thrash_scenario(backend, template, overload):
    """4 tenants admitted through a 2-adapter store budget, all queued
    before one flush — exactly PR 16's admit-then-thrash shape."""
    import jax

    from hyperscalees_t2i_tpu.serve import (
        ServeConfig, ServeEngine, adapter_bytes,
    )

    eng = ServeEngine(
        backend,
        ServeConfig(adapter_batch=4,
                    adapter_budget_bytes=int(2.5 * adapter_bytes(template)),
                    overload=overload),
        theta_template=template,
    )
    for i, aid in enumerate(["t0", "t1", "t2", "t3"]):
        theta = jax.tree_util.tree_map(
            lambda x, k=jax.random.fold_in(jax.random.PRNGKey(7), i):
            x + 0.01 * jax.random.normal(k, x.shape, x.dtype),
            template,
        )
        eng.put_adapter(aid, theta)
        eng.submit(aid, [0], seed=i)
    return eng, eng.flush()


def test_leases_zero_not_resident_refusals(backend, template):
    # OFF reproduces the PR-16 hazard: later admissions evict queued
    # tenants' adapters, which then miss at dispatch
    set_registry(MetricsRegistry())
    eng_off, results_off = _thrash_scenario(backend, template, overload=None)
    off_snap = eng_off.overload_snapshot()
    assert not off_snap["enabled"]
    assert off_snap["not_resident_refusals"] >= 1
    assert any(not r.ok for r in results_off)
    # ON: the lease pins every queued tenant's adapter; the store runs
    # over budget (counted) instead of thrashing, and the dispatch-time
    # not-resident count is exactly zero
    set_registry(MetricsRegistry())
    eng_on, results_on = _thrash_scenario(backend, template,
                                          overload=OverloadConfig())
    on_snap = eng_on.overload_snapshot()
    assert on_snap["enabled"]
    assert on_snap["not_resident_refusals"] == 0
    assert all(r.ok for r in results_on)
    assert on_snap["lease_blocked_evictions"] >= 1
    assert on_snap["leases_active"] == 0  # all released at completion
    assert "obs/serve_not_resident_refusals" not in get_registry().snapshot()


# ---------------------------------------------------------------------------
# observability: SLO burn, /healthz pressure view, exporter payload
# ---------------------------------------------------------------------------

def test_shed_burns_availability_slo(backend, template):
    from hyperscalees_t2i_tpu.serve import ServeShedError

    set_registry(MetricsRegistry())
    eng = _engine(backend, template, overload=OverloadConfig(),
                  slo="availability=99.9")
    eng._slo.tick()  # anchor sample at zero bad/total (a burn is a delta)
    with pytest.raises(ServeShedError):
        eng.submit("a", [0], seed=1, deadline_s=0.5,
                   t_submit=time.perf_counter() - 2.0)
    # the shed ticked the evaluator: 1 bad / 1 total torches the budget
    burn = eng._slo.max_burn("fast")
    assert burn is not None and burn > 1.0
    # and the pressure controller reads that burn as a saturated signal
    eng._pressure_eval()
    assert eng._governor.controller.last["burn"] >= 1.0


def test_healthz_pressure_view_and_metrics_payload(backend, template):
    set_registry(MetricsRegistry())
    eng = _engine(backend, template, overload=OverloadConfig())
    gov = eng._governor
    gov.count_shed("deadline")
    gov.count_shed("deadline")
    gov.breaker.record_fault("bad")
    gov.breaker.record_fault("bad")
    gov.breaker.record_fault("bad")  # open at default threshold 3
    eng.submit("a", [0], seed=1)
    health = eng.health()
    pv = health["pressure"]
    assert pv["rung"] == "normal" and pv["rung_index"] == 0
    assert pv["leases_active"] == 1
    assert pv["shed_total"] == 2 and pv["shed"] == {"deadline": 2}
    assert pv["breakers_open"] == 1
    assert health["serve"]["not_resident_refusals"] == 0
    # exporter scalar source: labeled shed-reason + breaker-state series
    m = eng.overload_metrics()
    assert m["serve/leases_active"] == 1
    assert m["serve_shed_total"] == 2
    assert ({"reason": "deadline"}, 2) in m["serve_shed_reason"]["labeled"]
    assert ({"adapter": "bad"}, 2) in m["serve_breaker_state"]["labeled"]
    # an OFF engine still reports lease/thrash scalars, no governor series
    eng.flush()
    from hyperscalees_t2i_tpu.serve import ServeConfig, ServeEngine

    off = ServeEngine(backend, ServeConfig(adapter_batch=2),
                      theta_template=template)
    assert "pressure" not in off.health()
    assert set(off.overload_metrics()) == {
        "serve/leases_active", "serve_not_resident_refusals",
    }


# ---------------------------------------------------------------------------
# harness: --deadline_s accounting in run_step (fake engine, no jax)
# ---------------------------------------------------------------------------

class _FakeQ:
    def __init__(self):
        self.items = []

    @property
    def depth(self):
        return len(self.items)


class _ShedFakeEngine:
    """Every 4th submit is shed (typed refusal); flushed results alternate
    ok-in-deadline / shed-in-queue / ok-past-deadline, so every terminal
    class of the deadline accounting shows up in one window."""

    def __init__(self, deadline_s):
        self.queue = _FakeQ()
        self.store = types.SimpleNamespace(
            stats=lambda: {"hits": 0, "misses": 0, "evictions": 0,
                           "resident": 0, "resident_bytes": 0})
        self.cfg = types.SimpleNamespace(adapter_batch=2, max_queue=10_000)
        self.backend = types.SimpleNamespace(num_items=4)
        self.deadline_s = deadline_s
        self.n_submit = 0
        self.seen_deadlines = []
        self.snap = {"enabled": True, "rung": 0, "shed": {}, "shed_total": 0,
                     "degraded_total": 0, "not_resident_refusals": 0,
                     "leases_active": 0, "lease_blocked_evictions": 0,
                     "breakers_open": 0}

    def submit(self, adapter_id, prompt_ids, seed, t_submit=None,
               deadline_s=None):
        from hyperscalees_t2i_tpu.serve import ServeShedError

        self.seen_deadlines.append(deadline_s)
        self.n_submit += 1
        if self.n_submit % 4 == 0:
            self.snap["shed_total"] += 1
            raise ServeShedError("brownout_priority")
        self.queue.items.append(types.SimpleNamespace(t_submit=t_submit))

    def flush(self, max_batches=None):
        out = []
        take = self.queue.items[:2]
        del self.queue.items[:2]
        now = time.perf_counter()
        for i, it in enumerate(take):
            kind = (self.n_submit + i) % 3
            if kind == 0:
                out.append(types.SimpleNamespace(
                    ok=True, latency_s=now - it.t_submit,
                    t_submit=it.t_submit, batch_occupancy=1.0))
            elif kind == 1:
                self.snap["shed_total"] += 1
                out.append(types.SimpleNamespace(
                    ok=False, shed_reason="deadline",
                    latency_s=now - it.t_submit, t_submit=it.t_submit))
            else:
                # served but late: the client already walked away
                out.append(types.SimpleNamespace(
                    ok=True, latency_s=self.deadline_s + 1.0,
                    t_submit=it.t_submit, batch_occupancy=1.0))
        return out

    def abandon_queued(self):
        out, self.queue.items = self.queue.items, []
        return out

    def overload_snapshot(self):
        return dict(self.snap, shed=dict(self.snap["shed"]))


class _FakePopLocal:
    def ensure(self, engine, index):
        return f"synth-{index:06d}"


def test_run_step_deadline_shed_and_expiry_accounting():
    from hyperscalees_t2i_tpu.tools.loadgen import (
        TrafficConfig, build_schedule, run_step,
    )

    set_registry(MetricsRegistry())
    cfg = TrafficConfig(rate_rps=60.0, window_s=1.0, seed=9, population=8)
    arrivals = build_schedule(cfg)
    assert len(arrivals) > 20
    eng = _ShedFakeEngine(deadline_s=0.25)
    row = run_step(eng, _FakePopLocal(), arrivals, cfg.window_s,
                   slo_p99_s=0.5, offered_rps=cfg.rate_rps, deadline_s=0.25)
    # the deadline threaded through to every submit
    assert all(d == 0.25 for d in eng.seen_deadlines)
    assert row["deadline_s"] == 0.25
    # every arrival lands in exactly one terminal class
    total = (row["completed"] + row["abandoned"] + row["rejected"]
             + row["errors"] + row["shed"] + row["client_expired"])
    assert total == len(arrivals)
    assert row["shed"] > 0 and row["client_expired"] > 0
    assert row["errors"] == 0
    # shed + expired waits are censored INTO the open tail, not deleted:
    # the fabricated late completions (deadline + 1.0 s) dominate the p99
    assert row["p99_open_s"] is not None and row["p99_open_s"] >= 1.0
    # no completed (in-deadline) latency can reach that tail value, so the
    # open p99 comes from the censored classes — survivorship honesty
    assert row["p99_s"] is None or row["p99_s"] < row["p99_open_s"]
    assert row["overload_enabled"] is True
    assert row["shed_by_reason"] == {}  # fake keeps no per-reason ledger
    assert row["not_resident_refusals"] == 0


def test_run_step_without_deadline_unchanged():
    """No deadline_s: legacy fakes (no deadline kwarg, no snapshot) work
    and the row carries no overload fields — back-compat with PR 16."""
    from hyperscalees_t2i_tpu.tools.loadgen import (
        TrafficConfig, build_schedule, run_step,
    )

    class _Legacy:
        def __init__(self):
            self.queue = _FakeQ()
            self.store = types.SimpleNamespace(
                stats=lambda: {"hits": 0, "misses": 0, "evictions": 0,
                               "resident": 0, "resident_bytes": 0})
            self.cfg = types.SimpleNamespace(adapter_batch=2, max_queue=100)
            self.backend = types.SimpleNamespace(num_items=4)

        def submit(self, adapter_id, prompt_ids, seed, t_submit=None):
            self.queue.items.append(types.SimpleNamespace(t_submit=t_submit))

        def flush(self, max_batches=None):
            out, self.queue.items = self.queue.items[:2], self.queue.items[2:]
            now = time.perf_counter()
            return [types.SimpleNamespace(ok=True, latency_s=now - o.t_submit,
                                          t_submit=o.t_submit,
                                          batch_occupancy=1.0) for o in out]

        def abandon_queued(self):
            out, self.queue.items = self.queue.items, []
            return out

    set_registry(MetricsRegistry())
    cfg = TrafficConfig(rate_rps=30.0, window_s=0.5, seed=2, population=4)
    arrivals = build_schedule(cfg)
    row = run_step(_Legacy(), _FakePopLocal(), arrivals, cfg.window_s,
                   slo_p99_s=1.0, offered_rps=cfg.rate_rps)
    assert row["completed"] + row["abandoned"] == len(arrivals)
    assert row["shed"] == 0 and row["client_expired"] == 0
    assert row["deadline_s"] is None
    assert "overload_enabled" not in row  # no snapshot -> no overload block


# ---------------------------------------------------------------------------
# DEGRADE artifact -> ingest_degrade -> sentry gate
# ---------------------------------------------------------------------------

def _degrade_doc(retention):
    return {
        "mode": "degrade", "schema_version": 1, "rung": "tiny",
        "overload_rate_rps": 1024.0, "goodput_retention": retention,
        "off_goodput_retention": 0.3, "on_p99_s": 1.2,
        "on_not_resident_refusals": 0,
    }


def test_ingest_degrade_keys_and_policy(tmp_path):
    from hyperscalees_t2i_tpu.obs import regress

    p = tmp_path / "DEGRADE_r01.json"
    p.write_text(json.dumps(_degrade_doc(0.82)))
    obs = regress.ingest(p)
    assert [(o.metric, o.key, o.value) for o in obs] == [
        ("goodput_retention", "degrade/tiny", 0.82)
    ]
    # DOWN-only: the policy gates a falling retention, never a rising one
    pol = regress.METRIC_POLICY["goodput_retention"]
    assert pol["direction"] == "lower"
    # a run dir full of artifacts picks the DEGRADE doc up too
    obs2 = regress.ingest_run_dir(tmp_path)
    assert any(o.metric == "goodput_retention" for o in obs2)
    # non-degrade docs fall through to the other ingesters, not here
    q = tmp_path / "OTHER.json"
    q.write_text(json.dumps({"mode": "capacity", "rung": "tiny"}))
    assert regress.ingest_degrade(q) == []


def test_sentry_trips_on_doctored_retention_collapse(tmp_path):
    from hyperscalees_t2i_tpu.tools import sentry

    clean = tmp_path / "DEGRADE_r01.json"
    clean.write_text(json.dumps(_degrade_doc(0.82)))
    base = tmp_path / "SENTRY_BASELINE.json"
    verdict = tmp_path / "verdict.json"
    assert sentry.main(["baseline", str(clean), "--out", str(base)]) == 0
    assert sentry.main(["check", str(clean), "--manifest", str(base),
                        "--out", str(verdict)]) == 0
    # the degradation path silently rotting (retention halved) must page
    bad = tmp_path / "DEGRADE_r02.json"
    bad.write_text(json.dumps(_degrade_doc(0.41)))
    assert sentry.main(["check", str(bad), "--manifest", str(base),
                        "--out", str(verdict)]) == 2
    # --merge folds the degrade entry into an existing baseline without
    # dropping entries the new source does not re-observe
    cap = tmp_path / "CAPACITY_r01.json"
    cap.write_text(json.dumps({
        "mode": "capacity", "schema_version": 1, "rung": "tiny",
        "capacity_rps": 256.0, "goodput_rps": 248.0, "knee_p99_s": 3.0,
        "steps": [], "knee": None,
    }))
    base2 = tmp_path / "BASE2.json"
    assert sentry.main(["baseline", str(cap), "--out", str(base2)]) == 0
    assert sentry.main(["baseline", str(clean), "--out", str(base2),
                        "--merge"]) == 0
    doc = json.loads(base2.read_text())
    metrics = {b["metric"] for b in doc["entries"]}
    assert "goodput_retention" in metrics and "capacity_rps" in metrics

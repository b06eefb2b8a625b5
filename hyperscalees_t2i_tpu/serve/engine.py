"""ServeEngine: multi-tenant LoRA inference over one resident frozen base.

The tentpole of ISSUE 12 / ROADMAP item 1, built from parts the training
path already proved:

- **One AOT-compiled generate program per serving geometry** (adapter-batch
  × images-per-request × static generation config), compiled once via
  ``jit(...).lower(...).compile()`` and reused for every batch — the same
  AOT discipline as the trainer/bench compile sites, with one ledger record
  (``site="serve"``) per program. The persistent compile cache sits where
  ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``
  (``utils/compile_cache``), so a restarted engine deserializes its warm
  pool instead of recompiling.
- **Adapters enter as program *arguments***: a batch axis of LoRA trees
  (``lora.stack_adapters`` → ``es.stacked_adapter_theta`` inside the
  ``lax.map`` lane — the member-axis contract of the training hot path,
  "member" re-read as "user request"). Serving a brand-new user is a new
  argument value; the compile/retrace counters stay FLAT (tier-1 asserted).
- **Continuous batching**: requests sharing a geometry coalesce into the
  adapter axis up to the admission-verified maximum (``serve/batcher.py``);
  partial batches pad with the first request's slot and the padded lanes
  are masked out host-side — idle work on the tail, never wrong results
  (pop_eval's padding convention).
- **Admission, not OOM**: before a geometry's program is ever executed, its
  compiled ``memory_analysis`` peak is checked against the HBM budget
  (``serve/admission.py``); a no-fit raises :class:`ServeAdmissionError`
  naming both numbers. ``tools/preflight.py --serve`` answers the same
  question offline with zero weights.
- **Obs from day one** (live since ISSUE 13): per-request latency as a
  streaming histogram *decomposed* — queue wait, batch assembly, device
  dispatch, total (``serve_*_seconds`` on the shared registry; p50/p95/p99
  derivable from the ``_bucket`` series) — plus monotonic request/error
  counters, queue-depth / batch-occupancy gauges, a trace-time
  ``serve_traces`` counter that makes silent retrace storms visible, and
  per-request distributed tracing: ``request_id`` threads submit → enqueue
  → coalesce → dispatch → complete as nested tracer spans carrying adapter
  sha, geometry key, batch occupancy and queue position, so one slow
  request is attributable to queueing vs compile vs device time.
  ``ServeConfig.metrics_port`` starts the live ``/metrics`` + ``/healthz``
  exporter (obs/exporter.py); ``ServeConfig.slo`` arms burn-rate alerts
  (obs/slo.py). Every obs emission on the request path goes through the
  ``resilience/retry.py`` pattern ``MetricsLogger.log`` established: a
  telemetry failure degrades observability, it can never fail a request.
"""

from __future__ import annotations

import copy
import dataclasses
import sys
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..backends.base import generate_parts
from ..lora import stack_adapters
from ..obs import get_registry, get_tracer, record_compile, span as obs_span
from ..parallel.pop_eval import make_adapter_batch_generator
from .adapter_store import AdapterStore
from .admission import (
    ServeAdmissionError,
    ServeShedError,
    check_fit,
    resolve_hbm_budget,
)
from .batcher import QueueFullError, RequestQueue, ServeRequest, ServeResult
from .overload import OverloadConfig, OverloadGovernor

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static engine knobs. ``adapter_batch`` is the coalescing width the
    admission gate verifies; ``images_per_request`` the default request
    shape (requests with other prompt counts compile their own admitted
    geometry). ``hbm_budget_bytes`` overrides the device-capacity budget
    (tests exercise refusal with it; None = capacity table by device kind,
    unknown → gate unarmed). ``adapter_budget_bytes`` bounds the store's
    host working set (0 = unbounded)."""

    adapter_batch: int = 4
    images_per_request: int = 1
    member_batch: int = 0  # lax.map chunk over the adapter axis (0 = vmap all)
    max_queue: int = 1024
    adapter_budget_bytes: int = 0
    hbm_budget_bytes: Optional[int] = None
    # live telemetry (obs/exporter.py): serve /metrics + /healthz on this
    # port (0 = off). Multi-process serving fleets follow the trainer's
    # per-process offset discipline (obs/multihost.exporter_port).
    metrics_port: int = 0
    # exporter bind address (default all interfaces for cross-host scrape;
    # 127.0.0.1 for loopback-only — the endpoint is unauthenticated)
    metrics_host: str = "0.0.0.0"
    # declarative SLOs (obs/slo.py grammar, e.g.
    # "latency_p95=2s,availability=99.9"): burn-rate gauges + loud stderr
    # alerts evaluated after every flush (None = off)
    slo: Optional[str] = None
    # bounded jax.profiler capture (round 21): write .xplane.pb traces for
    # the first `profile_batches` dispatched batches under this dir, then
    # stop — obs/xplane.py attributes the device time, obs/calib.py
    # reconciles it against the serve programs' ledger records. None = off.
    # close() flushes a still-open window (trainer finally-flush
    # discipline), so a short run still lands its trace.
    profile_dir: Optional[str] = None
    profile_batches: int = 8
    # overload protection (serve/overload.py, ISSUE 19): deadlines + doomed-
    # work shedding, adapter residency leases, the brownout ladder, and the
    # per-adapter circuit breaker. None = layer OFF = pre-overload behavior
    # (the PR 16 collapse, admit-then-thrash included) — the DEGRADE artifact
    # measures exactly this ON/OFF difference.
    overload: Optional[OverloadConfig] = None


class ServeEngine:
    """Owns the backend, the adapter store, the request queue, and the AOT
    program pool. The backend must already be ``setup()`` (prompt catalog +
    frozen params loaded) — engines are cheap, backends are not."""

    def __init__(
        self,
        backend: Any,
        cfg: Optional[ServeConfig] = None,
        theta_template: Optional[Pytree] = None,
        store: Optional[AdapterStore] = None,
    ):
        import jax

        self.backend = backend
        self.cfg = cfg or ServeConfig()
        if self.cfg.adapter_batch < 1:
            raise ValueError(f"adapter_batch must be >= 1, got {self.cfg.adapter_batch}")
        from ..utils.compile_cache import place_compile_cache

        place_compile_cache()
        if theta_template is None:
            theta_template = backend.init_theta(jax.random.PRNGKey(0))
        self.template = theta_template
        # `store or ...` would silently DISCARD a caller's store: AdapterStore
        # defines __len__, so an (always-initially-empty) store is falsy
        self.store = store if store is not None else AdapterStore(
            self.cfg.adapter_budget_bytes, template=theta_template
        )
        self.queue = RequestQueue(self.cfg.max_queue)
        # (adapter_batch, images_per_request, guidance) -> program entry
        self._programs: Dict[Tuple[int, int, Optional[float]], Dict[str, Any]] = {}
        # guidance -> (generate_p, frozen) over a config-variant backend
        self._variants: Dict[Optional[float], Tuple[Any, Pytree]] = {}
        self._budget, self._budget_source = resolve_hbm_budget(
            self.cfg.hbm_budget_bytes
        )
        self._key_template = np.asarray(jax.device_get(jax.random.PRNGKey(0)))
        # seed → PRNGKey without a jax dispatch (~0.1 ms/slot otherwise — a
        # per-request tax on the serving hot path): new-minted threefry keys
        # for 31-bit seeds are [0, seed] uint32. Verified against the real
        # thing once here; any mismatch (custom PRNG impl) disables the fast
        # path rather than serving wrong noise.
        self._fast_keys = (
            self._key_template.shape == (2,)
            and self._key_template.dtype == np.uint32
            and np.array_equal(
                np.asarray(jax.device_get(jax.random.PRNGKey(123456789))),
                np.array([0, 123456789], np.uint32),
            )
        )
        # steady-state dispatch cache: the host-stacked adapter batch for a
        # fixed (program, adapter line-up) — serving the same tenants
        # back-to-back re-uses the stacked arrays instead of re-stacking
        # per dispatch. Invalidation is by content version (part of the
        # key), so a hot-swapped adapter (same id, new bytes) misses and
        # restacks. Host arrays deliberately (not device-committed): a miss
        # then costs exactly one stack — a thrashing line-up mix degrades
        # to the uncached path, never to a per-leaf device-staging cliff.
        # Small LRU: recurring line-ups stay warm without unbounded growth.
        self._stacked_cache: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._stacked_cache_cap = 8
        # dispatch-time fault-isolation memo: (adapter_id, content version)
        # pairs that already passed validate_adapter_tree — adapters are
        # content-versioned, so a pair validates once, not once per request
        # (a hot-swap mints a new version and re-validates). Bounded by a
        # clear-on-cap: worst case is one redundant re-validation per pair.
        self._validated_adapters: set = set()
        self._validated_adapters_cap = 4096
        # results completed by a generate() call on behalf of OTHER queued
        # requests — delivered by the next flush()
        self._undelivered: List[ServeResult] = []
        self._last_occupancy: float = 0.0
        # per-adapter accepted-request counts (ISSUE 16 hot-adapter
        # telemetry). A plain dict, NOT per-adapter registry counters: the
        # synthetic populations the load harness drives reach 10^6 ids and
        # unbounded metric cardinality is how exporters die — only the
        # bounded top-K leaves the process (hot_adapters / /metrics).
        self._hotness: Dict[str, int] = {}
        # live telemetry: /metrics + /healthz exporter and the SLO burn-rate
        # evaluator, both optional and both OFF the request path's failure
        # domain (exporter is pull-only on a daemon thread; SLO ticks go
        # through _safe_obs like every other emission)
        self.exporter = None
        self._slo = None
        # overload governor (controller + breaker + EWMA + shed ledger);
        # None = layer off. Leases are acquired/released ONLY when armed, so
        # an OFF engine reproduces the pre-lease eviction behavior exactly.
        self._governor = (
            OverloadGovernor(self.cfg.overload)
            if self.cfg.overload is not None else None
        )
        # dispatch-time "adapter not resident" refusals — the admit-then-
        # thrash hazard counter (PERF round 20 measured ~240 at the knee;
        # with leases armed the acceptance bar is exactly 0)
        self._not_resident = 0
        # bounded profiler window state (cfg.profile_dir): armed until the
        # first dispatch, stopped after cfg.profile_batches of them
        self._profiling = False
        self._profile_batches_seen = 0
        self._profile_failed = False
        if self.cfg.slo:
            from ..obs.slo import build_serve_evaluator

            self._slo = build_serve_evaluator(self.cfg.slo, get_registry())
        if self.cfg.metrics_port:
            from ..obs.exporter import MetricsExporter
            from ..obs.multihost import exporter_port
            from ..resilience.telemetry import get_resilience_registry

            registries = [get_registry(), get_resilience_registry()]
            if self._slo is not None:
                registries.append(self._slo.registry)
            self.exporter = MetricsExporter(
                exporter_port(self.cfg.metrics_port),
                host=self.cfg.metrics_host,
                registries=registries,
                scalar_sources=[self.hotness_metrics, self.overload_metrics],
                healthz_source=self.health,
            ).start()

    def close(self) -> None:
        """Stop the exporter (if any) and flush a still-open profiler
        window (finally-flush: a short run, or one that raised mid-window,
        still lands its trace)."""
        self._profile_stop()
        if self.exporter is not None:
            self.exporter.stop()
            self.exporter = None

    # -- bounded profiler capture (cfg.profile_dir, round 21) ----------------
    def _profile_start_maybe(self) -> None:
        """Open the capture window just before the FIRST dispatch — compile
        and warmup stay out of the trace, mirroring bench.py --profile. A
        start failure is warned once and never fails a request."""
        if (not self.cfg.profile_dir or self._profiling
                or self._profile_failed or self._profile_batches_seen):
            return
        try:
            import jax

            jax.profiler.start_trace(str(self.cfg.profile_dir))
            self._profiling = True
            print(f"[serve] profiling first {self.cfg.profile_batches} "
                  f"batches -> {self.cfg.profile_dir}",
                  file=sys.stderr, flush=True)
        except Exception as e:
            self._profile_failed = True
            print(f"[serve] WARNING: profiler start failed ({e!r}); "
                  "serving unprofiled", file=sys.stderr, flush=True)

    def _profile_batch_done(self) -> None:
        if not self._profiling:
            return
        self._profile_batches_seen += 1
        if self._profile_batches_seen >= max(int(self.cfg.profile_batches), 1):
            self._profile_stop()

    def _profile_stop(self) -> None:
        if not self._profiling:
            return
        self._profiling = False
        try:
            import jax

            jax.profiler.stop_trace()
            print(f"[serve] profiler window flushed -> "
                  f"{self.cfg.profile_dir}", file=sys.stderr, flush=True)
        except Exception as e:
            print(f"[serve] WARNING: profiler stop failed ({e!r})",
                  file=sys.stderr, flush=True)

    def health(self) -> Dict[str, Any]:
        """The serve slice of /healthz: queue depth, last batch occupancy,
        resident programs/adapters — liveness is one curl, not a stats()
        round-trip through device handles. With the overload layer armed, a
        ``pressure`` view rides along (brownout rung, the raw signals behind
        it, breaker/lease occupancy, shed totals) so "is this engine
        browning out, and why" is the same one curl."""
        out: Dict[str, Any] = {
            "serve": {
                "queue_depth": self.queue.depth,
                "batch_occupancy": self._last_occupancy,
                "programs_resident": len(self._programs),
                "adapters_resident": self.store.stats().get("resident"),
                "undelivered_results": len(self._undelivered),
                "not_resident_refusals": self._not_resident,
            }
        }
        if self._governor is not None:
            out["pressure"] = self._governor.pressure_view(
                self.queue.depth, self.cfg.max_queue or 1024,
                self.store.leases_active,
            )
        return out

    def _safe_obs(self, fn, *args, **kwargs) -> None:
        """Every serve-side obs emission rides through here: bounded retry
        on transient I/O (the ``MetricsLogger.log`` pattern, site
        ``serve_obs``, sleep-free) and on exhaustion — or any non-I/O
        telemetry bug — the emission is DROPPED and counted. A telemetry
        write failure can never fail a user request."""
        from ..resilience.retry import call_with_retry

        try:
            call_with_retry(fn, args, kwargs, site="serve_obs",
                            base_delay_s=0.0, max_delay_s=0.0)
        except Exception as e:
            try:
                get_registry().inc("serve_obs_dropped")
                print(f"[serve] WARNING: obs emission dropped ({e!r})",
                      file=sys.stderr, flush=True)
            except Exception:
                pass

    def _seed_key(self, seed: int) -> np.ndarray:
        if self._fast_keys and 0 <= seed < 2**31:
            return np.array([0, seed], np.uint32)
        import jax

        return np.asarray(jax.device_get(jax.random.PRNGKey(seed)))

    # -- adapters ------------------------------------------------------------
    def put_adapter(self, adapter_id: str, theta: Pytree) -> str:
        """Register an in-memory adapter; returns its content version."""
        return self.store.put(adapter_id, theta).version

    def load_adapter(self, adapter_id: str, run_dir) -> str:
        """Register an adapter from a training run dir's checkpoint slots."""
        return self.store.load(adapter_id, run_dir, template=self.template).version

    # -- static generation-config variants (guidance) ------------------------
    @property
    def default_guidance(self) -> Optional[float]:
        return getattr(self.backend.cfg, "guidance_scale", None)

    def _variant(self, guidance: Optional[float]) -> Tuple[Any, Pytree]:
        base_g = self.default_guidance
        g = base_g if guidance is None else float(guidance)
        key = None if g == base_g else g
        if key not in self._variants:
            backend = self.backend
            if key is not None:
                if base_g is None:
                    raise ValueError(
                        f"backend {backend.name} has no guidance_scale knob; "
                        "restart with the backend's guidance flags instead "
                        "(--guidance_scale / --cfg_list)"
                    )
                # shallow copy shares every loaded array/catalog; only the
                # static cfg differs, so the serve program re-traces with the
                # new guidance and nothing else changes (the demo engine's
                # per-guidance recipe, now cached at engine level)
                backend = copy.copy(self.backend)
                backend.cfg = dataclasses.replace(self.backend.cfg, guidance_scale=g)
            self._variants[key] = generate_parts(backend)
        return self._variants[key]

    # -- program pool --------------------------------------------------------
    def _ensure_program(
        self, images_per_request: int, guidance: Optional[float]
    ) -> Dict[str, Any]:
        import jax
        import jax.numpy as jnp

        A = self.cfg.adapter_batch
        B = images_per_request
        base_g = self.default_guidance
        g_key = None if guidance is None or guidance == base_g else float(guidance)
        key = (A, B, g_key)
        entry = self._programs.get(key)
        if entry is not None:
            return entry
        gen_p, frozen = self._variant(guidance)
        serve_fn = make_adapter_batch_generator(
            gen_p, A, B, member_batch=self.cfg.member_batch
        )
        kt = jax.random.PRNGKey(0)
        stacked_struct = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct((A,) + tuple(np.asarray(l).shape),
                                           np.asarray(l).dtype),
            self.template,
        )
        ids_struct = jax.ShapeDtypeStruct((A, B), jnp.int32)
        keys_struct = jax.ShapeDtypeStruct((A,) + tuple(kt.shape), kt.dtype)
        label = f"serve_a{A}b{B}" + (f"_g{g_key:g}" if g_key is not None else "")
        t0 = time.perf_counter()
        with obs_span("serve/compile", label=label):
            lowered = jax.jit(serve_fn).lower(
                frozen, stacked_struct, ids_struct, keys_struct
            )
            lowering_s = time.perf_counter() - t0
            compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        rec = record_compile(
            site="serve", label=label, lowered=lowered, compiled=compiled,
            lowering_s=lowering_s, compile_s=compile_s - lowering_s,
            geometry={"adapter_batch": A, "images_per_request": B,
                      "member_batch": self.cfg.member_batch,
                      "guidance": g_key, "backend": self.backend.name},
        )
        # the admission gate: refuse BEFORE the first execution, never OOM
        armed = check_fit(
            label, rec.get("peak_bytes"), self._budget, self._budget_source
        )
        reg = get_registry()
        reg.inc("serve_compiles")
        reg.gauge("serve/programs_resident", len(self._programs) + 1)
        entry = {
            "compiled": compiled, "frozen": frozen, "record": rec,
            "label": label, "admission_armed": armed,
        }
        self._programs[key] = entry
        return entry

    def warmup(
        self, geometries: Optional[Sequence[Tuple[int, Optional[float]]]] = None
    ) -> List[str]:
        """Compile (admission-gated) and execute each geometry once with a
        zero adapter batch — the AOT warm pool. After this, the first real
        request pays dispatch only. Returns the warmed program labels."""
        import jax

        geoms = list(geometries) if geometries else [
            (self.cfg.images_per_request, None)
        ]
        labels = []
        for B, g in geoms:
            entry = self._ensure_program(B, g)
            A = self.cfg.adapter_batch
            zeros = jax.tree_util.tree_map(
                lambda l: np.zeros((A,) + tuple(np.asarray(l).shape),
                                   np.asarray(l).dtype),
                self.template,
            )
            ids = np.zeros((A, B), np.int32)
            keys = np.stack([np.asarray(jax.random.PRNGKey(0))] * A)
            with obs_span("serve/warmup", label=entry["label"]):
                out = entry["compiled"](entry["frozen"], zeros, ids, keys)
                jax.block_until_ready(out)
                np.asarray(jax.device_get(out))  # execution-synced warmup
            get_registry().inc("serve_warmups")
            labels.append(entry["label"])
        return labels

    # -- request path --------------------------------------------------------
    def submit(
        self,
        adapter_id: str,
        prompt_ids: Sequence[int],
        seed: int,
        guidance: Optional[float] = None,
        t_submit: Optional[float] = None,
        priority: int = 1,
        deadline_s: Optional[float] = None,
    ) -> ServeRequest:
        """Enqueue one request. The adapter must already be resident (a miss
        raises at submit — the cheapest place to fail) and the guidance knob
        is validated against the backend here, not at dispatch. Refusals
        (miss, bad knob, backpressure) count as ``serve_request_errors`` —
        the availability SLO's numerator; backpressure additionally counts
        ``serve_queue_rejected`` and ticks the queue-wait histogram for the
        rejected request (ISSUE 16: open-loop overload must not report only
        its survivors' waits).

        ``t_submit`` (a ``time.perf_counter()`` value) backdates the
        request's arrival — the open-loop harness stamps the *scheduled*
        arrival time so queue wait and latency measure from when the
        request arrived, not from when the single-threaded driver got
        around to the submit call.

        ``deadline_s`` is a relative deadline measured from the (possibly
        backdated) arrival; with the overload layer armed
        (``ServeConfig.overload``) an expired or doomed request is SHED —
        :class:`ServeShedError` here, an error result from :meth:`flush` —
        with its censored wait kept in the queue-wait histogram. The armed
        layer also gates submits through the brownout ladder (``priority``
        below the configured bar is shed at rung >= 1; geometry is
        truncated + flagged ``degraded`` at rung >= 2) and the per-adapter
        circuit breaker, and pins the adapter with a residency LEASE from
        here to dispatch-complete/shed/abandon — the admit-then-thrash
        eliminator."""
        req = ServeRequest(
            adapter_id=adapter_id,
            prompt_ids=tuple(int(i) for i in prompt_ids),
            seed=int(seed), guidance=guidance,
        )
        if t_submit is not None:
            req.t_submit = float(t_submit)
        req.priority = int(priority)
        gov = self._governor
        if (deadline_s is None and gov is not None
                and gov.cfg.deadline_default_s > 0):
            deadline_s = gov.cfg.deadline_default_s
        if deadline_s is not None:
            req.t_deadline = req.t_submit + float(deadline_s)
        if gov is not None:
            # overload gates, cheapest refusal first. Shed accounting
            # (errors counter, SLO tick, censored wait where the request
            # "waited" from a backdated arrival) happens in _shed_submit.
            if gov.rung >= 1 and req.priority < gov.cfg.shed_below_priority:
                self._shed_submit(req, "brownout_priority", censored=False)
            if (req.t_deadline is not None
                    and time.perf_counter() >= req.t_deadline):
                self._shed_submit(req, "deadline", censored=True)
            if not gov.breaker.allow(adapter_id):
                self._shed_submit(req, "breaker_open", censored=False)
            if (gov.rung >= 2
                    and len(req.prompt_ids) > max(gov.cfg.degraded_images, 1)):
                # brownout degradation: serve FEWER images per request, in
                # deadline, rather than full answers late. Truncating at
                # submit (not dispatch) keeps the geometry key consistent
                # for coalescing and compiles no new program shape.
                req.prompt_ids = req.prompt_ids[:max(gov.cfg.degraded_images, 1)]
                req.degraded = True
        try:
            entry = self.store.entry(adapter_id)  # raises KeyError on a miss
            if guidance is not None:
                self._variant(guidance)  # raises for knob-less backends
            if not prompt_ids:
                raise ValueError("a request needs at least one prompt id")
            self.queue.submit(req)
        except Exception as exc:
            rejected = isinstance(exc, QueueFullError)
            if gov is not None:
                # a refused submit that was the breaker's half-open probe
                # must return the probe slot, or the breaker wedges
                gov.breaker.abort_probe(adapter_id)

            def _refused() -> None:
                reg = get_registry()
                reg.inc("serve_request_errors")
                if rejected:
                    reg.inc("serve_queue_rejected")
                    # a rejected request "waited" from its (possibly
                    # backdated) arrival until the refusal — histogrammed so
                    # overload tails include the requests that never got in
                    reg.observe("serve_queue_wait_seconds",
                                max(time.perf_counter() - req.t_submit, 0.0))
                # the SLO evaluator must see refusals too — a total outage
                # of refused submits is exactly what availability pages on
                if self._slo is not None:
                    self._slo.tick()

            self._safe_obs(_refused)
            raise
        if gov is not None:
            # residency lease: the adapter is pinned from this accepted
            # submit until the request's exactly-once finalize (dispatch-
            # complete, shed, abandon, or per-request refusal) releases it —
            # budget eviction skips leased entries, so the request can no
            # longer reach dispatch after its adapter was thrashed out
            self.store.lease(adapter_id)
        # accepted: per-adapter hotness (host-side dict; top-K exported)
        self._hotness[adapter_id] = self._hotness.get(adapter_id, 0) + 1
        # the request enters the distributed trace here: one "serve/submit"
        # span per request_id, carrying the adapter's content sha and the
        # queue position — the first link of submit → coalesce → dispatch
        def _emit():
            with obs_span(
                "serve/submit", request_id=req.request_id,
                adapter=adapter_id, adapter_sha=entry.version,
                queue_position=req.queue_position,
                geometry=list(req.geometry_key),
            ):
                pass
            get_registry().gauge("serve/queue_depth", self.queue.depth)

        self._safe_obs(_emit)
        return req

    # -- overload layer (serve/overload.py, ISSUE 19) ------------------------
    def _finalize_request(self, r: ServeRequest, reason: str,
                          censored_wait: bool = False) -> bool:
        """EXACTLY-ONCE terminal accounting for an accepted request — the
        abandon/shed race fix: a request shed from the queue and then swept
        by an end-of-window ``abandon_queued`` (or vice versa) must release
        its residency lease and backdate its censored wait once, not twice.
        The first caller wins; later callers are counted no-ops
        (``serve_finalize_duplicates`` — a nonzero value is a bug made
        visible, not silently double-counted telemetry). Returns True when
        this call performed the finalize."""
        if r.finalized:
            self._safe_obs(get_registry().inc, "serve_finalize_duplicates")
            return False
        r.finalized = True
        gov = self._governor
        if gov is not None:
            self.store.release(r.adapter_id)
            if reason not in ("complete", "fault"):
                # an un-dispatched breaker probe returns its slot
                gov.breaker.abort_probe(r.adapter_id)
        if censored_wait:
            # the request waited from its (possibly backdated) arrival until
            # now and was never served — censored observation, same
            # histogram as every completed request's wait (ISSUE 16)
            wait = max(time.perf_counter() - r.t_submit, 0.0)
            self._safe_obs(get_registry().observe,
                           "serve_queue_wait_seconds", wait)
        return True

    def _shed_submit(self, req: ServeRequest, reason: str,
                     censored: bool) -> None:
        """Submit-time shed: account (error counter, shed ledger, SLO tick,
        censored wait for an already-expired deadline) and raise
        :class:`ServeShedError`. The request never entered the queue, so
        there is no lease to release — it is finalized directly."""
        gov = self._governor
        gov.count_shed(reason)
        req.finalized = True

        def _emit() -> None:
            reg = get_registry()
            reg.inc("serve_request_errors")
            reg.inc("serve_shed_total")
            if censored:
                reg.observe("serve_queue_wait_seconds",
                            max(time.perf_counter() - req.t_submit, 0.0))
            if self._slo is not None:
                self._slo.tick()

        self._safe_obs(_emit)
        raise ServeShedError(
            reason,
            f"request {req.request_id} adapter {req.adapter_id!r} "
            f"(rung {gov.controller.rung_name})",
        )

    def _shed_result(self, r: ServeRequest, reason: str) -> ServeResult:
        """Shed an ACCEPTED (queued / mid-assembly) request: exactly-once
        finalize (lease release + censored wait), shed + error accounting,
        and an error result so the caller's flush sees the outcome."""
        gov = self._governor
        if gov is not None:
            gov.count_shed(reason)
        t_now = time.perf_counter()
        self._finalize_request(r, reason="shed", censored_wait=True)

        def _emit() -> None:
            reg = get_registry()
            reg.inc("serve_request_errors")
            reg.inc("serve_shed_total")
            if self._slo is not None:
                self._slo.tick()
            get_tracer().event(
                "serve/request", r.t_submit, t_now,
                request_id=r.request_id, adapter=r.adapter_id,
                shed=reason,
            )

        self._safe_obs(_emit)
        return ServeResult(
            request=r, images=None, latency_s=t_now - r.t_submit,
            batch_size=0, batch_occupancy=0.0,
            error=f"shed ({reason})", shed_reason=reason, degraded=r.degraded,
        )

    def _shed_doomed(self) -> List[ServeResult]:
        """Prune doomed requests from the queue BEFORE batch assembly: a
        deadline already passed, or a remaining budget the geometry's EWMA
        dispatch time cannot fit, means dispatching would manufacture a
        late answer nobody is waiting for — shed it so the lane serves a
        live request instead."""
        gov = self._governor
        now = time.perf_counter()
        reasons: Dict[int, str] = {}

        def _doomed(req: ServeRequest) -> bool:
            why = gov.doom_reason(req, now)
            if why is not None:
                reasons[req.request_id] = why
            return why is not None

        return [self._shed_result(r, reasons[r.request_id])
                for r in self.queue.prune(_doomed)]

    def _pressure_eval(self) -> None:
        """One brownout-ladder evaluation per flush iteration: queue depth,
        the SLO evaluator's worst fast-window burn, and the store's eviction
        delta feed the controller; rung transitions are loud (stderr) and
        counted."""
        gov = self._governor
        burn = self._slo.max_burn("fast") if self._slo is not None else None
        before = gov.rung
        rung = gov.evaluate(
            self.queue.depth, self.cfg.max_queue or 1024, burn,
            self.store.evictions,
        )

        def _emit() -> None:
            reg = get_registry()
            reg.gauge("serve/pressure_rung", rung)
            if rung != before:
                reg.inc("serve_brownout_transitions")

        self._safe_obs(_emit)
        if rung != before:
            verb = "escalate" if rung > before else "recover"
            print(
                f"[serve] BROWNOUT {verb}: rung {before} -> {rung} "
                f"({gov.controller.rung_name}) signals="
                f"{ {k: round(v, 3) for k, v in gov.controller.last.items()} }",
                file=sys.stderr, flush=True,
            )

    def overload_metrics(self) -> Dict[str, Any]:
        """Exporter scalar source: lease occupancy always; with the layer
        armed, the governor's shed/breaker/rung series (bounded labeled
        cardinality — shed reasons are a fixed vocabulary, breaker states
        only cover tracked misbehaving adapters)."""
        out: Dict[str, Any] = {
            "serve/leases_active": self.store.leases_active,
            "serve_not_resident_refusals": self._not_resident,
        }
        if self._governor is not None:
            out.update(self._governor.metrics())
        return out

    def overload_snapshot(self) -> Dict[str, Any]:
        """Host-side counters for the load harness (duck-typed — fakes that
        lack it are skipped): shed ledger, degradation, thrash refusals,
        lease + breaker occupancy."""
        gov = self._governor
        return {
            "enabled": gov is not None,
            "rung": gov.rung if gov is not None else 0,
            "shed": dict(gov.shed) if gov is not None else {},
            "shed_total": gov.shed_total() if gov is not None else 0,
            "degraded_total": gov.degraded_total if gov is not None else 0,
            "not_resident_refusals": self._not_resident,
            "leases_active": self.store.leases_active,
            "lease_blocked_evictions": getattr(self.store, "lease_blocked", 0),
            "breakers_open": (
                len(gov.breaker.non_closed()) if gov is not None else 0
            ),
        }

    def _refuse_request(self, r: ServeRequest, exc: Exception) -> ServeResult:
        """Per-request fault isolation (ISSUE 15): one corrupt adapter fails
        ITS request — ticking ``serve_request_errors`` like every refusal —
        while its batchmates dispatch untouched. Never raises."""
        t_now = time.perf_counter()

        def _emit() -> None:
            reg = get_registry()
            reg.inc("serve_request_errors")
            reg.inc("serve_adapter_faults")
            if self._slo is not None:
                self._slo.tick()
            get_tracer().event(
                "serve/request", r.t_submit, t_now,
                request_id=r.request_id, adapter=r.adapter_id,
                error=repr(exc),
            )

        self._safe_obs(_emit)
        print(
            f"[serve] REFUSED request {r.request_id} (adapter "
            f"{r.adapter_id!r}): {exc}",
            file=sys.stderr, flush=True,
        )
        return ServeResult(
            request=r, images=None, latency_s=t_now - r.t_submit,
            batch_size=0, batch_occupancy=0.0, error=str(exc),
        )

    def _dispatch(self, batch: List[ServeRequest]) -> List[ServeResult]:
        import jax

        from .adapter_store import validate_adapter_tree

        gov = self._governor
        A = self.cfg.adapter_batch
        B = len(batch[0].prompt_ids)
        # may compile: attributed to its own serve/compile span + ledger
        # record, so a first-request latency outlier decomposes to "compile"
        entry = self._ensure_program(B, batch[0].guidance)
        t_assemble0 = time.perf_counter()
        # ---- per-request fault isolation: a resident adapter that fails to
        # resolve or validate (evicted mid-flight, doctored bytes admitted
        # through a template-less store, hot-swap race) refuses ITS request
        # and the rest of the coalesced batch dispatches untouched — a
        # corrupt slot must never poison a shared dispatch or the engine.
        # Every store access happens INSIDE this guard (ISSUE 19: the
        # injected store_io fault, like a real store I/O error, fails one
        # request and feeds that adapter's circuit breaker, never the batch)
        refused: List[ServeResult] = []
        good: List[ServeRequest] = []
        versions: List[str] = []
        thetas: List[Pytree] = []
        for r in batch:
            if gov is not None:
                # mid-assembly shed: the deadline may have expired between
                # the flush-time prune and this batch's assembly — a lane
                # must not serve an answer its client already abandoned
                why = gov.doom_reason(r, t_assemble0)
                if why is not None:
                    refused.append(self._shed_result(r, why))
                    continue
            try:
                store_entry = self.store.entry(r.adapter_id)
                version = store_entry.version
                if (r.adapter_id, version) not in self._validated_adapters:
                    validate_adapter_tree(
                        r.adapter_id, store_entry.theta, self.template,
                    )
                    if len(self._validated_adapters) >= self._validated_adapters_cap:
                        self._validated_adapters.clear()
                    self._validated_adapters.add((r.adapter_id, version))
                theta = self.store.get(r.adapter_id)  # LRU touch + hit count
            except Exception as exc:
                if isinstance(exc, KeyError):
                    # admit-then-thrash made visible: admitted at submit,
                    # not resident at dispatch. With leases armed this
                    # counter's acceptance bar is exactly zero.
                    self._not_resident += 1
                    self._safe_obs(get_registry().inc,
                                   "serve_not_resident_refusals")
                if gov is not None:
                    gov.breaker.record_fault(r.adapter_id)
                res = self._refuse_request(r, exc)
                self._finalize_request(r, reason="fault")
                refused.append(res)
                continue
            good.append(r)
            versions.append(version)
            thetas.append(theta)
        if not good:
            return refused
        batch = good
        n = len(batch)
        # partial batch: pad every per-slot argument with slot 0's values —
        # identical program shape, idle tail lanes, outputs sliced below
        padded_idx = list(range(n)) + [0] * (A - n)
        padded = [batch[i] for i in padded_idx]
        lineup = tuple((batch[i].adapter_id, versions[i]) for i in padded_idx)
        stack_key = (entry["label"], lineup)
        stacked = self._stacked_cache.get(stack_key)
        if stacked is None:
            stacked = stack_adapters([thetas[i] for i in padded_idx])
            while len(self._stacked_cache) >= self._stacked_cache_cap:
                self._stacked_cache.popitem(last=False)
            self._stacked_cache[stack_key] = stacked
        else:
            self._stacked_cache.move_to_end(stack_key)
            self._safe_obs(get_registry().inc, "serve_stack_cache_hits")
        ids = np.asarray([r.prompt_ids for r in padded], np.int32).reshape(A, B)
        keys = np.stack([self._seed_key(r.seed) for r in padded])
        assembly_s = time.perf_counter() - t_assemble0
        occupancy = n / A
        reg = get_registry()
        request_ids = [r.request_id for r in batch]
        self._profile_start_maybe()
        try:
            with obs_span(
                "serve/batch", program=entry["label"], requests=n,
                occupancy=occupancy, request_ids=request_ids,
            ):
                with obs_span("serve/dispatch", program=entry["label"]):
                    from ..resilience.faultinject import (
                        maybe_serve_fault, slow_fault_seconds,
                    )

                    t_disp0 = time.perf_counter()
                    if maybe_serve_fault("slow_dispatch"):
                        # injected dispatch straggle (chaos rig): inflates
                        # dispatch_s so the EWMA doomed-shed predictor and
                        # the latency SLO see a genuinely slow device
                        time.sleep(slow_fault_seconds())
                    out = entry["compiled"](entry["frozen"], stacked, ids, keys)
                    images = np.asarray(jax.device_get(out))  # execution sync
                    dispatch_s = time.perf_counter() - t_disp0
        except Exception:
            # a failed dispatch fails every request in the batch — count
            # them and tick the SLO evaluator (a 100%-error outage must
            # still burn the availability budget), then re-raise. Leases
            # release through the exactly-once finalize; the breaker is NOT
            # fed here — a batch-wide failure has no per-adapter
            # attribution, and quarantining every rider for a shared fault
            # would amplify the outage (per-request faults above are the
            # breaker's food).
            def _failed() -> None:
                reg.inc("serve_request_errors", n)
                if self._slo is not None:
                    self._slo.tick()

            self._safe_obs(_failed)
            for r in batch:
                self._finalize_request(r, reason="fault")
            raise
        t_done = time.perf_counter()
        self._profile_batch_done()
        self._last_occupancy = occupancy
        n_degraded = sum(1 for r in batch if r.degraded)
        if gov is not None:
            # the doomed-shed predictor learns from every real dispatch
            gov.ewma.observe(batch[0].geometry_key, dispatch_s)
            gov.degraded_total += n_degraded
        results = []
        for i, r in enumerate(batch):
            if gov is not None:
                gov.breaker.record_ok(r.adapter_id)
            self._finalize_request(r, reason="complete")
            results.append(ServeResult(
                request=r, images=images[i], latency_s=t_done - r.t_submit,
                batch_size=n, batch_occupancy=occupancy,
                adapter_version=versions[i], degraded=r.degraded,
            ))

        # every post-completion emission is droppable, never fatal: counters
        # + decomposed latency histograms + one retroactive "serve/request"
        # trace span per request (submit → complete, with the decomposition
        # and queue facts as attrs — the distributed-trace leaf)
        def _emit() -> None:
            reg.inc("serve_dispatches")
            reg.inc("serve_requests", n)
            reg.inc("serve_padded_slots", A - n)
            if n_degraded:
                reg.inc("serve_degraded_total", n_degraded)
            reg.gauge("serve/batch_occupancy", occupancy)
            reg.gauge("serve/queue_depth", self.queue.depth)
            reg.observe("serve_batch_assembly_seconds", assembly_s)
            reg.observe("serve_dispatch_seconds", dispatch_s)
            tracer = get_tracer()
            for i, r in enumerate(batch):
                queue_wait = max(
                    (r.t_dequeue or t_assemble0) - r.t_submit, 0.0
                )
                reg.observe("serve_queue_wait_seconds", queue_wait)
                reg.observe(
                    "serve_request_latency_seconds", results[i].latency_s
                )
                tracer.event(
                    "serve/request", r.t_submit, t_done, parent="serve/batch",
                    request_id=r.request_id, adapter=r.adapter_id,
                    adapter_sha=versions[i], geometry=list(r.geometry_key),
                    program=entry["label"], batch_size=n,
                    occupancy=occupancy, queue_position=r.queue_position,
                    queue_wait_s=round(queue_wait, 6),
                    assembly_s=round(assembly_s, 6),
                    dispatch_s=round(dispatch_s, 6),
                )

        self._safe_obs(_emit)
        if self._slo is not None:
            self._safe_obs(self._slo.tick)
        return refused + results

    def flush(self, max_batches: Optional[int] = None) -> List[ServeResult]:
        """Drain the queue: coalesce geometry-sharing requests into adapter
        batches (continuous batching) and dispatch until empty — or until
        ``max_batches`` dispatches (the open-loop harness steps one batch
        at a time so arrivals keep landing between dispatches). Also
        delivers any results completed by an interleaved :meth:`generate`
        call (a rider's result is buffered, never dropped).

        With the overload layer armed, each iteration first prunes DOOMED
        requests from the queue (deadline passed / EWMA-predicted miss) —
        their shed results are returned alongside served ones — and runs
        one pressure-controller evaluation (the brownout ladder's clock)."""
        results: List[ServeResult] = list(self._undelivered)
        self._undelivered.clear()
        dispatched = 0
        while self.queue.depth:
            if max_batches is not None and dispatched >= max_batches:
                break
            if self._governor is not None:
                results.extend(self._shed_doomed())
                self._pressure_eval()
                if not self.queue.depth:
                    break
            with obs_span("serve/coalesce", queue_depth=self.queue.depth):
                batch = self.queue.take_batch(self.cfg.adapter_batch)
            if not batch:
                break
            results.extend(self._dispatch(batch))
            dispatched += 1
        return results

    def abandon_queued(self) -> List[ServeRequest]:
        """Shutdown / end-of-window accounting: drain every still-queued
        request WITHOUT dispatching it, ticking the queue-wait histogram
        with each one's censored wait (now − arrival) and the
        ``serve_queue_abandoned`` counter (ISSUE 16). Without this an
        overloaded open-loop window histograms only completed requests —
        the tail that queued forever vanishes from p99. Returns the
        abandoned requests (the harness counts them against goodput)."""
        abandoned = self.queue.drain()
        if not abandoned:
            return abandoned

        def _emit() -> None:
            reg = get_registry()
            reg.inc("serve_queue_abandoned", len(abandoned))
            reg.gauge("serve/queue_depth", self.queue.depth)

        self._safe_obs(_emit)
        # exactly-once per request: the censored wait AND the lease release
        # ride the same finalize the shed path uses — a request that was
        # already shed (and somehow still referenced) is a counted no-op,
        # never a double observation (the abandon/shed race, ISSUE 19)
        for r in abandoned:
            self._finalize_request(r, reason="abandon", censored_wait=True)
        return abandoned

    # -- hot-adapter telemetry (ISSUE 16) ------------------------------------
    def hot_adapters(self, k: int = 10) -> List[Tuple[str, int]]:
        """Top-``k`` adapters by accepted-request count, hottest first."""
        return sorted(self._hotness.items(), key=lambda kv: (-kv[1], kv[0]))[:k]

    def hotness_metrics(self, k: int = 10) -> Dict[str, Any]:
        """Exporter scalar source: the top-K hotness as ONE labeled series
        (``serve_adapter_hotness{adapter="..."}``) plus the distinct-adapter
        count — bounded cardinality no matter how large the tenant
        population gets."""
        out: Dict[str, Any] = {
            "serve/adapters_seen": len(self._hotness),
        }
        hot = self.hot_adapters(k)
        if hot:
            out["serve_adapter_hotness"] = {
                "labeled": [({"adapter": aid}, n) for aid, n in hot],
            }
        return out

    def generate(
        self,
        adapter_id: str,
        prompt_ids: Sequence[int],
        seed: int,
        guidance: Optional[float] = None,
    ) -> np.ndarray:
        """Synchronous one-request client: submit + flush, return this
        request's images ``[B, H, W, C]``. Anything else already queued
        rides along in the same dispatch (that is the point); riders'
        results are buffered for the owner's next :meth:`flush`, never
        discarded."""
        req = self.submit(adapter_id, prompt_ids, seed, guidance)
        mine: Optional[ServeResult] = None
        for res in self.flush():
            if res.request.request_id == req.request_id:
                mine = res
            else:
                self._undelivered.append(res)
        if mine is None:
            raise RuntimeError("flush completed without serving the request")
        if mine.error is not None:
            raise RuntimeError(
                f"request {req.request_id} refused (adapter "
                f"{adapter_id!r}): {mine.error}"
            )
        return mine.images

    # -- introspection -------------------------------------------------------
    def latency_percentiles(self) -> Optional[Dict[str, float]]:
        """p50/p95/p99 recovered from the streaming request-latency
        histogram (one-bucket resolution; None before any request)."""
        h = get_registry().histogram("serve_request_latency_seconds")
        if not h.count:
            return None
        from ..utils.stats import histogram_percentiles

        return histogram_percentiles(h.bounds, h.cumulative())

    def stats(self) -> Dict[str, Any]:
        return {
            "latency": self.latency_percentiles(),
            "programs": {
                e["label"]: {
                    "flops": e["record"].get("flops"),
                    "bytes_accessed": e["record"].get("bytes_accessed"),
                    "peak_bytes": e["record"].get("peak_bytes"),
                    "admission_armed": e["admission_armed"],
                }
                for e in self._programs.values()
            },
            "hbm_budget_bytes": self._budget,
            "hbm_budget_source": self._budget_source,
            "queue_depth": self.queue.depth,
            "store": self.store.stats(),
        }


__all__ = [
    "OverloadConfig",
    "ServeAdmissionError",
    "ServeConfig",
    "ServeEngine",
    "ServeShedError",
]

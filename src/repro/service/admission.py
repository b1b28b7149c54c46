"""Admission service: request/response estimation over a worker pool.

The scheduler-facing API of the estimator (ISSUE 4 tentpole). An
:class:`AdmissionRequest` names one training (or serving) job by the
exact callables the runtime would execute; the service answers with an
:class:`AdmissionDecision` carrying the estimate, the safe threshold
(Eq. 5: the estimate usable as max-runnable-memory), the per-device
breakdown and cache provenance (memory-warm / disk-warm / traced).

Estimates are produced by the same ``XMemEstimator`` pipeline as the
one-shot CLIs — the equivalence test pins the service bit-identical to
direct calls. What the service adds:

* a shared thread-safe :class:`~repro.core.cache.TraceCache`, optionally
  layered over a persistent :class:`~repro.service.store.TraceStore`
  (content-addressed keys, so re-created but structurally identical
  step functions are warm — across decisions AND process restarts);
* concurrent serving: ``submit`` fans decisions out over a thread pool,
  one estimator per worker thread (the orchestrator mutates per-call
  policy state, so estimator instances are not shared across threads;
  the trace cache is);
* batched decisions: ``decide_sweep`` routes a family of requests that
  differ in one scalar (the batch-size admission sweep) through
  ``SweepService.estimate_many`` — probe traces + affine interpolation
  + vectorized replay instead of N full estimates;
* **robustness (ISSUE 6)**: a graceful-degradation ladder (exact
  replay -> cached/interpolated sweep point -> analytic upper bound,
  each degraded rung with a widened safety margin — see
  :mod:`repro.service.degrade`), per-request deadline budgets with
  capped-backoff retries on transient failures, and fault injection
  via :mod:`repro.service.faults`. A rung failure (tracer raise, store
  corruption, timeout) falls to the next rung instead of propagating:
  the service answers 100% of requests, and every decision reports
  which rung answered and the margin applied.

The fault-free, deadline-free path runs the exact rung inline with no
extra threads — bit-identical decisions and throughput within the
existing bench gate.
"""
from __future__ import annotations

import contextlib
import contextvars
import copy
import dataclasses
import math
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Sequence

from ..core.cache import GLOBAL_TRACE_CACHE, TraceCache
from ..core.estimator import EstimateReport, XMemEstimator
from ..core.sweep import SweepPoint, SweepService
from ..obs import CounterDict, Observability
from ..obs import spans as obs_spans
from .degrade import (RUNG_ANALYTIC, RUNG_EXACT, RUNG_SWEEP, DecisionLog,
                      DegradePolicy, RungTimeout, analytic_request_bound,
                      backoff_delays, request_family, request_scalar)
from .faults import TransientFaultError


@dataclasses.dataclass
class AdmissionRequest:
    """One job to gate: the ``estimate_training`` argument tuple plus
    the device capacity the scheduler would place it on.
    ``deadline_s`` is this request's answer budget — a slow or hung
    exact estimate is abandoned at the deadline and answered from a
    lower rung (None defers to the service-wide default)."""

    job_id: str
    fwd_bwd_fn: Callable
    params: Any
    batch: Any
    update_fn: Callable | None = None
    opt_init_fn: Callable | None = None
    shard_factor_fn: Callable | None = None
    collective_specs: Sequence = ()
    capacity: int = 16 * 2**30          # device HBM bytes
    probe_min_capacity: bool = False    # also compute min feasible capacity
    deadline_s: float | None = None     # per-request budget (ISSUE 6)
    # host-offload schedule (core.orchestrator.OffloadPlan) — the
    # estimate runs with the orchestrator's offload pass enabled and the
    # decision carries per-space peaks in its breakdown
    offload: Any | None = None
    # serving-knob signature (ServingKnobs.signature()) — separates
    # degradation-ladder evidence families per serving configuration
    serving: Any | None = None
    meta: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class AdmissionDecision:
    """The service's answer. ``safe_threshold`` is the (margin-widened)
    estimate — the value round 2 of the paper's protocol validates as a
    max-runnable-memory cap (Eq. 5). ``provenance["source"]`` records
    where stage 1 came from: "memory" (warm cache), "disk" (persistent
    store after a restart), "traced" (cold), or "degraded" (a lower
    rung answered — ``rung``/``margin`` say which and at what widening;
    ``provenance["rung_errors"]`` records why the upper rungs fell)."""

    job_id: str
    admit: bool
    capacity: int
    peak_bytes: int
    peak_tensor_bytes: int
    persistent_bytes: int
    safe_threshold: int
    breakdown: dict
    provenance: dict
    wall_s: float
    min_feasible_capacity: int | None = None
    report: EstimateReport | None = None     # full report (in-process use)
    # ranked feasible alternatives (ISSUE 5) — populated on rejection
    # when the request carries a ``meta["plan"]`` PlanContext
    counter_offers: list | None = None
    # degradation provenance (ISSUE 6)
    rung: str = RUNG_EXACT          # which ladder rung answered
    margin: float = 1.0             # safety widening applied to the peak
    raw_peak_bytes: int | None = None   # rung estimate before widening
    deadline_s: float | None = None     # budget this answer honored
    # per-request correlation ID (ISSUE 10) — set only when the
    # service runs with observability enabled; the same ID appears on
    # every span and audit record this decision produced
    correlation_id: str | None = None

    @property
    def degraded(self) -> bool:
        return self.rung != RUNG_EXACT

    def to_json(self) -> dict:
        d = {k: getattr(self, k) for k in (
            "job_id", "admit", "capacity", "peak_bytes",
            "peak_tensor_bytes", "persistent_bytes", "safe_threshold",
            "provenance", "wall_s", "min_feasible_capacity",
            "rung", "margin", "raw_peak_bytes", "deadline_s")}
        d["degraded"] = self.degraded
        d["breakdown"] = {k: v for k, v in self.breakdown.items()
                          if k in ("phase_peaks", "num_blocks",
                                   "liveness_peak", "degraded",
                                   "space_peaks", "offload", "serving")}
        if self.counter_offers is not None:
            d["counter_offers"] = [o.to_json()
                                   for o in self.counter_offers]
        if self.correlation_id is not None:
            d["correlation_id"] = self.correlation_id
        return d


def _provenance(cache: TraceCache | None, before: dict) -> dict:
    """Provenance from the calling thread's OWN counter deltas —
    concurrent decisions on other threads do not bleed into this
    request's hits/misses (``TraceCache.thread_stats``)."""
    if cache is None:
        return {"source": "traced", "trace_cache": {}}
    after = cache.thread_stats()
    hits = after["hits"] - before["hits"]
    misses = after["misses"] - before["misses"]
    store_hits = after["store_hits"] - before["store_hits"]
    source = ("traced" if misses else
              "disk" if store_hits else "memory")
    return {"source": source,
            "trace_cache": {"hits": hits, "misses": misses,
                            "store_hits": store_hits}}


def _call_with_deadline(fn: Callable[[], Any], timeout: float | None):
    """Run ``fn`` bounded by ``timeout`` seconds. ``None`` runs inline
    (zero overhead). Otherwise ``fn`` runs on a fresh daemon thread and
    a late result is abandoned: the thread finishes into the void (its
    side effects — e.g. a trace landing in the shared cache — are kept,
    so a later retry may be warm), and :class:`RungTimeout` is raised
    here. A per-call thread (not a pool) so a hung rung can never
    starve other requests' rung execution."""
    if timeout is None:
        return fn()
    box: dict = {}
    done = threading.Event()
    # ContextVars don't follow a fresh thread — copy the caller's
    # context so the observability span/correlation state (and any
    # other contextvar) survives onto the rung thread
    ctx = contextvars.copy_context()

    def run():
        try:
            box["value"] = ctx.run(fn)
        except BaseException as e:   # noqa: BLE001 — re-raised below
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=run, daemon=True,
                         name="xmem-rung")
    t.start()
    if not done.wait(timeout):
        raise RungTimeout(f"rung exceeded {timeout:.3f}s budget")
    if "error" in box:
        raise box["error"]
    return box["value"]


class AdmissionService:
    """Long-running estimation service (see module docstring).

    ``store_dir`` enables the persistent trace store; ``workers`` sizes
    the thread pool behind ``submit``; ``processes`` is forwarded to the
    underlying ``SweepService`` replay fan-out. ``degrade`` configures
    the degradation ladder (margins, retries, default deadline);
    ``deadline_s`` is shorthand for its ``default_deadline_s``.
    ``faults`` attaches a :class:`~repro.service.faults.FaultPlan`
    (tests / chaos replay; see also :meth:`inject_faults`).
    """

    def __init__(self, estimator_factory: Callable[..., XMemEstimator]
                 | None = None, *, store_dir: str | None = None,
                 workers: int = 2, processes: int = 0,
                 cache: TraceCache | None = None,
                 store_max_entries: int = 256,
                 degrade: DegradePolicy | None = None,
                 deadline_s: float | None = None,
                 faults=None, obs: Observability | None = None):
        self._factory = estimator_factory or XMemEstimator.for_tpu
        store = None
        if store_dir is not None:
            from .store import TraceStore
            store = TraceStore(store_dir, max_entries=store_max_entries)
        if cache is not None and store is not None:
            # attaching the service's store to a caller-owned (possibly
            # process-global) cache would silently make every estimator
            # in the process disk-backed — refuse instead
            raise ValueError(
                "pass either cache= (bring your own, optionally with its "
                "own store) or store_dir=, not both")
        if cache is not None:
            self.cache = cache
        elif store is not None:
            self.cache = TraceCache(store=store)
        else:
            # no explicit cache/store: share the process-global cache so
            # one-off service instances (per-gate construction) stay warm
            self.cache = GLOBAL_TRACE_CACHE
        self.degrade = degrade or DegradePolicy()
        if deadline_s is not None:
            self.degrade = dataclasses.replace(
                self.degrade, default_deadline_s=deadline_s)
        self.faults = faults
        self.log = DecisionLog()
        self.workers = max(int(workers), 1)
        self._processes = processes
        self._pool: ThreadPoolExecutor | None = None
        self._tls = threading.local()
        self._lock = threading.Lock()
        # decide_sweep runs on ONE estimator (SweepService is stateful)
        # — serialize it; decide()/submit() stay concurrent
        self._sweep_lock = threading.Lock()
        # ISSUE 10: every service owns an Observability handle. The
        # metrics registry is the SINGLE source for the service
        # counters — stats()/health() and the daemon's metrics kind
        # all read the same objects; spans/audit/correlation IDs only
        # activate when the handle is enabled (default: disabled).
        self.obs = obs if obs is not None else Observability(enabled=False)
        reg = self.obs.registry
        self._m_requests = reg.counter(
            "xmem_service_requests_total", "decisions served")
        self.rung_counts = CounterDict(
            (RUNG_EXACT, RUNG_SWEEP, RUNG_ANALYTIC), registry=reg,
            name="xmem_service_rung_total", label="rung",
            help="decisions answered per degradation-ladder rung")
        self._m_retries = reg.counter(
            "xmem_service_retries_total",
            "transient-fault retries on the exact rung")
        self._m_timeouts = reg.counter(
            "xmem_service_timeouts_total", "rung deadline expiries")
        self._m_abandoned = reg.counter(
            "xmem_service_abandoned_rungs_total",
            "rungs abandoned at the deadline")
        self._m_in_flight = reg.gauge(
            "xmem_service_in_flight", "decisions currently executing")
        self._m_decide_s = reg.histogram(
            "xmem_service_decide_seconds", "decide wall time")
        reg.register_collector("xmem_trace_cache",
                               lambda: self.cache.stats())
        reg.register_collector("xmem_decision_log",
                               lambda: self.log.stats())
        reg.register_collector(
            "xmem_faults",
            lambda: self.faults.stats() if self.faults is not None
            else {})
        self.sweep = SweepService(self._make_estimator(),
                                  processes=processes)

    # legacy counter surface — reads delegate to the registry so the
    # stats/health dict shapes (pinned by tests and old callers) can
    # never drift from the metrics export
    @property
    def requests_served(self) -> int:
        return self._m_requests.value

    @property
    def retry_count(self) -> int:
        return self._m_retries.value

    @property
    def timeout_count(self) -> int:
        return self._m_timeouts.value

    @property
    def abandoned_rungs(self) -> int:
        return self._m_abandoned.value

    @property
    def _in_flight(self) -> int:
        return self._m_in_flight.value

    # -- estimator plumbing --------------------------------------------------
    def _make_estimator(self) -> XMemEstimator:
        est = self._factory(trace_cache=self.cache)
        if est.trace_cache is not self.cache:
            raise ValueError("admission service needs a fastpath "
                             "estimator sharing the service cache")
        # route the estimator's stage checkpoints through the service's
        # (swappable) fault plan — a no-op attribute read when unset
        est.checkpoint = self._fault_site
        return est

    def _fault_site(self, site: str) -> None:
        plan = self.faults
        if plan is not None:
            plan.check(site)

    @property
    def estimator(self) -> XMemEstimator:
        """Per-thread estimator over the shared trace cache."""
        est = getattr(self._tls, "est", None)
        if est is None:
            est = self._tls.est = self._make_estimator()
        return est

    def _get_pool(self) -> ThreadPoolExecutor:
        with self._lock:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=self.workers,
                    thread_name_prefix="xmem-admit")
            return self._pool

    def close(self) -> None:
        with self._lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown()
        self.sweep.close()
        self.obs.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- fault plumbing ------------------------------------------------------
    def set_faults(self, plan) -> None:
        """Attach/detach a fault plan on the service AND its persistent
        store (if any)."""
        self.faults = plan
        store = getattr(self.cache, "store", None)
        if store is not None:
            store.faults = plan

    @contextlib.contextmanager
    def inject_faults(self, plan):
        """Scoped fault injection — chaos replays wrap themselves here
        so a failed assertion never leaves the service poisoned. Exit
        cancels the plan: workers stranded in an injected hang (their
        rung was abandoned at the deadline) wake immediately instead of
        sleeping out the full ``hang_s``."""
        prev = self.faults
        store = getattr(self.cache, "store", None)
        prev_store = store.faults if store is not None else None
        if plan is not None and hasattr(plan, "arm"):
            plan.arm()
        self.set_faults(plan)
        try:
            yield self
        finally:
            self.faults = prev
            if store is not None:
                store.faults = prev_store
            if plan is not None and hasattr(plan, "cancel"):
                plan.cancel()

    def _deadline_for(self, req: AdmissionRequest) -> float | None:
        if req.deadline_s is not None:
            return req.deadline_s
        return self.degrade.default_deadline_s

    def _count_rung(self, rung: str, served: int = 1) -> None:
        self._m_requests.inc(served)
        self.rung_counts.inc(rung, served)

    def _audit_decision(self, decision: AdmissionDecision,
                        via: str = "decide") -> None:
        """One audit record per decision (kind="decide") carrying the
        correlation ID, cache provenance, rung, and chosen offer — the
        offline reject→plan→retry reconstruction substrate."""
        obs = self.obs
        if obs.audit is None:
            return
        rec = {"via": via, "job_id": decision.job_id,
               "admit": decision.admit,
               "capacity": decision.capacity,
               "peak_bytes": decision.peak_bytes,
               "safe_threshold": decision.safe_threshold,
               "rung": decision.rung, "margin": decision.margin,
               "degraded": decision.degraded,
               "source": decision.provenance.get("source"),
               "wall_s": decision.wall_s}
        offers = decision.counter_offers
        if offers is not None:
            rec["n_offers"] = len(offers)
            if offers:
                top = offers[0].to_json()
                rec["chosen_offer"] = {
                    k: top.get(k) for k in
                    ("knob", "global_batch", "microbatches",
                     "peak_bytes", "slowdown")}
        obs.record("decide", correlation_id=decision.correlation_id,
                   **rec)

    # -- decisions -----------------------------------------------------------
    def decide(self, req: AdmissionRequest) -> AdmissionDecision:
        """Synchronous decision for one request. Never raises for
        estimator/store/timeout failures — those degrade down the rung
        ladder; only caller errors (bad request shapes on every rung)
        can propagate."""
        t0 = time.perf_counter()
        deadline_s = self._deadline_for(req)
        self._m_in_flight.inc()
        try:
            # ISSUE 10: mint the per-request correlation ID and open
            # the root span. decide() executes ON the worker thread
            # for submit()/decide_many(), so the context var reaches
            # every layer this decision touches. Observers never feed
            # back into the decision — the instrumented path stays
            # bit-identical.
            with self.obs.request("decide", job_id=req.job_id) as cid:
                if deadline_s is None and self.faults is None:
                    # fault-free fast path: exact rung inline, no
                    # extra threads — bit-identical to the pre-ladder
                    # service
                    decision = self._decide_exact(req, t0, None)
                    decision = self._attach_counter_offers(req, decision)
                else:
                    decision = self._decide_ladder(req, deadline_s, t0)
                    if not decision.degraded:
                        decision = self._attach_counter_offers(req,
                                                               decision)
                if cid is not None:
                    decision.correlation_id = cid
                self._m_decide_s.observe(decision.wall_s)
                self._audit_decision(decision)
                return decision
        finally:
            self._m_in_flight.dec()

    def _decide_exact(self, req: AdmissionRequest, t0: float,
                      deadline_s: float | None,
                      timeout: float | None = None) -> AdmissionDecision:
        """The exact rung: full-fidelity estimate (optionally bounded by
        ``timeout`` on a side thread), decision-log recording for the
        sweep rung's future evidence."""
        def run():
            est = self.estimator
            cache = est.trace_cache
            before = cache.thread_stats()
            # an offload request runs with the orchestrator's offload
            # pass swapped in for exactly this estimate (per-thread
            # estimator, so no cross-request bleed; restored either way)
            prev_policy = est.orchestrator.policy
            if req.offload is not None:
                est.orchestrator.policy = dataclasses.replace(
                    prev_policy, offload=req.offload)
            try:
                rep = est.estimate_training(
                    req.fwd_bwd_fn, req.params, req.batch,
                    update_fn=req.update_fn, opt_init_fn=req.opt_init_fn,
                    shard_factor_fn=req.shard_factor_fn,
                    collective_specs=req.collective_specs)
                min_cap = None
                if req.probe_min_capacity:
                    min_cap = est.min_feasible_capacity(
                        req.fwd_bwd_fn, req.params, req.batch, report=rep)
            finally:
                est.orchestrator.policy = prev_policy
            return rep, _provenance(cache, before), min_cap

        with obs_spans.span("rung.exact", job_id=req.job_id):
            rep, prov, min_cap = _call_with_deadline(run, timeout)
        self._count_rung(RUNG_EXACT)
        self._record_exact(req, rep)
        decision = self._decision(req, rep, prov,
                                  time.perf_counter() - t0, min_cap)
        decision.deadline_s = deadline_s
        return decision

    def _record_exact(self, req: AdmissionRequest,
                      rep: EstimateReport) -> None:
        try:
            self.log.record(request_family(req), request_scalar(req),
                            rep.peak_bytes, rep.persistent_bytes)
        except Exception:   # noqa: BLE001 — evidence is best-effort
            pass

    def _decide_ladder(self, req: AdmissionRequest,
                       deadline_s: float | None,
                       t0: float) -> AdmissionDecision:
        """Walk the rungs: exact (with capped-backoff retries on
        transient faults, abandoned at the deadline) -> sweep-log ->
        analytic. See module docstring of ``degrade``."""
        deadline_at = None if deadline_s is None else t0 + deadline_s
        errors: list[str] = []
        delays = backoff_delays(self.degrade, req.job_id)
        attempt = 0
        while True:
            remaining = None
            if deadline_at is not None:
                remaining = deadline_at - time.perf_counter()
                if remaining <= 0:
                    errors.append("deadline exhausted before exact replay")
                    break
            try:
                return self._decide_exact(req, t0, deadline_s,
                                          timeout=remaining)
            except TransientFaultError as e:
                errors.append(f"transient: {e}")
                if attempt >= len(delays):
                    errors.append("retries exhausted")
                    break
                delay = delays[attempt]
                attempt += 1
                if remaining is not None:
                    # never sleep past the budget — keep enough of it to
                    # still answer from a lower rung
                    delay = max(min(delay, remaining * 0.5), 0.0)
                self._m_retries.inc()
                time.sleep(delay)
            except RungTimeout as e:
                errors.append(f"timeout: {e}")
                self._m_timeouts.inc()
                self._m_abandoned.inc()
                break
            except Exception as e:   # noqa: BLE001 — rung falls, never propagates
                errors.append(f"{type(e).__name__}: {e}")
                break
        return self._decide_degraded(req, errors, t0, deadline_s)

    def _decide_degraded(self, req: AdmissionRequest, errors: list[str],
                         t0: float, deadline_s: float | None
                         ) -> AdmissionDecision:
        """Rungs 2-3: answer from the decision log or the analytic
        bound. Pure CPU arithmetic — never traces, never raises."""
        got = None
        try:
            got = self.log.lookup(request_family(req), request_scalar(req))
        except Exception as e:   # noqa: BLE001 — evidence lookup is best-effort
            errors.append(f"sweep-log: {type(e).__name__}: {e}")
        if got is not None:
            raw, how = got
            return self._degraded_decision(req, raw, RUNG_SWEEP, how,
                                           errors, t0, deadline_s)
        errors.append("sweep-log: no evidence for this job family")
        try:
            raw = analytic_request_bound(req, self.log)
            how = "bound"
        except Exception as e:   # noqa: BLE001 — last rung must answer
            errors.append(f"analytic: {type(e).__name__}: {e}")
            raw, how = req.capacity + 1, "refuse"  # unknowable: never admit
        return self._degraded_decision(req, raw, RUNG_ANALYTIC, how,
                                       errors, t0, deadline_s)

    def _degraded_decision(self, req: AdmissionRequest, raw_peak: int,
                           rung: str, how: str, errors: list[str],
                           t0: float, deadline_s: float | None
                           ) -> AdmissionDecision:
        margin = self.degrade.margin_for(rung)
        peak = int(math.ceil(raw_peak * margin))
        prov = {"source": "degraded", "rung": rung, "margin": margin,
                "derived": how, "rung_errors": list(errors),
                "trace_cache": {}}
        self._count_rung(rung)
        return AdmissionDecision(
            job_id=req.job_id,
            admit=peak <= req.capacity,
            capacity=req.capacity,
            peak_bytes=peak,
            peak_tensor_bytes=int(raw_peak),
            persistent_bytes=0,
            safe_threshold=peak,
            breakdown={"degraded": True},
            provenance=prov,
            wall_s=time.perf_counter() - t0,
            rung=rung, margin=margin, raw_peak_bytes=int(raw_peak),
            deadline_s=deadline_s)

    def _attach_counter_offers(self, req: AdmissionRequest,
                               decision: AdmissionDecision
                               ) -> AdmissionDecision:
        """ISSUE 5: a rejection whose request carries a structured plan
        context (``meta["plan"]`` = ``repro.plan.PlanContext``) comes
        back with ranked counter-offers instead of a bare no. Planner-
        internal probe requests carry no context, so this cannot
        recurse. Degraded decisions skip planning (the search's probe
        estimates would hit the same failing rungs)."""
        ctx = req.meta.get("plan") if req.meta else None
        if ctx is None or decision.admit or decision.degraded:
            return decision
        from ..plan import RemediationPlanner
        # candidates must be estimated under the request's OWN execution
        # model — a per-device rejection (custom shard factors /
        # collective specs) must not be answered with whole-model offers
        result = RemediationPlanner(self).plan(
            ctx.cfg, ctx.policy, ctx.shape, capacity=req.capacity,
            space=ctx.space, job_id=req.job_id, baseline=decision,
            shard_factor_fn=req.shard_factor_fn,
            collective_specs=req.collective_specs)
        decision.counter_offers = result.offers
        decision.provenance["plan"] = result.stats
        return decision

    def decide_serving(self, job_id: str, decode_fn: Callable, params,
                       cache_tree, batch, *, capacity: int,
                       shard_factor_fn=None,
                       deadline_s: float | None = None,
                       mix=None, stream=None, knobs=None,
                       kv_bytes_per_token: int | None = None,
                       resident_bytes_per_request: int = 0,
                       plan=None) -> AdmissionDecision:
        """Serving decision — the ``launch/serve.py`` gate.

        Two modes share one cached decode trace:

        * **static** (no ``mix``/``stream``): the original single-phase
          estimate of a decode step with a persistent monolithic cache;
        * **request-driven** (ISSUE 9): pass a ``RequestMix`` (or a
          concrete ``RequestStream``) plus ``knobs``/
          ``kv_bytes_per_token`` and the decision gates on the
          continuous-batching worst-case peak, with the full
          :class:`~repro.core.estimator.ServingEstimate` under
          ``breakdown["serving"]`` (whitelisted onto the wire).

        Degrades like ``decide``: a failed or over-deadline estimate is
        answered from the analytic rung over (params + cache + batch)
        avals, with serving knobs separating evidence families. A
        request-driven rejection carrying a ``plan``
        (``repro.plan.ServingPlanContext``) comes back with ranked
        serving counter-offers."""
        t0 = time.perf_counter()
        if deadline_s is None:
            deadline_s = self.degrade.default_deadline_s
        if stream is None and mix is not None:
            stream = mix.stream()
        if stream is not None and kv_bytes_per_token is None:
            raise ValueError(
                "request-driven serving decisions need kv_bytes_per_token")
        if stream is not None and knobs is None:
            from ..core.orchestrator import ServingKnobs
            knobs = ServingKnobs()
        knob_sig = knobs.signature() if knobs is not None else None

        def run():
            est = self.estimator
            cache = est.trace_cache
            before = cache.thread_stats()
            if stream is not None:
                se = est.estimate_request_stream(
                    decode_fn, params, cache_tree, batch, stream=stream,
                    knobs=knobs, kv_bytes_per_token=kv_bytes_per_token,
                    resident_bytes_per_request=resident_bytes_per_request,
                    shard_factor_fn=shard_factor_fn, capacity=capacity)
                rep = EstimateReport(
                    peak_bytes=se.worst_case_peak_bytes,
                    peak_tensor_bytes=se.steady_state_peak_bytes,
                    persistent_bytes=se.persistent_bytes,
                    oom=se.oom, sim=se.sim,
                    breakdown={"num_blocks": se.breakdown["num_blocks"],
                               "serving": se.to_json()},
                    wall_time_s=se.wall_time_s,
                    num_events=se.num_events)
            else:
                rep = est.estimate_serving(decode_fn, params, cache_tree,
                                           batch,
                                           shard_factor_fn=shard_factor_fn)
            return rep, _provenance(cache, before)

        req = AdmissionRequest(job_id, decode_fn, params, batch,
                               capacity=capacity, deadline_s=deadline_s,
                               serving=knob_sig)
        self._m_in_flight.inc()
        try:
            with self.obs.request("serve", job_id=job_id) as cid:
                decision = None
                if deadline_s is None and self.faults is None:
                    rep, prov = run()
                else:
                    try:
                        rep, prov = _call_with_deadline(run, deadline_s)
                    except Exception as e:   # noqa: BLE001 — degrade, never fail
                        errors = [f"{type(e).__name__}: {e}"]
                        if isinstance(e, RungTimeout):
                            self._m_timeouts.inc()
                            self._m_abandoned.inc()
                        # the resident KV cache is persistent state:
                        # count it with the params for the aval bound
                        proxy = AdmissionRequest(
                            job_id, decode_fn, (params, cache_tree),
                            batch, capacity=capacity, serving=knob_sig)
                        decision = self._decide_degraded(proxy, errors,
                                                         t0, deadline_s)
                if decision is None:
                    self._count_rung(RUNG_EXACT)
                    decision = self._decision(req, rep, prov,
                                              time.perf_counter() - t0,
                                              None)
                    decision.deadline_s = deadline_s
                    if plan is not None and not decision.admit \
                            and not decision.degraded:
                        decision = self._attach_serving_offers(
                            plan, decision, capacity)
                if cid is not None:
                    decision.correlation_id = cid
                self._m_decide_s.observe(decision.wall_s)
                self._audit_decision(decision, via="serve")
                return decision
        finally:
            self._m_in_flight.dec()

    def _attach_serving_offers(self, ctx, decision: AdmissionDecision,
                               capacity: int) -> AdmissionDecision:
        """A request-driven serving rejection with a
        ``ServingPlanContext`` comes back with ranked serving
        counter-offers (page size / concurrency / KV dtype /
        prefix-cache) — trace-free against the already-cached decode
        trace. Planning failures leave the bare rejection intact."""
        from ..plan import RemediationPlanner
        try:
            result = RemediationPlanner(self).plan_serving(
                ctx, capacity=capacity, job_id=decision.job_id,
                baseline=decision)
            decision.counter_offers = result.offers
            decision.provenance["plan"] = result.stats
        except Exception as e:   # noqa: BLE001 — offers are best-effort
            decision.provenance["plan"] = {
                "error": f"{type(e).__name__}: {e}"}
        return decision

    def _decision(self, req: AdmissionRequest, rep: EstimateReport,
                  provenance: dict, wall_s: float,
                  min_cap: int | None) -> AdmissionDecision:
        provenance.setdefault("rung", RUNG_EXACT)
        provenance.setdefault("margin", 1.0)
        return AdmissionDecision(
            job_id=req.job_id,
            admit=rep.peak_bytes <= req.capacity,
            capacity=req.capacity,
            peak_bytes=rep.peak_bytes,
            peak_tensor_bytes=rep.peak_tensor_bytes,
            persistent_bytes=rep.persistent_bytes,
            safe_threshold=rep.peak_bytes,
            breakdown=rep.breakdown,
            provenance=provenance,
            wall_s=wall_s,
            min_feasible_capacity=min_cap,
            report=rep,
            raw_peak_bytes=rep.peak_bytes)

    def submit(self, req: AdmissionRequest) -> "Future[AdmissionDecision]":
        """Concurrent decision: runs on the service's worker pool."""
        return self._get_pool().submit(self.decide, req)

    def decide_many(self, reqs: Sequence[AdmissionRequest]
                    ) -> list[AdmissionDecision]:
        """Fan a batch of independent requests over the worker pool.
        Each request keeps its own deadline budget (measured from when
        its decision starts executing)."""
        return [f.result() for f in [self.submit(r) for r in reqs]]

    def decide_sweep(self, reqs: Sequence[AdmissionRequest]
                     ) -> list[AdmissionDecision]:
        """Batched decisions through ``SweepService.estimate_many`` —
        requests sharing structure (a batch-size admission sweep) pay
        three probe traces, the rest interpolate. ``meta["plan"]``
        contexts are ignored on this path (a planner search per
        rejected point would defeat the batching); route individual
        rejections through ``decide`` for counter-offers.

        Deadline budget: the tightest request deadline bounds the whole
        batched sweep; a sweep that fails or runs past it is abandoned
        (the sweep estimator is rebuilt — the stranded worker finishes
        into the void) and EVERY point is answered from the degraded
        rungs instead."""
        t0 = time.perf_counter()
        cache = self.cache
        deadlines = [self._deadline_for(r) for r in reqs]
        bounded = [d for d in deadlines if d is not None]
        timeout = min(bounded) if bounded else None
        points = [SweepPoint(
            r.fwd_bwd_fn, r.params, r.batch, update_fn=r.update_fn,
            opt_init_fn=r.opt_init_fn, shard_factor_fn=r.shard_factor_fn,
            collective_specs=r.collective_specs, label=r.job_id)
            for r in reqs]

        def run_sweep():
            before = cache.thread_stats()
            result = self.sweep.estimate_many(points)
            return result, _provenance(cache, before)

        # one correlation ID covers the whole batched sweep — the
        # points share probe traces, so their spans and audit records
        # genuinely belong to one operation
        with self.obs.request("sweep") as cid:
            decisions = None
            with self._sweep_lock:
                if timeout is None and self.faults is None:
                    result, prov = run_sweep()
                else:
                    try:
                        result, prov = _call_with_deadline(run_sweep,
                                                           timeout)
                    except Exception as e:   # noqa: BLE001 — degrade every point
                        errors = [f"{type(e).__name__}: {e}"]
                        if isinstance(e, RungTimeout):
                            self._m_timeouts.inc()
                            self._m_abandoned.inc()
                            # the abandoned worker still owns the old
                            # sweep estimator — swap in a fresh one for
                            # later calls
                            self.sweep = SweepService(
                                self._make_estimator(),
                                processes=self._processes)
                        decisions = [
                            self._decide_degraded(r, list(errors), t0, d)
                            for r, d in zip(reqs, deadlines)]
            if decisions is None:
                prov["sweep"] = {k: result.stats[k] for k in
                                 ("points", "traced", "interpolated",
                                  "fallback", "pooled")}
                # per-decision wall_s is the AMORTIZED share of the
                # batched sweep (summing per-job costs must not
                # over-count the sweep N times); each decision gets its
                # own provenance copy so callers mutating one cannot
                # alter siblings
                wall = (time.perf_counter() - t0) / max(len(reqs), 1)
                self._count_rung(RUNG_EXACT, served=len(reqs))
                decisions = []
                for r, rep, d in zip(reqs, result.reports, deadlines):
                    self._record_exact(r, rep)
                    dec = self._decision(r, rep, copy.deepcopy(prov),
                                         wall, None)
                    dec.deadline_s = d
                    decisions.append(dec)
            for dec in decisions:
                if cid is not None:
                    dec.correlation_id = cid
                self._audit_decision(dec, via="sweep")
            return decisions

    def mesh_sweep(self, fwd_bwd_fn, params, batch, topologies, *,
                   update_fn=None, opt_init_fn=None, cfg=None,
                   shard_factors: str = "spec", collectives: bool = True,
                   capacity: int | None = None):
        """Per-device estimates over a mesh-topology grid from ONE
        cached trace (``SweepService.estimate_mesh_sweep``), serialized
        on the service's single sweep estimator like ``decide_sweep`` —
        the remediation planner's trace-free topology axis."""
        with self.obs.request("mesh_sweep"), self._sweep_lock:
            result = self.sweep.estimate_mesh_sweep(
                fwd_bwd_fn, params, batch, topologies,
                update_fn=update_fn, opt_init_fn=opt_init_fn, cfg=cfg,
                shard_factors=shard_factors, collectives=collectives,
                capacity=capacity)
        self._m_requests.inc(len(result))
        return result

    def stats(self) -> dict:
        return {"requests_served": self.requests_served,
                "workers": self.workers,
                "rungs": dict(self.rung_counts),
                "trace_cache": self.cache.stats()}

    def health(self) -> dict:
        """Liveness/diagnostics surface for the daemon's ``health``
        request kind: rung counters, degradation totals, store state
        (incl. quarantine/recovery), queue depth and in-flight count."""
        with self._lock:
            pool = self._pool
            d = {
                "status": "ok",
                "requests_served": self.requests_served,
                "in_flight": self._in_flight,
                "queue_depth": (pool._work_queue.qsize()
                                if pool is not None else 0),
                "workers": self.workers,
                "rungs": dict(self.rung_counts),
                "degraded": (self.rung_counts[RUNG_SWEEP]
                             + self.rung_counts[RUNG_ANALYTIC]),
                "retries": self.retry_count,
                "timeouts": self.timeout_count,
                "abandoned_rungs": self.abandoned_rungs,
                "deadline_s": self.degrade.default_deadline_s,
            }
        d["decision_log"] = self.log.stats()
        d["trace_cache"] = self.cache.stats()
        if self.faults is not None:
            d["faults"] = self.faults.stats()
        return d

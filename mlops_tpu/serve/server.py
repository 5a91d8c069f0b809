"""Dependency-free asyncio HTTP/1.1 server for the predict service.

The reference serves via FastAPI + uvicorn (`app/main.py:35-39,92-93`);
neither is a baked-in dependency here, so the framework carries its own thin
HTTP layer: an asyncio protocol server with keep-alive, routing, pydantic
validation (422 on bad bodies, matching FastAPI's contract), and the
reference's structured two-event JSON logging per request
(`app/main.py:57-84`). Model compute runs in a small thread pool so the
event loop keeps accepting connections while the device works.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import contextlib
import logging
import threading

from mlops_tpu.config import ServeConfig
from mlops_tpu.serve.batcher import MicroBatcher
from mlops_tpu.serve.engine import InferenceEngine

# The engine-free protocol layer lives in serve/httpcore.py (shared with
# the multi-worker front ends); the names re-exported here keep the
# seed-era import surface (`from mlops_tpu.serve.server import ...`)
# working.
from mlops_tpu.serve.httpcore import (  # noqa: F401  (re-exports)
    HttpProtocol,
    _DOCS_HTML,
    _LazyJson,
    _dumps,
    deadline_response,
    profile_payload,
)
from mlops_tpu.serve.metrics import ServingMetrics
from mlops_tpu.serve.tierroute import BrownoutGovernor
from mlops_tpu.serve.wire import DeadlineExceeded

logger = logging.getLogger("mlops_tpu.serve")


# tpulint Layer-3 manifest: JaxProfiler's one leaf lock serializes
# control() calls — debug-endpoint cadence only, never a request path.
TPULINT_LOCK_ORDER = {"JaxProfiler": ("_lock",)}

# tpulint Layer-5 manifest: HttpServer's mutable state is EVENT-LOOP
# CONFINED (the prose contract below, machine-checked since Layer 5) —
# every method runs on the one asyncio thread, so no method may make a
# blocking call; device/file work goes through self._executor.
TPULINT_LOOP_CONFINED = ("HttpServer",)


class JaxProfiler:
    """`jax.profiler` start/stop control for whichever process owns the
    device: the single-process server drives it from its /debug/profile
    routes; on the multi-worker plane the ENGINE process drives it from
    the ring's profile-control word (serve/ipc.py — front ends own no
    device, so they forward). Returns HTTP statuses; the payload shapes
    live in `httpcore.profile_payload` so both planes answer
    identically. ``_lock`` serializes calls: on the ring plane ops run
    on pool threads, and a front end whose ack wait timed out releases
    the channel lease while the consumed op may still be executing — a
    second client's op must queue behind it, not interleave with the
    unsynchronized ``_running`` state (serialized execution also keeps
    ack words in seq order). Holding a lock across a slow profiler call
    is the point here: it blocks only the next profile op, never a
    request."""

    def __init__(self, profile_dir: str) -> None:
        self.profile_dir = profile_dir
        self._running = False
        self._lock = threading.Lock()

    def control(self, action: str) -> tuple[int, str | None]:
        """-> (status, error-detail-or-None). Callers pre-filter unknown
        actions to their own 'not found'; the guard here keeps a bogus
        action from paying the jax import or touching profiler state."""
        if action not in ("start", "stop") or not self.profile_dir:
            return 404, None
        import jax

        with self._lock:
            return self._control_locked(jax, action)

    def _control_locked(self, jax, action: str) -> tuple[int, str | None]:
        try:
            if action == "start":
                if self._running:
                    return 409, None
                jax.profiler.start_trace(self.profile_dir)
                self._running = True
                return 200, None
            if action == "stop":
                if not self._running:
                    return 409, None
                jax.profiler.stop_trace()
                self._running = False
                return 200, None
        # Unwritable dir, profiler state errors: logged + reported as a
        # 500 body, never a dropped connection on a debug endpoint.
        except Exception as err:  # tpulint: disable=TPU201
            logger.exception("profiler %s failed", action)
            self._running = False
            return 500, str(err)
        return 404, None


class HttpServer(HttpProtocol):
    """The single-process server: HTTP protocol + a live InferenceEngine
    in one process (micro-batcher, predict thread pool, device-monitor
    telemetry). The multi-worker plane (serve/frontend.py) runs the same
    protocol in N SO_REUSEPORT processes against the shared-memory ring
    instead."""

    def __init__(
        self,
        engine: InferenceEngine,
        config: ServeConfig,
        lifecycle=None,
        registry=None,
    ):
        super().__init__(config.validate())
        self.engine = engine
        # Tenant fleet (mlops_tpu/tenancy/): ``registry`` (a
        # TenantRegistry) installs N engines behind the one HTTP plane —
        # requests route by the ``x-tenant`` header through the shared
        # shell's TenantRouter; each tenant gets its OWN micro-batcher
        # (tenants never share a grouped dispatch: one group = one
        # tenant's compiled program + params + monitor fold) over the
        # ONE shared predict thread pool. None = the 1-tenant fleet
        # around ``engine`` — the pre-tenancy server, bit-identically.
        self.registry = registry
        self.engines = list(registry.engines) if registry else [engine]
        if registry is not None:
            from mlops_tpu.tenancy import TenantRouter

            self.engine = registry.default_engine
            self.tenants = TenantRouter(
                registry.names, registry.default_index
            )
        # Optional lifecycle controllers (mlops_tpu/lifecycle/): owned
        # and started by _serve — one per tenant (a bare controller is
        # the 1-tenant form); the server's only jobs are exposing their
        # gauges on /metrics scrapes and keeping zero coupling on the
        # request path (each controller observes through its engine tee).
        self.lifecycle = lifecycle
        # The request cap can never exceed the largest warmed bucket, or
        # steady-state traffic would hit exact-shape recompiles. Clamps
        # land in LOCALS, never back into the caller's ServeConfig: a
        # config object reused to build a second server (tests, multi-
        # port deployments) must see its original values (ADVICE r5).
        # This one stays a runtime clamp (not a ServeConfig.validate
        # error) because the bound is the ENGINE's bucket grid, which the
        # config layer cannot see.
        self.max_batch = config.max_batch
        max_bucket = min(eng.max_bucket for eng in self.engines)
        if config.max_batch > max_bucket:
            logger.warning(
                "serve.max_batch=%d exceeds largest warmup bucket %d; clamping",
                config.max_batch,
                max_bucket,
            )
            self.max_batch = max_bucket
        self.metrics = ServingMetrics()
        max_workers = max(1, config.max_workers)
        # validate() guarantees dispatch bound + fetch ring (>= 1) + one
        # thread of headroom (solo fast path, monitor fetch) fit the pool.
        max_inflight = config.max_inflight
        self._executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=max_workers, thread_name_prefix="predict"
        )
        self._profiler = JaxProfiler(config.profile_dir)
        # sloscope (mlops_tpu/slo/), armed by _serve when slo.enabled:
        # the SLO engine ticks on its own timer task (start()) against
        # this server's ServingMetrics counters; the cost ledger renders
        # on scrapes. Both None = every hook is one is-None check.
        self.slo_engine = None
        self.cost_ledger = None
        # gridtuner (mlops_tpu/autotune/), armed by _serve when
        # autotune.enabled: the controller loops on its own thread; the
        # server's only job is rendering its gauges on scrapes.
        self.autotune = None
        self._slo_task: asyncio.Task | None = None
        # Device-resident monitor aggregate telemetry (serve/engine.py
        # monitor_snapshot): the request path only counts requests; the
        # aggregate is fetched OFF the hot path — after K requests, on the
        # T-second timer (started by start()), and on /metrics scrapes.
        # Concurrency note (tpulint Layer 3): every mutable field below
        # (_monitor_requests, _monitor_task, and the base class's drain
        # sets) is EVENT-LOOP CONFINED — touched only from coroutines on
        # the one asyncio thread, never from the predict executor — which
        # is why none of them carries a lock. Work crossing into the
        # executor goes through run_in_executor and returns via awaited
        # futures; keep it that way rather than adding locks here.
        self._accumulating = [
            bool(getattr(eng, "monitor_accumulating", False))
            for eng in self.engines
        ]
        self._monitor_accumulating = any(self._accumulating)
        self._monitor_requests = 0  # predicts since the last fetch
        self._monitor_task: asyncio.Task | None = None
        self._monitor_timer_task: asyncio.Task | None = None
        # One micro-batcher per tenant over the ONE shared executor:
        # grouping is a per-tenant affair (each grouped dispatch threads
        # one tenant's monitor accumulator through one tenant's compiled
        # program). The inflight/fetch bounds are DIVIDED across the
        # fleet: validate()'s pool-sizing invariant (dispatch bound +
        # fetch ring + one thread of headroom fit max_workers) assumes
        # the bounds describe the whole plane, so N batchers each
        # keeping the full bounds would admit N*(inflight+fetch)
        # executor tasks and queue dispatches inside the pool — exactly
        # the saturation the sizing exists to prevent. The division is
        # also the plane's fairness mechanism: each tenant's slice of
        # the pool is its own, so a hot tenant's flood queues in ITS
        # batcher while every other tenant's dispatch capacity stays
        # reserved. Floors at 1 keep tiny fleets serving (a fleet
        # larger than the pool can still oversubscribe — size
        # max_workers to the tenant count). The 1-tenant fleet keeps
        # the undivided bounds, exactly the pre-tenancy batcher.
        fetch_inflight = min(
            max_inflight, max(1, max_workers - max_inflight - 1)
        )
        n_tenants = len(self.engines)
        t_inflight = max(1, max_inflight // n_tenants)
        t_fetch = max(1, fetch_inflight // n_tenants)
        self.batchers = [
            MicroBatcher(
                eng,
                self._executor,
                window_ms=config.batch_window_ms,
                max_group=config.max_group,
                max_inflight=t_inflight,
                fetch_inflight=t_fetch,
                batch_mode=config.batch_mode,
                admit_fraction=config.batch_admit_fraction,
                # Accumulating engines fold monitor totals on device, so
                # _score's else-branch (observe_prediction, which needs the
                # dict) never runs for them — they can take the wire path:
                # responses come back as pre-encoded bytes built in the
                # executor, and the event loop skips the per-response
                # json.dumps (the encode-bound residue, ~7% of loop time
                # at c128). Non-accumulating (sklearn) engines keep dicts.
                wire_responses=self._accumulating[i],
            )
            for i, eng in enumerate(self.engines)
        ]
        self.batcher = self.batchers[
            registry.default_index if registry else 0
        ]
        # SLO tier routing + brownout (ISSUE 19, serve/tierroute.py):
        # armed only when the config asks for it AND at least one engine
        # actually committed a second tier (a single-tier fleet routing
        # by class would just rename the default path). Pressure is the
        # plane's in-flight predict depth over its dispatch capacity
        # (max_inflight overlapped groups of max_group requests) — the
        # same saturation signal that decides when work queues. All
        # fields are event-loop confined like the rest of the server.
        self.slo_routing = self.slo_routing and any(
            len(getattr(eng, "available_tiers", ())) > 1
            for eng in self.engines
        )
        self._brownout = (
            BrownoutGovernor(
                demote_depth=config.brownout_demote_depth,
                restore_depth=config.brownout_restore_depth,
            )
            if self.slo_routing
            else None
        )
        self._score_inflight = 0
        self._score_capacity = max(
            1, max_inflight * self.batcher.max_group
        )

    # ------------------------------------------------------------- routes
    def _ready(self) -> bool:
        return all(bool(eng.ready) for eng in self.engines)

    async def _metrics_endpoint(self):
        # Idle replicas scrape free: once a fetch has drained the
        # device window and no predicts arrived since, the window
        # is provably all-zero — skip the device round trip per scrape.
        if self._monitor_accumulating and (
            self._monitor_requests > 0
            or self.metrics.monitor_fetches == 0
        ):
            # Scrapes read FRESH: at most one aggregate fetch per
            # scrape (Prometheus cadence, ~15 s) — the per-request
            # path stays fetch-free. Awaits the single-flight slot
            # (joining any fetch already in flight) so a scrape
            # racing the K-trigger/timer can never apply an older
            # snapshot after a newer one. BOUNDED + best-effort: a
            # stalled device read or a failing one
            # must never wedge or 500 the scrape — on timeout or
            # error the gauges keep their last values (the task's
            # done-callback logs the failure) and Prometheus still
            # gets a page. shield(): the timeout abandons the wait,
            # never cancels the shared fetch task. Flat 1 s,
            # INDEPENDENT of the cadence knob in both directions: a
            # raised monitor_fetch_every_s must not let a stalled
            # fetch hold scrapes toward Prometheus's 10 s
            # scrape_timeout, and a sub-second cadence must not
            # shrink the wait below what a healthy fetch needs.
            timeout = 1.0
            with contextlib.suppress(Exception):
                await asyncio.wait_for(
                    asyncio.shield(self._spawn_monitor_fetch()),
                    timeout=timeout,
                )
        for tenant_label, controller in self._tenant_lifecycles():
            # Pure host-dict read (the controller's leaf lock, no device
            # work): scrapes always render each loop's current state.
            with contextlib.suppress(Exception):
                self.metrics.set_lifecycle(
                    controller.metrics_snapshot(), tenant=tenant_label
                )
        if self.autotune is not None:
            # gridtuner gauges (host-dict read under the controller's
            # leaf lock, no device work).
            with contextlib.suppress(Exception):
                self.metrics.set_autotune(self.autotune.metrics_snapshot())
        # Robustness counters (host-side reads, no device work): degraded
        # dispatches live on the engines (`_dispatch_padded`), deadline
        # sheds accumulate in the metrics object itself.
        self.metrics.set_degraded(
            sum(
                getattr(eng, "degraded_dispatch_total", 0)
                for eng in self.engines
            )
        )
        if self.tracer is not None:
            self.metrics.set_trace_dropped(self.tracer.dropped)
        if self.flightrec is not None:
            self.metrics.set_flight_dumps(self.flightrec.landed)
        if self.loop_monitor is not None:
            # Worst callback wall time since the previous scrape (the
            # window resets on read — gauge semantics, 0.0 = quiet).
            self.metrics.set_loop_lag(self.loop_monitor.snapshot_ms())
        text = self.metrics.render()
        shape_stats = getattr(self.engine, "shape_stats", None)
        if shape_stats is not None:
            # tracewire shape histograms (trace/shapes.py): the same
            # series names the ring renderer emits from its shm mirror.
            lines = shape_stats.render_lines()
            if lines:
                text += "\n".join(lines) + "\n"
        if self.slo_engine is not None:
            # Fresh SLO/alert gauges per scrape (an extra tick is cheap
            # host arithmetic; the timer task keeps them fresh between
            # scrapes too) — same series names as the ring render's shm
            # block. engine_down is structurally False here: the engine
            # lives in THIS process.
            self.slo_engine.tick()
            text += "\n".join(self.slo_engine.render_lines()) + "\n"
        if self.cost_ledger is not None:
            lines = self.cost_ledger.render_lines()
            if lines:
                text += "\n".join(lines) + "\n"
        return 200, text, "text/plain; version=0.0.4"

    def _slo_view(self):
        # /healthz verdict source (httpcore._healthz): the in-process
        # engine's current view.
        if self.slo_engine is None:
            return None
        return self.slo_engine.view()

    async def _profile(self, action: str):
        """On-demand device tracing (SURVEY.md SS5.1: the reference has no
        profiler at all; here the serving process can capture a
        ``jax.profiler`` trace of live traffic for TensorBoard). The
        start/stop state machine and wire shapes are shared with the
        multi-worker plane (`JaxProfiler` + `profile_payload`) — the ring
        front ends forward to the engine process's twin of this."""
        if action not in ("start", "stop"):
            # Same body as the ring front end's unknown-action answer —
            # distinct from the 'profiling disabled' 404.
            return 404, {"detail": "not found"}, "application/json"
        status, err = self._profiler.control(action)
        return profile_payload(status, action, self.config.profile_dir, err)

    def _tenant_lifecycles(self):
        """(tenant label, controller) pairs: a per-tenant list when the
        fleet attached one, else the pre-tenancy single controller on the
        default tenant label."""
        lifecycle = self.lifecycle
        if lifecycle is None:
            return []
        if isinstance(lifecycle, (list, tuple)):
            return [
                (self.tenants.names[t], controller)
                for t, controller in enumerate(lifecycle)
                if controller is not None
            ]
        return [(self.tenants.names[self.tenants.default_index], lifecycle)]

    async def _score(
        self,
        record_dicts: list[dict],
        request_id: str,
        deadline: float | None = None,
        span=None,
        tenant: int = 0,
        slo: int = 0,
    ):
        """The single-process scoring hook under the shared `_predict`
        shell (serve/httpcore.py): micro-batcher -> engine, with the
        deadline and failure contracts. ``span`` (tracewire) rides into
        the batcher/engine for the queue/encode/dispatch/fetch stamps.
        ``tenant`` (resolved from ``x-tenant`` by the shell) picks the
        batcher+engine pair — tenants share the thread pool and the HTTP
        plane, never a grouped dispatch. ``slo`` (the request's SLO
        class, resolved at admission) maps to a serving tier here —
        through the brownout governor first, which demotes DEFAULT-class
        traffic to the cheaper tier while the plane's in-flight depth is
        past the demote threshold (degraded answers instead of 503s)."""
        batcher = self.batchers[tenant]
        tier: str | None = None
        if self._brownout is not None:
            eng = self.engines[tenant]
            self._brownout.observe(
                self._score_inflight / self._score_capacity
            )
            routed_cls, demoted = self._brownout.route(slo)
            tier = eng.route_tier(routed_cls)
            tier_label = tier or eng.default_tier
            self.metrics.count_tier(tier_label)
            if demoted:
                self.metrics.count_demotion(brownout=True)
            if span is not None:
                span.tier = tier_label
        self._score_inflight += 1
        try:
            # Small concurrent requests coalesce into one vmapped dispatch
            # (serve/batcher.py); everything else runs solo in the pool.
            # The deadline exists for a STALLED DEVICE: without it every in-flight request wedges until the client
            # gives up, while liveness stays green. A client deadline
            # budget (x-request-deadline-ms) tightens the server-wide
            # timeout per request AND rides into the batcher so an
            # already-expired entry is purged engine-side instead of
            # dispatched (dead-work shedding under overload).
            timeout = self.config.request_timeout_s or None
            if deadline is not None:
                remaining = deadline - asyncio.get_running_loop().time()
                timeout = min(timeout or remaining, remaining)
            # Disarmed call shape unchanged (test stubs pin it): the
            # span/tier kwargs only appear when tracing/routing armed
            # them.
            if span is None and tier is None:
                call = batcher.predict(record_dicts, deadline=deadline)
            elif tier is None:
                call = batcher.predict(
                    record_dicts, deadline=deadline, span=span
                )
            else:
                call = batcher.predict(
                    record_dicts, deadline=deadline, span=span, tier=tier
                )
            if timeout is not None:
                response = await asyncio.wait_for(call, max(timeout, 0.0))
            else:
                response = await call
        except DeadlineExceeded:
            # Engine-side shed: the batcher's claim-time purge found the
            # budget already spent and never dispatched — count the dead
            # work it avoided; the wire answer is the same documented 504.
            # (The purge completed the entry before any dispatch task saw
            # it, so nothing else holds the span — no abandon needed.)
            self.metrics.count_deadline_expired()
            return deadline_response()
        except asyncio.TimeoutError:
            logger.error(
                "prediction deadline (%.1fs) exceeded request_id=%s — "
                "device stall?",
                timeout,
                request_id,
            )
            if span is not None:
                # The engine call keeps running in its executor thread and
                # may still stamp this span: hand it over entirely (never
                # finish/record a span another thread can be writing).
                span.abandoned = True
            return deadline_response(
                f"prediction exceeded the {timeout:g}s deadline"
            )
        # Top-of-handler boundary: ANY prediction failure (device error
        # included) must become a logged 500, not a dropped connection —
        # the breadth is the contract here, and logger.exception keeps
        # the traceback.
        except Exception:  # tpulint: disable=TPU201
            logger.exception("prediction failed request_id=%s", request_id)
            if span is not None:
                span.abandoned = True  # a grouped dispatch may outlive us
            return 500, {"detail": "prediction failed"}, "application/json"
        finally:
            # Event-loop confined, like the increment: the depth fraction
            # the brownout governor samples counts only requests whose
            # scoring is actually outstanding.
            self._score_inflight -= 1
        if self._accumulating[tenant]:
            # Monitor totals are folded ON DEVICE inside the fused predict
            # (monitor/state.py MonitorAccumulator) — the hot path only
            # counts requests toward the K-trigger; no per-response host
            # fold, no per-request aggregate fetch.
            self._monitor_requests += 1
            self._maybe_fetch_monitor()
        else:
            self.metrics.observe_prediction(
                response, tenant=self.tenants.names[tenant]
            )
        return response

    # ------------------------------------------------- monitor telemetry
    def _spawn_monitor_fetch(self) -> asyncio.Task:
        """SINGLE-FLIGHT aggregate fetch: every trigger (K requests, the
        T-second timer, a /metrics scrape) funnels through one task slot.
        Two concurrent fetches could apply an OLDER cumulative snapshot
        after a newer one, making the exported counters go backwards for
        one scrape — which Prometheus reads as a counter reset."""
        task = self._monitor_task
        if task is None or task.done():
            task = asyncio.get_running_loop().create_task(
                self._fetch_monitor()
            )
            task.add_done_callback(self._observe_monitor_fetch)
            self._monitor_task = task
        return task

    @staticmethod
    def _observe_monitor_fetch(task: asyncio.Task) -> None:
        # Retrieve + log: an unobserved failure (device stall mid-read)
        # would otherwise die silently and only surface as a GC-time
        # "Task exception was never retrieved" warning while the gauges
        # froze at stale values.
        if not task.cancelled() and task.exception() is not None:
            logger.error(
                "monitor aggregate fetch failed; gauges keep their last "
                "values until the next trigger succeeds",
                exc_info=task.exception(),
            )

    def _maybe_fetch_monitor(self) -> None:
        """Kick an async aggregate fetch when K requests accumulated since
        the last one. Never blocks the request path; at most one fetch is
        in flight (a running task absorbs the trigger)."""
        k = self.config.monitor_fetch_every_requests
        if not k or self._monitor_requests < k:
            return
        self._spawn_monitor_fetch()

    async def _fetch_monitor(self) -> None:
        """One aggregate read per accumulating tenant: device -> host ->
        that tenant's metrics gauges (sequential on the one executor
        slot — the fetches stay single-flight as a set). Failures are
        isolated PER TENANT (same discipline as the ring plane's
        telemetry loop): one tenant's failing device read must not
        freeze every later tenant's gauges."""
        loop = asyncio.get_running_loop()
        self._monitor_requests = 0
        failed = None
        for t, eng in enumerate(self.engines):
            if not self._accumulating[t]:
                continue
            try:
                snapshot = await loop.run_in_executor(
                    self._executor, eng.monitor_snapshot
                )
                self.metrics.set_monitor_aggregate(
                    snapshot, tenant=self.tenants.names[t]
                )
            except Exception as err:  # tpulint: disable=TPU201
                # Gauges keep their last values; the fetch-age gauge
                # (min over tenants) surfaces the staleness.
                logger.error(
                    "monitor fetch failed for tenant %r",
                    self.tenants.names[t], exc_info=True,
                )
                failed = err
        if failed is not None and len(self.engines) == 1:
            # Pre-tenancy contract: a single-tenant fetch failure still
            # propagates to the task's done-callback log.
            raise failed

    async def _monitor_timer(self) -> None:
        """T-second cadence floor for the aggregate gauges: bounds their
        staleness even under a trickle of traffic that never reaches the
        K-request trigger (docs/operations.md documents the bound)."""
        period = self.config.monitor_fetch_every_s
        while True:
            await asyncio.sleep(period)
            if self._monitor_requests > 0:
                self._spawn_monitor_fetch()

    # ------------------------------------------------------------ lifecycle
    async def _slo_timer(self) -> None:
        """The sloscope evaluation cadence (slo.tick_s): burn rates and
        alert transitions advance even when nobody scrapes — the alert
        contract ("flips within two ticks") and the flight recorder's
        alert trigger both ride this task."""
        period = self.slo_engine.config.tick_s
        while True:
            await asyncio.sleep(period)
            try:
                self.slo_engine.tick()
            # An evaluator bug costs one tick of gauge freshness, never
            # the timer task (logged; the next tick retries).
            except Exception:  # tpulint: disable=TPU201
                logger.exception("slo tick failed; alert gauges stale")

    async def start(self) -> asyncio.AbstractServer:
        if self._monitor_accumulating and self.config.monitor_fetch_every_s > 0:
            # Strong ref: a bare create_task could be garbage-collected.
            self._monitor_timer_task = asyncio.get_running_loop().create_task(
                self._monitor_timer()
            )
        if self.slo_engine is not None:
            self._slo_task = asyncio.get_running_loop().create_task(
                self._slo_timer()
            )
        return await asyncio.start_server(
            self.handle_connection, self.config.host, self.config.port
        )

    def stop_telemetry(self) -> None:
        """Cancel the monitor timer (an infinite loop) and any in-flight
        fetch on shutdown: left pending, asyncio logs 'Task was destroyed
        but it is pending!' on every clean rollout and the leaked task
        keeps the engine alive in start/stop test harnesses."""
        for task in (
            self._monitor_timer_task, self._monitor_task, self._slo_task
        ):
            if task is not None and not task.done():
                task.cancel()


async def _serve(
    engine: InferenceEngine,
    config: ServeConfig,
    lifecycle=None,
    trace=None,
    registry=None,
    slo=None,
    autotune=None,
) -> None:
    server = HttpServer(engine, config, lifecycle=lifecycle, registry=registry)
    server.autotune = autotune
    flightrec = None
    ledger = None
    if slo is not None and (slo.enabled or slo.ledger_dir):
        # sloscope (mlops_tpu/slo/): SLO engine + flight recorder when
        # slo.enabled; the cost ledger arms independently off
        # slo.ledger_dir (autotuner input, not alerting). Disabled, every
        # hot path keeps its is-None check.
        slo.validate()
        if slo.enabled:
            from mlops_tpu.slo import FlightRecorder, SLOEngine

            tenant_names = tuple(server.tenants.names)
            if slo.flightrec_enabled:
                flightrec = FlightRecorder(
                    slo.flightrec_dir,
                    capacity=slo.flightrec_capacity,
                    cooldown_s=slo.flightrec_cooldown_s,
                    keep=slo.flightrec_keep,
                    source="single",
                    spike_errors=slo.flightrec_spike_errors,
                    spike_window_s=slo.flightrec_spike_window_s,
                )
                server.flightrec = flightrec

            def _breakers() -> dict:
                # The lifecycle circuit breaker surfaces as an alert
                # (and therefore a flight-recorder trigger): host dict
                # reads under each controller's own leaf lock.
                out = {}
                for label, controller in server._tenant_lifecycles():
                    try:
                        snapshot = controller.metrics_snapshot()
                        out[label] = bool(snapshot.get("breaker_open"))
                    except Exception:  # tpulint: disable=TPU201
                        logger.exception(
                            "breaker probe failed (tenant %r)", label
                        )
                return out

            server.slo_engine = SLOEngine(
                slo,
                tenant_names,
                source=lambda: server.metrics.slo_counts(
                    slo.latency_threshold_ms, tenant_names
                ),
                breaker_source=_breakers,
                on_alert=(
                    flightrec.note_alert if flightrec is not None else None
                ),
            )
            logger.info(
                "sloscope armed (availability %.4f, latency %.4f @ %gms)",
                slo.availability_target, slo.latency_target,
                slo.latency_threshold_ms,
            )
        if slo.ledger_dir:
            from mlops_tpu.slo import CostLedger

            ledger = CostLedger(
                slo.ledger_dir, flush_interval_s=slo.ledger_flush_s
            )
            server.cost_ledger = ledger
            for eng in server.engines:
                eng.set_cost_ledger(ledger)
            logger.info("cost ledger armed -> %s", ledger.path)
    tracer = None
    if trace is not None and trace.enabled:
        # tracewire (mlops_tpu/trace/): spans to <trace.dir>/spans.jsonl,
        # shape histograms on the engine(s) — ONE shared ShapeStats
        # across the tenant fleet, since entries key by compiled shape —
        # both gated here; a disabled trace section leaves every hot
        # path at its is-None check.
        from pathlib import Path

        from mlops_tpu.trace import ShapeStats, TraceRecorder

        trace.validate()
        tracer = TraceRecorder(
            Path(trace.dir) / "spans.jsonl",
            capacity=trace.ring_capacity,
            flush_interval_s=trace.flush_interval_s,
        )
        server.tracer = tracer
        stats = ShapeStats()
        for eng in server.engines:
            eng.set_shape_stats(stats)
        logger.info("tracewire armed; spans -> %s", tracer.path)
    srv = await server.start()
    logger.info(
        "serving %s on %s:%s", config.service_name, config.host, config.port
    )
    # Bind FIRST, warm up concurrently: probes are reachable immediately and
    # /healthz/ready flips to 200 when every bucket is compiled. (Warming
    # before binding would make K8s liveness probes connection-refuse through
    # the whole compile window and restart the pod.)
    loop = asyncio.get_running_loop()
    if config.loop_lag_monitor:
        # Runtime half of the Layer-5 discipline: time every callback on
        # this loop, drain the window max into the
        # mlops_tpu_event_loop_lag_ms gauge on each /metrics scrape.
        from mlops_tpu.analysis.loopcheck import LoopLagSanitizer

        server.loop_monitor = LoopLagSanitizer(
            slow_ms=config.loop_lag_slow_ms
        )
        server.loop_monitor.attach(loop)
        logger.info(
            "loop-lag sanitizer armed (slow_ms=%g)", config.loop_lag_slow_ms
        )
    warmup_error: list[BaseException] = []

    async def _warm() -> None:
        try:
            if registry is not None:
                # Fleet warmup with architecture-level executable dedupe
                # (tenancy/registry.py): distinct architectures compile
                # once; twins adopt the donor's exec table by reference.
                report = await loop.run_in_executor(None, registry.warmup)
                logger.info("warmup complete; ready %s", _LazyJson(report))
            else:
                await loop.run_in_executor(None, engine.warmup)
                # warmup_stats carries the AOT compile-cache evidence:
                # wall time, program count, and hit/miss/bypass counts
                # with per-program compile vs deserialize seconds
                # (engine.py).
                logger.info(
                    "warmup complete; ready %s",
                    _LazyJson(getattr(engine, "warmup_stats", {})),
                )
            for _, controller in server._tenant_lifecycles():
                # Start each loop only once the live exec tables are
                # fully warmed: candidate shadow warm-sharing snapshots
                # them, and a pre-warmup trigger would have nothing to
                # mirror into.
                controller.start()
            if lifecycle is not None:
                logger.info("lifecycle controller(s) started")
            if autotune is not None:
                # Same post-warmup gate as lifecycle: the regrid loop
                # measures the warmed grid and warms new entries into
                # the live exec table — both need it fully built first.
                autotune.start()
                logger.info("autotune controller started")
        # Compile failure/OOM: die loudly so the orchestrator restarts the
        # pod instead of a forever-503 zombie. Not swallowed — the error is
        # stored and re-raised by _serve after the server closes.
        except BaseException as err:  # tpulint: disable=TPU201
            warmup_error.append(err)
            logger.error("warmup failed, shutting down: %s", err)
            srv.close()

    # Graceful drain on SIGTERM (K8s sends it on rollout/scale-down; the
    # default would sever in-flight requests mid-response): stop
    # accepting, flip readiness to 503 so the endpoint leaves the
    # Service, close IDLE keep-alive connections immediately (they would
    # otherwise hold ``wait_closed`` open forever), let busy exchanges
    # finish their current response, then exit 0.
    import signal

    draining = asyncio.Event()

    def _drain(signum, frame=None) -> None:
        logger.info("SIGTERM: draining (no new connections)")
        server.draining = True
        for eng in server.engines:
            eng.ready = False  # /healthz/ready -> 503
        draining.set()
        srv.close()
        for w in list(server._connections - server._busy):
            w.close()  # idle readline() sees EOF; handler exits
        if flightrec is not None:
            # Evidence-gated: a drain during an incident preserves the
            # ring's tail; a clean drain writes nothing (the serve-smoke
            # zero-dump contract). Executor, like every other dump site:
            # the busy exchanges this drain is letting finish must not
            # stall behind a disk write (asyncio.run's shutdown joins the
            # executor, so the dump always completes before exit).
            loop.run_in_executor(
                None, flightrec.dump_if_evidence, "sigterm"
            )

    try:
        loop.add_signal_handler(signal.SIGTERM, _drain, signal.SIGTERM)
    except (NotImplementedError, RuntimeError):
        pass  # non-unix event loops: no graceful path, default semantics

    warm_task = asyncio.create_task(_warm())
    try:
        # NOT ``async with srv``: its __aexit__ awaits wait_closed(),
        # which on 3.12+ blocks until every connection drops — an idle
        # keep-alive client would stall shutdown past the kubelet's
        # SIGKILL. The drain path closes connections itself.
        await srv.serve_forever()
    except asyncio.CancelledError:
        pass
    except BaseException:
        if flightrec is not None:
            # Fatal server-loop failure: preserve the ring's last N
            # seconds unconditionally — this dump IS the post-mortem.
            flightrec.dump("fatal")
        raise
    finally:
        srv.close()
        if server.loop_monitor is not None:
            server.loop_monitor.detach()
            server.loop_monitor = None
        server.stop_telemetry()
        for _, controller in server._tenant_lifecycles():
            # Controller drain (joins its worker thread, detaches the
            # engine tee, snapshots the reservoir) happens in the
            # executor: stop() joins a thread, which must not block the
            # event loop mid-drain.
            await loop.run_in_executor(None, controller.stop)
        if autotune is not None:
            # Joins the gridtuner thread (a mid-warm tick finishes its
            # current compile-cache write, then exits) — executor, same
            # reason as the lifecycle drains above.
            await loop.run_in_executor(None, autotune.stop)
        await warm_task
        if draining.is_set():
            # Warmup may have finished AFTER the drain flip and
            # re-advertised readiness; a draining pod is never ready.
            for eng in server.engines:
                eng.ready = False
            # Busy exchanges get a bounded window to write their
            # responses (serve.drain_deadline_s; the kubelet's
            # terminationGracePeriodSeconds is the hard stop); whatever
            # remains is then force-closed.
            deadline = loop.time() + config.drain_deadline_s
            while server._busy and loop.time() < deadline:
                await asyncio.sleep(0.05)
            for w in list(server._connections):
                w.close()
            with contextlib.suppress(asyncio.TimeoutError):
                await asyncio.wait_for(srv.wait_closed(), timeout=5)
            logger.info("drained; exiting")
        if tracer is not None:
            # AFTER the busy-drain window: every exchange that finished
            # its response has recorded its span. close() joins the
            # writer thread — run it in the executor so the final flush
            # never blocks the event loop.
            await loop.run_in_executor(None, tracer.close)
        if ledger is not None:
            # Final atomic flush of the cost ledger (close joins its
            # writer thread — executor, same reason as the tracer).
            await loop.run_in_executor(None, ledger.close)
    if warmup_error:
        raise SystemExit(f"warmup failed: {warmup_error[0]}")


def serve_forever(
    engine: InferenceEngine,
    config: ServeConfig,
    lifecycle=None,
    trace=None,
    registry=None,
    slo=None,
    autotune=None,
) -> None:
    """Blocking entry point (the uvicorn.run analogue, `app/main.py:92-93`).
    ``lifecycle`` is an optional `LifecycleController` (or a per-tenant
    list of them): started once warmup completes, drained on shutdown,
    gauges on /metrics. ``trace`` is the optional `TraceConfig` section:
    enabled, every /predict request records a stage span to
    <trace.dir>/spans.jsonl and the engine exports shape histograms
    (mlops_tpu/trace/). ``registry`` (a `TenantRegistry`) serves N
    tenants from this one plane; None = the 1-tenant fleet around
    ``engine``. ``autotune`` is an optional `AutotuneController`
    (mlops_tpu/autotune/): started once warmup completes (it warms new
    grid entries into the live exec table), drained on shutdown, gauges
    on /metrics."""
    asyncio.run(
        _serve(
            engine, config, lifecycle=lifecycle, trace=trace,
            registry=registry, slo=slo, autotune=autotune,
        )
    )

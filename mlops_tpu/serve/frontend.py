"""Multi-worker server plane: SO_REUSEPORT front ends + one engine.

``mlops-tpu serve --workers N`` (serve.workers >= 2) replaces the
single-process asyncio server with N front-end PROCESSES that each bind
the same host:port through ``SO_REUSEPORT`` — the kernel load-balances
accepted connections across them, so HTTP parsing, pydantic validation,
JSON serialization, and feature ENCODING (the native C++ encoder) run on
N cores instead of fighting one GIL — all feeding ONE engine process
over the zero-copy shared-memory ring (`serve/ipc.py`). The engine
process owns everything expensive exactly once: the compile cache, the
warmed exec tables, the device monitor accumulator.

Process model (Linux, ISSUE 11): the parent is a thread-free, jax-free
SUPERVISOR. It builds the ring, reserves the port, and forks EVERY other
process — the N front ends and the ENGINE child (which imports jax only
after the fork) — so no fork ever crosses a threaded world (jax/XLA
runtime, dispatch pool, collector — the classic fork-after-threads
deadlock), respawns included. Front ends restart freely: a crashed
worker is respawned within ~0.5 s and re-attaches to its slot partition
via the shm generation counters. ENGINE death is a survivable BROWNOUT,
not an outage: the supervisor forks a replacement that warm-starts from
the AOT compile cache, re-attaches to the same ring under a new
incarnation counter, and REPLAYS every busy slot whose completion never
arrived (`RingService.reattach` — slabs hold the full pre-encoded input
and packed predict is pure, so replayed answers are bit-identical).
While the engine is down, in-flight requests PARK against their PR 9
deadline budgets (200 if the replay lands in time, 504 only on true
budget expiry) and new admissions keep parking until the partition
fills.

Load shedding: each front end's slot partition is its bounded admission
queue, per bucket class (small/coalescable vs large/solo). No free slot
=> immediate ``503`` with ``Retry-After`` — overload degrades into fast
rejections while admitted requests keep their latency, instead of an
unbounded queue melting p99 (the fleet-goodput framing of PAPERS.md
arXiv 2502.06982). During an engine outage the partition doubles as the
parking lot and the shed becomes a BROWNOUT 503: Retry-After advertises
the respawn ETA and the shed counts in ``brownout_shed_total``.

Graceful drain: SIGTERM to the supervisor forwards to every front end;
each stops accepting, finishes in-flight exchanges (the engine child
keeps serving through this window, so parked slots still land), and
exits; the supervisor then SIGTERMs the engine (which drains the ring
service — every accepted slot still gets its response) and exits 0.
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import math
import multiprocessing
import os
import signal
import socket
import time
from typing import Any

import numpy as np

from mlops_tpu import faults
from mlops_tpu.config import Config, ServeConfig
from mlops_tpu.serve.httpcore import HttpProtocol, _LazyJson, deadline_response
from mlops_tpu.serve.ipc import RequestRing, RingClient, RingService, ShmWorkerMetrics
from mlops_tpu.serve.metrics import (
    ENG_DOWN_SINCE,
    ENG_RESPAWNS,
    render_ring_metrics,
)
from mlops_tpu.serve.tierroute import SLO_DEFAULT, BrownoutGovernor
from mlops_tpu.serve.wire import (
    EMPTY_RESPONSE_BYTES,
    RESP_EXPIRED,
    RESP_OK,
    encode_response,
)

logger = logging.getLogger("mlops_tpu.serve")

# tpulint Layer-5 manifest: each front-end process is one asyncio loop;
# FrontendServer's mutable state and the ring client's doorbell path are
# EVENT-LOOP CONFINED — blocking work (encode, flight-recorder dumps,
# anomaly scans) goes through run_in_executor, never the loop thread.
TPULINT_LOOP_CONFINED = ("FrontendServer", "RingClient.on_doorbell")

# How long a front end waits for the engine collector to acknowledge a
# forwarded /debug/profile request before cancelling it and answering
# 504. Covers any healthy collector iteration (its idle select tick is
# 1 s) with a wide margin; an operator debug endpoint, not a config knob.
_PROFILE_ACK_S = 10.0


def reuseport_socket(host: str, port: int) -> socket.socket:
    """A bound (not listening) TCP socket with SO_REUSEPORT: every front
    end binds its own; the kernel hashes incoming connections across all
    LISTENING sockets on the tuple. The parent binds one too — never
    listening — purely to pin the port (port=0 resolution, respawn
    safety)."""
    if not hasattr(socket, "SO_REUSEPORT"):  # pragma: no cover - non-Linux
        raise OSError("SO_REUSEPORT is not available on this platform")
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
    sock.bind((host, port))
    return sock


class FrontendServer(HttpProtocol):
    """The ring-backed front end: the same HTTP protocol, validation, and
    two-event logging as the single-process server, with the engine call
    replaced by claim slot -> write pre-encoded arrays -> await the
    completion doorbell -> encode the raw response arrays (the identical
    `encode_response` wire formatter the engine-side fetch uses, so
    responses are bit-identical to the single-process path)."""

    def __init__(
        self,
        config: ServeConfig,
        ring: RequestRing,
        worker_id: int,
        preprocessor: Any,
        trace: Any = None,
        tenancy: Any = None,
        slo: Any = None,
    ) -> None:
        from mlops_tpu.tenancy import QuotaGovernor, TenantRouter

        super().__init__(config)
        self.ring = ring
        self.worker_id = worker_id
        # Tenant fleet (mlops_tpu/tenancy/): one preprocessor per tenant
        # (each bundle's own encode contract, loaded at fork), the header
        # router, and a per-worker weighted max-min admission governor
        # over this worker's slot partition. A plain single preprocessor
        # (every pre-tenancy caller) is the 1-tenant fleet.
        self.preprocessors = (
            list(preprocessor)
            if isinstance(preprocessor, (list, tuple))
            else [preprocessor]
        )
        if len(self.preprocessors) != ring.tenants:
            raise ValueError(
                f"{len(self.preprocessors)} preprocessors for "
                f"{ring.tenants} ring tenants"
            )
        default_index = (
            tenancy.default_index if tenancy is not None else 0
        )
        weights = (
            tenancy.weights
            if tenancy is not None
            else (1.0,) * ring.tenants
        )
        self.tenants = TenantRouter(ring.tenant_names, default_index)
        # ONE GOVERNOR PER SLOT CLASS over the worker's partition: the
        # classes are separate physical pools (a large request can only
        # land in a large slab), so fairness must hold per class — a
        # single partition-wide governor would let a hot tenant park
        # requests in every large slab while staying under its combined
        # floor, starving cold tenants' large traffic with no quota
        # signal. Physical exhaustion within an admitted class still
        # sheds through the classic slot path at claim time. A 1-tenant
        # fleet needs no governor (fairness is trivial), and skipping it
        # keeps single-tenant admission EXACTLY the pre-tenancy path.
        # Event-loop confined like the RingClient free lists — no locks
        # (tenancy/quota.py).
        self.quota = (
            (
                QuotaGovernor(ring.slots_small, weights),
                QuotaGovernor(ring.slots_large, weights),
            )
            if ring.tenants > 1
            else None
        )
        self.client = RingClient(
            ring, worker_id, affinity_slack=config.replica_affinity_slack
        )
        # Brownout-over-shed governor (ISSUE 19, serve/tierroute.py):
        # per worker, fed by this worker's own slot-partition occupancy
        # — the resource whose exhaustion sheds — so each front end
        # demotes its own default-class traffic before its own partition
        # 503s. The demoted CLASS rides the slot header; the engine
        # resolves it to a tier, so a front end never needs the model's
        # tier ladder. ``slo_routing`` (the shared shell's flag, from
        # serve.tier_routing) gates header parsing and the governor
        # together.
        self._brownout = (
            BrownoutGovernor(
                demote_depth=config.brownout_demote_depth,
                restore_depth=config.brownout_restore_depth,
            )
            if self.slo_routing
            else None
        )
        self.metrics = ShmWorkerMetrics(
            ring, worker_id, default_tenant=default_index
        )
        self.trace_plane = "ring"
        self.trace_worker = worker_id
        if trace is not None and trace.enabled:
            # tracewire: this worker's spans -> its own JSONL (per-worker
            # files need no cross-process append coordination); drops
            # land in the worker's shm cell so any scrape sees the fleet
            # total. The engine half-stamps stitch in via `_score`.
            from pathlib import Path

            from mlops_tpu.trace import TraceRecorder

            def _count_drops(n: int) -> None:
                ring.trace_dropped[worker_id] += n

            self.tracer = TraceRecorder(
                Path(trace.dir) / f"spans-w{worker_id}.jsonl",
                capacity=trace.ring_capacity,
                flush_interval_s=trace.flush_interval_s,
                on_drop=_count_drops,
            )
        if slo is not None and slo.enabled and slo.flightrec_enabled:
            # sloscope flight recorder (mlops_tpu/slo/): EACH front end
            # keeps its own evidence ring (its requests, its spans) and
            # dumps it on anomaly — per-process files (pid in the name)
            # need no cross-process coordination, and the tmp+rename
            # discipline means a sibling's kill -9 can never tear a
            # dump. The SLO ENGINE itself runs engine-side (the lead
            # replica's telemetry loop); this worker watches the shm
            # alert flags and the respawn counter for its dump
            # triggers (_run_frontend's watchdog).
            from mlops_tpu.slo import FlightRecorder

            def _count_dump(path) -> None:
                # Single-writer shm cell (like trace_dropped): any
                # worker's scrape shows the fleet's landed dumps.
                ring.flight_dumps[worker_id] += 1

            self.flightrec = FlightRecorder(
                slo.flightrec_dir,
                capacity=slo.flightrec_capacity,
                cooldown_s=slo.flightrec_cooldown_s,
                keep=slo.flightrec_keep,
                source="ring",
                worker=worker_id,
                spike_errors=slo.flightrec_spike_errors,
                spike_window_s=slo.flightrec_spike_window_s,
                on_dump=_count_dump,
            )
        # The ring's large slabs are sized by the parent to the (possibly
        # bucket-clamped) request cap; the slab capacity is the contract.
        self.max_batch = min(config.max_batch, ring.large_rows)
        # Encoding runs in a tiny thread pool: the native C++ encoder
        # releases the GIL, and a 256-row encode would otherwise stall
        # the accept loop.
        import concurrent.futures

        self._encode_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=2, thread_name_prefix=f"encode-w{worker_id}"
        )

    # ------------------------------------------------------------- routes
    def _ready(self) -> bool:
        return self.ring.engine_ready and not self.draining

    def _outage_stamped(self) -> bool:
        """True when the supervisor has stamped at least one engine
        replica's death AND no replica is ready — a real FULL outage
        (every replica down), not a cold boot and not the partial-outage
        brownout the router absorbs by routing around the hole."""
        return not self.ring.engine_ready and bool(
            (self.ring.eng_vals[:, ENG_DOWN_SINCE] > 0).any()
        )

    def _respawn_retry_after(self) -> int:
        """Retry-After seconds for a BROWNOUT 503 (every engine replica
        down, parking full): the configured respawn ETA minus how long
        the outage has been running — a well-behaved client's retry
        lands just after the first replacement's replay finishes,
        instead of hammering into the same full parking lot. The outage
        clock starts at the EARLIEST still-down replica's stamp (the
        furthest-along respawn is what ends a full outage). Never below
        1 s (the header is integer seconds, and 0 invites an immediate
        retry)."""
        eta = self.config.engine_respawn_eta_s
        stamps = [
            float(v) for v in self.ring.eng_vals[:, ENG_DOWN_SINCE] if v > 0
        ]
        down_since = min(stamps) if stamps else 0.0
        remaining = eta - (time.monotonic() - down_since) if down_since else eta
        if remaining <= 0:
            # The ETA estimate is already blown (a respawn slower than
            # advertised — e.g. the AOT cache was cold and the
            # replacement is recompiling): re-advertise the FULL ETA so
            # clients pace their retries at the estimate's cadence
            # instead of hammering 1 s retries into a still-full parking
            # lot for the whole recompile.
            remaining = eta
        return max(1, math.ceil(remaining))

    def _slo_view(self):
        # /healthz verdict source (httpcore._healthz): the fleet view the
        # lead replica last mirrored into shm — rows never written render
        # the zero baseline (last-known-values contract).
        if not self.ring.slo_armed:
            return None
        from mlops_tpu.slo.engine import read_slo_view

        return read_slo_view(
            self.ring.slo_vals,
            self.ring.alert_vals,
            tuple(self.ring.tenant_names),
            tuple(float(x) for x in self.ring.slo_meta[:4]),
        )

    def _engine_down(self) -> bool:
        # The /healthz verdict's "down" condition IS the full-outage
        # predicate the brownout shed uses.
        return self._outage_stamped()

    async def _metrics_endpoint(self):
        # Every gauge renders straight from shared memory — all workers'
        # request/latency blocks, the ring depth/shed counters, and the
        # engine-process monitor aggregate (single-flight in the engine's
        # telemetry loop; a front end never touches the device). Any
        # worker can serve the scrape with the full fleet view, which is
        # what SO_REUSEPORT requires: Prometheus lands on a random one.
        return (
            200,
            render_ring_metrics(self.ring),
            "text/plain; version=0.0.4",
        )

    async def _score(
        self,
        record_dicts: list[dict],
        request_id: str,
        deadline: float | None = None,
        span=None,
        tenant: int = 0,
        slo: int = SLO_DEFAULT,
    ):
        """The ring-backed scoring hook under the shared `_predict` shell
        (serve/httpcore.py): per-tenant quota, then slot admission, then
        encode, then the slot round trip. The deadline budget
        (``x-request-deadline-ms``) decrements across every stage:
        checked before the encode pool is touched, stamped into the slot
        header so the ENGINE can complete an expired descriptor without
        dispatching, and bounding the completion wait — each stage
        answers the documented 504 rather than doing work the client
        stopped waiting for.

        ``tenant`` (resolved by the shell from ``x-tenant``) selects the
        preprocessor, tags the slot so the engine dispatches the right
        bundle, and is the quota/metrics dimension."""
        if not record_dicts:
            return EMPTY_RESPONSE_BYTES
        if self.quota is None:
            # 1-tenant fleet: fairness is trivial; admission is exactly
            # the pre-tenancy slot path.
            return await self._score_admitted(
                record_dicts, request_id, deadline, span, tenant, slo
            )
        # QUOTA BEFORE EVERYTHING (weighted max-min, tenancy/quota.py),
        # per slot CLASS — the request's row count picks the physical
        # pool it will claim from, and fairness is enforced over that
        # pool: a hot tenant past its share sheds against its OWN quota
        # while every other tenant's reserved floor in EACH class stays
        # claimable. The 503 + Retry-After is the same wire contract as
        # the slot shed, with the tenant and the word "quota" in the
        # detail and the rejection counted per tenant
        # (mlops_tpu_tenant_quota_shed_total — quota sheds are NOT
        # physical sheds: shed_total stays a pure slot-exhaustion
        # counter operators can difference against). A physically FULL
        # class is NOT a quota event: it falls through to the classic
        # slot-shed contract (class detail, brownout ETA during an
        # engine outage) via claim() below.
        governor = self.quota[
            0 if len(record_dicts) <= self.ring.small_rows else 1
        ]
        verdict = governor.try_acquire(tenant)
        if verdict == "quota":
            self.client.count_quota_shed(tenant)
            retry_s = self.config.shed_retry_after_s
            name = self.tenants.names[tenant]
            return (
                503,
                {
                    "detail": f"tenant {name!r} over quota; retry in "
                    f"{retry_s}s"
                },
                "application/json",
                {"retry-after": str(retry_s)},
            )
        if verdict == "full":
            # No governor hold to release: score through the claim path,
            # which answers the physical-shed 503 (claim can still
            # succeed if a slot freed since the check — benign).
            return await self._score_admitted(
                record_dicts, request_id, deadline, span, tenant, slo
            )
        try:
            return await self._score_admitted(
                record_dicts, request_id, deadline, span, tenant, slo
            )
        finally:
            # The governor tracks ADMITTED REQUESTS, not slots: a zombie
            # slot awaiting a late engine completion keeps holding its
            # slot (never its quota), so a stalled engine degrades into
            # slot sheds, never into quota leakage.
            governor.release(tenant)

    async def _score_admitted(
        self,
        record_dicts: list[dict],
        request_id: str,
        deadline: float | None,
        span,
        tenant: int,
        slo: int = SLO_DEFAULT,
    ):
        from mlops_tpu.schema import records_to_columns

        # Injection point (mlops_tpu/faults): kill = a front-end worker
        # crash mid-request — the supervisor-respawn + slot-quarantine
        # path the chaos smoke drives.
        faults.fire("serve.frontend.predict")
        n = len(record_dicts)
        # ADMISSION BEFORE ENCODE: a to-be-shed request must cost nothing
        # — the row count is known from the validated records, so the
        # shed 503 never queues through (or wastes) the encode pool, and
        # its latency stays flat no matter how deep the overload. On a
        # multi-tenant plane the claim may not cross classes: the quota
        # governor admitted against the class the row count names, so an
        # overflow slab would hold capacity the other class's governor
        # never accounted (tenancy/quota.py).
        # Brownout before shed (ISSUE 19): when this worker's partition
        # occupancy crosses the governor's threshold, default-class
        # requests demote to the cheap class BEFORE claiming — the
        # demoted class rides the slot header and the engine serves the
        # cheaper tier, so pressure turns into faster (still-correct)
        # answers instead of 503s. Explicit cheap/accurate headers are
        # never overridden, and the governor auto-restores once
        # occupancy falls back through the restore threshold.
        demoted = False
        if self._brownout is not None:
            self._brownout.observe(self.client.pressure())
            slo, demoted = self._brownout.route(slo)
        slot = self.client.claim(
            n, tenant, allow_overflow=self.quota is None, slo=slo
        )
        if slot is None:
            # Bounded admission per bucket class: shed FAST with a
            # Retry-After instead of queueing — the slots free up as
            # in-flight responses land, so a well-behaved client's retry
            # lands in capacity. During an ENGINE OUTAGE (ISSUE 11) the
            # partition doubles as the parking lot, so a full partition
            # means "parking full": the shed becomes a BROWNOUT 503
            # whose Retry-After advertises the respawn ETA, counted
            # separately — shed latency stays flat either way.
            self.client.count_shed(n, tenant)
            cls = "small" if n <= self.ring.small_rows else "large"
            if self._outage_stamped():
                # A real OUTAGE (the supervisor stamped the engine's
                # death), not a cold boot: first-boot warmup can take
                # minutes and its sheds must advertise the steady-state
                # Retry-After below, not a ~5 s respawn ETA that would
                # hammer retries into a still-warming plane.
                self.ring.brownout_shed[self.worker_id] += 1
                retry_s = self._respawn_retry_after()
                return (
                    503,
                    {
                        "detail": "engine restarting and parking is "
                        f"full (no free {cls} request slot); retry in "
                        f"{retry_s}s"
                    },
                    "application/json",
                    {"retry-after": str(retry_s)},
                )
            retry_s = self.config.shed_retry_after_s
            return (
                503,
                {
                    "detail": "overloaded: no free "
                    f"{cls} "
                    f"request slot; retry in {retry_s}s"
                },
                "application/json",
                {"retry-after": str(retry_s)},
            )
        if demoted:
            # Counted only for ADMITTED requests: a demote-then-shed is a
            # shed (the demotion never served anyone), so the counter
            # stays "requests served below their requested class".
            self.client.count_demotion(brownout=True)
        submitted = False
        try:
            loop = asyncio.get_running_loop()
            if deadline is not None and loop.time() >= deadline:
                # Budget spent before the encode pool was touched (slot
                # waits, slow header/body): release the claim unused and
                # shed the dead work — the cheap 504.
                self.client.release(slot)
                slot = None
                self.metrics.count_deadline_expired()
                return deadline_response()
            # Encode BEFORE enqueue (the tentpole's division of labor):
            # the engine process receives ready-to-scatter arrays and
            # spends its cycles on device dispatch only. The native
            # encoder releases the GIL, so the pool keeps the accept loop
            # responsive through a 256-row encode.
            preprocessor = self.preprocessors[tenant]
            ds = await loop.run_in_executor(
                self._encode_pool,
                lambda: preprocessor.encode(
                    records_to_columns(record_dicts)
                ),
            )
            if span is not None:
                span.stamp("encode")
            # The slot header carries the absolute deadline (the loop
            # clock IS time.monotonic, which the engine process shares):
            # a descriptor that expires while queued in the ring comes
            # back RESP_EXPIRED without ever dispatching.
            future = self.client.submit(
                slot, ds.cat_ids, ds.numeric, deadline=deadline
            )
            submitted = True
            timeout = self.config.request_timeout_s or None
            if deadline is not None:
                remaining = deadline - loop.time()
                timeout = min(timeout or remaining, remaining)
            # Parking (ISSUE 11): a request admitted while the engine is
            # down holds its slot and WAITS — the respawned engine's
            # re-attach replays it (200 if the budget allows) or the
            # deadline below turns it into the documented 504. The gauge
            # counts requests currently parked this way; like the
            # brownout shed above it requires a supervisor-stamped
            # OUTAGE, so routine first-boot warmup waits never read as
            # outage evidence on dashboards.
            parked = self._outage_stamped()
            if parked:
                self.ring.parked[self.worker_id] += 1
            try:
                if timeout is not None:
                    status = await asyncio.wait_for(future, max(timeout, 0.0))
                else:
                    status = await future
            except asyncio.TimeoutError:
                logger.error(
                    "prediction deadline (%.1fs) exceeded request_id=%s — "
                    "engine stall?",
                    timeout,
                    request_id,
                )
                self.client.abandon(slot)
                slot = None
                return deadline_response(
                    f"prediction exceeded the {timeout:g}s deadline"
                )
            finally:
                if parked:
                    self.ring.parked[self.worker_id] -= 1
            if status == RESP_EXPIRED:
                # The engine shed the dead work (already counted engine-
                # side); the completion is the proof the slab is quiescent.
                self.client.release(slot)
                slot = None
                return deadline_response()
            if status != RESP_OK:
                # The engine process logged the traceback; the wire
                # contract matches the single-process 500.
                self.client.release(slot)
                slot = None
                return 500, {"detail": "prediction failed"}, "application/json"
            if span is not None:
                self._stitch_engine_half(span, slot)
            pred, out, drift = self.client.response_arrays(slot)
            # encode_response (serve/wire.py) goes straight from the slab
            # views to wire bytes — byte-identical to the old
            # format_response + json.dumps, but the handler's event loop
            # never re-serializes the dict (the encode-bound residue).
            # The encode materializes every float, so the slab is
            # quiescent before release.
            response = encode_response(pred, out, drift)
            self.client.release(slot)
            slot = None
            return response
        # Top-of-handler boundary (same contract as the single-process
        # server): ANY failure becomes a logged 500, never a dropped
        # connection or a leaked slot.
        except Exception:  # tpulint: disable=TPU201
            logger.exception("prediction failed request_id=%s", request_id)
            if slot is not None:
                if submitted:
                    self.client.abandon(slot)
                else:
                    self.client.release(slot)
            return 500, {"detail": "prediction failed"}, "application/json"

    def _stitch_engine_half(self, span, slot: int) -> None:
        """Fold the engine process's half-span (the four CLOCK_MONOTONIC
        stamps + compiled-entry encoding it wrote into the slot header —
        serve/ipc.py ``resp_trace``) into this request's span: one
        stitched record whose stages are monotone and non-overlapping by
        the span's clamping rule. Read between completion and release —
        the same ownership window as the response slab."""
        stamps = self.ring.resp_trace[slot]
        collect, jobstart, dispatched, fetched = (
            float(stamps[0]), float(stamps[1]),
            float(stamps[2]), float(stamps[3]),
        )
        if not (collect and jobstart and dispatched and fetched):
            return  # engine ran untraced (armed mid-flight); keep ours
        span.stamp_at("ring_wait", collect)
        span.stamp_at("engine_queue", jobstart)
        span.stamp_at("dispatch", dispatched)
        span.stamp_at("device_fetch", fetched)
        # Which engine replica served (the router's choice, read from the
        # slot tag inside the same ownership window): trace-report
        # --replica slices per-replica latency pictures from this.
        span.replica = int(self.ring.slot_replica[slot]) % self.ring.replicas
        kind, geom = int(stamps[4]), int(stamps[5])
        if kind == 1:
            span.entry = f"bucket_{geom}"
        elif kind == 2:
            span.entry = f"group_{geom // 100000}x{geom % 100000}"

    async def _profile(self, action: str):
        """Forward /debug/profile to the ENGINE process (the only one
        holding the device) through the ring's single-word control
        channel: claim the channel non-blocking (busy -> 409), publish
        the request word, await the collector's acknowledgement, answer
        with the shared wire shapes (`httpcore.profile_payload`)."""
        from mlops_tpu.serve.httpcore import profile_payload

        if not self.config.profile_dir:
            return profile_payload(404, action, "")
        code = {"start": 1, "stop": 2}.get(action)
        if code is None:
            return 404, {"detail": "not found"}, "application/json"
        ring = self.ring
        token = ring.try_claim_profile()
        if token is None:
            return 409, {"detail": "profile control busy"}, "application/json"
        try:
            seq = ring.post_profile_request(code)
            deadline = asyncio.get_running_loop().time() + _PROFILE_ACK_S
            while True:
                status = ring.read_profile_ack(seq)
                if status is not None:
                    break
                if asyncio.get_running_loop().time() >= deadline:
                    # Engine collector never answered (stalled in a long
                    # compile / chaos stall): CANCEL the pending word so
                    # the start/stop does not execute later against a
                    # client already told it failed.
                    ring.cancel_profile_request(seq, token)
                    status = 504
                    break
                await asyncio.sleep(0.02)
        finally:
            ring.release_profile(token)
        return profile_payload(status, action, self.config.profile_dir)

    def close_tracer(self) -> None:
        """Drain-path flush of this worker's span recorder (joins the
        writer thread; call only once the in-flight exchanges finished)."""
        if self.tracer is not None:
            self.tracer.close()

    # ---------------------------------------------------------- lifecycle
    async def start(self) -> asyncio.AbstractServer:
        """Bind this worker's own SO_REUSEPORT socket and hook the
        completion doorbell into the event loop."""
        sock = reuseport_socket(self.config.host, self.config.port)
        loop = asyncio.get_running_loop()
        for replica in range(self.ring.replicas):
            # One reader per engine replica's completion doorbell: each
            # (worker, replica) queue has its own counted-credit fence.
            loop.add_reader(
                self.ring.worker_doorbell(self.worker_id, replica).fileno(),
                self.client.on_doorbell,
                replica,
            )
            # One unconditional kick per replica: a respawned client may
            # have seeded credit for completions whose doorbell the DEAD
            # incarnation already drained — the eventfd sits at 0, so
            # add_reader alone would never fire, and with every slot
            # quarantined no new traffic could ring it either (permanent
            # 503s). A spurious call is harmless (zero credit pops
            # nothing).
            loop.call_soon(self.client.on_doorbell, replica)
        return await asyncio.start_server(self.handle_connection, sock=sock)

    def stop_doorbell(self) -> None:
        for replica in range(self.ring.replicas):
            with contextlib.suppress(Exception):
                asyncio.get_running_loop().remove_reader(
                    self.ring.worker_doorbell(
                        self.worker_id, replica
                    ).fileno()
                )


# --------------------------------------------------------------- children
def _frontend_main(
    worker_id: int,
    config: ServeConfig,
    ring: RequestRing,
    preprocess_path: str | list[str],
    trace: Any = None,
    tenancy: Any = None,
    slo: Any = None,
) -> None:
    """Front-end child process entry (forked — everything arrives by
    inheritance). Never imports jax, never touches the device.
    ``preprocess_path`` is one path per tenant (a bare string = the
    1-tenant fleet)."""
    from mlops_tpu.data.encode import Preprocessor

    paths = (
        [preprocess_path]
        if isinstance(preprocess_path, str)
        else list(preprocess_path)
    )
    preprocessors = [Preprocessor.load(path) for path in paths]
    try:
        asyncio.run(
            _run_frontend(
                worker_id, config, ring, preprocessors, trace, tenancy, slo
            )
        )
    except KeyboardInterrupt:  # pragma: no cover - interactive stop
        pass


async def _run_frontend(
    worker_id: int,
    config: ServeConfig,
    ring: RequestRing,
    preprocessor,
    trace: Any = None,
    tenancy: Any = None,
    slo: Any = None,
) -> None:
    server = FrontendServer(
        config, ring, worker_id, preprocessor, trace, tenancy, slo
    )
    srv = await server.start()
    logger.info(
        "frontend %d serving %s on %s:%s (pid %d)",
        worker_id, config.service_name, config.host, config.port, os.getpid(),
    )
    loop = asyncio.get_running_loop()
    if config.loop_lag_monitor:
        # Runtime half of the Layer-5 discipline, per worker process:
        # the watchdog drains each window max into this worker's shm
        # cell, so any worker's scrape renders the fleet's lag gauges.
        from mlops_tpu.analysis.loopcheck import LoopLagSanitizer

        server.loop_monitor = LoopLagSanitizer(
            slow_ms=config.loop_lag_slow_ms
        )
        server.loop_monitor.attach(loop)
        logger.info(
            "frontend %d: loop-lag sanitizer armed (slow_ms=%g)",
            worker_id, config.loop_lag_slow_ms,
        )
    draining = asyncio.Event()

    def _drain(signum=None, frame=None) -> None:
        server.draining = True
        draining.set()
        srv.close()
        for w in list(server._connections - server._busy):
            w.close()  # idle keep-alive readers see EOF; handlers exit

    with contextlib.suppress(NotImplementedError, RuntimeError):
        loop.add_signal_handler(signal.SIGTERM, _drain)
        loop.add_signal_handler(signal.SIGINT, _drain)

    parent = os.getppid()

    def _read_alert_flags() -> dict:
        # ONE snapshot rule for the edge detector's seed and its
        # per-pass read: the two must stay identical or a respawned
        # worker would re-trigger dumps on historical alerts.
        from mlops_tpu.slo.engine import ENGINE_ALERTS

        return {
            (alert, tenant): bool(ring.alert_vals[t, a_i])
            for a_i, alert in enumerate(ENGINE_ALERTS)
            for t, tenant in enumerate(ring.tenant_names)
        }

    def _watch_anomalies(state: dict) -> None:
        # Flight-recorder triggers this worker can only see in shm
        # (mlops_tpu/slo/): an engine respawn (the supervisor bumped a
        # replica's counter) and alert flags flipping ACTIVE (the lead
        # replica's SLO engine mirrored a rising edge). Edge-detected
        # against the previous watchdog pass, so a sustained alert
        # triggers once (plus the recorder's own cooldown).
        from mlops_tpu.slo.engine import ALERT_SEVERITY

        respawns = int(ring.eng_vals[:, ENG_RESPAWNS].sum())
        if respawns > state["respawns"]:
            server.flightrec.trigger("engine_respawn")
        state["respawns"] = respawns
        flags = _read_alert_flags()
        for key, active in flags.items():
            if active and not state["alerts"].get(key):
                alert, tenant = key
                server.flightrec.note_alert(
                    alert, tenant, ALERT_SEVERITY[alert]
                )
        state["alerts"] = flags

    async def _watch_plane() -> None:
        # Two drain triggers besides the direct SIGTERM: the shared ring
        # drain flag (a front end forked mid-drain, or a missed signal),
        # and a DEAD parent — the supervisor in production, the test
        # harness process otherwise; either way nobody can respawn this
        # worker anymore, so drain rather than linger. ENGINE death is
        # deliberately NOT a drain trigger (ISSUE 11): the supervisor
        # respawns the engine, in-flight requests park against their
        # deadline budgets, and the replay answers them — the watchdog
        # split that turned engine death from an outage into a brownout.
        # Seed the edge detector from the CURRENT shm state: a worker
        # (re)spawned into a plane mid-incident must not re-trigger on
        # history it never witnessed — only on new transitions.
        anomaly_state = {
            "respawns": int(ring.eng_vals[:, ENG_RESPAWNS].sum()),
            "alerts": {},
        }
        if server.flightrec is not None and ring.slo_armed:
            anomaly_state["alerts"] = _read_alert_flags()
        while not draining.is_set():
            await asyncio.sleep(1.0)
            if server.loop_monitor is not None:
                # Single-writer shm publish (this worker's own cell):
                # the gauge shows each worker's worst callback over the
                # last watchdog window, 0.0 when the loop stayed smooth.
                server.metrics.set_loop_lag(
                    server.loop_monitor.snapshot_ms()
                )
            if server.flightrec is not None:
                # Executor: a triggered dump writes a file, which must
                # not stall the accept loop (the recorder is
                # thread-safe; one leaf lock).
                await loop.run_in_executor(
                    None, _watch_anomalies, anomaly_state
                )
            if ring.draining:
                logger.info("frontend %d: ring drain flag set; draining",
                            worker_id)
                _drain()
            elif os.getppid() != parent:
                logger.error("frontend %d: parent process died; draining",
                             worker_id)
                _drain()

    watchdog = asyncio.create_task(_watch_plane())
    await draining.wait()
    # Busy exchanges get a bounded window to finish their responses and
    # in-flight ring slots to land (serve.drain_deadline_s; the kubelet's
    # grace period is the hard stop).
    deadline = loop.time() + config.drain_deadline_s
    while (server._busy or server.client.pending_count()) and (
        loop.time() < deadline
    ):
        await asyncio.sleep(0.05)
    for w in list(server._connections):
        w.close()
    server.stop_doorbell()
    watchdog.cancel()
    if server.loop_monitor is not None:
        server.loop_monitor.detach()
        server.loop_monitor = None
    with contextlib.suppress(asyncio.TimeoutError):
        await asyncio.wait_for(srv.wait_closed(), timeout=5)
    # AFTER the busy/pending drain above: every finished exchange has
    # recorded its span; the final flush guarantees no torn or lost
    # lines on SIGTERM (O_APPEND single-write discipline in the writer).
    await asyncio.get_running_loop().run_in_executor(
        None, server.close_tracer
    )
    if server.flightrec is not None:
        # Evidence-gated SIGTERM dump (a clean drain writes nothing).
        await asyncio.get_running_loop().run_in_executor(
            None, server.flightrec.dump_if_evidence, "sigterm"
        )
    logger.info("frontend %d drained; exiting", worker_id)


def start_frontends(
    config: ServeConfig,
    ring: RequestRing,
    preprocess_path: str | list[str],
    trace: Any = None,
    tenancy: Any = None,
    slo: Any = None,
) -> list[multiprocessing.Process]:
    """Fork one front-end process per worker (call BEFORE any jax backend
    initializes in the parent — the children inherit a clean world)."""
    return [
        _respawn(
            config, ring, preprocess_path, worker_id, trace, tenancy, slo
        )
        for worker_id in range(ring.workers)
    ]


def _write_pid_files(engine_pids: list[int | None]) -> None:
    """Operator convenience (ISSUE 11 satellite): pid files live under
    ``runs/`` (gitignored), never at the repo root — ``serve.pid`` is the
    supervisor (SIGTERM target for a drain), ``engine.pid`` the current
    engine incarnations ONE PID PER LINE, replica order (SIGKILL targets
    for a survivability drill — line k is replica k). Best-effort: a
    read-only working directory must not fail serving."""
    try:
        os.makedirs("runs", exist_ok=True)
        with open(os.path.join("runs", "serve.pid"), "w") as f:
            f.write(f"{os.getpid()}\n")
        pids = [pid for pid in engine_pids if pid is not None]
        if pids:
            with open(os.path.join("runs", "engine.pid"), "w") as f:
                f.write("".join(f"{pid}\n" for pid in pids))
    except OSError:
        logger.warning(
            "could not write pid files under runs/", exc_info=True
        )


def _engine_main(
    config: Config,
    ring: RequestRing,
    bundle_dir: str,
    trace: Any = None,
    tenancy: Any = None,
    replica: int = 0,
) -> None:
    """Engine child process entry (forked from the jax-free supervisor —
    ring, doorbells, and locks arrive by inheritance; jax imports happen
    HERE, after the fork, so no backend thread ever crosses one). Loads
    the tenant fleet's bundles (the 1-tenant "default" fleet when no
    tenants.toml was given), warms through the AOT compile cache with
    architecture-level executable dedupe (`tenancy/registry.py`),
    re-attaches to the ring under a fresh incarnation — replaying any
    slots a dead predecessor left busy, each under its shm-tagged tenant
    (`RingService.reattach`) — and serves until SIGTERM or supervisor
    death. ``kill -9`` of this process is the survivable-engine
    tentpole: the supervisor forks a replacement that runs this same
    function against the same shm ring."""
    from mlops_tpu.compilecache.cache import from_config
    from mlops_tpu.compilecache.location import enable_persistent_cache
    from mlops_tpu.tenancy import TenantRegistry, single_tenant_config

    # Before this process's first compile.
    enable_persistent_cache(aot_store_on=bool(config.cache.dir))
    serve_cfg = config.serve
    stop = {"flag": False}

    def _stop(signum=None, frame=None) -> None:
        stop["flag"] = True

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)

    if tenancy is None:
        tenancy = single_tenant_config(bundle_dir)
    # Per-replica device assignment: every replica is its own process
    # that sees the whole backend, and replica r takes its own S-device
    # slice of it. The slice index rides into the AOT cache key
    # (device_tag), so differently-placed artifacts never cross-load. A
    # replica that cannot have its own slice REFUSES to start — sharing
    # device 0 in silence would report E replicas while one chip does
    # the work. (Per-process chip visibility, so each replica owns a
    # chip outright, is ROADMAP R7.)
    import jax

    shards = serve_cfg.model_shards
    device_index: int | None = None
    if ring.replicas > 1:
        needed = ring.replicas * shards
        if jax.device_count() < needed:
            raise SystemExit(
                f"engine replica {replica} cannot have its own device: "
                f"serve.engine_replicas={ring.replicas} x "
                f"serve.model_shards={shards} needs {needed} devices, "
                f"this process sees {jax.device_count()}; refusing to "
                "share one device between replicas"
            )
        device_index = replica * shards
        logger.info(
            "engine replica %d pinned to device slice [%d, %d)",
            replica, device_index, device_index + shards,
        )
    registry = TenantRegistry(
        tenancy,
        buckets=tuple(serve_cfg.warmup_batch_sizes),
        service_name=serve_cfg.service_name,
        enable_grouping=serve_cfg.batch_window_ms > 0,
        compile_cache=from_config(config),
        warmup_workers=config.cache.warmup_workers,
        model_shards=serve_cfg.model_shards,
        device_index=device_index,
        serve_tier=serve_cfg.serve_tier,
        tier_routing=serve_cfg.tier_routing,
    )
    engines = registry.engines
    if trace is not None:
        # Shape histograms accumulate ENGINE-side (the only process that
        # dispatches); ONE shared ShapeStats across the fleet — entries
        # are keyed by compiled shape, which tenants share by design —
        # mirrored into shm for every front end's /metrics.
        from mlops_tpu.trace import ShapeStats

        stats = ShapeStats()
        for eng in engines:
            eng.set_shape_stats(stats)
    slo_cfg = getattr(config, "slo", None)
    ledger = None
    if slo_cfg is not None and slo_cfg.ledger_dir:
        # Device-time cost ledger (slo/ledger.py): ONE per engine
        # process, shared across the tenant fleet (entries key by
        # entry + model fingerprint, so arch twins correctly share);
        # sharded per replica on disk so concurrent flushes never
        # clobber a sibling's totals.
        from mlops_tpu.slo import CostLedger

        ledger = CostLedger(
            slo_cfg.ledger_dir,
            flush_interval_s=slo_cfg.ledger_flush_s,
            shard=f"r{replica}" if ring.replicas > 1 else "",
        )
        for eng in engines:
            eng.set_cost_ledger(ledger)
        logger.info("cost ledger armed -> %s", ledger.path)
    service = RingService(
        engines[0],
        ring,
        max_group=serve_cfg.max_group,
        max_inflight=serve_cfg.max_inflight,
        threads=serve_cfg.max_workers,
        monitor_fetch_every_s=serve_cfg.monitor_fetch_every_s,
        monitor_fetch_every_requests=serve_cfg.monitor_fetch_every_requests,
        engines=engines,
        replica=replica,
    )
    service.cost_ledger = ledger
    if slo_cfg is not None and slo_cfg.enabled and replica == 0:
        # SLO engine on the LEAD replica only (one writer for the shm
        # alert rows; every replica reads the same fleet-wide counters
        # anyway): evaluated each telemetry tick from the ring's shm
        # request matrices, mirrored for the front ends' renders. The
        # lifecycle breaker flags ride in from the life rows so a broken
        # retrain path alerts through the same channel as a burn.
        from mlops_tpu.serve.metrics import LIFE_BREAKER_OPEN
        from mlops_tpu.slo import SLOEngine
        from mlops_tpu.slo.engine import SLO_NAMES, read_slo_view

        def _ring_breakers() -> dict:
            return {
                name: bool(ring.life_vals[t, LIFE_BREAKER_OPEN])
                for t, name in enumerate(ring.tenant_names)
            }

        # Respawn-base seed (the ISSUE 11 monotone-counter discipline):
        # a respawned engine's fresh evaluator re-baselines against the
        # surviving shm request counters — seed it with the dead
        # incarnation's last-published totals so slo_*_total never
        # regresses across a respawn (first boot reads the zero view).
        prev = read_slo_view(
            ring.slo_vals, ring.alert_vals, tuple(ring.tenant_names),
            tuple(float(x) for x in ring.slo_meta[:4]),
        )
        prior = {
            name: (
                prev[name]["slos"][SLO_NAMES[0]]["good"],
                prev[name]["slos"][SLO_NAMES[0]]["total"],
                prev[name]["slos"][SLO_NAMES[1]]["good"],
                prev[name]["slos"][SLO_NAMES[1]]["total"],
            )
            for name in ring.tenant_names
        }
        service.slo = SLOEngine(
            slo_cfg,
            tuple(ring.tenant_names),
            source=lambda: ring.slo_counts(slo_cfg.latency_threshold_ms),
            breaker_source=_ring_breakers,
            prior_counts=prior,
        )
        logger.info("sloscope armed (lead replica evaluator)")
    if serve_cfg.profile_dir and replica == 0:
        # /debug/profile: front ends forward start/stop through the
        # ring's single control word, answered by the LEAD replica (one
        # device trace at a time).
        from mlops_tpu.serve.server import JaxProfiler

        service.profiler = JaxProfiler(serve_cfg.profile_dir).control
    # Warmup -> re-attach (incarnation bump + busy-slot replay) -> serve:
    # parked requests are re-answered by the replay BEFORE this
    # replica's ready flag flips, so "ready" means "this replica's share
    # of the outage is fully healed". Replicas warm from the SAME
    # compile cache — replica 0's cold boot compiles, every sibling (and
    # every respawn) deserializes.
    warm_report = registry.warmup()
    attach = service.reattach()
    service.start()
    ring.set_ready(True, replica)
    ring.eng_vals[replica, ENG_DOWN_SINCE] = 0.0
    logger.info("warmup complete; ready %s", _LazyJson(warm_report))
    logger.info(
        "engine replica %d incarnation %d attached %s",
        replica, attach["incarnation"], _LazyJson(attach),
    )
    if config.lifecycle.enabled and replica == 0:
        # The closed loops run ENGINE-SIDE (the only process with the
        # device, the exec tables, and the compile cache) — ONE
        # controller PER TENANT, each on a tenant-namespaced state dir,
        # so tenant A drifting retrains/shadows/promotes A alone; the
        # telemetry loop mirrors each controller's gauges into its
        # tenant's shm row. The fork-time preprocessors are the encode
        # contract, so every controller is forced onto its incumbent
        # preprocessor. A respawned engine restarts each loop from its
        # on-disk reservoir state. (The 1-tenant "default" fleet keeps
        # the un-namespaced state dir — bit-identical to pre-tenancy.)
        from mlops_tpu.lifecycle import LifecycleController
        from mlops_tpu.tenancy import tenant_scoped_config

        single_default = len(registry) == 1 and registry.names[0] == "default"
        service.lifecycles = []
        for name, eng in zip(registry.names, engines):
            scoped = (
                config if single_default
                else tenant_scoped_config(config, name)
            )
            controller = LifecycleController(
                eng, scoped, force_incumbent_preprocessor=True
            )
            controller.start()
            service.lifecycles.append(controller)
        service.lifecycle = service.lifecycles[0]
        logger.info(
            "lifecycle controllers started (engine process, %d tenants)",
            len(service.lifecycles),
        )
    autotune = None
    if getattr(config, "autotune", None) is not None and config.autotune.enabled:
        # gridtuner (mlops_tpu/autotune/), engine-side like the
        # lifecycle loops: the LEAD replica fits/searches/applies and
        # persists the plan (plan_dir/plan.json, atomic); every sibling
        # runs an ADOPT-mode controller that applies the lead's plan
        # locally — warming through the SHARED compile cache, so the
        # lead paid each new bucket's compile exactly once and siblings
        # deserialize. Started after warmup (it measures the warmed
        # grid); gauges mirror into this replica's shm row each
        # telemetry tick.
        from mlops_tpu.autotune import AutotuneController

        autotune = AutotuneController(
            engines[0],
            config.autotune,
            adopt=(replica != 0),
            replica=replica,
        )
        autotune.start()
        service.autotune = autotune
        logger.info(
            "autotune controller started (replica %d, %s mode)",
            replica, "adopt" if replica != 0 else "plan",
        )

    supervisor = os.getppid()
    rc = 0
    try:
        # NOT drained by the ring's drain flag: during a graceful drain
        # the front ends finish their in-flight slots FIRST and this
        # process must keep answering them; the supervisor SIGTERMs the
        # engine only after the front ends have joined.
        while not stop["flag"]:
            time.sleep(0.5)
            # Injection point (mlops_tpu/faults): kill = deterministic
            # in-process engine death (the chaos path without needing a
            # pid from outside); raise = an engine main-loop failure —
            # either way the supervisor forks a replacement.
            faults.fire("serve.engine.exit")
            if os.getppid() != supervisor:
                logger.error(
                    "engine: supervisor died; exiting for restart"
                )
                rc = 1
                break
    finally:
        ring.set_ready(False)
        for _, controller in service._tenant_lifecycles():
            controller.stop()
        if autotune is not None:
            autotune.stop()
        service.stop()
        if ledger is not None:
            ledger.close()  # final atomic flush
        logger.info("engine process drained; exiting")
    if rc:
        raise SystemExit(rc)


def _spawn_engine(
    config: Config,
    ring: RequestRing,
    bundle_dir: str,
    trace: Any = None,
    tenancy: Any = None,
    replica: int = 0,
) -> multiprocessing.Process:
    """Fork one engine replica child from the (thread-free, jax-free)
    supervisor — first boot and every respawn run the identical path."""
    ctx = multiprocessing.get_context("fork")
    proc = ctx.Process(
        target=_engine_main,
        args=(config, ring, bundle_dir, trace, tenancy, replica),
        name=f"mlops-tpu-engine-{replica}",
    )
    proc.start()
    return proc


# --------------------------------------------------------------- parent
# Engine crash-loop guard: more than this many engine deaths inside one
# 60 s window means the engine cannot hold (corrupt bundle, broken
# cache, OOM loop) — the supervisor drains and exits 1 so the
# orchestrator restarts the pod instead of brownout-flapping forever.
_ENGINE_STORM_DEATHS = 5
_ENGINE_STORM_WINDOW_S = 60.0


class _DrainNow(Exception):
    """Internal control flow: a replica crash-loop verdict inside the
    per-replica supervision loop must break out of BOTH loops into the
    drain path (a bare ``break`` would only leave the replica scan)."""


def serve_multi_worker(config: Config, bundle_dir: str) -> int:
    """Parent orchestration (ISSUE 11): the parent is a thread-free,
    jax-free SUPERVISOR — ring -> fork front ends -> fork engine child ->
    supervise both.

    Because the supervisor never loads a backend and never starts a
    thread, every fork it performs is safe (the PR 6 zygote's guarantee,
    absorbed into the parent now that the engine lives in a child): a
    crashed front end respawns in ~0.5 s, and a crashed/killed ENGINE is
    a brownout — the replacement warm-starts from the AOT cache,
    re-attaches under a new incarnation, and replays every busy slot
    while in-flight requests park against their deadline budgets
    (docs/operations.md "Engine death is a brownout").
    """
    from pathlib import Path

    serve_cfg = config.serve.validate()
    # eventfd is part of the gate, not just an optimization: the
    # completion-credit protocol rides the eventfd counter, and the pipe
    # fallback exists for dev harnesses, not deployments (macOS passes
    # the fork + SO_REUSEPORT checks but has no eventfd).
    if (
        not hasattr(os, "fork")
        or not hasattr(socket, "SO_REUSEPORT")
        or not hasattr(os, "eventfd")
    ):
        raise SystemExit(
            "serve.workers > 1 needs fork + SO_REUSEPORT + eventfd "
            "(Linux); run single-process (serve.workers=0) on this "
            "platform"
        )
    # Tenant fleet (mlops_tpu/tenancy/): serve.tenants_path names a
    # tenants.toml; without one the plane is the 1-tenant "default"
    # fleet serving the resolved bundle — the identical code path with a
    # one-row tenant axis (bit-identical degradation, test-pinned).
    from mlops_tpu.tenancy import (
        load_tenants_toml,
        single_tenant_config,
    )

    if serve_cfg.tenants_path:
        try:
            tenancy = load_tenants_toml(serve_cfg.tenants_path).validate()
        except ValueError as err:
            raise SystemExit(str(err))
    else:
        tenancy = single_tenant_config(bundle_dir)
    # Engine replica set (ISSUE 13): E supervised engine children behind
    # one ring. The lifecycle loop is single-writer machinery (one
    # controller hot-swaps ONE engine's bundle); running it against a
    # replica fleet would promote replica 0 alone and silently serve
    # mixed generations. The gridtuner (mlops_tpu/autotune/) shipped a
    # fleet-wide lead-plans/siblings-adopt protocol for EXEC-TABLE
    # changes (docs/operations.md "Hot regrid runbook"), but bundle
    # promotion also moves params/preprocessor state, which that
    # adoption path deliberately does not carry — lifting this
    # restriction stays out of scope here; refuse at startup.
    replicas = serve_cfg.engine_replicas
    if replicas > 1 and config.lifecycle.enabled:
        raise SystemExit(
            "serve.engine_replicas > 1 is incompatible with "
            "lifecycle.enabled: the lifecycle controller hot-swaps one "
            "engine process's bundle, and a replica fleet would serve "
            "mixed generations — run E=1 with the lifecycle loop, or "
            "the replica set without it. (The autotune plane's "
            "lead-plans/siblings-adopt regrid protocol covers exec-table "
            "changes only, not bundle promotion — see docs/operations.md)"
        )
    preprocess_paths: list[str] = []
    for spec in tenancy.tenants:
        path = str(Path(spec.bundle_dir) / "preprocess.npz")
        if not Path(path).is_file():
            raise SystemExit(
                f"no preprocessor at {path} (tenant {spec.name!r})"
            )
        preprocess_paths.append(path)

    # Same invariant the single-process server clamps at runtime: the
    # request cap must not exceed the largest warmed bucket, or
    # steady-state traffic triggers exact-shape compiles on the serving
    # hot path. Front ends cannot see the engine, but the bucket grid IS
    # config here (warmup_batch_sizes feeds the engine below), so clamp
    # BEFORE sizing slabs and forking — the children enforce the clamped
    # cap via their 413 gate.
    max_batch = serve_cfg.max_batch
    max_bucket = max(serve_cfg.warmup_batch_sizes)
    if max_batch > max_bucket:
        logger.warning(
            "serve.max_batch=%d exceeds largest warmup bucket %d; clamping",
            max_batch,
            max_bucket,
        )
        max_batch = max_bucket

    ring = RequestRing(
        workers=serve_cfg.workers,
        slots_small=serve_cfg.ring_slots_small,
        slots_large=serve_cfg.ring_slots_large,
        large_rows=max_batch,
        tenant_names=tenancy.names,
        replicas=replicas,
    )
    trace_cfg = getattr(config, "trace", None)
    if trace_cfg is not None and trace_cfg.enabled:
        # tracewire: validate + create the span dir BEFORE the fork (the
        # children write their per-worker JSONL into it) and flip the
        # shm tracing flag so the engine side stamps slot half-spans.
        trace_cfg.validate()
        Path(trace_cfg.dir).mkdir(parents=True, exist_ok=True)
        ring.set_tracing(True)
    else:
        trace_cfg = None
    slo_cfg = getattr(config, "slo", None)
    if slo_cfg is not None and (slo_cfg.enabled or slo_cfg.ledger_dir):
        # sloscope (mlops_tpu/slo/): validate + publish the SLO geometry
        # into shm BEFORE the fork — front ends render the SLO/alert
        # block (and label its windows) straight from the ring; the
        # lead engine replica evaluates and mirrors (_engine_main).
        slo_cfg.validate()
        if slo_cfg.enabled:
            ring.arm_slo(slo_cfg)
    else:
        slo_cfg = None
    # Reserve the port once (also resolves port=0), then hand the concrete
    # port to every child; the placeholder never listens, so the kernel
    # routes nothing to it.
    placeholder = reuseport_socket(serve_cfg.host, serve_cfg.port)
    import dataclasses

    child_cfg = dataclasses.replace(
        serve_cfg, port=placeholder.getsockname()[1], max_batch=max_batch
    )
    procs = start_frontends(
        child_cfg, ring, preprocess_paths, trace_cfg, tenancy, slo_cfg
    )
    logger.info(
        "supervisor %d spawned %d front ends (pids %s) for %d tenant(s) %s",
        os.getpid(), len(procs), [p.pid for p in procs],
        len(tenancy.tenants), list(tenancy.names),
    )
    # STAGGERED spawn (post-review fix): replica 0 boots FIRST and the
    # siblings fork only once its ready word flips — on a cold cache
    # every replica would otherwise compile the full warmup grid
    # simultaneously (E× the multi-minute compile bill; the tmp+rename
    # persist keeps it correct but wasteful). Replica 0 pays the
    # compiles once, persists them, and the siblings deserialize — the
    # "E deserializes, not E compiles" math, made true on cold boots
    # too. (Per-device-pinned artifacts still compile per slice; the
    # shared-device case — and every respawn — deserializes.)
    engine_procs: list[multiprocessing.Process | None] = [
        _spawn_engine(
            config, ring, bundle_dir, trace_cfg, tenancy, replica=0
        )
    ] + [None] * (replicas - 1)
    logger.info(
        "serving %s on %s:%s with %d SO_REUSEPORT front ends "
        "(engine pid %s)",
        serve_cfg.service_name, child_cfg.host, child_cfg.port,
        serve_cfg.workers, engine_procs[0].pid,
    )
    logger.info("engine replica 0 started (pid %s)", engine_procs[0].pid)
    _write_pid_files([p.pid if p else None for p in engine_procs])

    stopping = {"sigterm": False}

    def _sigterm(signum, frame=None) -> None:
        stopping["sigterm"] = True

    signal.signal(signal.SIGTERM, _sigterm)
    signal.signal(signal.SIGINT, _sigterm)

    # Per-replica crash-loop windows: replica k flapping must drain the
    # pod exactly as the single engine did, and sibling deaths must not
    # pool into one shared storm counter (two replicas each dying twice
    # is two brownouts, not one crash loop).
    engine_deaths: list[list[float]] = [[] for _ in range(replicas)]
    rc = 0
    try:
        # ---- supervise: front ends respawn in-place; an engine replica
        # respawns as a 1/E BROWNOUT (its ready word drops, the router
        # routes around it, its busy slots park and replay when the
        # replacement re-attaches) ----
        while not stopping["sigterm"]:
            time.sleep(0.5)
            for i, proc in enumerate(procs):
                if proc.is_alive() or stopping["sigterm"]:
                    continue
                logger.error(
                    "frontend %d (pid %s) died with exit code %s; "
                    "respawning",
                    i, proc.pid, proc.exitcode,
                )
                procs[i] = _respawn(
                    child_cfg, ring, preprocess_paths, i, trace_cfg,
                    tenancy, slo_cfg,
                )
            if engine_procs[-1] is None and ring.rep_ready[0]:
                # Replica 0 is warm: its compiles are persisted, so the
                # siblings' warmups deserialize — spawn the rest of the
                # fleet now (the staggered cold-boot contract above).
                for r in range(1, replicas):
                    engine_procs[r] = _spawn_engine(
                        config, ring, bundle_dir, trace_cfg, tenancy,
                        replica=r,
                    )
                    logger.info(
                        "engine replica %d started (pid %s)",
                        r, engine_procs[r].pid,
                    )
                _write_pid_files([p.pid if p else None for p in engine_procs])
            for r, engine_proc in enumerate(engine_procs):
                if engine_proc is None:
                    continue
                if engine_proc.is_alive() or stopping["sigterm"]:
                    continue
                now = time.monotonic()
                engine_deaths[r] = [
                    t for t in engine_deaths[r]
                    if now - t < _ENGINE_STORM_WINDOW_S
                ] + [now]
                if len(engine_deaths[r]) > _ENGINE_STORM_DEATHS:
                    logger.error(
                        "engine replica %d died %d times inside %.0f s "
                        "— crash loop, not a blip; draining for an "
                        "orchestrator restart",
                        r, len(engine_deaths[r]), _ENGINE_STORM_WINDOW_S,
                    )
                    rc = 1
                    raise _DrainNow
                logger.error(
                    "engine replica %d (pid %s) died with exit code %s; "
                    "respawning",
                    r, engine_proc.pid, engine_proc.exitcode,
                )
                # Brownout begins for THIS replica: its ready word drops
                # (the router routes fresh admissions around it; only a
                # full outage parks), the supervisor stamps the outage
                # start for the Retry-After math and counts the respawn
                # in the replica's own row.
                ring.set_ready(False, r)
                ring.eng_vals[r, ENG_DOWN_SINCE] = now
                ring.eng_vals[r, ENG_RESPAWNS] += 1
                engine_procs[r] = _spawn_engine(
                    config, ring, bundle_dir, trace_cfg, tenancy, replica=r
                )
                logger.info(
                    "engine replica %d started (pid %s)",
                    r, engine_procs[r].pid,
                )
                _write_pid_files([p.pid if p else None for p in engine_procs])
        return rc
    except _DrainNow:
        return rc
    finally:
        # ---- graceful drain: front ends FIRST (their in-flight slots
        # need live engines to land), then the engine replicas ----
        ring.set_draining()
        ring.set_ready(False)
        for proc in procs:
            if proc.is_alive() and proc.pid:
                with contextlib.suppress(ProcessLookupError):
                    os.kill(proc.pid, signal.SIGTERM)
        # One shared wall-clock budget for ALL front-end joins (they
        # drain concurrently — per-child timeouts would compound when
        # several are stuck; serve.zygote_join_deadline_s), then SIGKILL
        # the stragglers: they already ignored SIGTERM.
        deadline = time.monotonic() + serve_cfg.zygote_join_deadline_s
        for proc in procs:
            proc.join(timeout=max(0.0, deadline - time.monotonic()))
        for proc in procs:
            if proc.is_alive():  # pragma: no cover - stuck child
                proc.kill()
                proc.join(timeout=5)
        live_engines = [p for p in engine_procs if p is not None]
        for engine_proc in live_engines:
            if engine_proc.is_alive() and engine_proc.pid:
                with contextlib.suppress(ProcessLookupError):
                    os.kill(engine_proc.pid, signal.SIGTERM)
        # The engines drain their ring services (final monitor write,
        # in-flight jobs) on SIGTERM, concurrently; one shared
        # serve.engine_zygote_join_s budget bounds the waits before
        # SIGKILL escalation.
        deadline = time.monotonic() + serve_cfg.engine_zygote_join_s
        for engine_proc in live_engines:
            engine_proc.join(
                timeout=max(0.0, deadline - time.monotonic())
            )
        for engine_proc in live_engines:
            if engine_proc.is_alive():  # pragma: no cover - stuck engine
                engine_proc.kill()
                engine_proc.join(timeout=5)
        placeholder.close()
        ring.close()
        logger.info("multi-worker plane drained; exiting")


def _respawn(
    config: ServeConfig,
    ring: RequestRing,
    preprocess_path: str | list[str],
    worker_id: int,
    trace: Any = None,
    tenancy: Any = None,
    slo: Any = None,
) -> multiprocessing.Process:
    """Fork a replacement front end for one worker slot partition (the
    generation counters in shm make any of the dead worker's in-flight
    completions stale on arrival). Call only from a process without
    running threads — the supervisor in production, the harness process
    in tests — never from the engine once its backend is up."""
    ctx = multiprocessing.get_context("fork")
    proc = ctx.Process(
        target=_frontend_main,
        args=(worker_id, config, ring, preprocess_path, trace, tenancy, slo),
        name=f"mlops-tpu-frontend-{worker_id}",
    )
    proc.start()
    return proc

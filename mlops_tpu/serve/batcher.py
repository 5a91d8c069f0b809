"""Micro-batching queue: coalesce concurrent requests into one dispatch.

SURVEY.md SS7 step 5 names this as serving hardening the reference lacks
(its model is called strictly once per request, `app/main.py:72`). Under
concurrent load, per-request dispatch leaves the chip idle between small
kernels; here requests that arrive within a short window ride a single
vmapped program (``InferenceEngine.predict_group``) — identical per-request
responses, up to GROUP_SLOT_BUCKETS[-1]x fewer dispatches.

Policy: only small requests (<= GROUP_ROW_BUCKET rows) coalesce — large
ones already fill the MXU alone and go straight through. The window closes
early the moment a full group is waiting, so the added latency under load
is ~0 (the group fills faster than the window) and at idle is bounded by
``window_ms`` (default 1 ms, well inside the 5 ms p50 budget).

Two admission modes (ISSUE 17, ``serve.batch_mode``): the legacy
"windowed" wave holds every group open for the fixed window first;
"continuous" (default) admits pending requests into in-flight group slots
at dispatch boundaries — the in-flight round trip is itself the
coalescing window (paid for free), and only an empty pipe waits, for a
deadline derived from the measured dispatch time instead of a guess. See
``MicroBatcher.__init__`` and docs/performance.md "Continuous
micro-batching".
"""

from __future__ import annotations

import asyncio
from typing import Any

from mlops_tpu.serve.engine import InferenceEngine

# The coalescing policy constants come from the jax-free wire-contract
# module shared with the multi-worker plane: the shared-memory ring
# service (serve/ipc.py RingService) applies the SAME small-request
# grouping rule engine-side, so one process or N, identical requests
# ride identical compiled shapes.
from mlops_tpu.serve.wire import (
    GROUP_ROW_BUCKET,
    GROUP_SLOT_BUCKETS,
    DeadlineExceeded,
)

# Declared order for the two-phase rings, OUTERMOST FIRST (tpulint Layer 3
# manifest — analysis/concurrency.py / lockcheck.py): the fetch ring is
# only ever claimed while a dispatch slot is held (`_dispatch` claims it
# BEFORE releasing the slot — round-5 review: released-first let a lagging
# fetch path pile un-purgeable handles at the ring). The reverse nesting
# would deadlock once both rings sit at capacity. The `_inflight`
# acquire/release pair legitimately spans `_drain` -> `_dispatch` (the slot
# outlives the method that claimed it), which the static pairing rule
# (TPU404) cannot follow lexically — declared below so the split is intent,
# not an accident of `_drain`'s error-path release; the seeded stress tests
# in tests/test_batcher.py exercise the pairing at runtime.
TPULINT_LOCK_ORDER = {"MicroBatcher": ("_inflight", "_fetch_ring")}
TPULINT_CROSS_METHOD_SEMAPHORES = {"MicroBatcher": ("_inflight",)}


class MicroBatcher:
    """Single drain-loop + overlapped dispatches: one background task owns
    the queue and no task is ever cancelled (a cancel racing a
    mid-dispatch flush would strand futures). The loop waits out the
    window, claims up to ``max_group`` requests, and fires the dispatch as
    its own task WITHOUT awaiting it — a dispatch is wall-clocked by a
    device round trip, and round trips from separate threads overlap, so
    serial dispatches would cap throughput at one group per round trip.
    ``max_inflight`` bounds the overlap (it must not exceed the engine
    thread pool, or dispatches would queue inside the executor anyway).

    With the packed two-phase engine API (dispatch_group / fetch_group)
    each dispatch task additionally splits into a dispatch phase (encode +
    device enqueue + async D2H copy start, under the inflight bound) and a
    fetch phase (the blocking host-copy wait, under the fetch ring) — the
    drain loop dispatches group N+1 while group N's bytes land."""

    def __init__(
        self,
        engine: InferenceEngine,
        executor,
        window_ms: float = 1.0,
        max_group: int = GROUP_SLOT_BUCKETS[-1],
        max_inflight: int = 4,
        fetch_inflight: int | None = None,
        batch_mode: str = "continuous",
        admit_fraction: float = 0.5,
        wire_responses: bool = False,
    ):
        if batch_mode not in ("continuous", "windowed"):
            raise ValueError(
                f"batch_mode must be 'continuous' or 'windowed', "
                f"got {batch_mode!r}"
            )
        self.engine = engine
        self._executor = executor
        self.window_s = window_ms / 1e3
        # A group can never exceed the largest warmed slot bucket — beyond
        # it predict_group would have no compiled shape to run.
        self.max_group = min(max_group, GROUP_SLOT_BUCKETS[-1])
        # Admission policy (ISSUE 17). "windowed" (the legacy wave): every
        # group holds its window open for the full window_s before
        # claiming. "continuous": admission happens at DISPATCH
        # BOUNDARIES — the drain loop claims the in-flight slot first,
        # then admits whatever is pending. While other dispatches are in
        # flight the admit wait is ZERO (their device round trips already
        # gave co-travelers time to accumulate — that accumulation IS the
        # window, paid for free); only an empty pipe waits, and then for
        # ``admit_fraction`` of the EWMA-measured dispatch-stage seconds
        # (the span stage a lone request waits in longest: the blocking
        # fetch), capped by window_s. Group
        # geometry never changes per-request math, so responses are
        # bit-identical across modes at any load.
        self.batch_mode = batch_mode
        self.admit_fraction = admit_fraction
        self._dispatch_ewma_s = 0.0  # EWMA of measured dispatch-phase
        # seconds (event-loop confined: updated by _dispatch tasks, read
        # by _drain — both on the loop thread, never the executor)
        # (records, future, absolute loop-clock deadline or None,
        #  tracewire span or None, routed tier name or None)
        self._pending: list[
            tuple[list[dict], asyncio.Future, float | None, Any, str | None]
        ] = []
        self._drain_task: asyncio.Task | None = None
        self._full = asyncio.Event()  # set when a full group is waiting
        self._inflight = asyncio.Semaphore(max_inflight)
        # Fetch ring: engines exposing the two-phase dispatch_group /
        # fetch_group API (serve/engine.py) release their DISPATCH slot as
        # soon as the device work + async D2H copy are in flight, then
        # complete the blocking fetch under this SECOND bound — so the
        # drain loop claims and dispatches the next group while the
        # previous group's host copy lands. The two bounds together can
        # occupy dispatch + fetch executor threads at once; callers that
        # share the executor with other work (the server's solo fast path,
        # /metrics monitor fetches) size ``fetch_inflight`` so the sum
        # leaves headroom (serve/server.py) — default: max_inflight.
        self._fetch_ring = asyncio.Semaphore(
            max_inflight if fetch_inflight is None else max(1, fetch_inflight)
        )
        self._dispatch_tasks: set[asyncio.Task] = set()  # strong refs
        self._last_enqueue = float("-inf")  # loop-clock time of the most
        # recent coalescable arrival (idle fast-path bookkeeping)
        self._solo_inflight = 0  # fast-path calls currently in the
        # executor: they must count against the idle condition, or a
        # stalled engine would accumulate unbounded un-cancellable
        # executor work outside the batcher's claim-time purge
        # Wire mode (encode-residue fix): prefer the engine's *_wire
        # fetches — responses come back as pre-encoded json bytes built in
        # the EXECUTOR thread, so the event loop never pays the
        # per-response `json.dumps` (~7% of loop time at c128, profiled).
        # getattr fallbacks keep stub/sklearn engines on the dict path.
        self.wire_responses = bool(wire_responses)
        self._predict_solo = (
            getattr(engine, "predict_records_wire", None)
            if wire_responses
            else None
        ) or engine.predict_records

    @property
    def enabled(self) -> bool:
        return self.engine.supports_grouping and self.window_s > 0

    async def predict(
        self,
        records: list[dict[str, Any]],
        deadline: float | None = None,
        span: Any = None,
        tier: str | None = None,
    ) -> dict[str, Any] | bytes:
        """Entry point for the request handler. ``deadline`` (absolute
        loop-clock time, from the request's ``x-request-deadline-ms``
        budget) rides with the queued entry: the drain loop's claim-time
        purge completes an already-expired entry with
        ``DeadlineExceeded`` INSTEAD of dispatching it — dead work is
        shed engine-side, before it costs a device dispatch, not just
        abandoned by the waiting handler. ``span`` (tracewire) rides the
        same way and gets the queue/dispatch/fetch stage stamps; None
        (the default, tracing disarmed) costs one branch per path.
        ``tier`` (ISSUE 19 SLO routing, resolved upstream by
        `engine.route_tier`) rides the entry too: a group is ONE compiled
        program, so the drain loop only coalesces same-tier entries and
        the dispatch carries the tier down to the engine. None (the
        default and the single-tier fast path) is the engine's default
        tier — stub engines without the keyword never see it."""
        loop = asyncio.get_running_loop()
        if (
            not self.enabled
            or not (1 <= len(records) <= GROUP_ROW_BUCKET)
        ):
            if span is None and tier is None:
                return await loop.run_in_executor(
                    self._executor, self._predict_solo, records
                )
            # Span/tier threading needs the keyword form; stub engines
            # (tests, sklearn shims) only see it with tracing armed or
            # tier routing on.
            return await loop.run_in_executor(
                self._executor,
                lambda: self._predict_solo(records, span=span, tier=tier)
                if tier is not None
                else self._predict_solo(records, span=span),
            )

        # Idle fast-path: a request arriving with nothing queued, nothing
        # in flight (grouped OR solo), and no arrival within the last
        # window has no co-travelers to wait for — holding it the full
        # window would buy zero coalescing and cost the whole window in
        # p50 (measured: the 1 ms default tripled sequential-client
        # latency). Sustained load arrives within the window of the
        # previous request and still coalesces; a stalled solo call
        # (counter > 0) pushes new arrivals back onto the batcher, whose
        # claim-time purge and max_inflight bound the backlog.
        now = loop.time()
        idle = (
            not self._pending
            and not self._dispatch_tasks
            and self._solo_inflight == 0
            and (now - self._last_enqueue) > self.window_s
        )
        self._last_enqueue = now
        if idle:
            # The decrement is tied to EXECUTOR completion, not caller
            # exit: a deadline-cancelled caller leaves the engine call
            # occupying its thread, and decrementing early would re-open
            # the fast-path for the next victim — re-creating the
            # unbounded-dead-backlog failure the counter exists to stop.
            self._solo_inflight += 1
            if span is None and tier is None:
                fut = loop.run_in_executor(
                    self._executor, self._predict_solo, records
                )
            else:
                fut = loop.run_in_executor(
                    self._executor,
                    lambda: self._predict_solo(records, span=span, tier=tier)
                    if tier is not None
                    else self._predict_solo(records, span=span),
                )

            def _done(f: asyncio.Future) -> None:
                self._solo_inflight -= 1
                if not f.cancelled():
                    f.exception()  # retrieve, or the loop logs a warning
                    # when the deadline-cancelled caller never awaits it

            fut.add_done_callback(_done)
            # shield: a deadline-cancelled caller must not cancel the
            # wrapper future (that would fire _done at cancel time while
            # the thread still runs — the early decrement again).
            return await asyncio.shield(fut)

        future: asyncio.Future = loop.create_future()
        self._pending.append((records, future, deadline, span, tier))
        if len(self._pending) >= self.max_group:
            self._full.set()  # close the window early
        if self._drain_task is None or self._drain_task.done():
            self._drain_task = asyncio.create_task(self._drain())
        return await future

    def _admit_deadline_s(self) -> float:
        """Continuous mode's empty-pipe admit wait. 0 while dispatches are
        in flight (the dispatch boundary IS the admission point — arrivals
        during the in-flight round trip coalesced for free); otherwise a
        fraction of the measured dispatch time, capped by the configured
        window (cold start, before any measurement, waits the full cap)."""
        if self._dispatch_tasks:
            return 0.0
        if self._dispatch_ewma_s <= 0.0:
            return self.window_s
        return min(self.window_s, self.admit_fraction * self._dispatch_ewma_s)

    async def _drain(self) -> None:
        continuous = self.batch_mode == "continuous"
        while self._pending:
            if continuous:
                # Admission at the dispatch boundary: claim the in-flight
                # slot FIRST (the declared _inflight -> _fetch_ring order
                # is unchanged — the wait below holds no other lock), then
                # give an empty pipe a short, measured co-traveler wait.
                await self._inflight.acquire()
                admit = self._admit_deadline_s()
                if admit > 0 and len(self._pending) < self.max_group:
                    self._full.clear()
                    try:
                        await asyncio.wait_for(self._full.wait(), admit)
                    except asyncio.TimeoutError:
                        pass
            else:
                if len(self._pending) < self.max_group:
                    # Hold the window open for co-travelers; a full group
                    # (or anything setting _full) closes it early.
                    self._full.clear()
                    try:
                        await asyncio.wait_for(
                            self._full.wait(), self.window_s
                        )
                    except asyncio.TimeoutError:
                        pass
                # Claim a group, then block only on the in-flight bound —
                # NOT on the dispatch itself, so up to max_inflight groups
                # ride overlapping device round trips.
                await self._inflight.acquire()
            # Claim-time purge, two kinds of dead entry: ABANDONED ones
            # (the server's request deadline cancelled the caller's
            # future, e.g. during a device stall) are dropped — without
            # this, a long stall with ongoing traffic grows _pending
            # unboundedly and a recovering device would burn through a
            # dead backlog before serving live requests. EXPIRED ones
            # (deadline budget spent waiting in this queue) are completed
            # with DeadlineExceeded so the handler answers 504 NOW and
            # the entry never costs a dispatch — the engine-side
            # dead-work shed.
            now = asyncio.get_running_loop().time()
            live = []
            for entry in self._pending:
                _, future, entry_deadline, _, _ = entry
                if future.done():
                    continue
                if entry_deadline is not None and now >= entry_deadline:
                    future.set_exception(DeadlineExceeded())
                    continue
                live.append(entry)
            self._pending = live
            if not self._pending:
                self._inflight.release()
                continue
            # Same-tier claim (ISSUE 19): a group rides ONE compiled
            # program, so a mixed-tier queue splits into per-tier
            # dispatches — take the head entry's tier and every queued
            # co-traveler on it (FIFO within the tier); other tiers stay
            # queued and dispatch on the next loop iteration.
            head_tier = self._pending[0][4]
            batch: list = []
            rest: list = []
            for entry in self._pending:
                if len(batch) < self.max_group and entry[4] == head_tier:
                    batch.append(entry)
                else:
                    rest.append(entry)
            self._pending = rest
            task = asyncio.create_task(self._dispatch(batch, head_tier))
            self._dispatch_tasks.add(task)
            task.add_done_callback(self._dispatch_tasks.discard)
        # Exit with an empty queue: predict() observes the done() task and
        # spawns a fresh drain for the next arrival (no lost wakeups — both
        # run on the event loop and the final emptiness check returns
        # without awaiting). In-flight dispatch tasks complete on their
        # own; their futures don't need the drain loop.

    def _observe_dispatch_s(self, seconds: float) -> None:
        """Fold one measured dispatch-phase duration into the EWMA the
        continuous admit deadline reads (event-loop confined, like every
        other mutable batcher field)."""
        if self._dispatch_ewma_s <= 0.0:
            self._dispatch_ewma_s = seconds
        else:
            self._dispatch_ewma_s = (
                0.8 * self._dispatch_ewma_s + 0.2 * seconds
            )

    async def _dispatch(
        self,
        batch: list[
            tuple[list[dict], asyncio.Future, float | None, Any, str | None]
        ],
        tier: str | None = None,
    ) -> None:
        loop = asyncio.get_running_loop()
        requests = [records for records, _, _, _, _ in batch]
        spans = [span for _, _, _, span, _ in batch]
        if any(span is not None for span in spans):
            # Queue stage ends at claim: the window wait + any
            # inflight-bound wait the entry paid before this task ran.
            for span in spans:
                if span is not None:
                    span.stamp("queue")
        # Two-phase path when the engine supports it: dispatch (encode +
        # device enqueue + async D2H start) holds the inflight slot, the
        # blocking fetch rides the fetch ring — overlapping the next
        # group's dispatch with this group's host copy. The handle is
        # local to this task, so responses can never cross-wire between
        # overlapped groups (each task owns exactly its batch's futures).
        dispatch = getattr(self.engine, "dispatch_group", None)
        fetch = (
            getattr(self.engine, "fetch_group_wire", None)
            if self.wire_responses
            else None
        ) or getattr(self.engine, "fetch_group", None)
        released = False
        t_dispatch = loop.time()
        try:
            if dispatch is None or fetch is None:
                responses = await loop.run_in_executor(
                    self._executor,
                    (lambda: self.engine.predict_group(requests, tier=tier))
                    if tier is not None
                    else (lambda: self.engine.predict_group(requests)),
                )
                # One-phase engines: the whole call is the best available
                # dispatch-time proxy for the continuous admit deadline.
                self._observe_dispatch_s(loop.time() - t_dispatch)
            else:
                handle = await loop.run_in_executor(
                    self._executor,
                    (lambda: dispatch(requests, tier=tier))
                    if tier is not None
                    else (lambda: dispatch(requests)),
                )
                self._observe_dispatch_s(loop.time() - t_dispatch)
                for span in spans:
                    if span is not None:
                        # Encode rides inside dispatch_group on this plane
                        # (the engine's flat-encode optimization), so the
                        # dispatch stage covers encode + device enqueue.
                        span.stamp("dispatch")
                        span.entry = getattr(handle, "entry", None)
                # Claim the fetch ring BEFORE releasing the dispatch slot:
                # released first, a lagging fetch path would let the drain
                # loop keep dispatching while handles (each pinning live
                # device buffers) pile up un-purgeably at the ring — this
                # order hard-bounds dispatched-but-unfetched groups at
                # max_inflight + fetch_inflight. No deadlock: ring permits
                # free on fetch completion, which never needs a dispatch
                # slot.
                async with self._fetch_ring:
                    self._inflight.release()
                    released = True
                    responses = await loop.run_in_executor(
                        self._executor, fetch, handle
                    )
                for span in spans:
                    if span is not None:
                        span.stamp("device_fetch")
        # Not swallowed: whatever the dispatch raised (device error,
        # encode bug) is re-routed onto every waiter's future, where the
        # request handler surfaces it as a 500.
        except Exception as err:  # tpulint: disable=TPU201
            for _, future, _, _, _ in batch:
                if not future.done():
                    future.set_exception(err)
        else:
            for (_, future, _, _, _), response in zip(batch, responses):
                if not future.done():
                    future.set_result(response)
        finally:
            if not released:
                self._inflight.release()

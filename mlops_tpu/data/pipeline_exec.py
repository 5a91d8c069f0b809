"""Pipelined streaming executor: overlap host I/O, encode, device compute,
and output across chunks.

The bulk paths (`data/stream.py score_csv_stream` / `fit_streaming`,
`parallel/bulk.py score_dataset`) are chunked loops whose per-chunk work
decomposes into independent stages — read+parse, vectorized encode,
host->device transfer, device compute, result fetch, output write. Run
serially, the chip idles during host work and the host idles during
compute; "ML Productivity Goodput" (arXiv 2502.06982) identifies exactly
this input-pipeline stall as the dominant accelerator fleet-efficiency
loss. This module is the shared fix: a bounded-queue software pipeline
that keeps every stage busy on a different chunk at once.

Execution model
---------------
``run_pipeline(source, stages, sink, depth)`` wires

    source ──q──> stage 1 ──q──> ... ──q──> stage S ──q──> sink

with one thread per producer stage (the source iterator pumps on its own
thread; each ``Stage.fn`` runs on its own thread; the ``sink`` runs on
the CALLER's thread). Every link is a ``queue.Queue(maxsize=depth)``:

- **Backpressure / memory model**: a stage that races ahead blocks on its
  full output queue, so peak in-flight work is bounded at
  ``(S + 1) * depth`` queued items (per-stage ``queue_depth`` overrides
  included) plus one in-hand item per stage — a fixed small number of
  chunks regardless of dataset size.
- **Ordering**: single-threaded stages + FIFO queues preserve chunk
  order end to end, so a deterministic stage graph produces BIT-IDENTICAL
  output at any depth. ``depth <= 1`` short-circuits to a plain serial
  loop on the caller thread — exactly the pre-pipeline behavior.
- **Double buffering** falls out of the structure: with a transfer stage
  ahead of the compute stage, chunk N+1's ``jax.device_put`` runs while
  chunk N computes, and a fetch stage behind it pulls chunk N-1's results
  during chunk N's dispatch.
- **Batch stages** (``Stage(batch_max=k)``): the worker gathers whatever
  is immediately available (1..k items) and passes the LIST to ``fn``,
  which must return one output per input. Grouping varies with timing, so
  ``fn`` must be grouping-invariant (e.g. a batched ``jax.device_get``
  that amortizes transport round trips without changing per-item values).
- **Failure semantics**: an exception in ANY stage (or the source, or the
  sink) stops the pipeline promptly and cleanly — the failing worker
  forwards a failure marker downstream and keeps draining its input so no
  producer is ever left blocked on a full queue; upstream workers see the
  stop flag and discard. The caller joins every thread, then re-raises
  the ORIGINAL exception. No hung threads, no half-consumed queues.

Per-stage wall/occupancy timing (`utils/timing.py StageClock`) comes back
in the returned ``PipelineStats`` so overlap wins are measured, not
asserted: occupancies sum to ~1.0 when serial and exceed it when
overlapped, and the largest occupancy names the bottleneck stage. A
worker that is not busy is blocked on its input queue (nothing to do:
upstream is the pace) or on its output queue (downstream is): every
``get`` and ``put`` of a worker is timed and summed per stage and side
(``wait_in_s``, ``wait_out_s``), with the longest wait of each side and
the ordinal of the stage execution it belongs to, so a stall is located
by stage and chunk. The serial mode has no queues and reports zero waits.

Concurrency discipline (tpulint Layer 3): this executor deliberately owns
NO explicit locks — all cross-thread state rides the bounded
``queue.Queue`` links (internally locked) plus one ``threading.Event``
stop flag, so there is no order to violate and nothing for
blocking-under-lock to flag. The schedule-dependent invariants (FIFO
bit-identical outputs, clean failure drain) are exercised under seeded
schedule perturbation instead (`analysis/lockcheck.py SchedulePerturber`,
tests/test_pipeline_exec.py) — keep new shared state on the queues, not
on ad-hoc locks.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Iterable

from mlops_tpu.utils.timing import StageClock

# How long the caller waits for workers to drain after the last sentinel
# before declaring the executor wedged. Generous: drain is bounded by the
# in-flight item count, not the dataset.
_JOIN_TIMEOUT_S = 60.0


@dataclasses.dataclass(frozen=True)
class Stage:
    """One pipeline stage: ``fn(item) -> item`` on its own worker thread.

    ``batch_max > 1`` switches ``fn`` to list-in/list-out over whatever
    items are immediately available (at most ``batch_max``); results must
    not depend on the grouping (see module docstring).

    ``queue_depth`` overrides the bound of this stage's INPUT queue
    (default: the pipeline's ``depth``). A batched fetch stage uses it to
    keep a deep async-dispatch window — its producer can run that many
    chunks ahead — without deepening every other queue in the pipeline.
    """

    name: str
    fn: Callable[[Any], Any]
    batch_max: int = 1
    queue_depth: int | None = None


@dataclasses.dataclass
class PipelineStats:
    """Timing evidence for one pipeline run."""

    depth: int
    wall_s: float
    items: int  # items the sink consumed
    stages: dict[str, dict[str, float]]  # name -> busy_s / items /
    # occupancy / waits by side (`utils/timing.py StageClock.report`)


class _Failure:
    """A stage's exception, traveling the queues in place of an item."""

    __slots__ = ("stage", "exc")

    def __init__(self, stage: str, exc: BaseException):
        self.stage = stage
        self.exc = exc


_DONE = object()  # end-of-stream sentinel; exactly one per producer


def _get(inq, clock, name):
    """``inq.get()``, its blocked seconds into the stage's ``wait_in_s``."""
    start = time.perf_counter()
    item = inq.get()
    clock.waited(name, "in", time.perf_counter() - start)
    return item


def _put(outq, item, clock, name) -> None:
    """``outq.put(item)``, its blocked seconds into the stage's
    ``wait_out_s``."""
    start = time.perf_counter()
    outq.put(item)
    clock.waited(name, "out", time.perf_counter() - start)


def run_pipeline(
    source: Iterable[Any],
    stages: list[Stage],
    sink: Callable[[Any], None],
    depth: int = 4,
    source_name: str = "read",
    sink_name: str = "write",
    span_attrs: dict | None = None,
) -> PipelineStats:
    """Stream ``source`` through ``stages`` into ``sink`` (see module
    docstring for the execution model). Returns per-stage timing stats;
    re-raises the original exception if any stage fails. With
    ``span_attrs`` every stage execution is also a ``mlops:pipe.<stage>``
    span in a profiler trace, carrying them (`utils/timing.py
    StageClock`)."""
    depth = max(1, int(depth))
    clock = StageClock(span_attrs)
    start = time.perf_counter()
    if depth <= 1:
        items = _run_serial(source, stages, sink, clock, source_name, sink_name)
    else:
        items = _run_threaded(
            source, stages, sink, depth, clock, source_name, sink_name
        )
    wall = time.perf_counter() - start
    return PipelineStats(
        depth=depth, wall_s=wall, items=items, stages=clock.report(wall)
    )


def _run_serial(source, stages, sink, clock, source_name, sink_name) -> int:
    """depth<=1: the exact pre-pipeline serial loop, instrumented."""
    iterator = iter(source)
    count = 0
    while True:
        with clock.stage(source_name):
            item = next(iterator, _DONE)
        if item is _DONE:
            break
        for stage in stages:
            with clock.stage(stage.name):
                if stage.batch_max > 1:
                    item = stage.fn([item])[0]
                else:
                    item = stage.fn(item)
        with clock.stage(sink_name):
            sink(item)
        count += 1
    return count


def _run_threaded(
    source, stages, sink, depth, clock, source_name, sink_name
) -> int:
    stop = threading.Event()
    links = [
        queue.Queue(maxsize=stage.queue_depth or depth) for stage in stages
    ] + [queue.Queue(maxsize=depth)]

    threads = [
        threading.Thread(
            target=_pump_source,
            args=(source, links[0], stop, clock, source_name),
            name=f"pipeline-{source_name}",
            daemon=True,
        )
    ]
    for i, stage in enumerate(stages):
        threads.append(
            threading.Thread(
                target=_run_stage,
                args=(stage, links[i], links[i + 1], stop, clock),
                name=f"pipeline-{stage.name}",
                daemon=True,
            )
        )
    for t in threads:
        t.start()

    failures: list[_Failure] = []
    count = 0
    final = links[-1]
    try:
        # The sink loop consumes to _DONE UNCONDITIONALLY — even after a
        # failure — so upstream workers can always finish their drain.
        while True:
            item = _get(final, clock, sink_name)
            if item is _DONE:
                break
            if isinstance(item, _Failure):
                stop.set()
                failures.append(item)
                continue
            if failures or stop.is_set():
                continue  # draining after a sink-side failure
            try:
                with clock.stage(sink_name):
                    sink(item)
                count += 1
            # Captured, forwarded, and re-raised after the drain —
            # nothing is swallowed.  # tpulint: disable=TPU201
            except BaseException as exc:
                stop.set()
                failures.append(_Failure(sink_name, exc))
    finally:
        for t in threads:
            t.join(timeout=_JOIN_TIMEOUT_S)
        wedged = [t.name for t in threads if t.is_alive()]
        if wedged:
            # Executor invariant broken (a worker failed to drain). Never
            # silently returns with live threads.
            raise RuntimeError(
                f"pipeline workers failed to drain: {wedged}"
            ) from (failures[0].exc if failures else None)
    if failures:
        raise failures[0].exc
    return count


def _pump_source(source, out, stop, clock, name) -> None:
    try:
        iterator = iter(source)
        while not stop.is_set():
            with clock.stage(name):
                item = next(iterator, _DONE)
            if item is _DONE:
                break
            _put(out, item, clock, name)
    # Captured as a _Failure and re-raised by the caller.  # tpulint: disable=TPU201
    except BaseException as exc:
        stop.set()
        _put(out, _Failure(name, exc), clock, name)
    finally:
        _put(out, _DONE, clock, name)


def _run_stage(stage: Stage, inq, outq, stop, clock) -> None:
    draining = False
    try:
        while True:
            item = _get(inq, clock, stage.name)
            if item is _DONE:
                break
            if isinstance(item, _Failure):
                stop.set()
                _put(outq, item, clock, stage.name)
                draining = True
                continue
            if draining or stop.is_set():
                continue
            try:
                if stage.batch_max > 1:
                    if _run_batch(stage, item, inq, outq, stop, clock):
                        break
                else:
                    with clock.stage(stage.name):
                        out = stage.fn(item)
                    _put(outq, out, clock, stage.name)
            # Captured as a _Failure and re-raised by the caller.  # tpulint: disable=TPU201
            except BaseException as exc:
                stop.set()
                _put(outq, _Failure(stage.name, exc), clock, stage.name)
                draining = True
    finally:
        _put(outq, _DONE, clock, stage.name)


def _run_batch(stage: Stage, first, inq, outq, stop, clock) -> bool:
    """Gather up to ``batch_max`` immediately-available items, run ``fn``
    over the list, forward each result. Handles its OWN fn failure — the
    gather may have swallowed the _DONE sentinel, and an exception escaping
    past that fact would leave the worker blocked on an empty queue.
    Returns True when _DONE was swallowed (the stage must exit)."""
    batch = [first]
    saw_done = False
    pending: _Failure | None = None
    while len(batch) < stage.batch_max:
        try:
            extra = inq.get_nowait()
        except queue.Empty:
            break
        if extra is _DONE:
            saw_done = True
            break
        if isinstance(extra, _Failure):
            pending = extra
            break
        batch.append(extra)
    try:
        with clock.stage(stage.name, items=len(batch)):
            outs = stage.fn(batch)
    # Captured as a _Failure and re-raised by the caller.  # tpulint: disable=TPU201
    except BaseException as exc:
        stop.set()
        _put(outq, _Failure(stage.name, exc), clock, stage.name)
        outs = []
    for out in outs:
        _put(outq, out, clock, stage.name)
    if pending is not None:
        stop.set()
        _put(outq, pending, clock, stage.name)
        # Keep draining on the normal loop; the failure is already forwarded.
    return saw_done

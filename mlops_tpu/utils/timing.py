"""Wall-clock timing helpers (used by serving metrics, the pipelined
streaming executor and the bulk job's record)."""

from __future__ import annotations

import contextlib
import gc
import math
import threading
import time

# tpulint Layer-3 manifest: two leaf locks, never held together. A clock's
# guards its sums; the module's guards the one registration of the garbage
# collector's callback.
TPULINT_LOCK_ORDER = {"StageClock": ("_lock",), "<module>": ("_INSTALL_LOCK",)}


# what a clock keeps the longest single instance of, per stage
_LONGEST = ("busy", "wait_in", "wait_out")


def sums_delta(before: dict, after: dict) -> dict:
    """``after - before`` key by key, of two snapshots of a counter's sums
    (seconds as floats, rounded to the microsecond; counts as they are)."""
    return {
        key: (
            round(value - before[key], 6)
            if isinstance(value, float)
            else value - before[key]
        )
        for key, value in after.items()
    }


class StageClock:
    """Per-stage busy-time and queue-wait accumulator for pipelined
    executors (`data/pipeline_exec.py`).

    Each worker wraps its unit of work in ``with clock.stage(name): ...``
    and reports how long it was blocked on its input or output queue with
    ``clock.waited(name, side, seconds)``. ``report(wall_s)`` returns per
    stage ``busy_s``, ``items``, ``occupancy`` (the fraction of the
    pipeline's wall clock the stage spent busy), ``wait_in_s`` and
    ``wait_out_s`` (seconds blocked on ``inq.get()``: upstream is the pace;
    on ``outq.put()``: downstream is), and the longest single execution and
    wait of each side as ``max_busy_s``, ``max_wait_in_s``,
    ``max_wait_out_s``, each with ``..._at``: the ordinal (from 0) of the
    stage execution it belongs to, a wait for input to the execution that
    followed it, a wait for output to the one whose result was handed on
    (``None`` where the stage never waited on that side: a source has no
    input queue, a sink no output queue, the serial mode neither).
    Occupancies are the overlap evidence: in a serial run they sum to
    ~1.0; in an overlapped run the sum exceeds 1.0 and the largest single
    occupancy names the bottleneck stage. A stage's ``busy_s + wait_in_s
    + wait_out_s`` is its thread's whole time but for the loop's own few
    statements.

    Thread-safe: each stage runs on its own thread, and the executor's
    serial mode shares one clock across all stages on the caller thread.

    ``span_attrs`` (optional): with them, every stage execution also runs
    under a ``jax.profiler.TraceAnnotation`` named ``mlops:pipe.<stage>``
    carrying ``items`` and these attributes (`parallel/bulk.py` passes
    its ``job``), on the stage's own thread. With no profiler session open
    the annotation is inert (one flag test); in a traced run the stage
    lands on the device operations' clock, and the gaps between one
    thread's spans are its waits. Without ``span_attrs`` the clock imports
    nothing: jax-free callers pass none.
    """

    def __init__(self, span_attrs: dict | None = None) -> None:
        self._lock = threading.Lock()
        self._stages: dict[str, dict] = {}
        self._span_attrs = span_attrs
        if span_attrs is not None:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation

    def _span(self, name: str, items: int):
        if self._span_attrs is None:
            return contextlib.nullcontext()
        return self._annotation(
            f"mlops:pipe.{name}", items=items, **self._span_attrs
        )

    def _stage(self, name: str) -> dict:
        """The stage's sums (the caller holds the lock)."""
        sums = self._stages.get(name)
        if sums is None:
            sums = self._stages[name] = {
                "busy_s": 0.0, "items": 0, "executions": 0,
                "wait_in_s": 0.0, "wait_out_s": 0.0,
                **{f"max_{kind}_s": 0.0 for kind in _LONGEST},
                **{f"max_{kind}_at": None for kind in _LONGEST},
            }
        return sums

    @staticmethod
    def _longest(sums: dict, kind: str, seconds: float, at: int) -> None:
        if seconds > sums[f"max_{kind}_s"]:
            sums[f"max_{kind}_s"], sums[f"max_{kind}_at"] = seconds, at

    @contextlib.contextmanager
    def stage(self, name: str, items: int = 1):
        with self._span(name, items):
            start = time.perf_counter()
            try:
                yield
            finally:
                elapsed = time.perf_counter() - start
                with self._lock:
                    sums = self._stage(name)
                    self._longest(sums, "busy", elapsed, sums["executions"])
                    sums["busy_s"] += elapsed
                    sums["items"] += items
                    sums["executions"] += 1

    def waited(self, name: str, side: str, seconds: float) -> None:
        """``seconds`` blocked on the stage's input (``side`` "in") or
        output ("out") queue."""
        with self._lock:
            sums = self._stage(name)
            done = sums["executions"]
            self._longest(
                sums, f"wait_{side}", seconds, done if side == "in" else done - 1
            )
            sums[f"wait_{side}_s"] += seconds

    def report(self, wall_s: float) -> dict[str, dict[str, float]]:
        with self._lock:
            return {
                name: {
                    "busy_s": round(sums["busy_s"], 4),
                    "items": sums["items"],
                    "occupancy": (
                        round(sums["busy_s"] / wall_s, 4) if wall_s > 0 else 0.0
                    ),
                    "wait_in_s": round(sums["wait_in_s"], 6),
                    "wait_out_s": round(sums["wait_out_s"], 6),
                    **{
                        f"max_{kind}_s": round(sums[f"max_{kind}_s"], 6)
                        for kind in _LONGEST
                    },
                    **{f"max_{kind}_at": sums[f"max_{kind}_at"] for kind in _LONGEST},
                }
                for name, sums in self._stages.items()
            }


class PauseCounter:
    """Seconds the process stood still for the garbage collector, by the
    collector's own callback (``gc.callbacks``: it runs on whichever thread
    triggered the collection, with the interpreter lock held, so every
    Python thread waits). A caller that wants one piece of work's share
    takes ``snapshot()`` before and after it and reads ``delta``:
    `parallel/bulk.py score_dataset` does, so a job whose stages all stopped
    at once can tell a collection from a wait on the device.

    No lock: the interpreter runs one collection at a time and the callback
    under the interpreter lock, and a lock taken here could be asked for by
    a collection that starts on the thread that already holds it."""

    def __init__(self) -> None:
        self._started: float | None = None
        self._totals = {"gc_s": 0.0, "gc_collections": 0, "gc_gen2_s": 0.0}

    def _on_gc(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":  # collections do not nest
            self._started = now
        elif self._started is not None:
            seconds, self._started = now - self._started, None
            self._totals["gc_s"] += seconds
            self._totals["gc_collections"] += 1
            if info.get("generation") == 2:
                self._totals["gc_gen2_s"] += seconds

    def snapshot(self) -> dict:
        return dict(self._totals)

    delta = staticmethod(sums_delta)


_INSTALL_LOCK = threading.Lock()
_PAUSES: PauseCounter | None = None


def pause_counter() -> PauseCounter:
    """The process's one counter; the first call registers its callback
    (as `compilecache/events.py compile_counter` registers its listeners)."""
    global _PAUSES
    with _INSTALL_LOCK:
        if _PAUSES is None:
            _PAUSES = PauseCounter()
            gc.callbacks.append(_PAUSES._on_gc)
        return _PAUSES


def percentile(sorted_values: list[float], q: float) -> float:
    """Nearest-rank percentile of an ascending-sorted list."""
    if not sorted_values:
        return float("nan")
    n = len(sorted_values)
    rank = min(n - 1, max(0, math.ceil(q / 100.0 * n) - 1))
    return sorted_values[rank]

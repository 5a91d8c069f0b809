"""Wall-clock timing helpers (used by serving metrics, the pipelined
streaming executor, and bench)."""

from __future__ import annotations

import contextlib
import math
import threading
import time


class Timer:
    """Context-manager stopwatch: ``with Timer() as t: ...; t.ms``."""

    def __enter__(self) -> "Timer":
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._start
        self.ms = self.seconds * 1e3


class StageClock:
    """Per-stage busy-time accumulator for pipelined executors
    (`data/pipeline_exec.py`).

    Each worker wraps its unit of work in ``with clock.stage(name): ...``;
    ``report(wall_s)`` returns ``{stage: {busy_s, items, occupancy}}``
    where ``occupancy`` is the fraction of the pipeline's wall clock the
    stage spent busy. Occupancies are the overlap evidence: in a serial
    run they sum to ~1.0; in an overlapped run the sum exceeds 1.0 and
    the largest single occupancy names the bottleneck stage.

    Thread-safe: each stage runs on its own thread, and the executor's
    serial mode shares one clock across all stages on the caller thread.

    ``span_attrs`` (optional): with them, every stage execution also runs
    under a ``jax.profiler.TraceAnnotation`` named ``mlops:pipe.<stage>``
    carrying ``items`` and these attributes (`parallel/bulk.py` passes
    its ``job``), on the stage's own thread. With no profiler session open
    the annotation is inert (one flag test); in a traced run the stage
    lands on the device operations' clock. Without ``span_attrs`` the
    clock imports nothing: jax-free callers pass none.
    """

    def __init__(self, span_attrs: dict | None = None) -> None:
        self._lock = threading.Lock()
        self._busy: dict[str, float] = {}
        self._items: dict[str, int] = {}
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

    @contextlib.contextmanager
    def stage(self, name: str, items: int = 1):
        with self._span(name, items):
            start = time.perf_counter()
            try:
                yield
            finally:
                elapsed = time.perf_counter() - start
                with self._lock:
                    self._busy[name] = self._busy.get(name, 0.0) + elapsed
                    self._items[name] = self._items.get(name, 0) + items

    def report(self, wall_s: float) -> dict[str, dict[str, float]]:
        with self._lock:
            return {
                name: {
                    "busy_s": round(busy, 4),
                    "items": self._items[name],
                    "occupancy": (
                        round(busy / wall_s, 4) if wall_s > 0 else 0.0
                    ),
                }
                for name, busy in self._busy.items()
            }


def percentile(sorted_values: list[float], q: float) -> float:
    """Nearest-rank percentile of an ascending-sorted list."""
    if not sorted_values:
        return float("nan")
    n = len(sorted_values)
    rank = min(n - 1, max(0, math.ceil(q / 100.0 * n) - 1))
    return sorted_values[rank]

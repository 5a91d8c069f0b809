"""What the process traced, lowered, compiled and took from JAX's persistent
cache: `jax.monitoring` listeners, registered once, summed process-wide.

JAX reports each of these as it happens, and only then: the listeners fire
while something traces or compiles, never when a compiled program runs.
A caller that wants to know what ONE piece of work cost takes
``compile_counter().snapshot()`` before and after it and reads
``CompileCounter.delta``: `parallel/bulk.py score_dataset` does, so every
bulk job says what it re-traced (``compile_events`` of the job's record:
`BulkScoreResult`, ``job_log()``, and the ``mlops:bulk.compile_events``
marker in a profiler trace).

A function traced inside another one's trace (every ``jnp`` function is
its own ``jit``) reports a duration that its caller's duration already
holds. JAX announces the start of a trace too (a scalar of the same event
name), so the counter keeps a depth per thread and sums top-level traces
only; lowering and compiling are reported once per program by JAX itself.
JAX reads its persistent cache INSIDE the interval it reports as
``backend_compile_duration``: ``backend_compile_s`` holds
``cache_retrieval_s``, and trace + lower + backend compile is the whole.
"""

from __future__ import annotations

import threading

from mlops_tpu.utils.timing import sums_delta

# tpulint Layer-3 manifest: two leaf locks, never held together. The
# counter's lock guards the sums; the module's guards the one registration.
TPULINT_LOCK_ORDER = {"CompileCounter": ("_lock",), "<module>": ("_INSTALL_LOCK",)}

TRACE = "/jax/core/compile/jaxpr_trace_duration"
# event -> the key its seconds are summed under
DURATIONS = {
    TRACE: "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
}
COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
    # every compile for which JAX asked its persistent cache: requests less
    # hits is what the backend compiled. Misses will not do for that: JAX
    # counts one only where it STORES the executable, so a program under
    # the cache's minimum compile time, compiled anew in every process and
    # never stored, is neither a hit nor a miss.
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
}


class CompileCounter:
    """Sums of the events above since the listeners were registered.

    ``totals``: ``programs_traced`` (top-level traces), the seconds under
    each key of ``DURATIONS`` and the counts under each key of ``COUNTS``.
    ``programs``: per program name (JAX gives it with a trace), how often
    it was traced."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._depth = threading.local()
        self._totals: dict[str, float] = {
            "programs_traced": 0,
            **dict.fromkeys(DURATIONS.values(), 0.0),
            **dict.fromkeys(COUNTS.values(), 0),
        }
        self._programs: dict[str, int] = {}

    # ---------------------------------------------------------- listeners
    def _on_scalar(self, event: str, value, **kwargs) -> None:
        if event == TRACE:  # a trace starts on this thread
            self._depth.n = getattr(self._depth, "n", 0) + 1

    def _on_duration(self, event: str, seconds: float, **kwargs) -> None:
        key = DURATIONS.get(event)
        if key is None:
            return
        traced = event == TRACE
        if traced:
            # 0 where the listeners were registered inside a running trace
            self._depth.n = max(0, getattr(self._depth, "n", 0) - 1)
            if self._depth.n:
                return  # nested: the caller's duration holds it
        with self._lock:
            self._totals[key] += seconds
            if traced:
                name = str(kwargs.get("fun_name", ""))
                self._totals["programs_traced"] += 1
                self._programs[name] = self._programs.get(name, 0) + 1

    def _on_event(self, event: str, **kwargs) -> None:
        key = COUNTS.get(event)
        if key is not None:
            with self._lock:
                self._totals[key] += 1

    # ------------------------------------------------------------ readers
    def snapshot(self) -> dict:
        with self._lock:
            return {
                "totals": dict(self._totals),
                "programs": dict(self._programs),
            }

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        """What happened between two snapshots: the totals' differences,
        and the names of the programs traced in between."""
        out = sums_delta(before["totals"], after["totals"])
        out["programs"] = [
            name
            for name, traced in after["programs"].items()
            if traced > before["programs"].get(name, 0)
        ]
        return out


_INSTALL_LOCK = threading.Lock()
_COUNTER: CompileCounter | None = None


def compile_counter() -> CompileCounter:
    """The process's one counter; the first call registers its listeners
    (JAX keeps listeners for the life of the process, so there is one set,
    not one per caller)."""
    global _COUNTER
    with _INSTALL_LOCK:
        if _COUNTER is None:
            from jax import monitoring

            counter = CompileCounter()
            monitoring.register_scalar_listener(counter._on_scalar)
            monitoring.register_event_duration_secs_listener(counter._on_duration)
            monitoring.register_event_listener(counter._on_event)
            _COUNTER = counter
        return _COUNTER

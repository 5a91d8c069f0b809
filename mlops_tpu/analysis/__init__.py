"""tpulint — five-layer static analysis for the TPU hot paths.

The production path (train -> register -> serve -> monitor) only hits its
latency/goodput targets while the compiled hot paths STAY compiled: one
stray host sync inside a jitted function, or a dtype-driven recompile, and
the <5 ms p50 serving target silently dies without any test failing. This
package keeps the codebase honest on every PR:

- **Layer 1** (`astrules`): named AST rules over the package source — pure
  ``ast``, no JAX import, so it runs anywhere in milliseconds. Catches
  TPU-hostile patterns at the source level (host syncs under trace, Python
  RNG/clock under trace, tracer-dependent branches, jit signatures missing
  ``static_argnames``/``donate_argnums``, broad excepts, mutable defaults).
- **Layer 2** (`traces` + `entrypoints`): the framework's REGISTERED jitted
  entry points (train step, TP step, serve predict) are abstract-evaluated
  via ``jax.make_jaxpr`` on schema-derived dummy batches — no device code
  executes — and the resulting jaxprs are checked for recompile and
  numerics hazards (float64 leaks, weak-type outputs, convert_element_type
  round-trips, per-bucket shape polymorphism, producer/consumer sharding
  mismatches).
- **Layer 3** (`concurrency`): lock-discipline rules over the hand-rolled
  threading layer (serving engine, micro-batcher, pipeline executor,
  compile cache) — lock-order graph vs the declared ``TPULINT_LOCK_ORDER``
  manifest, guard inference for shared attributes, blocking calls under a
  held mutex, semaphore acquire/release pairing. Pure ``ast``, opt-in via
  ``analyze --concurrency`` (CI runs it). The RUNTIME half (`lockcheck`)
  swaps real locks for instrumented wrappers in tests: per-thread
  acquisition stacks asserted against the same declared order, lock-wait
  accounting (``total_wait_ms``), and seeded schedule perturbation.
- **Layer 4** (`contracts` + `seriesreg`): cross-process CONTRACT rules,
  analyzed project-wide rather than per file — shm ring fields checked
  against the declared writer-role manifest (``TPULINT_SHM_OWNERSHIP``),
  the Prometheus series surface extracted from both renderer planes and
  checked for parity, bounded labels, alert-rule references and docs
  coverage, config knobs that validate but are never read (the PR 13
  ``replica_affinity_slack`` class), and fault points without a fire
  site. Pure ``ast``, opt-in via ``analyze --contracts`` (CI runs it).
- **Layer 5** (`asyncdiscipline`): async/event-loop discipline over the
  serve plane, analyzed project-wide like Layer 4 — a call graph seeds
  event-loop confinement from ``async def`` bodies, loop-callback
  registrations, and the declared ``TPULINT_LOOP_CONFINED`` manifest,
  propagates it through sync helpers reachable only from confined
  contexts, then gates blocking calls on the loop (TPU601, sharing Layer
  3's blocking table via `blocking`), fire-and-forget tasks (TPU602),
  cross-thread writes to loop-confined state (TPU603), and ``await``
  under a sync mutex (TPU604). Pure ``ast``, opt-in via ``analyze
  --async`` (CI runs it). The RUNTIME half (`loopcheck`) wraps the
  running loop's callback execution in tests and production: per-callback
  wall time with attribution, a max-lag assert, and the
  ``mlops_tpu_event_loop_lag_ms`` gauge.

The suppression ledger stays honest via ``analyze --list-suppressions``
(every ``# tpulint: disable`` with live/stale status) and ``--fail-stale``
(stale ones gate as TPU400).

CLI: ``mlops-tpu analyze [--strict] [--concurrency] [--contracts]
[--async] [paths ...]``
(`analysis/cli.py`); CI runs it as a gate before pytest. Suppress a
finding inline with ``# tpulint: disable=TPU101`` (see
`docs/static-analysis.md`).
"""

from __future__ import annotations

from mlops_tpu.analysis.findings import Finding, Severity, format_findings
from mlops_tpu.analysis.astrules import RULES, analyze_paths, analyze_source
from mlops_tpu.analysis.concurrency import (
    CONCURRENCY_RULES,
    analyze_concurrency_paths,
    analyze_concurrency_source,
)
from mlops_tpu.analysis.contracts import (
    CONTRACT_RULES,
    analyze_contracts_paths,
    analyze_contracts_source,
)
from mlops_tpu.analysis.asyncdiscipline import (
    ASYNC_RULES,
    analyze_async_paths,
    analyze_async_project,
    analyze_async_source,
)

__all__ = [
    "ASYNC_RULES",
    "CONCURRENCY_RULES",
    "CONTRACT_RULES",
    "Finding",
    "RULES",
    "Severity",
    "analyze_async_paths",
    "analyze_async_project",
    "analyze_async_source",
    "analyze_concurrency_paths",
    "analyze_concurrency_source",
    "analyze_contracts_paths",
    "analyze_contracts_source",
    "analyze_paths",
    "analyze_source",
    "format_findings",
]

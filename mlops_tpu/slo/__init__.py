"""sloscope (ISSUE 14): the fleet-health layer — jax-free.

Three cooperating pieces, threaded through BOTH serving planes:

- `engine.SLOEngine` — declarative SLO accounting (availability +
  latency, per tenant) evaluated in-process from the existing request
  counters into multi-window multi-burn-rate gauges, alert flags, and a
  ``/healthz`` verdict. Shipped Prometheus alert rules live under
  ``configs/alerts/``.
- `flightrec.FlightRecorder` — a bounded in-memory ring of recent
  request summaries + spans, dumped atomically (tmp+rename) to
  ``runs/flightrec-*.json`` when an anomaly trips (burn-rate alert,
  engine respawn, 5xx/504 spike, breaker open) and on SIGTERM/fatal —
  the post-mortem evidence that survives the incident.
- `ledger.CostLedger` — per-compiled-entry cumulative device-time /
  dispatch / row accounting persisted across runs, keyed by
  entry + model fingerprint so a regrid or promotion never
  cross-pollutes entries: the measured cost model ROADMAP item 2's
  autotuner consumes.

Everything here follows the faultline discipline: disarmed, every hot
path pays one ``is None`` check.
"""

from mlops_tpu.slo.engine import (  # noqa: F401
    ENGINE_ALERTS,
    SLO_NAMES,
    SLOEngine,
    health_verdict,
    render_slo_lines,
)
from mlops_tpu.slo.flightrec import FlightRecorder  # noqa: F401
from mlops_tpu.slo.ledger import CostLedger, ledger_report  # noqa: F401

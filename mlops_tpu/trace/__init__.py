"""tracewire: end-to-end request tracing + shape/goodput telemetry.

The reference repo's whole monitoring story is structured per-request
logs queryable after the fact (`app/main.py:59-84` -> Log Analytics /
Kusto). This package is that story rebuilt for a serving path that now
crosses three processes (front end -> shm ring -> engine -> device):

- `Span` (span.py): one request's monotonic stage stamps — admission ->
  encode -> ring wait -> engine queue -> dispatch -> device fetch ->
  respond — stitched across the process boundary from the engine-half
  stamps the shm slot carries (serve/ipc.py ``resp_trace``).
- `TraceRecorder` (recorder.py): a bounded, drop-counting ring buffer
  flushed to JSONL by a background writer — the queryable-log story,
  locally; `jq` is the Kusto console (docs/observability.md).
- `ShapeStats` (shapes.py): per-compiled-entry shape histograms
  (requested rows vs padded rows, group geometry occupancy) exported as
  real Prometheus ``_bucket`` series plus the ``padding_waste_pct`` /
  ``useful_rows_per_s`` goodput keys — the exact input ROADMAP item 4's
  traffic-shape autotuner needs.
- `report.py`: the ``mlops-tpu trace-report`` CLI's aggregation —
  p50/p99 per stage per compiled entry from the span JSONL.

The bulk path (`score-batch`) is not traced here. A bulk job times its
own phases, its executor's queue waits and what it re-traced, always
(its record: `parallel/bulk.py job_record`, printed in the command's
summary and kept in the process's ``job_log()``), and under a `jax.profiler` session it writes ``mlops:bulk.*``
and ``mlops:pipe.*`` spans into the profiler's trace, on the device
operations' clock (docs/observability.md "Bulk jobs").

Everything here is jax-free (front-end processes import it) and gated
behind the ``trace`` config section: disarmed, the serving hot path pays
one ``is None`` check per request (the faultline discipline).
"""

from mlops_tpu.trace.recorder import TraceRecorder
from mlops_tpu.trace.report import format_report, load_spans, stage_report
from mlops_tpu.trace.shapes import ShapeStats
from mlops_tpu.trace.span import Span

__all__ = [
    "Span",
    "TraceRecorder",
    "ShapeStats",
    "load_spans",
    "stage_report",
    "format_report",
]

"""sloscope (ISSUE 14): SLO engine, flight recorder, cost ledger.

Covers the acceptance contracts: burn alerts flip within two evaluation
ticks; the SLO/alert series render identically on both planes (and keep
serving last-known values with ``engine_down`` raised through a full
engine outage); flight-recorder dumps are atomic (SIGKILL mid-write
never lands a torn file) and a clean plane writes ZERO of them; the
cost ledger round-trips monotone across runs, keys by entry + model
fingerprint, and ranks by cost_ms_per_row; build_info label sets are
identical across planes; log sampling never samples out a non-200.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

from mlops_tpu.config import Config, SLOConfig, SLOConfigError, ServeConfig
from mlops_tpu.slo import (
    CostLedger,
    FlightRecorder,
    SLOEngine,
    health_verdict,
    ledger_report,
    render_slo_lines,
)
from mlops_tpu.slo.engine import (
    ENGINE_ALERTS,
    read_slo_view,
    window_label,
    zero_view,
)

REPO = Path(__file__).resolve().parent.parent


def _fast_cfg(**overrides) -> SLOConfig:
    """Test-scale windows: seconds, not hours."""
    base = dict(
        enabled=True,
        fast_short_s=1.0,
        fast_long_s=2.0,
        slow_short_s=4.0,
        slow_long_s=8.0,
        tick_s=0.1,
        availability_target=0.999,
        latency_target=0.99,
        latency_threshold_ms=50.0,
    )
    base.update(overrides)
    return SLOConfig(**base).validate()


class _Counters:
    """A mutable cumulative counter source."""

    def __init__(self):
        self.good = 0
        self.total = 0

    def __call__(self):
        return {
            "default": (self.good, self.total, self.good, self.total)
        }


# ------------------------------------------------------------- SLO engine
def test_window_label_humanizes_round_windows():
    assert window_label(300) == "5m"
    assert window_label(3600) == "1h"
    assert window_label(21600) == "6h"
    assert window_label(259200) == "3d"
    assert window_label(7) == "7s"


def test_burn_alert_flips_within_two_ticks_and_clears():
    """The acceptance contract: counters crossing the burn threshold flip
    alert_active within two evaluation ticks; a recovered burn clears
    the fast alert once the short window drains."""
    src = _Counters()
    fired = []
    eng = SLOEngine(
        _fast_cfg(), ("default",), src,
        on_alert=lambda a, t, s: fired.append((a, t)),
    )
    t0 = time.monotonic()
    # Clean traffic: no alerts.
    src.good = src.total = 100
    eng.tick(t0 + 2.5)
    assert not eng.view()["default"]["alerts"]["availability_fast_burn"]
    assert not fired
    # A 504 storm: 50% bad — far past 14.4x the 0.1% budget.
    src.total = 200  # 100 bad
    eng.tick(t0 + 2.6)
    eng.tick(t0 + 2.7)  # within two ticks of the cross
    view = eng.view()
    assert view["default"]["alerts"]["availability_fast_burn"]
    assert ("availability_fast_burn", "default") in fired
    assert view["default"]["slos"]["availability"]["budget_pct"] < 0
    # Burn stops; once the fast windows drain past the bad interval the
    # fast alert clears (the short window is what ends alerts quickly).
    src.good = 10_200
    src.total = 10_300  # 10,100 good since — dilution plus window exit
    eng.tick(t0 + 6.0)
    assert not eng.view()["default"]["alerts"]["availability_fast_burn"]


def test_breaker_source_surfaces_as_alert_and_trigger():
    src = _Counters()
    fired = []
    breaker = {"default": False}
    eng = SLOEngine(
        _fast_cfg(), ("default",), src,
        breaker_source=lambda: breaker,
        on_alert=lambda a, t, s: fired.append(a),
    )
    eng.tick()
    assert not eng.view()["default"]["alerts"]["lifecycle_breaker"]
    breaker["default"] = True
    eng.tick()
    assert eng.view()["default"]["alerts"]["lifecycle_breaker"]
    assert fired == ["lifecycle_breaker"]
    eng.tick()  # sustained: no re-fire on a level, only on the edge
    assert fired == ["lifecycle_breaker"]


def test_zero_view_always_emits_every_series():
    """The always-emit contract: a fresh (or never-ticked) plane exports
    every SLO series at its zero baseline and every alert at 0."""
    cfg = _fast_cfg()
    lines = render_slo_lines(
        zero_view(("default",), (1.0, 2.0, 4.0, 8.0))
    )
    text = "\n".join(lines)
    for series in (
        'mlops_tpu_slo_good_total{slo="availability",tenant="default"} 0',
        'mlops_tpu_slo_total{slo="latency",tenant="default"} 0',
        'mlops_tpu_error_budget_remaining_pct{slo="availability",'
        'tenant="default"} 100.0',
        'mlops_tpu_slo_burn_rate{slo="availability",tenant="default",'
        'window="1s"} 0.0',
        'mlops_tpu_alert_active{alert="engine_down",severity="page",'
        'tenant="default"} 0',
    ):
        assert series in text, text
    for alert in ENGINE_ALERTS:
        assert f'alert="{alert}"' in text
    del cfg


def test_shm_mirror_round_trip_renders_identically():
    """Plane parity: the single-process engine's render and the ring
    render (write_rows -> read_slo_view) must produce byte-identical
    SLO blocks — the ONE-formatter discipline."""
    import numpy as np

    from mlops_tpu.slo.engine import N_ENGINE_ALERTS, SLO_FIELDS

    src = _Counters()
    src.good, src.total = 180, 200
    eng = SLOEngine(_fast_cfg(), ("default",), src)
    src.good, src.total = 380, 500
    eng.tick()
    direct = eng.render_lines()
    slo_vals = np.zeros((1, SLO_FIELDS))
    alert_vals = np.zeros((1, N_ENGINE_ALERTS))
    eng.write_rows(slo_vals, alert_vals)
    view = read_slo_view(
        slo_vals, alert_vals, ("default",), eng.windows
    )
    assert render_slo_lines(view) == direct


def test_health_verdict_states():
    view = zero_view(("default",), (1.0, 2.0, 4.0, 8.0))
    status, payload, _ = health_verdict(view, ready=True)
    assert (status, payload["verdict"]) == (200, "ok")
    view["default"]["alerts"]["availability_fast_burn"] = True
    status, payload, _ = health_verdict(view, ready=True)
    assert (status, payload["verdict"]) == (200, "degraded")
    assert payload["alerts"][0]["alert"] == "availability_fast_burn"
    status, payload, _ = health_verdict(view, ready=True, engine_down=True)
    assert (status, payload["verdict"]) == (503, "down")
    status, payload, _ = health_verdict(None, ready=False)
    assert (status, payload["verdict"]) == (503, "down")


def test_slo_config_validation_names_problems():
    with pytest.raises(SLOConfigError, match="availability_target"):
        SLOConfig(availability_target=1.0).validate()
    with pytest.raises(SLOConfigError, match="fast_short_s"):
        SLOConfig(fast_short_s=10.0, fast_long_s=5.0).validate()
    with pytest.raises(SLOConfigError, match="flightrec_keep"):
        SLOConfig(flightrec_keep=0).validate()
    # A threshold past the largest finite histogram edge would map to
    # +Inf and count every request as good — a silently dead alert.
    with pytest.raises(SLOConfigError, match="finite latency bucket"):
        SLOConfig(latency_threshold_ms=2000.0).validate()
    from mlops_tpu.serve.metrics import ServingMetrics

    SLOConfig(
        latency_threshold_ms=ServingMetrics.LATENCY_BUCKETS[-2]
    ).validate()  # the boundary itself is fine
    # Colliding window labels would overwrite each other's burn gauges.
    with pytest.raises(SLOConfigError, match="duplicate window labels"):
        SLOConfig(fast_short_s=90.0, fast_long_s=90.5).validate()


# ------------------------------------------------------- counter sources
def test_serving_metrics_slo_counts():
    from mlops_tpu.serve.metrics import ServingMetrics

    m = ServingMetrics()
    for status, latency in ((200, 1.0), (200, 80.0), (503, 0.2),
                            (504, 30000.0), (422, 1.0)):
        m.observe_request("/predict", status, latency)
    m.observe_request("/metrics", 200, 1.0)  # never SLO traffic
    counts = m.slo_counts(50.0, ("default",))
    good, total, lat_good, lat_total = counts["default"]
    # 422 counts as served (client error, no budget spend); 503/504 spend.
    assert (good, total) == (3, 5)
    # BOTH dimensions are /predict-scoped: the /metrics sample is
    # excluded (probe/scrape traffic must not dilute the latency SLO).
    # Threshold 50 -> good: 1.0, 0.2, 1.0; bad: 80 and 30000.
    assert (lat_good, lat_total) == (3, 5)


def test_ring_slo_counts_and_outage_render():
    """Ring twin of the counter source + the full-outage contract: with
    every replica down (supervisor-stamped) the scrape still renders —
    SLO gauges from the last-written rows, engine_down raised — and
    NEVER errors."""
    from mlops_tpu.serve.ipc import RequestRing, ShmWorkerMetrics
    from mlops_tpu.serve.metrics import render_ring_metrics

    ring = RequestRing(workers=2, slots_small=4, slots_large=1,
                       large_rows=8)
    cfg = _fast_cfg()
    ring.arm_slo(cfg)
    metrics = ShmWorkerMetrics(ring, 0)
    for status in (200, 200, 503, 504):
        metrics.observe_request("/predict", status, 1.0)
    good, total, lat_good, lat_total = ring.slo_counts(50.0)["default"]
    assert (good, total) == (2, 4)
    assert (lat_good, lat_total) == (4, 4)
    # The lead replica evaluates + mirrors:
    eng = SLOEngine(
        cfg, ring.tenant_names,
        source=lambda: ring.slo_counts(cfg.latency_threshold_ms),
    )
    for status in [503] * 40:
        metrics.observe_request("/predict", status, 1.0)
    eng.tick()
    eng.tick()
    eng.write_rows(ring.slo_vals, ring.alert_vals)
    # Now the full outage: every replica down, stamped.
    ring.set_ready(False)
    ring.eng_vals[0, 1] = time.monotonic()  # ENG_DOWN_SINCE
    text = render_ring_metrics(ring)
    assert (
        'mlops_tpu_alert_active{alert="engine_down",severity="page",'
        'tenant="default"} 1'
    ) in text
    assert (
        'mlops_tpu_alert_active{alert="availability_fast_burn",'
        'severity="page",tenant="default"} 1'
    ) in text
    # Last-known values, not zeros: the 503 flood (everything since the
    # engine armed — its construction-time sample is the baseline, so
    # the 4 pre-arm requests never bill) is still visible.
    assert 'mlops_tpu_slo_total{slo="availability",tenant="default"} 40' \
        in text


def test_respawned_evaluator_keeps_slo_totals_monotone():
    """ISSUE 11 discipline applied to sloscope: a respawned engine's
    fresh evaluator seeds from the dead incarnation's published shm
    rows, so the exported slo_good_total/slo_total never regress across
    a respawn (the chaos smoke's monotone-counter gate)."""
    from mlops_tpu.serve.ipc import RequestRing, ShmWorkerMetrics
    from mlops_tpu.slo.engine import SLO_NAMES

    ring = RequestRing(workers=1, slots_small=2, slots_large=1,
                       large_rows=8)
    cfg = _fast_cfg()
    ring.arm_slo(cfg)
    metrics = ShmWorkerMetrics(ring, 0)
    first = SLOEngine(
        cfg, ring.tenant_names,
        source=lambda: ring.slo_counts(cfg.latency_threshold_ms),
    )
    for status in (200,) * 50 + (503,) * 10:
        metrics.observe_request("/predict", status, 1.0)
    first.tick()
    first.write_rows(ring.slo_vals, ring.alert_vals)
    published = read_slo_view(
        ring.slo_vals, ring.alert_vals, ring.tenant_names, first.windows
    )["default"]["slos"]["availability"]
    assert published["total"] == 60
    # "kill -9": a successor evaluator boots against the SAME surviving
    # shm request counters, seeded with the published totals.
    prior = {
        "default": tuple(
            published_part
            for slo in SLO_NAMES
            for published_part in (
                read_slo_view(
                    ring.slo_vals, ring.alert_vals, ring.tenant_names,
                    first.windows,
                )["default"]["slos"][slo]["good"],
                read_slo_view(
                    ring.slo_vals, ring.alert_vals, ring.tenant_names,
                    first.windows,
                )["default"]["slos"][slo]["total"],
            )
        )
    }
    second = SLOEngine(
        cfg, ring.tenant_names,
        source=lambda: ring.slo_counts(cfg.latency_threshold_ms),
        prior_counts=prior,
    )
    second.tick()
    second.write_rows(ring.slo_vals, ring.alert_vals)
    after = read_slo_view(
        ring.slo_vals, ring.alert_vals, ring.tenant_names, second.windows
    )["default"]["slos"]["availability"]
    assert after["total"] >= published["total"]
    assert after["good"] >= published["good"]
    # New traffic keeps growing the continued counters.
    metrics.observe_request("/predict", 200, 1.0)
    second.tick()
    second.write_rows(ring.slo_vals, ring.alert_vals)
    grown = read_slo_view(
        ring.slo_vals, ring.alert_vals, ring.tenant_names, second.windows
    )["default"]["slos"]["availability"]
    assert grown["total"] == published["total"] + 1


def test_build_info_identical_label_set_across_planes():
    from mlops_tpu.serve.ipc import RequestRing
    from mlops_tpu.serve.metrics import (
        ServingMetrics,
        build_info_lines,
        render_ring_metrics,
    )

    line = build_info_lines()[1]
    assert line.startswith("mlops_tpu_build_info{backend=")
    for label in ("backend=", "jax=", "jaxlib=", "version="):
        assert label in line
    single = ServingMetrics().render()
    assert line in single
    ring = RequestRing(workers=1, slots_small=2, slots_large=1,
                       large_rows=8)
    ring_text = render_ring_metrics(ring)
    assert line in ring_text
    # The flight-dump counter rides the shared robustness block: zero
    # baseline on both planes (dumps are observable fleet-wide).
    assert "mlops_tpu_flightrec_dumps_total 0" in single
    assert "mlops_tpu_flightrec_dumps_total 0" in ring_text


# --------------------------------------------------------- flight recorder
def test_flightrec_spike_trigger_dumps_and_clean_ring_writes_nothing(
    tmp_path,
):
    rec = FlightRecorder(tmp_path, cooldown_s=0.0, spike_errors=5,
                         spike_window_s=10.0)
    for _ in range(20):
        rec.observe_request("/predict", 200, 1.0)
    assert list(tmp_path.glob("flightrec-*.json")) == []
    assert rec.dump_if_evidence("sigterm") is None  # clean drain: nothing
    for _ in range(5):
        rec.observe_request("/predict", 504, 30.0)
    # The triggered dump writes on a daemon thread (off the request
    # path): poll briefly for it to land.
    deadline = time.monotonic() + 5.0
    dumps: list = []
    while time.monotonic() < deadline and not dumps:
        dumps = list(tmp_path.glob("flightrec-*.json"))
        time.sleep(0.02)
    assert len(dumps) == 1
    payload = json.loads(dumps[0].read_text())
    assert payload["reason"] == "error_spike"
    statuses = [e["status"] for e in payload["events"]
                if e["kind"] == "request"]
    assert statuses.count(504) == 5


def test_flightrec_cooldown_bounds_dump_stream_and_retention(tmp_path):
    rec = FlightRecorder(tmp_path, cooldown_s=60.0, keep=2)
    writer = rec.trigger("one")
    assert writer is not None
    writer.join(timeout=10)
    assert rec.trigger("two") is None  # inside the cooldown
    assert rec.suppressed == 1
    rec2 = FlightRecorder(tmp_path, cooldown_s=0.0, keep=2)
    for i in range(5):
        writer = rec2.trigger(f"r{i}")
        assert writer is not None
        writer.join(timeout=10)  # serialize: retention is the subject
    assert len(list(tmp_path.glob("flightrec-*.json"))) == 2  # retention


def test_flightrec_alert_note_lands_in_timeline(tmp_path):
    rec = FlightRecorder(tmp_path, cooldown_s=0.0)
    rec.observe_request("/predict", 504, 31000.0, request_id="victim")
    rec.note_span({"kind": "span", "trace_id": "victim", "status": 504,
                   "entry": "bucket_8", "wall_ms": 31000.0,
                   "stages": {"dispatch": 30999.0}})
    rec.note_alert("availability_fast_burn", "default", "page")
    deadline = time.monotonic() + 5.0
    dumps: list = []
    while time.monotonic() < deadline and not dumps:
        dumps = list(tmp_path.glob("flightrec-*.json"))
        time.sleep(0.02)
    assert len(dumps) == 1
    path = dumps[0]
    from mlops_tpu.slo.flightrec import format_timeline, load_dump

    dump = load_dump(path)
    kinds = [e["kind"] for e in dump["events"]]
    assert kinds == ["request", "span", "alert"]
    timeline = format_timeline(dump)
    assert "victim" in timeline and "bucket_8" in timeline
    assert "availability_fast_burn" in timeline


def test_flightrec_failed_dump_keeps_evidence_and_cooldown(
    tmp_path, monkeypatch
):
    """A failed write (full disk mid-incident) must neither eat the
    evidence nor burn the cooldown: the next dump attempt retries and
    preserves the ring."""
    import mlops_tpu.slo.flightrec as fr

    rec = FlightRecorder(tmp_path, cooldown_s=60.0)
    rec.observe_request("/predict", 500, 1.0)
    real = fr.atomic_write

    def failing(path, data):
        raise OSError("disk full")

    monkeypatch.setattr(fr, "atomic_write", failing)
    assert rec.dump("incident") is None
    monkeypatch.setattr(fr, "atomic_write", real)
    # Evidence survived the failed write — the drain-time dump lands...
    assert rec.dump_if_evidence("sigterm") is not None
    # ...and the failed attempt's cooldown slot was restored (a fresh
    # trigger is not suppressed).
    rec.observe_request("/predict", 500, 1.0)
    assert rec.suppressed == 0


def test_slo_engine_sample_retention_stays_bounded():
    """Days of 1 s ticks must not grow per-tick work unboundedly: the
    per-tenant sample list caps (old half thins), and the burn math
    stays correct on the thinned history."""
    src = _Counters()
    cfg = _fast_cfg(slow_long_s=1e9, slow_short_s=1e8, tick_s=1.0)
    eng = SLOEngine(cfg, ("default",), src)
    t0 = time.monotonic()
    for i in range(9000):
        src.good = src.total = i * 10
        eng.tick(t0 + i)
    from mlops_tpu.slo.engine import _MAX_SAMPLES

    assert len(eng._samples["default"]) <= _MAX_SAMPLES
    # A burst of bad traffic still computes sane recent burns.
    src.total += 100  # 100 bad
    eng.tick(t0 + 9001)
    burn = eng.view()["default"]["slos"]["availability"]["burn"]["1s"]
    assert burn > 0


_FLIGHTREC_KILL = r"""
import sys
sys.path.insert(0, %(repo)r)
from mlops_tpu import faults
from mlops_tpu.slo.flightrec import FlightRecorder
faults.arm(faults.FaultPlan.from_rules(
    [{"point": "io.atomic_write.midwrite", "mode": "kill"}]
))
rec = FlightRecorder(%(dir)r, cooldown_s=0.0)
rec.observe_request("/predict", 500, 1.0)
rec.dump("chaos")  # SIGKILLs between tmp write and rename
"""


def test_flightrec_dump_survives_sigkill_midwrite(tmp_path):
    """The PR 9 persistence proof applied to dumps: SIGKILL between the
    tmp write and the rename (the exact window a sibling's kill -9 can
    land in) leaves NO torn flightrec-*.json — every landed dump
    parses, and the temp file never counts as a dump."""
    proc = subprocess.run(
        [sys.executable, "-c",
         _FLIGHTREC_KILL % {"repo": str(REPO), "dir": str(tmp_path)}],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]
    assert list(tmp_path.glob("flightrec-*.json")) == []
    # A second, unarmed run against the same dir dumps cleanly (the
    # leaked tmp never blocks the directory).
    rec = FlightRecorder(tmp_path, cooldown_s=0.0)
    rec.observe_request("/predict", 500, 1.0)
    assert rec.dump("after") is not None
    for dump in tmp_path.glob("flightrec-*.json"):
        json.loads(dump.read_text())  # every landed file parses


# ------------------------------------------------------------ cost ledger
def test_ledger_accumulates_monotone_across_runs(tmp_path):
    """Two 'serve runs' against one ledger dir: totals accumulate, never
    reset — the acceptance's monotone contract."""
    led = CostLedger(tmp_path, flush_interval_s=1000)
    led.observe("bucket_8", "aaaa1111", 5, 8, 0.002)
    led.observe("bucket_8", "aaaa1111", 3, 8, 0.001)
    led.close()
    first = json.loads((tmp_path / "ledger.json").read_text())
    assert first["entries"]["bucket_8@aaaa1111"]["dispatches"] == 2
    led2 = CostLedger(tmp_path, flush_interval_s=1000)
    led2.observe("bucket_8", "aaaa1111", 8, 8, 0.004)
    led2.close()
    second = json.loads((tmp_path / "ledger.json").read_text())
    entry = second["entries"]["bucket_8@aaaa1111"]
    assert entry["dispatches"] == 3
    assert entry["rows"] == 16
    assert entry["device_s"] >= first["entries"]["bucket_8@aaaa1111"][
        "device_s"
    ]


def test_ledger_keys_by_model_tag_never_cross_pollute(tmp_path):
    """A promotion to a different architecture (new model fingerprint)
    lands in a FRESH entry under the same shape name."""
    led = CostLedger(tmp_path, flush_interval_s=1000)
    led.observe("bucket_8", "aaaa1111", 8, 8, 0.010)
    led.observe("bucket_8", "bbbb2222", 8, 8, 0.001)
    led.close()
    report = ledger_report(tmp_path)
    keys = {row["key"] for row in report["entries"]}
    assert keys == {"bucket_8@aaaa1111", "bucket_8@bbbb2222"}
    # Ranked by cost_ms_per_row, most expensive first.
    assert report["entries"][0]["model"] == "aaaa1111"
    assert report["entries"][0]["cost_ms_per_row"] > report["entries"][1][
        "cost_ms_per_row"
    ]


def test_ledger_shm_mirror_and_merge(tmp_path):
    import numpy as np

    from mlops_tpu.slo.ledger import (
        TABLE_KEY_BYTES,
        TABLE_ROWS,
        TABLE_VALS,
        merge_entries,
        read_table,
        render_entry_lines,
    )

    led = CostLedger(tmp_path, flush_interval_s=1000)
    led.observe("group_16x1", "aaaa1111", 12, 16, 0.003)
    keys = np.zeros((TABLE_ROWS, TABLE_KEY_BYTES), np.uint8)
    vals = np.zeros((TABLE_ROWS, TABLE_VALS))
    led.write_table(keys, vals)
    led.close()
    entries = read_table(keys, vals)
    assert list(entries) == ["group_16x1@aaaa1111"]
    merged = merge_entries([entries, entries])
    assert merged["group_16x1@aaaa1111"][1] == 2  # dispatches add
    text = "\n".join(render_entry_lines(merged))
    assert (
        'mlops_tpu_entry_device_seconds_total{entry="group_16x1",'
        'model="aaaa1111"}'
    ) in text
    assert "mlops_tpu_entry_cost_ms_per_row" in text


def test_engine_ledger_hook_accounts_solo_and_grouped(
    warm_engine, sample_request, tmp_path
):
    """The engine-path integration: packed solo + grouped dispatches
    account device seconds under entry@fingerprint keys; disarmed the
    engine carries no hook state."""
    led = CostLedger(tmp_path, flush_interval_s=1000)
    warm_engine.set_cost_ledger(led)
    try:
        warm_engine.predict_records(sample_request * 3)  # bucket_8
        warm_engine.predict_group([sample_request, sample_request])
    finally:
        warm_engine.set_cost_ledger(None)
        led.close()
    report = ledger_report(tmp_path)
    by_entry = {row["entry"]: row for row in report["entries"]}
    assert "bucket_8" in by_entry
    group_entries = [e for e in by_entry if e.startswith("group_")]
    assert group_entries, by_entry
    tag = by_entry["bucket_8"]["model"]
    assert len(tag) == 8 and tag == warm_engine._cost_tag
    assert by_entry["bucket_8"]["device_s"] > 0
    assert by_entry["bucket_8"]["rows"] == 3
    assert by_entry["bucket_8"]["padded_rows"] == 8


# ------------------------------------------------------------ HTTP layer
class _StubShell:
    """Minimal HttpProtocol host for _predict-level tests."""

    def __new__(cls, config, score):
        from mlops_tpu.serve.httpcore import HttpProtocol
        from mlops_tpu.serve.metrics import ServingMetrics

        shell = HttpProtocol(config)
        shell.metrics = ServingMetrics()
        shell._score = score
        return shell


def test_log_sampling_always_logs_non_200s(caplog):
    """serve.log_sample_rate=0.01 under a shed burst: the sampled-out
    requests' InferenceData events are skipped, but EVERY 503 still
    logs its event (errors never sample out)."""

    async def shed_score(records, request_id, deadline=None, span=None,
                         tenant=0, slo=0):
        return (
            503, {"detail": "overloaded"}, "application/json",
            {"retry-after": "1"},
        )

    shell = _StubShell(
        ServeConfig(log_sample_rate=0.01).validate(), shed_score
    )
    body = json.dumps([{"credit_limit": 1000, "age": 30}]).encode()

    async def drive(n):
        results = []
        for i in range(n):
            results.append(
                await shell._predict(body, request_id=f"r{i}")
            )
        return results

    with caplog.at_level(logging.INFO, logger="mlops_tpu.serve"):
        results = asyncio.run(drive(50))
    assert all(r[0] == 503 for r in results)
    events = [r.getMessage() for r in caplog.records
              if "InferenceData" in r.getMessage()]
    assert len(events) == 50  # every shed logged despite rate 0.01


def test_log_sampling_samples_successes(caplog):
    async def ok_score(records, request_id, deadline=None, span=None,
                       tenant=0, slo=0):
        return {"predictions": [0.1], "outliers": [0],
                "feature_drift_batch": {}}

    shell = _StubShell(
        ServeConfig(log_sample_rate=0.01).validate(), ok_score
    )
    body = json.dumps([{"credit_limit": 1000, "age": 30}]).encode()

    async def drive(n):
        for i in range(n):
            await shell._predict(body, request_id=f"r{i}")

    with caplog.at_level(logging.INFO, logger="mlops_tpu.serve"):
        asyncio.run(drive(60))
    events = [r for r in caplog.records
              if "InferenceData" in r.getMessage()]
    # Statistically: 60 draws at p=0.01 — the chance of 20+ logs is
    # astronomically small; the assertion is "sampling happened".
    assert len(events) < 20


def test_log_sample_rate_validation():
    from mlops_tpu.config import ServeConfigError

    with pytest.raises(ServeConfigError, match="log_sample_rate"):
        ServeConfig(log_sample_rate=0.0).validate()
    with pytest.raises(ServeConfigError, match="log_sample_rate"):
        ServeConfig(log_sample_rate=1.5).validate()


def test_healthz_route_answers_verdict():
    """`GET /healthz` rides the shared router on every plane: the base
    protocol (no sloscope) answers from readiness alone."""
    from mlops_tpu.serve.httpcore import HttpProtocol
    from mlops_tpu.serve.metrics import ServingMetrics

    shell = HttpProtocol(ServeConfig().validate())
    shell.metrics = ServingMetrics()
    shell._ready = lambda: True

    async def drive():
        return await shell._route("GET", "/healthz", b"")

    status, payload, _ = asyncio.run(drive())
    assert status == 200 and payload["verdict"] == "ok"
    shell._ready = lambda: False
    status, payload, _ = asyncio.run(drive())
    assert status == 503 and payload["verdict"] == "down"


def test_frontend_healthz_and_slo_view_from_shm():
    """The ring plane's /healthz verdict reads the shm mirror: an armed
    ring with an active alert answers 'degraded'; a stamped full outage
    answers 503 'down'."""
    import numpy as np

    from mlops_tpu.serve.ipc import RequestRing
    from mlops_tpu.slo.engine import ENGINE_ALERTS as ALERTS

    ring = RequestRing(workers=1, slots_small=2, slots_large=1,
                       large_rows=8)
    cfg = _fast_cfg()
    ring.arm_slo(cfg)
    ring.slo_vals[0, 0] = 1.0  # HAS
    ring.alert_vals[0, ALERTS.index("availability_fast_burn")] = 1.0
    view = read_slo_view(
        ring.slo_vals, ring.alert_vals, ring.tenant_names,
        tuple(float(x) for x in ring.slo_meta[:4]),
    )
    status, payload, _ = health_verdict(view, ready=True)
    assert (status, payload["verdict"]) == (200, "degraded")
    ring.eng_vals[0, 1] = time.monotonic()  # ENG_DOWN_SINCE, not ready
    ring.set_ready(False)
    engine_down = not ring.engine_ready and bool(
        (np.asarray(ring.eng_vals[:, 1]) > 0).any()
    )
    status, payload, _ = health_verdict(
        view, ready=False, engine_down=engine_down
    )
    assert (status, payload["verdict"]) == (503, "down")


# ------------------------------------------------------------ trace-report
def test_load_spans_accepts_glob(tmp_path):
    from mlops_tpu.trace import load_spans

    for worker in (0, 1):
        with open(tmp_path / f"spans-w{worker}.jsonl", "w") as f:
            f.write(json.dumps({"kind": "span", "plane": "ring",
                                "worker": worker, "wall_ms": 1.0,
                                "stages": {"respond": 1.0}}) + "\n")
    spans = load_spans(tmp_path)  # dir form (existing)
    assert len(spans) == 2
    spans = load_spans(str(tmp_path / "spans-w*.jsonl"))  # glob form
    assert len(spans) == 2
    spans = load_spans(str(tmp_path / "spans-w1.jsonl"))  # file form
    assert len(spans) == 1

"""tracewire tests (mlops_tpu/trace/ + the serving-plane threading).

The correctness bar for ISSUE 10:

- inbound ``x-request-id`` echoed on BOTH planes (the caller's trace id
  correlates logs, span record, and response);
- a multi-worker request produces ONE stitched span whose stage stamps
  are monotone and non-overlapping, whose stages sum to its wall clock,
  and which names the compiled entry the ENGINE process chose — the
  engine half-stamps crossing in the shm slot;
- span JSONL survives the SIGTERM drain with zero torn lines (O_APPEND
  single-write discipline);
- the bounded recorder DROPS on overflow (counted in
  ``trace_dropped_total``) instead of ever blocking the hot path;
- /debug/profile start/stop round-trips over the ring to the engine
  process (the only device owner);
- shape histograms render as real Prometheus ``_bucket`` series with
  identical names on both telemetry planes, and the latency histogram
  exports ``_bucket``/``_sum``/``_count`` on both renderers.
"""

import asyncio
import json
import time
from pathlib import Path

import numpy as np
import pytest

from test_frontend import (  # the shared plane harnesses
    http_exchange,
    multi_worker_plane,
    single_process_server,
)

from mlops_tpu.config import TraceConfig, TraceConfigError
from mlops_tpu.trace import (
    ShapeStats,
    Span,
    TraceRecorder,
    load_spans,
    stage_report,
)


@pytest.fixture(scope="module")
def engine(warm_engine):
    return warm_engine  # session-shared warmed engine (conftest)


@pytest.fixture(scope="module")
def prep_path(warm_engine, tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "preprocess.npz"
    warm_engine.bundle.preprocessor.save(path)
    return str(path)


# ------------------------------------------------------------------- span
def test_span_stages_are_monotone_and_sum_to_wall():
    span = Span("t1", plane="ring", worker=3)
    span.stamp("admission")
    span.stamp("encode")
    # Cross-process stamp from "the past" (clock skew / reordering must
    # never manufacture a negative stage): clamped to zero duration.
    span.stamp_at("ring_wait", time.monotonic() - 5.0)
    span.stamp("respond")
    record = span.finish(200)
    assert record["stages"]["ring_wait"] == 0.0
    offsets = [offset for _, offset in record["stamps"]]
    assert offsets == sorted(offsets), "stamps must be monotone"
    assert sum(record["stages"].values()) == pytest.approx(
        record["wall_ms"], abs=1e-2
    )


# --------------------------------------------------------------- recorder
def test_recorder_overflow_drops_and_never_blocks(tmp_path):
    drops = []
    recorder = TraceRecorder(
        tmp_path / "spans.jsonl",
        capacity=4,
        flush_interval_s=30.0,  # writer effectively parked: force overflow
        on_drop=lambda n: drops.append(n),
    )
    t0 = time.perf_counter()
    for i in range(100):
        recorder.record({"kind": "span", "trace_id": f"t{i}", "stages": {}})
    enqueue_s = time.perf_counter() - t0
    assert enqueue_s < 1.0, "record() must never block the hot path"
    assert recorder.dropped == 96
    assert len(drops) == 96
    recorder.close()
    lines = (tmp_path / "spans.jsonl").read_text().splitlines()
    assert len(lines) == 4  # capacity survived; every buffered span landed
    for line in lines:
        json.loads(line)


def test_recorder_close_flushes_and_every_line_parses(tmp_path):
    recorder = TraceRecorder(tmp_path / "spans.jsonl", capacity=1024)
    for i in range(64):
        recorder.record(
            {"kind": "span", "trace_id": f"t{i}", "stages": {"respond": 0.1}}
        )
    recorder.close()
    lines = (tmp_path / "spans.jsonl").read_text().splitlines()
    assert len(lines) == 64
    assert all(json.loads(line)["kind"] == "span" for line in lines)


# ----------------------------------------------------------------- shapes
def test_shape_stats_histogram_and_goodput_keys():
    stats = ShapeStats()
    stats.observe("bucket_8", 1, 8)
    stats.observe("bucket_8", 8, 8)
    stats.observe("group_16x1", 4, 16)
    text = "\n".join(stats.render_lines())
    assert 'mlops_tpu_shape_occupancy_bucket{entry="bucket_8",le="0.125"} 1' in text
    assert 'mlops_tpu_shape_occupancy_bucket{entry="bucket_8",le="+Inf"} 2' in text
    assert 'mlops_tpu_shape_occupancy_count{entry="bucket_8"} 2' in text
    assert 'mlops_tpu_requested_rows_total{entry="group_16x1"} 4' in text
    assert 'mlops_tpu_padded_rows_total{entry="group_16x1"} 16' in text
    # waste = 1 - (1+8+4)/(8+8+16) = 1 - 13/32
    assert stats.padding_waste_pct() == pytest.approx(59.375, abs=0.01)
    assert "mlops_tpu_padding_waste_pct 59.375" in text
    assert stats.useful_rows_per_s() >= 0


def test_shape_table_shm_round_trip_renders_same_series():
    from mlops_tpu.trace.shapes import (
        TABLE_KEY_BYTES,
        TABLE_ROWS,
        TABLE_VALS,
        render_table_lines,
    )

    stats = ShapeStats()
    stats.observe("bucket_64", 10, 64)
    stats.observe("group_2x8", 9, 16)
    keys = np.zeros((TABLE_ROWS, TABLE_KEY_BYTES), np.uint8)
    vals = np.zeros((TABLE_ROWS, TABLE_VALS), np.float64)
    stats.write_table(keys, vals)
    direct = [
        line for line in stats.render_lines()
        if "useful_rows_per_s" not in line  # rate base differs by clock read
    ]
    mirrored = [
        line for line in render_table_lines(keys, vals, 10.0)
        if "useful_rows_per_s" not in line
    ]
    assert direct == mirrored


# ------------------------------------------------------- engine span hooks
def test_engine_stamps_span_and_names_the_bucket(engine, sample_request):
    span = Span("eng-1")
    engine.predict_records(sample_request * 3, span=span)
    span.stamp("respond")
    record = span.finish(200)
    assert record["entry"] == "bucket_8"  # 3 rows pad to the 8 bucket
    for stage in ("encode", "dispatch", "device_fetch", "respond"):
        assert stage in record["stages"], record["stages"]
    offsets = [offset for _, offset in record["stamps"]]
    assert offsets == sorted(offsets)


def test_engine_shape_stats_observe_solo_and_grouped(engine, sample_request):
    stats = ShapeStats()
    engine.set_shape_stats(stats)
    try:
        engine.predict_records(sample_request * 3)  # -> bucket_8, 3/8
        engine.predict_group([sample_request, sample_request])  # 2 slots
    finally:
        engine.set_shape_stats(None)
    snap = stats.snapshot()
    assert snap["bucket_8"][1] == 3 and snap["bucket_8"][2] == 8
    group_keys = [k for k in snap if k.startswith("group_")]
    assert group_keys, snap
    slots, rows = group_keys[0].removeprefix("group_").split("x")
    assert snap[group_keys[0]][1] == 2  # two batch-1 requests
    assert snap[group_keys[0]][2] == int(slots) * int(rows)


# ----------------------------------------------------- request-id echo
def test_inbound_request_id_echoed_single_process(engine, sample_request):
    with single_process_server(engine) as port:
        status, headers, _ = http_exchange(
            port, "POST", "/predict", sample_request,
            headers={"x-request-id": "echo-test-42"},
        )
    assert status == 200
    assert headers["x-request-id"] == "echo-test-42"


def test_inbound_request_id_echoed_two_workers(engine, prep_path, sample_request):
    with multi_worker_plane(engine, prep_path, workers=2) as (port, *_):
        status, headers, _ = http_exchange(
            port, "POST", "/predict", sample_request,
            headers={"x-request-id": "echo-ring-7"},
        )
    assert status == 200
    assert headers["x-request-id"] == "echo-ring-7"


# ------------------------------------------------- single-process tracing
def test_single_process_span_records_to_jsonl(engine, sample_request, tmp_path):
    tracer = TraceRecorder(tmp_path / "spans.jsonl", flush_interval_s=0.05)
    with single_process_server(engine, tracer=tracer) as port:
        status, headers, _ = http_exchange(
            port, "POST", "/predict", sample_request,
            headers={"x-request-id": "solo-span-1"},
        )
        assert status == 200
    tracer.close()
    spans = load_spans(tmp_path / "spans.jsonl")
    [span] = [s for s in spans if s["trace_id"] == "solo-span-1"]
    assert span["plane"] == "single"
    assert span["status"] == 200 and span["rows"] == 1
    assert "admission" in span["stages"] and "respond" in span["stages"]
    # The engine half ran in-process: dispatch/fetch stamps present.
    assert "dispatch" in span["stages"] and "device_fetch" in span["stages"]
    assert span.get("entry", "").startswith("bucket_")
    assert sum(span["stages"].values()) == pytest.approx(
        span["wall_ms"], abs=1e-2
    )


# ------------------------------------------------------ ring-plane tracing
def test_ring_plane_stitched_span_and_sigterm_drain(
    engine, prep_path, sample_request, tmp_path
):
    """THE acceptance pin: a multi-worker request returns its trace id
    and produces ONE stitched span — monotone non-overlapping stages
    covering admission -> encode -> ring_wait -> engine_queue ->
    dispatch -> device_fetch -> respond, summing to the span's wall
    clock, naming the engine-chosen compiled entry — and the span JSONL
    survives the SIGTERM drain with zero torn lines."""
    trace = TraceConfig(
        enabled=True, dir=str(tmp_path / "traces"), flush_interval_s=0.05
    )
    walls: dict[str, float] = {}
    with multi_worker_plane(
        engine, prep_path, workers=2, trace=trace
    ) as (port, ring, procs, service):
        assert ring.tracing
        for i in range(4):
            trace_id = f"ring-span-{i}"
            t0 = time.perf_counter()
            status, headers, _ = http_exchange(
                port, "POST", "/predict", sample_request,
                headers={"x-request-id": trace_id},
            )
            walls[trace_id] = (time.perf_counter() - t0) * 1e3
            assert status == 200
            assert headers["x-request-id"] == trace_id
    # Plane drained (SIGTERM via the harness): recorders flushed on exit.
    files = sorted(Path(trace.dir).glob("spans-w*.jsonl"))
    assert files, "no per-worker span files after drain"
    for file in files:
        for line in file.read_text().splitlines():
            json.loads(line)  # zero torn lines
    spans = load_spans(trace.dir)
    by_id = {s["trace_id"]: s for s in spans}
    for i in range(4):
        span = by_id[f"ring-span-{i}"]  # exactly one record per request
        assert span["plane"] == "ring"
        for stage in (
            "admission", "encode", "ring_wait", "engine_queue",
            "dispatch", "device_fetch", "respond",
        ):
            assert stage in span["stages"], (stage, span["stages"])
        offsets = [offset for _, offset in span["stamps"]]
        assert offsets == sorted(offsets), "stitched stamps must be monotone"
        assert sum(span["stages"].values()) == pytest.approx(
            span["wall_ms"], abs=0.05
        )
        # Sanity vs the client-observed wall, with ABSOLUTE slack only: on
        # a contended 1-core box the OS can deschedule the worker between
        # its socket write (client stops its clock) and the respond stamp,
        # so the span wall can legitimately exceed the client wall by
        # scheduler jitter — the bound exists to catch gross pathologies
        # (a stale future stamp stitched in), not scheduling noise.
        assert 0.0 < span["wall_ms"] <= walls[span["trace_id"]] + 100.0
        assert span.get("entry", "").startswith(("bucket_", "group_"))
    assert len([s for s in spans if s["trace_id"].startswith("ring-span")]) == 4


def test_ring_trace_dropped_counter_and_metrics_series(
    engine, prep_path, sample_request
):
    """The dropped-span counter is exported from shm on any worker's
    scrape, zero-baseline (chaos monotonicity discipline)."""
    with multi_worker_plane(engine, prep_path, workers=2) as (port, *_):
        assert http_exchange(port, "POST", "/predict", sample_request)[0] == 200
        status, _, body = http_exchange(port, "GET", "/metrics")
    assert status == 200
    assert b"mlops_tpu_trace_dropped_total 0" in body


# --------------------------------------------------- ring shape telemetry
def test_ring_renders_shape_histograms_from_shm(
    engine, prep_path, sample_request
):
    stats = ShapeStats()
    engine.set_shape_stats(stats)
    try:
        with multi_worker_plane(engine, prep_path, workers=1) as (
            port, ring, _, service,
        ):
            assert http_exchange(
                port, "POST", "/predict", sample_request * 3
            )[0] == 200
            service._write_shapes()  # the telemetry loop's mirror, driven
            status, _, body = http_exchange(port, "GET", "/metrics")
    finally:
        engine.set_shape_stats(None)
    text = body.decode()
    assert status == 200
    assert 'mlops_tpu_shape_occupancy_bucket{entry="bucket_8"' in text
    assert "mlops_tpu_padding_waste_pct" in text
    assert "mlops_tpu_useful_rows_per_s" in text


# -------------------------------------------------- profile over the ring
def test_profile_round_trips_over_the_ring(
    engine, prep_path, sample_request, tmp_path
):
    """/debug/profile start/stop on the 2-worker plane: the front end
    forwards through the ring's control word to the engine process's
    JaxProfiler (the device owner), same statuses as single-process."""
    from mlops_tpu.serve.server import JaxProfiler

    profile_dir = str(tmp_path / "prof")
    with multi_worker_plane(
        engine, prep_path, workers=2, profile_dir=profile_dir
    ) as (port, ring, procs, service):
        service.profiler = JaxProfiler(profile_dir).control
        statuses = []
        for action in ("stop", "start", "start", "stop"):
            status, _, _ = http_exchange(
                port, "POST", f"/debug/profile/{action}"
            )
            statuses.append(status)
        assert statuses == [409, 200, 409, 200]
        assert any(Path(profile_dir).iterdir()), "no trace output captured"


def test_profile_404_when_engine_has_no_profiler(
    engine, prep_path, tmp_path
):
    """profile_dir configured on the front end but no engine-side
    profiler attached (serve.profile_dir empty on the engine): the
    engine answers the control word with 404 rather than wedging the
    front end's poll."""
    with multi_worker_plane(
        engine, prep_path, workers=1, profile_dir=str(tmp_path)
    ) as (port, *_):
        status, _, body = http_exchange(port, "POST", "/debug/profile/start")
    assert status == 404
    assert b"profiling disabled" in body


def test_profile_control_word_unit():
    """The single-word protocol itself: seq/ack pairing, unknown action
    -> 404, handler errors -> 500 (never the collector thread)."""
    from mlops_tpu.serve.ipc import RequestRing, RingService

    class _Stub:
        supports_grouping = False
        monitor_accumulating = False

    ring = RequestRing(workers=1, slots_small=1, slots_large=1, large_rows=8)
    try:
        service = RingService(_Stub(), ring)  # never started: unit-drive
        calls = []

        def profiler(action):
            calls.append(action)
            if action == "stop":
                raise RuntimeError("boom")
            return 200, None

        service.profiler = profiler

        def ack(seq, timeout=10.0):
            # The profiler runs on the service pool (a slow start_trace
            # must never stall the collector); poll the ack word the way
            # a front end does.
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                status = ring.read_profile_ack(seq)
                if status is not None:
                    return status
                time.sleep(0.01)
            raise TimeoutError("no profile ack")

        token = ring.try_claim_profile()
        assert token is not None
        seq = ring.post_profile_request(1)  # start
        service._handle_profile()
        assert ack(seq) == 200
        service._handle_profile()  # same seq: handled once
        assert calls == ["start"]
        seq2 = ring.post_profile_request(2)  # stop -> handler raises
        service._handle_profile()
        assert ack(seq2) == 500
        assert ring.read_profile_ack(seq) is None  # old seq superseded
        seq3 = ring.post_profile_request(9)  # unknown action code
        service._handle_profile()
        assert ack(seq3) == 404
        # Timed-out ack (the front end's 504 path): the CANCEL overwrite
        # must stop a late collector from executing the action the client
        # was told failed, while keeping the seq numbering monotone.
        calls.clear()
        seq4 = ring.post_profile_request(1)  # start...
        # ...504'd before the collector ran:
        ring.cancel_profile_request(seq4, token)
        service._handle_profile()
        assert ack(seq4) == 404  # no-op acknowledged
        assert calls == []  # the start never executed late
        seq5 = ring.post_profile_request(1)
        assert seq5 == seq4 + 1  # numbering survived the cancel
        service._handle_profile()
        assert ack(seq5) == 200 and calls == ["start"]
        ring.release_profile(token)

        # Death tolerance: the claim is a shm LEASE, so a front end
        # killed mid-poll frees by expiry instead of wedging the channel
        # into permanent 409 (every other ring structure survives worker
        # death; this one must too).
        stale = ring.try_claim_profile()
        assert stale is not None
        assert ring.try_claim_profile() is None  # live claim -> busy
        ring.prof_claim[0] = time.monotonic() - 1.0  # claimant died; expired
        live = ring.try_claim_profile()  # lease takeover
        assert live is not None
        # The stalled EX-claimant resumes: its cancel/release must be
        # no-ops against the successor's live lease and pending word.
        seq6 = ring.post_profile_request(1)
        ring.cancel_profile_request(seq6, stale)
        assert int(ring.prof_ctl[0]) & 0xFF == 1  # word not clobbered
        ring.release_profile(stale)
        assert float(ring.prof_claim[0]) == live  # lease still the successor's
        service._handle_profile()
        assert ack(seq6) == 200
        ring.release_profile(live)
        assert float(ring.prof_claim[0]) == 0.0
    finally:
        ring.close()


# ------------------------------------------------------- latency histogram
def test_latency_histogram_bucket_series_on_both_planes(
    engine, prep_path, sample_request
):
    """Satellite pin: the per-plane latency histogram exports real
    Prometheus _bucket/_sum/_count series (le-labelled) on BOTH the
    single-process and ring renderers."""
    with single_process_server(engine) as port:
        assert http_exchange(port, "POST", "/predict", sample_request)[0] == 200
        _, _, body = http_exchange(port, "GET", "/metrics")
    text = body.decode()
    assert (
        'mlops_tpu_request_latency_ms_bucket{le="0.5",tenant="default"}'
        in text
    )
    assert (
        'mlops_tpu_request_latency_ms_bucket{le="+Inf",tenant="default"}'
        in text
    )
    assert "mlops_tpu_request_latency_ms_sum" in text
    assert "mlops_tpu_request_latency_ms_count" in text

    with multi_worker_plane(engine, prep_path, workers=2) as (port, *_):
        assert http_exchange(port, "POST", "/predict", sample_request)[0] == 200
        _, _, body = http_exchange(port, "GET", "/metrics")
    text = body.decode()
    assert (
        'mlops_tpu_request_latency_ms_bucket{le="0.5",worker="0",'
        'tenant="default"}' in text
    )
    assert (
        'mlops_tpu_request_latency_ms_bucket{le="+Inf",worker="1",'
        'tenant="default"}' in text
    )
    assert (
        'mlops_tpu_request_latency_ms_sum{worker="0",tenant="default"}'
        in text
    )
    assert (
        'mlops_tpu_request_latency_ms_count{worker="1",tenant="default"}'
        in text
    )


# ----------------------------------------------------------- trace-report
def test_trace_report_aggregates_p50_p99_per_stage_per_entry(tmp_path):
    recorder = TraceRecorder(tmp_path / "spans.jsonl")
    for i in range(20):
        span = Span(f"r{i}", plane="ring")
        span.entry = "bucket_8" if i % 2 else "group_4x1"
        span.stamp("admission")
        span.stamp("respond")
        recorder.record(span.finish(200))
    recorder.record({"kind": "stage", "stage": "encode"})  # skipped
    recorder.close()
    report = stage_report(load_spans(tmp_path))
    assert report["spans"] == 20
    entries = {g["entry"]: g for g in report["groups"]}
    assert set(entries) == {"bucket_8", "group_4x1"}
    for group in entries.values():
        assert group["requests"] == 10
        assert group["stages"]["admission"]["count"] == 10
        assert group["stages"]["admission"]["p50_ms"] >= 0
        assert group["wall_p99_ms"] >= group["wall_p50_ms"]


def test_trace_report_cli_handler(tmp_path, capsys):
    from mlops_tpu.commands import _trace_report
    from mlops_tpu.config import Config

    recorder = TraceRecorder(tmp_path / "spans.jsonl")
    span = Span("cli-1")
    span.stamp("admission")
    span.stamp("respond")
    recorder.record(span.finish(200))
    recorder.close()
    config = Config()
    config.trace.dir = str(tmp_path)
    assert _trace_report(config) == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    report = json.loads(out)
    assert report["spans"] == 1
    # Empty dir: parseable output, exit 2 (nothing to report).
    config.trace.dir = str(tmp_path / "empty")
    assert _trace_report(config) == 2


# ----------------------------------------------------------------- config
def test_trace_config_validation():
    with pytest.raises(TraceConfigError, match="ring_capacity"):
        TraceConfig(ring_capacity=0).validate()
    with pytest.raises(TraceConfigError, match="flush_interval_s"):
        TraceConfig(flush_interval_s=0).validate()
    with pytest.raises(TraceConfigError, match="trace.dir"):
        TraceConfig(enabled=True, dir="").validate()
    assert TraceConfig(enabled=True).validate().enabled


# -------------------------------------------------------- StageClock spans
def test_stage_clock_emits_span_events_to_sink(tmp_path):
    """The sink is the profiler's own trace: with ``span_attrs`` every
    stage execution is a ``mlops:pipe.<stage>`` annotation carrying them;
    without, the clock annotates nothing (and imports nothing)."""
    from conftest import program_spans

    from mlops_tpu.utils.timing import StageClock

    clock, bare = StageClock({"job": 7}), StageClock()
    with program_spans(tmp_path) as spans:
        with clock.stage("encode", items=3):
            pass
        with clock.stage("compute"):
            pass
        with bare.stage("write"):
            pass
    assert [(name, attrs) for name, _, _, attrs in spans] == [
        ("mlops:pipe.encode", {"items": 3, "job": 7}),
        ("mlops:pipe.compute", {"items": 1, "job": 7}),
    ]
    assert all(end >= start for _, start, end, _ in spans)
    # report() is the same with and without spans (the existing contract).
    assert set(clock.report(1.0)) == {"encode", "compute"}
    assert bare.report(1.0)["write"]["items"] == 1


def test_stream_scoring_emits_stage_records(tiny_pipeline, tmp_path):
    """The production wiring: `score-batch score.streaming=true` under a
    profiler session writes every pipeline stage execution into the trace
    as a ``mlops:pipe.<stage>`` span carrying the job's number."""
    from conftest import program_spans

    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.data import generate_synthetic, write_csv_columns
    from mlops_tpu.data.stream import score_csv_stream

    _, result = tiny_pipeline
    bundle = load_bundle(result.bundle_dir)
    columns, labels = generate_synthetic(400, seed=3)
    write_csv_columns(tmp_path / "in.csv", columns, labels)
    with program_spans(tmp_path / "profile") as spans:
        stats = score_csv_stream(
            bundle,
            tmp_path / "in.csv",
            tmp_path / "out.csv",
            chunk_rows=256,
            pipeline_depth=1,
        )
    assert stats["rows"] == 400
    assert spans, "no stage spans landed"
    stages = {name.removeprefix("mlops:pipe.") for name, *_ in spans}
    assert {"read", "encode", "transfer", "compute", "fetch", "write"} == stages
    assert len({attrs["job"] for *_, attrs in spans}) == 1
    compute = [s for s in spans if s[0] == "mlops:pipe.compute"]
    assert len(compute) == stats["stages"]["compute"]["items"] == 2

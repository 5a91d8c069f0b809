"""Serving tests: engine semantics + real-socket HTTP round trips.

Upgrades the reference's 200-only smoke test (SURVEY.md SS4: CI curls
`app/sample-request.json` and checks the status code, response body never
validated) into payload-asserting golden tests.
"""

import asyncio
import json
import time

import numpy as np
import pytest

from mlops_tpu.bundle import load_bundle
from mlops_tpu.config import ServeConfig
from mlops_tpu.schema import FEATURE_NAMES
from mlops_tpu.serve import HttpServer, InferenceEngine


@pytest.fixture(scope="module")
def engine(warm_engine):
    return warm_engine  # session-shared warmed engine (conftest)


# ------------------------------------------------------------------ engine


def test_engine_padding_invariance(engine, sample_request):
    """Bucket padding must not change any statistic: a 3-row request (padded
    to 8) and the same rows at exact shape agree."""
    records = sample_request * 3
    padded = engine.predict_records(records)
    # Bypass bucketing: exact-shape path.
    from mlops_tpu.schema import records_to_columns

    ds = engine.bundle.preprocessor.encode(records_to_columns(records))
    big = InferenceEngine(engine.bundle, buckets=(3,))
    exact = big.predict_arrays(ds.cat_ids, ds.numeric)
    np.testing.assert_allclose(
        padded["predictions"], exact["predictions"], rtol=1e-6
    )
    np.testing.assert_array_equal(padded["outliers"], exact["outliers"])
    for name in FEATURE_NAMES:
        assert abs(
            padded["feature_drift_batch"][name]
            - exact["feature_drift_batch"][name]
        ) < 1e-5


def test_engine_oversized_batch(engine, sample_request):
    out = engine.predict_records(sample_request * 100)  # > max bucket 64
    assert len(out["predictions"]) == 100
    assert len(out["outliers"]) == 100


def test_engine_response_contract(engine, sample_request):
    out = engine.predict_records(sample_request)
    assert set(out) == {"predictions", "outliers", "feature_drift_batch"}
    assert len(out["predictions"]) == 1
    assert 0.0 <= out["predictions"][0] <= 1.0
    assert out["outliers"][0] in (0.0, 1.0)
    assert list(out["feature_drift_batch"]) == list(FEATURE_NAMES)


# ------------------------------------------------------------- HTTP server


async def _http(server_port_payloads):
    """Open the server on an ephemeral port, run client exchanges, return
    (status, headers, body-json) per exchange."""
    server, exchanges = server_port_payloads
    srv = await server.start()
    port = srv.sockets[0].getsockname()[1]
    results = []
    try:
        for method, path, body in exchanges:
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            data = b"" if body is None else json.dumps(body).encode()
            request = (
                f"{method} {path} HTTP/1.1\r\nhost: t\r\n"
                f"content-length: {len(data)}\r\nconnection: close\r\n\r\n"
            ).encode() + data
            writer.write(request)
            await writer.drain()
            raw = await reader.read()
            writer.close()
            head, _, payload = raw.partition(b"\r\n\r\n")
            status = int(head.split(b" ")[1])
            results.append((status, head.decode("latin1"), payload))
    finally:
        srv.close()
        await srv.wait_closed()
    return results


def _run_exchanges(engine, exchanges, port=0):
    config = ServeConfig(host="127.0.0.1", port=port)
    server = HttpServer(engine, config)
    return asyncio.run(_http((server, exchanges)))


def test_http_predict_golden(engine, sample_request):
    """The reference's exact smoke payload over a real socket -> validated
    body (vs the reference CI's unchecked `cat`, `deploy-kubernetes.yml:271`).
    """
    [(status, _, body)] = _run_exchanges(
        engine, [("POST", "/predict", sample_request)]
    )
    assert status == 200
    payload = json.loads(body)
    assert set(payload) == {"predictions", "outliers", "feature_drift_batch"}
    assert len(payload["predictions"]) == 1
    assert 0.0 <= payload["predictions"][0] <= 1.0
    # Determinism: same request -> identical response.
    [(_, _, body2)] = _run_exchanges(
        engine, [("POST", "/predict", sample_request)]
    )
    assert json.loads(body2)["predictions"] == payload["predictions"]


def test_http_validation_and_probes(engine):
    results = _run_exchanges(
        engine,
        [
            ("POST", "/predict", [{"age": "not-a-number"}]),
            ("GET", "/healthz/live", None),
            ("GET", "/healthz/ready", None),
            ("GET", "/metrics", None),
            ("GET", "/nope", None),
            ("GET", "/", None),
        ],
    )
    statuses = [r[0] for r in results]
    assert statuses == [422, 200, 200, 200, 404, 200]
    assert b"mlops_tpu_requests_total" in results[3][2]
    assert b"credit-default-api" in results[5][2]


def test_http_defaults_fill_missing_fields(engine):
    # Reference parity: every LoanApplicant field has a default
    # (`app/model.py:12-34`), so an empty record is valid.
    [(status, _, body)] = _run_exchanges(engine, [("POST", "/predict", [{}])])
    assert status == 200
    assert len(json.loads(body)["predictions"]) == 1


def test_http_malformed_json_rejected(engine):
    config = ServeConfig(host="127.0.0.1", port=0)
    server = HttpServer(engine, config)

    async def go():
        srv = await server.start()
        port = srv.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        body = b"{not json"
        writer.write(
            (
                f"POST /predict HTTP/1.1\r\nhost: t\r\n"
                f"content-length: {len(body)}\r\nconnection: close\r\n\r\n"
            ).encode()
            + body
        )
        await writer.drain()
        raw = await reader.read()
        writer.close()
        srv.close()
        await srv.wait_closed()
        return int(raw.split(b" ")[1])

    assert asyncio.run(go()) == 422


def test_http_empty_request_no_drift_poison(engine):
    # An empty list is valid, returns empty outputs, and must not report
    # drift (an all-padded batch has no signal).
    [(status, _, body)] = _run_exchanges(engine, [("POST", "/predict", [])])
    assert status == 200
    payload = json.loads(body)
    assert payload["predictions"] == []
    assert all(v == 0.0 for v in payload["feature_drift_batch"].values())


def test_metrics_unknown_route_bounded(engine):
    results = _run_exchanges(
        engine,
        [("GET", f"/scan-{i}", None) for i in range(5)] + [("GET", "/metrics", None)],
    )
    body = results[-1][2].decode()
    assert 'route="<other>"' in body
    assert "/scan-0" not in body


def test_http_max_batch_cap(engine, sample_request):
    config = ServeConfig(host="127.0.0.1", port=0, max_batch=4)
    server = HttpServer(engine, config)
    [(status, _, body)] = asyncio.run(
        _http((server, [("POST", "/predict", sample_request * 5)]))
    )
    assert status == 413
    assert b"max_batch" in body


def test_http_bad_content_length(engine):
    config = ServeConfig(host="127.0.0.1", port=0)
    server = HttpServer(engine, config)

    async def go():
        srv = await server.start()
        port = srv.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(
            b"POST /predict HTTP/1.1\r\nhost: t\r\ncontent-length: abc\r\n\r\n"
        )
        await writer.drain()
        raw = await reader.read()
        writer.close()
        srv.close()
        await srv.wait_closed()
        return int(raw.split(b" ")[1])

    assert asyncio.run(go()) == 400


def test_readiness_gate(tiny_pipeline):
    _, result = tiny_pipeline
    bundle = load_bundle(result.bundle_dir)
    cold = InferenceEngine(bundle, buckets=(1,))  # no warmup
    [(status, _, body)] = _run_exchanges(cold, [("GET", "/healthz/ready", None)])
    assert status == 503


def test_profile_endpoints(engine, tmp_path):
    """jax.profiler trace start/stop over the socket (SURVEY.md SS5.1)."""
    config = ServeConfig(host="127.0.0.1", port=0, profile_dir=str(tmp_path))
    server = HttpServer(engine, config)
    exchanges = [
        ("POST", "/debug/profile/stop", None),   # nothing running -> 409
        ("POST", "/debug/profile/start", None),  # -> 200 tracing
        ("POST", "/debug/profile/start", None),  # already running -> 409
        ("POST", "/debug/profile/stop", None),   # -> 200 stopped
    ]
    results = asyncio.run(_http((server, exchanges)))
    assert [s for s, _, _ in results] == [409, 200, 409, 200]
    assert any(tmp_path.iterdir()), "trace output expected in profile_dir"


def test_profile_disabled(engine):
    config = ServeConfig(host="127.0.0.1", port=0, profile_dir="")
    server = HttpServer(engine, config)
    [(status, _, _)] = asyncio.run(
        _http((server, [("POST", "/debug/profile/start", None)]))
    )
    assert status == 404


def test_openapi_document(engine):
    """GET /openapi.json serves a valid document generated from the SAME
    pydantic models that validate requests (reference parity: FastAPI's
    auto-docs at `/`, `app/main.py:37`), and `/` serves the Swagger page."""
    [(status, _, body), (hstatus, hhead, hbody)] = _run_exchanges(
        engine, [("GET", "/openapi.json", None), ("GET", "/", None)]
    )
    assert status == 200
    doc = json.loads(body)
    assert doc["openapi"].startswith("3.")
    assert "/predict" in doc["paths"]
    applicant = doc["components"]["schemas"]["LoanApplicant"]
    assert len(applicant["properties"]) == 23
    request_schema = doc["paths"]["/predict"]["post"]["requestBody"]
    assert request_schema["required"] is True
    output = doc["components"]["schemas"]["FeatureBatchDrift"]
    assert len(output["properties"]) == 23
    assert hstatus == 200 and b"swagger-ui" in hbody


def test_sigterm_graceful_drain():
    """SIGTERM flips readiness, closes IDLE keep-alive connections
    immediately, lets an IN-FLIGHT request finish its response, and
    _serve returns promptly (K8s rollout contract) — the idle-connection
    case is what stalls a naive wait_closed() shutdown forever."""
    import os
    import signal
    import time as _time

    from mlops_tpu.serve.server import _serve

    class StubEngine:
        ready = False
        max_bucket = 64
        supports_grouping = False

        def warmup(self):
            self.ready = True

        def predict_records(self, records):
            _time.sleep(0.8)  # in-flight work straddling the SIGTERM
            return {
                "predictions": [0.5],
                "outliers": [0.0],
                "feature_drift_batch": dict.fromkeys(FEATURE_NAMES, 0.0),
            }

    engine = StubEngine()
    body = json.dumps([{}]).encode()
    request = (
        b"POST /predict HTTP/1.1\r\nhost: t\r\n"
        b"content-type: application/json\r\n"
        + f"content-length: {len(body)}\r\n\r\n".encode()
        + body
    )

    async def run():
        config = ServeConfig(host="127.0.0.1", port=5173)
        serve_task = asyncio.create_task(_serve(engine, config))
        for _ in range(100):  # wait for bind + warmup
            if engine.ready:
                break
            await asyncio.sleep(0.05)
        assert engine.ready

        # Idle keep-alive connection: must be closed by the drain, not
        # hold shutdown open.
        idle_reader, idle_writer = await asyncio.open_connection(
            "127.0.0.1", config.port
        )
        # In-flight request: send, then SIGTERM while the stub predict
        # sleeps; the response must still arrive complete.
        busy_reader, busy_writer = await asyncio.open_connection(
            "127.0.0.1", config.port
        )
        busy_writer.write(request)
        await busy_writer.drain()
        await asyncio.sleep(0.2)  # let the exchange enter _route

        t0 = asyncio.get_running_loop().time()
        os.kill(os.getpid(), signal.SIGTERM)

        head = await asyncio.wait_for(busy_reader.readline(), timeout=10)
        assert b"200" in head
        raw = await asyncio.wait_for(busy_reader.read(), timeout=10)
        assert b"predictions" in raw
        assert b"connection: close" in (head + raw).lower()

        # The idle connection gets EOF instead of stalling shutdown.
        assert await asyncio.wait_for(idle_reader.read(), timeout=10) == b""

        await asyncio.wait_for(serve_task, timeout=10)
        elapsed = asyncio.get_running_loop().time() - t0
        assert elapsed < 8, f"drain took {elapsed:.1f}s"
        for w in (idle_writer, busy_writer):
            w.close()

    asyncio.run(run())
    assert engine.ready is False  # readiness stays down through exit


def test_inbound_request_id_is_honored_and_echoed(engine, sample_request):
    """A well-formed x-request-id correlates the caller's trace end to end:
    echoed as a response header and stamped on both log events; malformed
    ids are replaced with a fresh hex (log-injection gate)."""
    config = ServeConfig(host="127.0.0.1", port=0)
    server = HttpServer(engine, config)

    async def run():
        srv = await server.start()
        port = srv.sockets[0].getsockname()[1]
        out = []
        try:
            for rid in ("trace-abc_123", "bad id with spaces", "x" * 100):
                reader, writer = await asyncio.open_connection("127.0.0.1", port)
                data = json.dumps(sample_request).encode()
                writer.write(
                    (
                        f"POST /predict HTTP/1.1\r\nhost: t\r\n"
                        f"x-request-id: {rid}\r\n"
                        f"content-length: {len(data)}\r\nconnection: close\r\n\r\n"
                    ).encode()
                    + data
                )
                await writer.drain()
                raw = await reader.read()
                writer.close()
                head = raw.partition(b"\r\n\r\n")[0].decode("latin1")
                echoed = [
                    line.split(":", 1)[1].strip()
                    for line in head.splitlines()
                    if line.lower().startswith("x-request-id:")
                ]
                out.append((rid, echoed[0]))
        finally:
            srv.close()
            await srv.wait_closed()
        return out

    results = asyncio.run(run())
    assert results[0] == ("trace-abc_123", "trace-abc_123")  # honored
    for sent, echoed in results[1:]:
        assert echoed != sent  # malformed -> replaced
        assert len(echoed) == 32 and all(c in "0123456789abcdef" for c in echoed)


def test_request_deadline_504s_on_stalled_device(engine, sample_request):
    """A wedged predict path (stalled device) must answer the documented
    504 within the deadline instead of hanging every in-flight
    connection. 504, not 503: deadline is distinct from the shed path,
    which alone carries Retry-After (ISSUE 9)."""
    config = ServeConfig(host="127.0.0.1", port=0, request_timeout_s=0.3)
    server = HttpServer(engine, config)

    async def hang_forever(records, deadline=None):
        await asyncio.sleep(3600)

    server.batcher.predict = hang_forever  # simulate the stall

    async def run():
        srv = await server.start()
        port = srv.sockets[0].getsockname()[1]
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            data = json.dumps(sample_request).encode()
            writer.write(
                (
                    f"POST /predict HTTP/1.1\r\nhost: t\r\n"
                    f"content-length: {len(data)}\r\nconnection: close\r\n\r\n"
                ).encode()
                + data
            )
            await writer.drain()
            raw = await asyncio.wait_for(reader.read(), timeout=5)
        finally:
            srv.close()
            await srv.wait_closed()
        head, _, body = raw.partition(b"\r\n\r\n")
        return int(head.split(b" ")[1]), json.loads(body)

    status, payload = asyncio.run(run())
    assert status == 504
    assert "deadline" in payload["detail"]


def test_deadline_header_sheds_dead_work_before_the_engine(
    engine, sample_request
):
    """An already-expired x-request-deadline-ms budget answers the
    documented 504 WITHOUT the engine (or batcher) ever being touched —
    the dead-work shed — and the shed is counted in
    mlops_tpu_deadline_expired_total (ISSUE 9)."""
    config = ServeConfig(host="127.0.0.1", port=0)
    server = HttpServer(engine, config)
    touched = []

    async def must_not_run(records, deadline=None):
        touched.append(records)
        return {}

    server.batcher.predict = must_not_run

    async def run():
        srv = await server.start()
        port = srv.sockets[0].getsockname()[1]
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            data = json.dumps(sample_request).encode()
            writer.write(
                (
                    f"POST /predict HTTP/1.1\r\nhost: t\r\n"
                    f"content-length: {len(data)}\r\n"
                    # 1 ms budget, then stall the body so it is spent
                    # before the request completes admission.
                    f"x-request-deadline-ms: 1\r\nconnection: close\r\n\r\n"
                ).encode()
            )
            await writer.drain()
            await asyncio.sleep(0.05)  # budget expires while body pends
            writer.write(data)
            await writer.drain()
            raw = await asyncio.wait_for(reader.read(), timeout=5)
        finally:
            srv.close()
            await srv.wait_closed()
        head, _, body = raw.partition(b"\r\n\r\n")
        return int(head.split(b" ")[1]), json.loads(body)

    status, payload = asyncio.run(run())
    assert status == 504
    assert "deadline" in payload["detail"]
    assert touched == []  # the engine path never ran — dead work shed
    assert server.metrics.deadline_expired == 1
    assert "mlops_tpu_deadline_expired_total 1" in server.metrics.render()


def test_deadline_header_tightens_the_server_timeout(engine, sample_request):
    """A live (not yet expired) budget bounds the wait on a stalled
    engine: the 504 lands within the header budget even though
    serve.request_timeout_s is far larger."""
    config = ServeConfig(host="127.0.0.1", port=0, request_timeout_s=30.0)
    server = HttpServer(engine, config)

    async def hang_forever(records, deadline=None):
        await asyncio.sleep(3600)

    server.batcher.predict = hang_forever

    async def run():
        srv = await server.start()
        port = srv.sockets[0].getsockname()[1]
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            data = json.dumps(sample_request).encode()
            writer.write(
                (
                    f"POST /predict HTTP/1.1\r\nhost: t\r\n"
                    f"content-length: {len(data)}\r\n"
                    f"x-request-deadline-ms: 200\r\nconnection: close\r\n\r\n"
                ).encode()
                + data
            )
            await writer.drain()
            t0 = time.perf_counter()
            raw = await asyncio.wait_for(reader.read(), timeout=5)
            elapsed = time.perf_counter() - t0
        finally:
            srv.close()
            await srv.wait_closed()
        head, _, body = raw.partition(b"\r\n\r\n")
        return int(head.split(b" ")[1]), elapsed

    status, elapsed = asyncio.run(run())
    assert status == 504
    assert elapsed < 2.0  # the 200 ms budget governed, not the 30 s knob


def test_batcher_purges_expired_entries_engine_side(engine, sample_request):
    """The micro-batcher's claim-time purge completes an expired entry
    with DeadlineExceeded INSTEAD of dispatching it (dead-work shedding):
    the handler answers 504 and the engine never sees the request."""
    import concurrent.futures

    from mlops_tpu.serve.batcher import MicroBatcher
    from mlops_tpu.serve.wire import DeadlineExceeded

    dispatched = []

    class Recorder:
        supports_grouping = True

        def predict_records(self, records):
            dispatched.append(records)
            return {"predictions": [0.0]}

        def predict_group(self, requests):
            dispatched.extend(requests)
            return [{"predictions": [0.0]} for _ in requests]

    async def run():
        loop = asyncio.get_running_loop()
        pool = concurrent.futures.ThreadPoolExecutor(2)
        batcher = MicroBatcher(Recorder(), pool, window_ms=20.0, max_group=8)
        # Seed the queue so the entry below is NOT idle-fast-pathed.
        warm = asyncio.ensure_future(batcher.predict(sample_request))
        await asyncio.sleep(0)
        expired = asyncio.ensure_future(
            batcher.predict(sample_request, deadline=loop.time() - 0.001)
        )
        results = await asyncio.gather(warm, expired, return_exceptions=True)
        pool.shutdown(wait=True)
        return results

    warm_result, expired_result = asyncio.run(run())
    assert isinstance(warm_result, dict)  # the live entry still served
    assert isinstance(expired_result, DeadlineExceeded)
    # Exactly one request reached the engine: the expired one was purged.
    assert len(dispatched) == 1


def test_degraded_dispatch_falls_back_to_next_warmed_bucket(
    engine, sample_request
):
    """A compile/cache failure for an unwarmed bucket (injected at
    serve.engine.compile) degrades to the next-larger WARMED bucket with
    a bit-identical response and a degraded_dispatch_total increment —
    never a 500 (ISSUE 9 degraded-mode contract)."""
    from mlops_tpu import faults

    record = sample_request[0]
    records = [dict(record) for _ in range(3)]
    baseline = engine.predict_records(records)
    before = engine.degraded_dispatch_total
    # Make bucket 8 (the 3-row target) unwarmed, and fail its compile.
    with engine._compile_lock:
        saved = engine._exec.pop(("bucket", 8))
    try:
        faults.arm(
            faults.FaultPlan.from_rules(
                [{"point": "serve.engine.compile", "mode": "raise"}]
            )
        )
        degraded = engine.predict_records(records)
    finally:
        faults.disarm()
        with engine._compile_lock:
            engine._exec[("bucket", 8)] = saved
    assert degraded == baseline  # masked padding = identical statistics
    assert engine.degraded_dispatch_total == before + 1
    # With the fault disarmed and the entry restored, the target bucket
    # serves again without touching the degraded path.
    assert engine.predict_records(records) == baseline
    assert engine.degraded_dispatch_total == before + 1

"""PR 4 hot-path contract: packed single-buffer responses are BIT-IDENTICAL
to the seed dict-path responses (every bucket, every group slot shape), the
device monitor accumulator counts exactly what was scored, and overlapped
batcher fetches never cross-wire requests.
"""

import asyncio
import concurrent.futures

import jax
import numpy as np
import pytest

from mlops_tpu.ops.predict import (
    make_grouped_predict_fn,
    make_padded_predict_fn,
    packed_layout,
)
from mlops_tpu.schema import SCHEMA, records_to_columns
from mlops_tpu.serve.batcher import MicroBatcher
from mlops_tpu.serve.engine import (
    GROUP_ROW_BUCKET,
    GROUP_ROW_BUCKETS,
    GROUP_SLOT_BUCKETS,
)


@pytest.fixture(scope="module")
def engine(warm_engine):
    return warm_engine  # session-shared warmed engine (conftest)


@pytest.fixture(scope="module")
def seed_padded(warm_engine):
    """The SEED dict-output padded program, bound over the same bundle —
    the pre-packing reference the parity pins against."""
    b = warm_engine.bundle
    return make_padded_predict_fn(b.model, b.variables, b.monitor, b.temperature)


@pytest.fixture(scope="module")
def seed_grouped(warm_engine):
    b = warm_engine.bundle
    return make_grouped_predict_fn(b.model, b.variables, b.monitor, b.temperature)


def _records(sample_request, k, offset=0):
    out = []
    for i in range(k):
        rec = dict(sample_request[0])
        rec["age"] = 20.0 + offset + 2.0 * i
        rec["bill_amount_1"] = 100.0 * (i + 1) + offset
        rec["credit_limit"] = 1000.0 + 977.0 * i + offset
        rec["payment_amount_1"] = 37.0 * i + offset
        out.append(rec)
    return out


def _seed_response_arrays(seed_fn, cat, num, bucket):
    """The seed engine's exact predict_arrays assembly (pad to bucket,
    device_get the dict tree, slice, cast, round)."""
    n = cat.shape[0]
    pad = bucket - n
    if pad:
        cat = np.pad(cat, ((0, pad), (0, 0)))
        num = np.pad(num, ((0, pad), (0, 0)))
    mask = np.arange(bucket) < n
    out = jax.device_get(seed_fn(cat, num, mask))
    return {
        "predictions": np.asarray(out["predictions"])[:n].astype(float).tolist(),
        "outliers": np.asarray(out["outliers"])[:n].astype(float).tolist(),
        "feature_drift_batch": dict(
            zip(
                SCHEMA.feature_names,
                np.asarray(out["feature_drift_batch"])
                .astype(float)
                .round(6)
                .tolist(),
            )
        ),
    }


# ------------------------------------------------------------ padded parity
def test_packed_padded_bit_identical_every_bucket(
    engine, seed_padded, sample_request
):
    """For EVERY warmed bucket: the packed-path response equals the seed
    dict-path response bit for bit (no tolerance)."""
    for bucket in engine.buckets:
        n = max(1, bucket - 1) if bucket > 1 else 1
        records = _records(sample_request, n, offset=bucket)
        ds = engine.bundle.preprocessor.encode(records_to_columns(records))
        got = engine.predict_arrays(ds.cat_ids, ds.numeric)
        want = _seed_response_arrays(seed_padded, ds.cat_ids, ds.numeric, bucket)
        assert got == want, f"bucket {bucket} diverged"


def test_packed_layout_slices():
    p, o, d = packed_layout(8)
    D = SCHEMA.num_categorical + SCHEMA.num_numeric
    assert (p.start, p.stop) == (0, 8)
    assert (o.start, o.stop) == (8, 16)
    assert (d.start, d.stop) == (16, 16 + D)


# ----------------------------------------------------------- grouped parity
def test_packed_grouped_bit_identical_every_slot(
    engine, seed_grouped, sample_request
):
    """For EVERY slot bucket and BOTH row families: grouped packed
    responses equal the seed grouped dict-path assembly bit for bit."""
    names = SCHEMA.feature_names

    def seed_group(requests):
        # The seed engine's exact predict_group body against the dict fn.
        import bisect

        sizes = [len(r) for r in requests]
        slots = GROUP_SLOT_BUCKETS[
            bisect.bisect_left(GROUP_SLOT_BUCKETS, len(requests))
        ]
        rows = GROUP_ROW_BUCKETS[0] if max(sizes) == 1 else GROUP_ROW_BUCKET
        cat = np.zeros((slots, rows, SCHEMA.num_categorical), np.int32)
        num = np.zeros((slots, rows, SCHEMA.num_numeric), np.float32)
        mask = np.zeros((slots, rows), bool)
        flat = [record for records in requests for record in records]
        ds = engine.bundle.preprocessor.encode(records_to_columns(flat))
        offset = 0
        for i, k in enumerate(sizes):
            cat[i, :k] = ds.cat_ids[offset : offset + k]
            num[i, :k] = ds.numeric[offset : offset + k]
            mask[i, :k] = True
            offset += k
        out = jax.device_get(seed_grouped(cat, num, mask))
        preds = np.asarray(out["predictions"]).astype(float)
        outs = np.asarray(out["outliers"]).astype(float)
        drifts = np.asarray(out["feature_drift_batch"]).astype(float).round(6)
        return [
            {
                "predictions": preds[i, :k].tolist(),
                "outliers": outs[i, :k].tolist(),
                "feature_drift_batch": dict(zip(names, drifts[i].tolist())),
            }
            for i, k in enumerate(sizes)
        ]

    for slots in GROUP_SLOT_BUCKETS:
        # Batch-1 family ([slots, 1]) at exactly this slot bucket.
        reqs = [[r] for r in _records(sample_request, slots, offset=slots)]
        assert engine.predict_group(reqs) == seed_group(reqs), (
            f"slots={slots} rows=1 diverged"
        )
        # Mixed-size family ([slots, GROUP_ROW_BUCKET]).
        mixed = [
            [r] * ((i % GROUP_ROW_BUCKET) + 1)
            for i, r in enumerate(
                _records(sample_request, slots, offset=100 + slots)
            )
        ]
        if max(len(m) for m in mixed) == 1:
            mixed[0] = mixed[0] * 2  # force the 8-row family
        assert engine.predict_group(mixed) == seed_group(mixed), (
            f"slots={slots} rows={GROUP_ROW_BUCKET} diverged"
        )


# ------------------------------------------------------ monitor accumulator
def test_monitor_accumulator_counts_scored_rows(engine, sample_request):
    assert engine.monitor_accumulating
    before = engine.monitor_snapshot()
    records = _records(sample_request, 5)
    engine.predict_records(records)  # one padded dispatch, 5 valid rows
    engine.predict_group([[r] for r in _records(sample_request, 3)])
    after = engine.monitor_snapshot()
    assert after["rows"] - before["rows"] == 8.0
    # 5-row solo = 1 batch; 3 batch-1 group slots = 3 batches.
    assert after["batches"] - before["batches"] == 4.0
    assert after["outliers"] >= before["outliers"]
    assert set(after["drift_last"]) == set(SCHEMA.feature_names)
    assert set(after["drift_mean"]) == set(SCHEMA.feature_names)


def test_monitor_accumulator_ignores_empty_requests(engine):
    before = engine.monitor_snapshot()
    out = engine.predict_arrays(
        np.zeros((0, SCHEMA.num_categorical), np.int32),
        np.zeros((0, SCHEMA.num_numeric), np.float32),
    )
    after = engine.monitor_snapshot()
    assert out["predictions"] == []
    assert after["rows"] == before["rows"]
    assert after["batches"] == before["batches"]


def test_monitor_snapshot_resets_window_keeps_exact_totals(
    engine, sample_request
):
    """Every snapshot fetches-and-RESETS the device window, folding it
    into host f64 totals: an unreset f32 counter would silently stop
    incrementing at 2^24 rows (~2 h of benched traffic). Totals must
    survive an empty window unchanged — including drift_last."""
    engine.predict_records(_records(sample_request, 3))
    first = engine.monitor_snapshot()
    window = jax.device_get(engine._acc)
    assert float(window.rows) == 0.0
    assert float(window.batches) == 0.0
    second = engine.monitor_snapshot()  # empty window
    assert second["rows"] == first["rows"]
    assert second["batches"] == first["batches"]
    assert second["drift_last"] == first["drift_last"]
    assert second["drift_mean"] == first["drift_mean"]


def test_failed_snapshot_fetch_delays_counts_not_drops_them(
    engine, sample_request, monkeypatch
):
    """A transient device_get failure in monitor_snapshot must fold the already-swapped-out window BACK into the
    live accumulator: the counts arrive on the next successful fetch
    instead of silently vanishing from the /metrics totals."""
    engine.monitor_snapshot()  # drain any prior window
    baseline = engine.monitor_snapshot()
    engine.predict_records(_records(sample_request, 4))

    real_get = jax.device_get

    def failing_get(x):
        raise RuntimeError("transport hiccup")

    monkeypatch.setattr(jax, "device_get", failing_get)
    with pytest.raises(RuntimeError, match="transport hiccup"):
        engine.monitor_snapshot()
    monkeypatch.setattr(jax, "device_get", real_get)

    after = engine.monitor_snapshot()  # window survived the failed fetch
    assert after["rows"] - baseline["rows"] == 4.0
    assert after["batches"] - baseline["batches"] == 1.0


def test_padding_slots_never_poison_drift_gauges(engine, sample_request):
    """A grouped dispatch with PADDING slots (3 requests -> 4-slot
    bucket): the padding slot computes drift over zero rows, where the
    chi-squared path yields NaN — the fold must select it away, not
    multiply by zero (NaN * 0 is NaN and would poison drift_sum/drift_last
    in /metrics forever)."""
    engine.monitor_snapshot()  # drain any prior window
    engine.predict_group([[r] for r in _records(sample_request, 3)])
    window = jax.device_get(engine._acc)
    assert not np.isnan(np.asarray(window.drift_sum)).any()
    assert not np.isnan(np.asarray(window.drift_last)).any()
    snap = engine.monitor_snapshot()
    assert not any(np.isnan(v) for v in snap["drift_mean"].values())
    assert not any(np.isnan(v) for v in snap["drift_last"].values())


def test_novel_shape_compiles_once_outside_warmup(engine, sample_request):
    """An oversized request (no bucket) AOT-compiles into the dispatch
    table on first sight — outside the accumulator lock — and every
    repeat reuses the entry instead of recompiling."""
    n = engine.max_bucket + 3
    records = _records(sample_request, n, offset=11)
    key = ("bucket", n)
    engine._exec.pop(key, None)
    first = engine.predict_records(records)
    assert key in engine._exec
    fn = engine._exec[key]
    second = engine.predict_records(records)
    assert engine._exec[key] is fn
    assert first == second


def test_monitor_drift_last_matches_response(engine, sample_request):
    """After a solo dispatch, the aggregate's drift_last IS that batch's
    response drift (same round(6) discipline)."""
    records = _records(sample_request, 4, offset=7)
    response = engine.predict_records(records)
    snap = engine.monitor_snapshot()
    assert snap["drift_last"] == response["feature_drift_batch"]


# ----------------------------------------------------- batcher burst safety
def test_batcher_burst_never_cross_wires_responses(engine, sample_request):
    """A burst of DISTINCT concurrent requests through the overlapped
    dispatch/fetch ring: every response must carry its own request's
    prediction — no reordering, no cross-wired futures. Distinctness is
    asserted first so a swap cannot hide."""
    requests = [[r] for r in _records(sample_request, 40)]
    expected = [engine.predict_records(r) for r in requests]
    preds = [e["predictions"][0] for e in expected]
    # Sanity floor: most fixtures must map to distinct predictions, or a
    # swap could hide (f32 sigmoid collisions cost a few duplicates; the
    # elementwise comparison below is the actual cross-wiring check).
    assert len(set(preds)) >= (len(preds) * 3) // 4, "fixture degenerate"

    async def run():
        executor = concurrent.futures.ThreadPoolExecutor(max_workers=8)
        batcher = MicroBatcher(
            engine, executor, window_ms=2.0, max_group=8, max_inflight=3
        )
        try:
            return await asyncio.gather(
                *[batcher.predict(r) for r in requests]
            )
        finally:
            executor.shutdown(wait=True)

    got = asyncio.run(run())
    assert [g["predictions"] for g in got] == [
        e["predictions"] for e in expected
    ]
    assert [g["outliers"] for g in got] == [e["outliers"] for e in expected]


def test_batcher_two_phase_fetch_releases_dispatch_slot(engine, sample_request):
    """With max_inflight=1, a second group must still be DISPATCHABLE while
    the first group's fetch is blocked — the dispatch slot is released at
    fetch time (the fetch ring owns the blocking wait)."""
    import threading
    import time

    release = threading.Event()
    real_fetch = engine.fetch_group
    fetch_started = threading.Event()

    def slow_fetch(handle):
        fetch_started.set()
        release.wait(timeout=10)
        return real_fetch(handle)

    dispatches = []
    real_dispatch = engine.dispatch_group

    def counting_dispatch(requests):
        dispatches.append(time.monotonic())
        return real_dispatch(requests)

    async def run():
        executor = concurrent.futures.ThreadPoolExecutor(max_workers=4)
        batcher = MicroBatcher(
            engine, executor, window_ms=50.0, max_group=2, max_inflight=1
        )
        batcher.engine = _Proxy(engine, counting_dispatch, slow_fetch)
        # Suppress the idle fast path: these must ride GROUPED dispatches
        # (a full group of 2 closes the window early, so the big window
        # costs nothing).
        batcher._last_enqueue = asyncio.get_running_loop().time()
        first = [
            asyncio.create_task(batcher.predict([r]))
            for r in _records(sample_request, 2)
        ]
        await asyncio.get_running_loop().run_in_executor(
            None, fetch_started.wait, 10
        )
        # First group is parked in its (stalled) fetch. A second group must
        # still dispatch under max_inflight=1.
        second = [
            asyncio.create_task(batcher.predict([r]))
            for r in _records(sample_request, 2, offset=50)
        ]
        for _ in range(200):
            if len(dispatches) >= 2:
                break
            await asyncio.sleep(0.01)
        assert len(dispatches) >= 2, "second group never dispatched"
        release.set()
        out = await asyncio.gather(*first, *second)
        executor.shutdown(wait=True)
        return out

    responses = asyncio.run(run())
    assert len(responses) == 4
    for r in responses:
        assert 0.0 <= r["predictions"][0] <= 1.0


class _Proxy:
    """Engine wrapper overriding dispatch/fetch without mutating the
    session-shared engine."""

    def __init__(self, engine, dispatch, fetch):
        self._engine = engine
        self.dispatch_group = dispatch
        self.fetch_group = fetch

    def __getattr__(self, name):
        return getattr(self._engine, name)

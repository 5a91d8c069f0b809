"""Pipelined streaming executor (data/pipeline_exec.py) + its rewired
consumers: depth parity (bit-identical outputs serial vs overlapped),
bounded-queue backpressure, clean failure drain, and the satellite
vectorizations (reservoir scatter, vocab searchsorted encode)."""

import csv
import threading
import time

import numpy as np
import pytest

from mlops_tpu.data import generate_synthetic, write_csv_columns
from mlops_tpu.data.pipeline_exec import Stage, run_pipeline
from mlops_tpu.schema import SCHEMA


# --------------------------------------------------------------- executor
def test_executor_preserves_order_and_results_at_any_depth():
    expected = [-(x * x) for x in range(200)]
    for depth in (1, 2, 4, 8):
        out = []
        stats = run_pipeline(
            range(200),
            [Stage("sq", lambda x: x * x), Stage("neg", lambda x: -x)],
            out.append,
            depth=depth,
        )
        assert out == expected
        assert stats.items == 200
        assert stats.depth == max(1, depth)
        assert set(stats.stages) == {"read", "sq", "neg", "write"}


def test_executor_backpressure_bounds_in_flight_items():
    """A slow sink must throttle the source: in-flight items stay at the
    queue-bound ceiling regardless of source length."""
    lock = threading.Lock()
    state = {"produced": 0, "consumed": 0, "max_inflight": 0}

    def produce():
        for i in range(100):
            with lock:
                state["produced"] += 1
                state["max_inflight"] = max(
                    state["max_inflight"],
                    state["produced"] - state["consumed"],
                )
            yield i

    def slow_sink(_):
        time.sleep(0.002)
        with lock:
            state["consumed"] += 1

    depth = 2
    stages = [Stage("a", lambda x: x), Stage("b", lambda x: x)]
    run_pipeline(produce(), stages, slow_sink, depth=depth)
    # (stages + 1) bounded queues of `depth` plus one in-hand item per
    # worker (source, 2 stages, sink).
    ceiling = (len(stages) + 1) * depth + len(stages) + 2
    assert state["max_inflight"] <= ceiling


@pytest.mark.parametrize("where", ["source", "stage", "batch-stage", "sink"])
def test_executor_failure_propagates_and_drains(where):
    """The ORIGINAL exception must reach the caller from any position, with
    every worker thread joined (no hung threads, no blocked producers) —
    under SEEDED SCHEDULE PERTURBATION (analysis/lockcheck.py): each seed
    shifts which stages are mid-flight when the failure lands, so the
    drain path is exercised across genuinely different interleavings."""
    from mlops_tpu.analysis.lockcheck import SchedulePerturber

    for seed in (0, 1, 2):
        perturber = SchedulePerturber(seed, max_delay_s=0.0005)

        def src():
            for i in range(50):
                if where == "source" and i == 10:
                    raise ValueError("boom in source")
                yield i

        def mid(x):
            if where == "stage" and x == 10:
                raise ValueError("boom in stage")
            return x

        def batch(xs):
            if where == "batch-stage" and 10 in xs:
                raise ValueError("boom in batch-stage")
            return xs

        def sink(x):
            if where == "sink" and x == 10:
                raise ValueError("boom in sink")

        with pytest.raises(ValueError, match="boom"):
            run_pipeline(
                src(),
                [
                    Stage("mid", perturber.wrap(mid)),
                    Stage("batch", perturber.wrap(batch), batch_max=4),
                ],
                perturber.wrap(sink),
                depth=3,
            )
        # run_pipeline joins its workers before re-raising: its own threads
        # (an xdist worker's process has others, which come and go)
        leaked = [
            t.name for t in threading.enumerate() if t.name.startswith("pipeline-")
        ]
        assert not leaked, f"seed {seed} leaked {leaked}"


def test_executor_perturbed_schedules_bit_identical_across_seeds():
    """Three seeded schedules, one answer: random per-stage delays shift
    thread interleavings (and batch-gather groupings) run to run, while
    FIFO ordering must keep the output BIT-IDENTICAL to the serial loop."""
    from mlops_tpu.analysis.lockcheck import SchedulePerturber

    expected = [-(x * x) for x in range(150)]
    for seed in (0, 1, 2):
        perturber = SchedulePerturber(seed, max_delay_s=0.0005)
        out = []
        stats = run_pipeline(
            range(150),
            [
                Stage("sq", perturber.wrap(lambda x: x * x)),
                Stage(
                    "neg",
                    perturber.wrap(lambda xs: [-x for x in xs]),
                    batch_max=4,
                ),
            ],
            perturber.wrap(out.append),
            depth=3,
        )
        assert out == expected, f"seed {seed} output diverged"
        assert stats.items == 150


def test_executor_batch_stage_is_grouping_invariant():
    """Batch gathers vary with timing; results must not."""
    expected = [x * 3 for x in range(100)]
    for depth in (1, 3, 8):
        out = []
        run_pipeline(
            range(100),
            [Stage("b", lambda xs: [x * 3 for x in xs], batch_max=5)],
            out.append,
            depth=depth,
        )
        assert out == expected


def test_executor_stage_timing_reports_occupancy():
    stats = run_pipeline(
        range(20),
        [Stage("work", lambda x: (time.sleep(0.001), x)[1])],
        lambda _: None,
        depth=2,
    )
    work = stats.stages["work"]
    assert work["items"] == 20
    assert work["busy_s"] >= 0.02
    assert 0.0 < work["occupancy"] <= 1.5
    assert stats.depth == 2


# ------------------------------------------------------------ queue waits
WAIT_KEYS = ("wait_in_s", "wait_out_s", "max_wait_in_s", "max_wait_out_s")
DELAY_S, DELAYED = 0.2, 5  # the one slow execution, and its ordinal
ITEMS = 14


def _run_with_one_slow(where: str, depth: int = 2):
    """``span`` -> ``transfer`` -> ``compute`` -> ``store`` over ``ITEMS``
    items, item ``DELAYED`` sleeping ``DELAY_S`` in stage ``where``; the
    stats, and the seconds that execution really took."""
    took = {}

    def stage_fn(name):
        def fn(x):
            if name == where and x == DELAYED:
                start = time.perf_counter()
                time.sleep(DELAY_S)
                took["s"] = time.perf_counter() - start
            return x
        return fn

    stats = run_pipeline(
        range(ITEMS),
        [Stage("transfer", stage_fn("transfer")), Stage("compute", stage_fn("compute"))],
        stage_fn("store"),
        depth=depth,
        source_name="span",
        sink_name="store",
    )
    return stats, took["s"]


def upstream_is_the_pace(depth=2):
    """A slow ``transfer``: the dispatch thread had nothing to dispatch."""
    stats, took = _run_with_one_slow("transfer")
    compute, transfer = stats.stages["compute"], stats.stages["transfer"]
    assert compute["wait_in_s"] == pytest.approx(took, rel=0.2)
    assert compute["max_wait_in_s"] == pytest.approx(took, rel=0.2)
    assert compute["max_wait_in_at"] == DELAYED, "the chunk it waited for"
    assert compute["wait_out_s"] < 0.2 * took
    assert (transfer["max_busy_at"], transfer["items"]) == (DELAYED, ITEMS)
    assert transfer["max_busy_s"] == pytest.approx(took, rel=0.2)
    # the sink waited for the same chunk, the source behind a full queue
    assert stats.stages["store"]["max_wait_in_at"] == DELAYED
    assert stats.stages["span"]["wait_out_s"] == pytest.approx(took, rel=0.2)
    return stats


def downstream_is_the_pace(depth=2):
    """A slow sink: the stage before it blocks on its full output queue
    once ``depth`` results lie there, one in its own hands."""
    stats, took = _run_with_one_slow("store")
    compute = stats.stages["compute"]
    assert compute["wait_out_s"] == pytest.approx(took, rel=0.2)
    assert compute["max_wait_out_s"] == pytest.approx(took, rel=0.2)
    assert compute["max_wait_out_at"] == DELAYED + depth + 1
    assert compute["wait_in_s"] < 0.2 * took
    assert stats.stages["store"]["max_busy_at"] == DELAYED
    # a sink has no output queue, a source no input queue
    assert stats.stages["store"]["max_wait_out_at"] is None
    assert stats.stages["span"]["max_wait_in_at"] is None
    return stats


def serial_mode_has_no_queues(depth=1):
    stats, took = _run_with_one_slow("transfer", depth=depth)
    for stage in stats.stages.values():
        assert [stage[key] for key in WAIT_KEYS] == [0.0] * 4
        assert stage["max_wait_in_at"] is None and stage["max_wait_out_at"] is None
    assert stats.stages["transfer"]["max_busy_at"] == DELAYED
    assert stats.stages["transfer"]["max_busy_s"] == pytest.approx(took, rel=0.2)
    return stats


@pytest.mark.parametrize("case", [
    upstream_is_the_pace, downstream_is_the_pace, serial_mode_has_no_queues,
], ids=lambda case: case.__name__)
def test_executor_times_its_queue_waits_by_side(case):
    stats = case()
    assert set(stats.stages) == {"span", "transfer", "compute", "store"}
    for name, stage in stats.stages.items():
        # busy or blocked on one queue or the other: a thread's whole time
        assert (
            stage["busy_s"] + stage["wait_in_s"] + stage["wait_out_s"]
            <= stats.wall_s + 0.005
        ), name
        assert stage["max_busy_s"] <= stage["busy_s"] + 1e-4
        assert stage["max_wait_in_s"] <= stage["wait_in_s"] + 1e-6
        assert stage["max_wait_out_s"] <= stage["wait_out_s"] + 1e-6


def test_batch_stage_times_each_put_and_jax_free_clock_still_reports():
    """A batch stage's waits belong to the execution that made the batch;
    a `StageClock` without spans (`compilecache/cache.py`) reports as ever."""
    from mlops_tpu.utils.timing import StageClock

    stats = run_pipeline(
        range(40),
        [Stage("fetch", lambda xs: xs, batch_max=8, queue_depth=8)],
        lambda x: time.sleep(0.002),
        depth=2,
    )
    fetch = stats.stages["fetch"]
    assert fetch["items"] == 40 and fetch["wait_out_s"] > 0.02
    assert 0 <= fetch["max_wait_out_at"] < 40
    clock = StageClock()
    with clock.stage("compile"):
        pass
    assert clock.report(1.0)["compile"]["items"] == 1


# ------------------------------------------------- satellite vectorizations
def test_reservoir_scatter_bit_identical_to_loop():
    """The vectorized last-write-wins scatter must replay the replaced
    per-value loop exactly, duplicate slots included."""
    from mlops_tpu.data.stream import StreamingStats

    def loop_fold(reservoir, values, seen, k, rng):
        if reservoir.size < k:
            taken = min(k - reservoir.size, values.size)
            reservoir = np.concatenate([reservoir, values[:taken]])
            values = values[taken:]
            seen += taken
        if values.size == 0:
            return reservoir
        idx = seen + 1 + np.arange(values.size, dtype=np.float64)
        accept = rng.random(values.size) < (k / idx)
        slots = rng.integers(0, k, size=values.size)
        for v, s in zip(values[accept], slots[accept]):
            reservoir[s] = v
        return reservoir

    rng_data = np.random.default_rng(3)
    k = 64  # tiny reservoir -> dense slot collisions
    stats = StreamingStats(reservoir_size=k, seed=9)
    reference = np.empty(0, np.float64)
    ref_rng = np.random.default_rng(9)
    reservoir = np.empty(0, np.float64)
    seen = 0
    for _ in range(6):
        values = rng_data.normal(size=500)
        reference = loop_fold(reference.copy(), values, seen, k, ref_rng)
        reservoir = stats._fold_reservoir(reservoir, values, seen)
        seen += values.size
        np.testing.assert_array_equal(reservoir, reference)


def test_vectorized_encode_matches_dict_lookup_reference():
    from mlops_tpu.data import Preprocessor

    columns, labels = generate_synthetic(2000, seed=12)
    feat = SCHEMA.categorical[1]
    vals = list(columns[feat.name])
    vals[0] = ""  # missing -> OOV
    vals[1] = "never_seen"  # unseen -> OOV
    vals[2] = feat.vocab[0] + "_suffix"  # longer than any vocab word -> OOV
    vals[3] = feat.vocab[-1]
    columns[feat.name] = vals
    prep = Preprocessor.fit(columns)
    ds = prep.encode(columns, labels)
    for j, f in enumerate(SCHEMA.categorical):
        lut = {v: i for i, v in enumerate(f.vocab)}
        expected = [lut.get(v, f.oov_id) for v in columns[f.name]]
        np.testing.assert_array_equal(ds.cat_ids[:, j], expected)


# ---------------------------------------------------------- raw byte reader
def test_raw_chunk_reader_reassembles_to_batch_read(tmp_path):
    from mlops_tpu.data import Preprocessor, load_csv_columns
    from mlops_tpu.data.stream import iter_raw_csv_chunks
    from mlops_tpu.native import encode_csv_bytes, native_available

    columns, labels = generate_synthetic(3000, seed=4)
    path = tmp_path / "plain.csv"
    write_csv_columns(path, columns, labels)
    prep = Preprocessor.fit(columns)
    batch = prep.encode(*load_csv_columns(path))

    chunks = list(iter_raw_csv_chunks(path, chunk_rows=700))
    assert [kind for kind, _ in chunks] == ["bytes"] * len(chunks)
    if not native_available():
        pytest.skip("native kernel unavailable")
    encoded = [encode_csv_bytes(payload, prep) for _, payload in chunks]
    assert [e.n for e in encoded[:-1]] == [700] * (len(encoded) - 1)
    np.testing.assert_array_equal(
        np.concatenate([e.cat_ids for e in encoded]), batch.cat_ids
    )
    np.testing.assert_array_equal(
        np.concatenate([e.numeric for e in encoded]), batch.numeric
    )


def test_raw_chunk_reader_degrades_on_quoted_fields(tmp_path):
    """A quote anywhere flips the reader to the csv-module tail — row
    content must survive, including a quoted embedded newline."""
    columns, labels = generate_synthetic(50, seed=6)
    path = tmp_path / "quoted.csv"
    write_csv_columns(path, columns, labels)
    text = path.read_text().splitlines()
    row = text[11].split(",")  # line 11 = data row 10 (line 0 is the header)
    row[1] = '"uni\nversity"'  # quoted field with embedded newline
    text[11] = ",".join(row)
    path.write_text("\n".join(text) + "\n")

    from mlops_tpu.data.stream import iter_raw_csv_chunks

    kinds, total = [], 0
    edu = []
    for kind, payload in iter_raw_csv_chunks(path, chunk_rows=20):
        kinds.append(kind)
        assert kind == "columns"
        total += len(payload[SCHEMA.categorical[0].name])
        edu.extend(payload["education"])
    assert total == 50
    assert edu[10] == "uni\nversity"


def test_raw_chunk_reader_handles_crlf(tmp_path):
    columns, labels = generate_synthetic(40, seed=7)
    path = tmp_path / "crlf.csv"
    write_csv_columns(path, columns, labels)
    path.write_bytes(path.read_bytes().replace(b"\r\n", b"\n").replace(b"\n", b"\r\n"))

    from mlops_tpu.data.stream import iter_raw_csv_chunks
    from mlops_tpu.data import Preprocessor
    from mlops_tpu.native import encode_csv_bytes, native_available

    if not native_available():
        pytest.skip("native kernel unavailable")
    prep = Preprocessor.fit(columns)
    chunks = list(iter_raw_csv_chunks(path, chunk_rows=16))
    encoded = [encode_csv_bytes(payload, prep) for _, payload in chunks]
    assert sum(e.n for e in encoded) == 40


# ----------------------------------------------------------- depth parity
@pytest.fixture(scope="module")
def stream_setup(tiny_pipeline, tmp_path_factory):
    from mlops_tpu.bundle import load_bundle

    _, result = tiny_pipeline
    bundle = load_bundle(result.bundle_dir)
    root = tmp_path_factory.mktemp("pipe")
    columns, labels = generate_synthetic(3000, seed=21)
    path = root / "in.csv"
    write_csv_columns(path, columns, labels)
    return bundle, path, root


def test_stream_scoring_depth_parity_bit_identical(stream_setup):
    """score_csv_stream at depth 1 vs 4 (and python vs native parse) must
    write byte-identical output files and equal aggregate stats."""
    from mlops_tpu.data.stream import score_csv_stream

    bundle, path, root = stream_setup
    runs = {}
    for name, kwargs in (
        ("serial-python", dict(pipeline_depth=1, native=False)),
        ("serial-auto", dict(pipeline_depth=1)),
        ("deep-auto", dict(pipeline_depth=4)),
    ):
        out = root / f"{name}.csv"
        stats = score_csv_stream(bundle, path, out, chunk_rows=512, **kwargs)
        runs[name] = (out.read_bytes(), stats)
    baseline_bytes, baseline_stats = runs["serial-python"]
    for name, (data, stats) in runs.items():
        assert data == baseline_bytes, f"{name} output diverged"
        assert stats["rows"] == 3000
        assert stats["mean_prediction"] == baseline_stats["mean_prediction"]
        assert stats["outlier_rate"] == baseline_stats["outlier_rate"]
        assert set(stats["stages"]) >= {"read", "encode", "compute", "write"}


def test_fit_streaming_depth_parity_bit_identical(stream_setup):
    from mlops_tpu.data import fit_streaming

    _, path, _ = stream_setup
    serial = fit_streaming(path, chunk_rows=700, pipeline_depth=1)
    deep = fit_streaming(path, chunk_rows=700, pipeline_depth=4)
    np.testing.assert_array_equal(serial.numeric_median, deep.numeric_median)
    np.testing.assert_array_equal(serial.numeric_mean, deep.numeric_mean)
    np.testing.assert_array_equal(serial.numeric_std, deep.numeric_std)


@pytest.mark.slow  # unique 1024-chunk compile; the serial 870s tier-1
# gate is at capacity (CI's parallel job still runs slow tests)
def test_score_dataset_depth_parity_bit_identical(stream_setup):
    from mlops_tpu.parallel.bulk import score_dataset

    bundle, _, _ = stream_setup
    columns, _ = generate_synthetic(5000, seed=31)
    ds = bundle.preprocessor.encode(columns)
    serial = score_dataset(bundle, ds, chunk_rows=1024, pipeline_depth=1)
    deep = score_dataset(bundle, ds, chunk_rows=1024, pipeline_depth=4)
    np.testing.assert_array_equal(serial.predictions, deep.predictions)
    np.testing.assert_array_equal(serial.outliers, deep.outliers)
    assert deep.pipeline is not None
    assert set(deep.pipeline["stages"]) >= {"slice", "compute", "fetch"}
    assert "pipeline" in deep.summary()


# ------------------------------------------------------------ fault drain
def _thread_names():
    return {t.name for t in threading.enumerate()}


def test_encode_fault_drains_pipeline_and_leaves_no_output(
    stream_setup, monkeypatch
):
    """A mid-stream encode exception must propagate (original type), join
    every pipeline thread, and leave NO output file behind — neither the
    final path nor the .tmp working file."""
    from mlops_tpu.data.encode import Preprocessor
    from mlops_tpu.data.stream import score_csv_stream

    bundle, path, root = stream_setup
    calls = {"n": 0}
    real_encode = Preprocessor.encode

    def flaky_encode(self, columns, labels=None, schema=SCHEMA):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("encode blew up mid-stream")
        return real_encode(self, columns, labels, schema)

    monkeypatch.setattr(Preprocessor, "encode", flaky_encode)
    out = root / "fault.csv"
    before = _thread_names()
    # chunk_rows=512 shares the parity tests' compiled chunk program
    # (persistent compile cache) — tier-1 wall budget is tight.
    with pytest.raises(RuntimeError, match="encode blew up"):
        score_csv_stream(
            bundle, path, out, chunk_rows=512, pipeline_depth=4, native=False
        )
    assert calls["n"] >= 3
    assert not out.exists()
    assert not list(root.glob("*.tmp"))
    assert _thread_names() == before


def test_device_fault_drains_pipeline_and_propagates(
    stream_setup, monkeypatch
):
    """Same contract when the DEVICE stage fails (compute raising mid-
    sweep): pipeline drains, original exception propagates, no output."""
    import mlops_tpu.parallel.bulk as bulk

    from mlops_tpu.data.stream import score_csv_stream

    bundle, path, root = stream_setup
    real_make = bulk.make_chunk_scorer

    def flaky_scorer_factory(*args, **kwargs):
        scorer = real_make(*args, **kwargs)
        calls = {"n": 0}

        def flaky(cat, num, mask):
            calls["n"] += 1
            if calls["n"] == 4:  # past warmup + first chunks
                raise RuntimeError("device fell over")
            return scorer(cat, num, mask)

        return flaky

    monkeypatch.setattr(bulk, "make_chunk_scorer", flaky_scorer_factory)
    out = root / "devfault.csv"
    before = _thread_names()
    with pytest.raises(RuntimeError, match="device fell over"):
        score_csv_stream(bundle, path, out, chunk_rows=512, pipeline_depth=4)
    assert not out.exists()
    assert not list(root.glob("*.tmp"))
    assert _thread_names() == before


# --------------------------------------------------------- throughput smoke
@pytest.mark.slow
def test_pipelined_throughput_beats_old_serial_path(tiny_pipeline, tmp_path):
    """On a synthetic 200k-row dataset the pipelined path (native chunk
    encode, depth 2) must beat the pre-executor serial path (Python csv
    parse, depth 1) on rows/s."""
    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.data.stream import score_csv_stream

    _, result = tiny_pipeline
    bundle = load_bundle(result.bundle_dir)
    columns, labels = generate_synthetic(200_000, seed=5)
    path = tmp_path / "big.csv"
    write_csv_columns(path, columns, labels)

    def best_rows_per_s(**kwargs):
        return max(
            score_csv_stream(
                bundle, path, None, chunk_rows=16_384, **kwargs
            )["rows_per_s"]
            for _ in range(2)
        )

    serial = best_rows_per_s(pipeline_depth=1, native=False)
    pipelined = best_rows_per_s(pipeline_depth=2)
    assert pipelined >= serial, (pipelined, serial)

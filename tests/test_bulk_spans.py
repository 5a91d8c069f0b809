"""A bulk job says where its time went, and what it re-traced, from inside
the program (ISSUE 25): ``mlops:bulk.*`` and ``mlops:pipe.*`` spans in a
profiler trace, `BulkScoreResult.phases` and ``compile_events`` always,
device scopes on what flax does not name. And it re-traces nothing it has
compiled before (ISSUE 28): the chunk program is kept from job to job.
Its own account of itself is ONE record that outlives the call (ISSUE 35):
`BulkScoreResult`, the summary, the marker and ``job_log()`` read it.
All on the CPU: what is asserted is what the program writes, never a
time."""

import functools
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import program_spans

from mlops_tpu.bundle.bundle import Bundle
from mlops_tpu.compilecache.events import compile_counter
from mlops_tpu.config import ModelConfig
from mlops_tpu.data.encode import EncodedDataset, Preprocessor
from mlops_tpu.models import build_model, init_params
from mlops_tpu.monitor.state import fit_monitor
from mlops_tpu.parallel import bulk
from mlops_tpu.parallel.bulk import (
    CHUNK_PROGRAMS,
    PHASES,
    make_bulk_jit,
    score_dataset,
)
from mlops_tpu.schema import SCHEMA
from mlops_tpu.utils.timing import pause_counter

ROWS, CHUNK = 700, 256  # 2 whole chunks and a padded tail
CHUNKS = math.ceil(ROWS / CHUNK)


TINY_BERT = ModelConfig(family="bert", token_dim=32, depth=2, heads=2)


def _bundle(ds, config=TINY_BERT, weights_seed=0, reference_rows=ROWS):
    """A bundle made by hand (no training run): a NEW model object of
    ``config`` every time, weights from ``weights_seed``, a monitor fitted
    on the first ``reference_rows`` rows."""
    model = build_model(config)
    zeros = np.zeros(SCHEMA.num_numeric, np.float32)
    return Bundle(
        manifest={"flavor": "flax", "model_config": {},
                  "calibration": {"temperature": 1.5}},
        model=model,
        variables=init_params(model, jax.random.PRNGKey(weights_seed)),
        preprocessor=Preprocessor(zeros, zeros, zeros + 1, SCHEMA.fingerprint()),
        monitor=fit_monitor(ds.slice(np.arange(reference_rows))),
    )


@pytest.fixture(scope="module")
def tiny_bert():
    """A 2-layer `bert` bundle, and rows."""
    rng = np.random.default_rng(0)
    cat = np.stack([rng.integers(0, c, ROWS) for c in SCHEMA.cards], 1)
    ds = EncodedDataset(
        cat.astype(np.int32),
        rng.normal(size=(ROWS, SCHEMA.num_numeric)).astype(np.float32),
    )
    return _bundle(ds), ds


def _job(bundle, ds, depth=2, chunk_rows=CHUNK):
    start = time.perf_counter()
    result = score_dataset(bundle, ds, chunk_rows=chunk_rows, exact=True,
                           pipeline_depth=depth)
    return result, time.perf_counter() - start


@pytest.fixture(scope="module")
def traced_jobs(tiny_bert, tmp_path_factory):
    """Two jobs back to back under one profiler session, the counter's
    process-wide sums around them, and one job with no session open."""
    bundle, ds = tiny_bert
    untraced, _ = _job(bundle, ds)
    counter = compile_counter()
    before = counter.snapshot()
    with program_spans(tmp_path_factory.mktemp("profile")) as spans:
        jobs = [_job(bundle, ds), _job(bundle, ds)]
    return {
        "spans": spans,
        "jobs": jobs,
        "untraced": untraced,
        "totals": (before["totals"], counter.snapshot()["totals"]),
    }


def _named(spans, name):
    return [span for span in spans if span[0] == name]


def one_job_span_per_call(spans):
    jobs = _named(spans, "mlops:bulk.job")
    assert len(jobs) == 2
    numbers = [job[3]["job"] for job in jobs]
    assert numbers[1] == numbers[0] + 1, "a per-process count"
    for job in jobs:
        assert {"pid", "rows", "chunk_rows", "chunks", "path"} <= set(job[3])
        assert (job[3]["rows"], job[3]["chunk_rows"], job[3]["chunks"]) == (
            ROWS, CHUNK, CHUNKS)
        assert job[3]["path"] == "exact"


def phases_nest_inside_their_job_in_order(spans):
    for _, lo, hi, attrs in _named(spans, "mlops:bulk.job"):
        inner = [
            span for span in spans
            if span[0].startswith("mlops:bulk.") and span[0] != "mlops:bulk.job"
            and span[3]["job"] == attrs["job"]
        ]
        assert [span[0] for span in inner] == [
            f"mlops:bulk.{name}" for name in (*PHASES, "compile_events")
        ]
        edges = [lo, *[t for span in inner for t in span[1:3]], hi]
        assert edges == sorted(edges), "nested, one after the other"


def every_pipe_span_carries_its_job(spans):
    jobs = {job[3]["job"]: job for job in _named(spans, "mlops:bulk.job")}
    pipe = [span for span in spans if span[0].startswith("mlops:pipe.")]
    assert {span[0] for span in pipe} == {
        f"mlops:pipe.{stage}"
        for stage in ("span", "slice", "transfer", "compute", "fetch", "store")
    }
    for name, lo, hi, attrs in pipe:
        (sweep,) = [
            span for span in _named(spans, "mlops:bulk.sweep")
            if span[3]["job"] == attrs["job"]
        ]
        assert attrs["job"] in jobs and attrs["items"] >= 1
        # a stage runs on its own thread, inside its job's sweep
        assert sweep[1] <= lo and hi <= sweep[2], name


def compute_spans_number_the_chunks(spans):
    for job in _named(spans, "mlops:bulk.job"):
        compute = [
            span for span in _named(spans, "mlops:pipe.compute")
            if span[3]["job"] == job[3]["job"]
        ]
        assert len(compute) == CHUNKS
        fetched = sum(
            span[3]["items"] for span in _named(spans, "mlops:pipe.fetch")
            if span[3]["job"] == job[3]["job"]
        )
        assert fetched == CHUNKS


@pytest.mark.parametrize("check", [
    one_job_span_per_call,
    phases_nest_inside_their_job_in_order,
    every_pipe_span_carries_its_job,
    compute_spans_number_the_chunks,
], ids=lambda check: check.__name__)
def test_profile_of_two_jobs(traced_jobs, check):
    check(traced_jobs["spans"])


@pytest.mark.parametrize("which", [0, 1], ids=["first_job", "second_job"])
def test_phases_sum_to_the_jobs_wall_time(traced_jobs, which):
    result, wall = traced_jobs["jobs"][which]
    assert tuple(result.phases) == PHASES
    assert sum(result.phases.values()) == pytest.approx(wall, rel=0.05)
    assert result.phases["sweep"] == pytest.approx(result.elapsed_s, rel=0.05)
    record = result.record
    assert sum(record["phases"].values()) == pytest.approx(
        record["wall_s"], abs=0.005 + 0.01 * record["wall_s"])
    assert record["wall_s"] <= wall
    summary = result.summary()
    assert set(summary["phases"]) == set(PHASES)
    assert summary["compile_events"] == result.compile_events


def job_reuses_its_chunk_program(traced_jobs, which):
    events = traced_jobs["jobs"][which][0].compile_events
    # both follow an untraced job of the same bundle: the chunk program is
    # kept, so neither traces, lowers nor compiles it again
    assert events["chunk_program_reused"] == 1
    assert "fused" not in events["programs"]
    assert events["lower_s"] == 0 and events["backend_compile_s"] == 0
    marker = _named(traced_jobs["spans"], "mlops:bulk.compile_events")[which][3]
    assert marker["chunk_program_reused"] == 1
    assert events["drift_program_reused"] == marker["drift_program_reused"] == 1
    assert marker["programs_traced"] == events["programs_traced"]
    # a profiler drops an empty attribute: a job that traced nothing has none
    assert marker.get("programs", "") == "|".join(events["programs"])
    for key in ("trace_s", "lower_s", "backend_compile_s", "cache_retrieval_s"):
        assert marker[key] == pytest.approx(events[key])
    assert (marker["cache_hits"], marker["cache_misses"]) == (
        events["cache_hits"], events["cache_misses"])


def process_wide_sums_are_the_jobs_deltas(traced_jobs, _):
    before, after = traced_jobs["totals"]
    deltas = [job[0].compile_events for job in traced_jobs["jobs"]]
    for key in before:
        assert after[key] - before[key] == pytest.approx(
            sum(delta[key] for delta in deltas), abs=1e-5), key


@pytest.mark.parametrize("check,which", [
    (job_reuses_its_chunk_program, 0),
    (job_reuses_its_chunk_program, 1),
    (process_wide_sums_are_the_jobs_deltas, None),
], ids=["first_job", "second_job", "process_wide_sums"])
def test_compile_events(traced_jobs, check, which):
    check(traced_jobs, which)


# ------------------------------------------------------ the job's record
RECORD_KEYS = {
    "job", "rows", "chunk_rows", "chunks", "path", "histories",
    "tail_chunk_rows", "rows_run", "started", "wall_s", "phases",
    "compile_events", "depth", "stages", "pauses",
}


def _logged(job: int) -> dict:
    (record,) = [r for r in bulk.job_log() if r["job"] == job]
    return record


def _plain(value) -> bool:
    """Numbers, strings and lists and dicts of them: no array, nothing
    that could hold a scorer, a bundle or a dataset."""
    if isinstance(value, dict):
        return all(isinstance(k, str) and _plain(v) for k, v in value.items())
    if isinstance(value, list):
        return all(map(_plain, value))
    return value is None or type(value) in (int, float, str, bool)


def result_and_log_hold_the_one_record(traced_jobs, which):
    result, _ = traced_jobs["jobs"][which]
    record = result.record
    assert set(record) == RECORD_KEYS  # a dense model: no ``routing``
    assert _logged(record["job"]) is record
    assert result.phases is record["phases"]
    assert result.compile_events is record["compile_events"]
    assert result.pipeline["stages"] is record["stages"]
    assert (result.rows, result.path) == (record["rows"], record["path"])
    assert (record["rows"], record["chunk_rows"], record["chunks"],
            record["histories"], record["depth"]) == (ROWS, CHUNK, CHUNKS, ROWS, 2)
    # a tail of 188 rows rounds back up to the chunk: every run is 256
    assert (record["tail_chunk_rows"], record["rows_run"]) == (CHUNK, CHUNKS * CHUNK)
    assert result.pipeline["items"] == CHUNKS
    assert set(record["pauses"]) == {"gc_s", "gc_collections", "gc_gen2_s"}
    assert _plain(record)
    for name in ("span", "slice", "transfer", "compute", "fetch", "store"):
        assert {"busy_s", "wait_in_s", "wait_out_s", "max_busy_at",
                "max_wait_in_at", "max_wait_out_at"} <= set(record["stages"][name])
    assert record["stages"]["compute"]["items"] == CHUNKS


def summary_prints_the_record(traced_jobs, which):
    result, _ = traced_jobs["jobs"][which]
    record, summary = result.record, result.summary()
    for key in RECORD_KEYS - {"stages", "depth"}:
        assert summary[key] == record[key], key
    assert summary["pipeline"]["stages"] == record["stages"]
    assert summary["pipeline"]["depth"] == record["depth"]
    assert {"elapsed_s", "rows_per_s", "default_rate", "outlier_rate",
            "feature_drift_batch"} <= set(summary)


def spans_read_the_record(traced_jobs, which):
    record = traced_jobs["jobs"][which][0].record
    span = _named(traced_jobs["spans"], "mlops:bulk.job")[which]
    marker = _named(traced_jobs["spans"], "mlops:bulk.compile_events")[which][3]
    for key in ("job", "rows", "chunk_rows", "chunks", "path", "histories",
                "tail_chunk_rows", "rows_run"):
        assert span[3][key] == record[key], key
    # the record's clock at the span's opening, and the span's own length
    assert span[3]["started"] == pytest.approx(record["started"], abs=1e-5)
    assert (span[2] - span[1]) / 1e9 == pytest.approx(record["wall_s"], abs=0.005)
    events = record["compile_events"]
    # a profiler drops an empty attribute: a job that traced nothing has none
    assert set(marker) | {"programs"} == {"job", *events}
    assert marker["job"] == record["job"]
    assert marker.get("programs", "") == "|".join(events["programs"])
    for key in set(events) - {"programs"}:
        assert marker[key] == pytest.approx(events[key]), key
    assert "cache_requests" in events


@pytest.mark.parametrize("which", [0, 1], ids=["first_job", "second_job"])
@pytest.mark.parametrize("check", [
    result_and_log_hold_the_one_record,
    summary_prints_the_record,
    spans_read_the_record,
], ids=lambda check: check.__name__)
def test_everything_that_reports_a_job_reads_its_record(traced_jobs, check, which):
    check(traced_jobs, which)


def test_log_keeps_the_newest_records_oldest_first(traced_jobs):
    assert bulk.LOGGED_JOBS == 64 and bulk.JOB_LOG._records.maxlen == 64
    log = bulk.JobLog(bulk.LOGGED_JOBS)
    for job in range(70):
        log.append({"job": job})
    assert [record["job"] for record in log.records()] == list(range(6, 70))
    # the process's own log: every job of this module so far, in order,
    # the untraced one (whose result the fixture could have dropped) among them
    numbers = [record["job"] for record in bulk.job_log()]
    assert numbers == sorted(numbers) and len(numbers) <= bulk.LOGGED_JOBS
    assert traced_jobs["untraced"].record["job"] in numbers
    assert all(map(_plain, bulk.job_log()))


def test_log_tells_a_process_first_job_from_its_second(tiny_bert):
    """What a driver's dropped warm-up job leaves behind: the first job of
    an architecture compiled its chunk program and says so, the second
    found it compiled."""
    _, ds = tiny_bert
    CHUNK_PROGRAMS.clear()  # whatever ran before: this process has not seen it
    bundle = _bundle(ds)
    _job(bundle, ds)
    _job(bundle, ds)  # both results dropped
    first, second = bulk.job_log()[-2:]
    assert second["job"] == first["job"] + 1
    assert first["compile_events"]["chunk_program_reused"] == 0
    assert "fused" in first["compile_events"]["programs"]
    assert first["compile_events"]["lower_s"] > 0
    assert first["phases"]["warmup"] > second["phases"]["warmup"]
    events = second["compile_events"]
    assert events["chunk_program_reused"] == 1 and "fused" not in events["programs"]
    assert events["trace_s"] + events["lower_s"] + events["backend_compile_s"] < 0.05
    assert events["cache_requests"] >= events["cache_hits"]


def test_pauses_count_the_collections_inside_a_job(tiny_bert, monkeypatch):
    """One callback a process, and a collection that happens inside a job
    is in that job's ``pauses``."""
    bundle, ds = tiny_bert
    counter = pause_counter()
    assert pause_counter() is counter
    assert gc.callbacks.count(counter._on_gc) == 1
    before = counter.snapshot()
    gc.collect()
    delta = counter.delta(before, counter.snapshot())
    assert delta["gc_collections"] >= 1
    assert 0 < delta["gc_gen2_s"] <= delta["gc_s"]

    real = bulk.drift_scores

    def collecting(*args):
        gc.collect()
        return real(*args)

    monkeypatch.setattr(bulk, "drift_scores", collecting)
    result, _ = _job(bundle, ds)
    pauses = result.record["pauses"]
    assert pauses["gc_collections"] >= 1
    assert 0 < pauses["gc_gen2_s"] <= pauses["gc_s"] <= result.phases["drift"]


def test_the_drift_sample_is_one_program_compiled_once(tiny_bert, monkeypatch):
    """A job's drift sample is one compiled program: the first job of a
    sample length traces it, the next traces nothing in its drift phase,
    and both answer what the eager ``drift_scores`` answers on the sample."""
    from mlops_tpu.monitor.state import drift_scores as eager_drift_scores

    bundle, ds = tiny_bert
    rows = 613  # a sample length no other job of this module has
    sample = ds.slice(np.arange(rows))
    counter = compile_counter()
    traced = []
    program = bulk.drift_scores

    def watched_drift(*args):
        before = counter.snapshot()
        try:
            return program(*args)
        finally:
            traced.append(counter.delta(before, counter.snapshot()))

    monkeypatch.setattr(bulk, "drift_scores", watched_drift)
    first, _ = _job(bundle, sample)
    second, _ = _job(bundle, sample)
    assert first.compile_events["drift_program_reused"] == 0
    assert "drift_scores" in traced[0]["programs"]
    assert "drift_scores" in first.compile_events["programs"]
    assert second.compile_events["drift_program_reused"] == 1
    assert traced[1]["programs_traced"] == 0 and traced[1]["lower_s"] == 0
    assert "drift_scores" not in second.compile_events["programs"]
    assert _logged(second.record["job"])["compile_events"]["drift_program_reused"] == 1
    eager = np.asarray(eager_drift_scores(
        bundle.monitor, sample.cat_ids, sample.numeric, np.ones(rows, bool)))
    for result in (first, second):
        served = np.asarray(list(result.feature_drift.values()), np.float32)
        # the statistics are exact; the p-value series may round apart by a
        # few float32 steps where the compiler fuses it
        np.testing.assert_allclose(
            served, eager, rtol=0, atol=8 * np.finfo(np.float32).eps)


# ------------------------------------------- the chunk program is kept
@pytest.fixture
def watched(monkeypatch):
    """What a job does where: the compile counter's delta around its
    warm-up and around its sweep, and how often its scorer is called."""
    from mlops_tpu.data import pipeline_exec

    counter = compile_counter()
    seen = {"calls": 0}

    def around(name, fn):
        def watched_fn(*args, **kwargs):
            before = counter.snapshot()
            try:
                return fn(*args, **kwargs)
            finally:
                seen[name] = counter.delta(before, counter.snapshot())
        return watched_fn

    make = bulk.make_chunk_scorer

    def counted(*args, **kwargs):
        scorer = make(*args, **kwargs)

        @functools.wraps(scorer)  # what the scorer says of itself goes along
        def score_chunk(cat, num, mask):
            seen["calls"] += 1
            return scorer(cat, num, mask)
        return score_chunk

    monkeypatch.setattr(bulk, "make_chunk_scorer", counted)
    monkeypatch.setattr(
        bulk, "warm_chunk_scorer", around("warmup", bulk.warm_chunk_scorer))
    monkeypatch.setattr(
        pipeline_exec, "run_pipeline", around("sweep", pipeline_exec.run_pipeline))
    return seen


def _compiled_inside_warmup(result, seen):
    """The job compiled its chunk program, all of it before the sweep."""
    events = result.compile_events
    assert events["chunk_program_reused"] == 0 and "fused" in events["programs"]
    assert "fused" in seen["warmup"]["programs"]
    assert seen["warmup"]["lower_s"] > 0
    # the phase holds the compile: JAX times it inside the warm-up call
    assert result.phases["warmup"] >= sum(
        seen["warmup"][key] for key in ("trace_s", "lower_s", "backend_compile_s"))
    _sweep_compiled_nothing(seen)


def _sweep_compiled_nothing(seen):
    """``elapsed_s`` holds no trace, lowering or compile."""
    sweep = seen["sweep"]
    assert sweep["programs"] == [] and sweep["programs_traced"] == 0
    assert sweep["lower_s"] == 0 and sweep["backend_compile_s"] == 0


def test_first_job_of_a_new_model_compiles_inside_warmup(tiny_bert, watched):
    _, ds = tiny_bert
    CHUNK_PROGRAMS.clear()  # whatever ran before: this process has not seen it
    result, _ = _job(_bundle(ds), ds)
    _compiled_inside_warmup(result, watched)
    assert watched["calls"] == 1 + CHUNKS  # a chunk of zeros, then the sweep


def test_other_weights_reuse_the_program_and_answer_the_same_bits(
    tiny_bert, watched
):
    bundle, ds = tiny_bert
    first, _ = _job(bundle, ds)
    watched["calls"] = 0
    other = _bundle(ds, weights_seed=1)  # a promoted model: a new module
    assert other.model is not bundle.model and other.model == bundle.model
    reused, _ = _job(other, ds)
    assert reused.compile_events["chunk_program_reused"] == 1
    assert "fused" not in reused.compile_events["programs"]
    assert watched["warmup"]["programs"] == []
    _sweep_compiled_nothing(watched)
    assert watched["calls"] == CHUNKS, "no run of the program before the sweep"
    assert np.abs(reused.predictions - first.predictions).max() > 1e-3
    CHUNK_PROGRAMS.clear()  # a jax.jit made for this bundle alone
    fresh, _ = _job(other, ds)
    assert fresh.compile_events["chunk_program_reused"] == 0
    np.testing.assert_array_equal(reused.predictions, fresh.predictions)
    np.testing.assert_array_equal(reused.outliers, fresh.outliers)
    assert reused.feature_drift == fresh.feature_drift


@pytest.mark.parametrize("what", ["chunk_rows", "reference_length"])
def test_a_new_signature_compiles_inside_warmup_never_inside_sweep(
    tiny_bert, watched, what
):
    bundle, ds = tiny_bert
    _job(bundle, ds)
    if what == "chunk_rows":
        # 384 + a tail of 316, which rounds back up to 384: one shape, so
        # the job's only signature is the warmed one
        job = functools.partial(_job, bundle, ds, chunk_rows=3 * CHUNK // 2)
    else:  # a monitor of another reference length: other avals
        shorter = _bundle(ds, reference_rows=ROWS // 2)
        assert (shorter.monitor.num_ref_sorted.shape
                != bundle.monitor.num_ref_sorted.shape)
        job = functools.partial(_job, shorter, ds)
    result, _ = job()
    _compiled_inside_warmup(result, watched)
    again, _ = job()
    assert again.compile_events["chunk_program_reused"] == 1
    _sweep_compiled_nothing(watched)
    # and the first signature is still compiled: one jit, two executables
    back, _ = _job(bundle, ds)
    assert back.compile_events["chunk_program_reused"] == 1
    np.testing.assert_array_equal(result.predictions, again.predictions)


def test_a_reused_job_launches_its_chunks_and_nothing_before(
    tiny_bert, watched, tmp_path
):
    bundle, ds = tiny_bert
    _job(bundle, ds)
    watched["calls"] = 0
    with program_spans(tmp_path) as spans:
        result, _ = _job(bundle, ds)
    assert result.compile_events["chunk_program_reused"] == 1
    assert watched["calls"] == CHUNKS
    compute = _named(spans, "mlops:pipe.compute")
    (warmup,) = _named(spans, "mlops:bulk.warmup")
    (sweep,) = _named(spans, "mlops:bulk.sweep")
    assert len(compute) == CHUNKS
    assert warmup[2] <= sweep[1] <= min(span[1] for span in compute)
    assert tuple(result.phases) == PHASES


def test_nested_traces_are_counted_once():
    """A ``jnp`` function traced inside another trace reports a duration
    its caller's already holds: one program, one duration."""
    def outer_program(x):
        return jnp.where(x > 0, jnp.tanh(x @ x), 0.0)

    x = jnp.ones((4, 4))  # made first: an eager jnp call is a program too
    counter = compile_counter()
    before = counter.snapshot()
    jax.jit(outer_program).lower(x)
    delta = counter.delta(before, counter.snapshot())
    assert delta["programs_traced"] == 1
    assert delta["programs"] == ["outer_program"]


@pytest.mark.parametrize("scope", ["attend", "ffn", "embed", "head", "outlier"])
def test_lowered_chunk_program_holds_the_scope(tiny_bert, scope):
    bundle, _ = tiny_bert
    text = make_bulk_jit(bundle.model, None).lower(
        bundle.variables, bundle.monitor, np.float32(1.5),
        jnp.zeros((CHUNK, SCHEMA.num_categorical), jnp.int8),
        jnp.zeros((CHUNK, SCHEMA.num_numeric), jnp.float32),
        jnp.ones(CHUNK, bool),
    ).as_text(debug_info=True)
    where = {
        "attend": "BertEncoder/block_1/MultiHeadSelfAttention_0/attend/",
        "ffn": "BertEncoder/block_1/ffn/Dense_0/",
        "embed": "BertEncoder/embed/tok_embed/",
        "head": "BertEncoder/head/pooler/",
        "outlier": "jit(fused)/outlier/",
    }[scope]
    assert where in text
    # flax's own names are still there, and name the same parameters
    assert "BertEncoder/block_0/MultiHeadSelfAttention_0/qkv/" in text
    assert set(bundle.variables["params"]["block_0"]) == {
        "LayerNorm_0", "MultiHeadSelfAttention_0", "LayerNorm_1",
        "Dense_0", "Dense_1",
    }


@pytest.mark.parametrize("depth", [1, 2], ids=["serial", "pipelined"])
def test_answers_are_the_same_bits_with_and_without_a_session(
    tiny_bert, traced_jobs, tmp_path, depth
):
    bundle, ds = tiny_bert
    plain, _ = _job(bundle, ds, depth)
    with program_spans(tmp_path) as spans:
        traced, _ = _job(bundle, ds, depth)
    assert len(_named(spans, "mlops:pipe.compute")) == CHUNKS
    for result in (traced, traced_jobs["untraced"], traced_jobs["jobs"][0][0]):
        np.testing.assert_array_equal(result.predictions, plain.predictions)
        np.testing.assert_array_equal(result.outliers, plain.outliers)
        assert result.feature_drift == plain.feature_drift
    # the record and its timed queues change no answer and hold none
    for result in (plain, traced):
        assert result.record["depth"] == depth and _plain(result.record)
        waits = [
            stage[key] for stage in result.record["stages"].values()
            for key in ("wait_in_s", "wait_out_s")
        ]
        assert all(w == 0 for w in waits) if depth == 1 else any(w > 0 for w in waits)

"""A bulk job says where its time went, and what it re-traced, from inside
the program (ISSUE 25): ``mlops:bulk.*`` and ``mlops:pipe.*`` spans in a
profiler trace, `BulkScoreResult.phases` and ``compile_events`` always,
device scopes on what flax does not name. All on the CPU: what is asserted
is what the program writes, never a time."""

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
from mlops_tpu.parallel.bulk import PHASES, make_bulk_jit, score_dataset
from mlops_tpu.schema import SCHEMA

ROWS, CHUNK = 700, 256  # 2 whole chunks and a padded tail
CHUNKS = math.ceil(ROWS / CHUNK)


@pytest.fixture(scope="module")
def tiny_bert():
    """A 2-layer `bert` bundle made by hand (no training run), and rows."""
    rng = np.random.default_rng(0)
    cat = np.stack([rng.integers(0, c, ROWS) for c in SCHEMA.cards], 1)
    ds = EncodedDataset(
        cat.astype(np.int32),
        rng.normal(size=(ROWS, SCHEMA.num_numeric)).astype(np.float32),
    )
    model = build_model(ModelConfig(family="bert", token_dim=32, depth=2, heads=2))
    zeros = np.zeros(SCHEMA.num_numeric, np.float32)
    bundle = Bundle(
        manifest={"flavor": "flax", "model_config": {},
                  "calibration": {"temperature": 1.5}},
        model=model,
        variables=init_params(model, jax.random.PRNGKey(0)),
        preprocessor=Preprocessor(zeros, zeros, zeros + 1, SCHEMA.fingerprint()),
        monitor=fit_monitor(ds),
    )
    return bundle, ds


def _job(bundle, ds, depth=2):
    start = time.perf_counter()
    result = score_dataset(bundle, ds, chunk_rows=CHUNK, exact=True,
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
    summary = result.summary()
    assert set(summary["phases"]) == set(PHASES)
    assert summary["compile_events"] == result.compile_events


def job_names_its_chunk_program(traced_jobs, which):
    events = traced_jobs["jobs"][which][0].compile_events
    # the per-job jax.jit: every job re-traces its chunk program
    assert events["programs_traced"] >= 1 and "fused" in events["programs"]
    assert events["trace_s"] > 0 and events["lower_s"] > 0
    marker = _named(traced_jobs["spans"], "mlops:bulk.compile_events")[which][3]
    assert marker["programs_traced"] == events["programs_traced"]
    assert marker["programs"].split("|") == events["programs"]
    assert marker["trace_s"] == pytest.approx(events["trace_s"])


def process_wide_sums_are_the_jobs_deltas(traced_jobs, _):
    before, after = traced_jobs["totals"]
    deltas = [job[0].compile_events for job in traced_jobs["jobs"]]
    for key in before:
        assert after[key] - before[key] == pytest.approx(
            sum(delta[key] for delta in deltas), abs=1e-5), key


@pytest.mark.parametrize("check,which", [
    (job_names_its_chunk_program, 0),
    (job_names_its_chunk_program, 1),
    (process_wide_sums_are_the_jobs_deltas, None),
], ids=["first_job", "second_job", "process_wide_sums"])
def test_compile_events(traced_jobs, check, which):
    check(traced_jobs, which)


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

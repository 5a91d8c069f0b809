"""The reduction of the program's own spans and scopes
(``benchmark/program_trace.py``) and its five readers: on a hand-made
flattened profile whose answers can be worked out on paper, on a profile
without the program's spans (the parent of PR 25: nothing to read), on a
real CPU profile of the rehearsal's jobs (the search for the run's profile,
the attributes as the profiler gives them back), and on a profile recorded
on a TPU v5e."""

import json
import os
import tempfile
from pathlib import Path

import pytest
from conftest import CELLS

from benchmark import program_trace as pt
from benchmark import run

RECORDED = Path(__file__).resolve().parent / "recorded_program_trace_tpu_v5e.json"
US = 1_000
PID = 42
ATTEND = "jit(fused)/BertEncoder/block_{}/MultiHeadSelfAttention_0/attend/dot_general:"
QKV = "jit(fused)/BertEncoder/block_1/MultiHeadSelfAttention_0/qkv/dot_general:"
FFN = "jit(fused)/BertEncoder/block_{}/ffn/Dense_0/dot_general:"
READERS = ["bulk_job_start_s", "bulk_job_retrace_s", "bulk_warmup_device_pct",
           "bulk_drift_s", "bulk_attn_device_pct"]


def hand_made():
    """Window 0..1000 us. Job 1 whole (20..480); job 2 runs past the window's
    end (520..1100) and is clipped to it, its drift sample falls outside."""
    def span(name, lo, hi, **attrs):
        return [name, lo * US, (hi - lo) * US, attrs]

    events = {"trace_s": 2e-5, "lower_s": 1e-5, "backend_compile_s": 3e-5,
              "cache_retrieval_s": 2.5e-5, "cache_hits": 1, "programs": "fused|add"}
    main = [
        span("bench:window", 0, 1000),
        span("bench:job", 10, 500),
        span("mlops:bulk.job", 20, 480, job=1, pid=PID, rows=700, chunks=3),
        span("mlops:bulk.build", 20, 50, job=1),
        span("mlops:bulk.warmup", 50, 200, job=1),
        span("mlops:bulk.sweep", 200, 400, job=1),
        span("mlops:bulk.drift", 400, 470, job=1),
        span("mlops:bulk.compile_events", 470, 471, job=1, **events),
        span("mlops:bulk.job", 520, 1100, job=2, pid=PID, rows=700, chunks=3),
        span("mlops:bulk.build", 520, 540, job=2),
        span("mlops:bulk.warmup", 540, 700, job=2),
        span("mlops:bulk.sweep", 700, 1100, job=2),
        span("mlops:bulk.drift", 1100, 1150, job=2),
        span("some other annotation", 0, 7),
    ]
    pipeline_thread = [
        span("mlops:pipe.compute", 210, 220, job=1, items=1),
        span("mlops:pipe.fetch", 220, 390, job=1, items=1),
    ]
    ops = [
        ["fusion", 100 * US, 80 * US, ATTEND.format(0)],  # job 1's warm-up run
        ["fusion", 230 * US, 70 * US, FFN.format(1)],
        ["fusion", 300 * US, 80 * US, QKV],
        # an operation that holds another: each instant goes to the innermost
        ["while", 410 * US, 20 * US, "jit(_one_hot)/drift/while:"],
        ["compare", 415 * US, 10 * US, "jit(_one_hot)/drift/while/body/eq:"],
        ["fusion", 600 * US, 90 * US, ATTEND.format(7)],  # job 2's warm-up run
        ["fusion", 750 * US, 300 * US, FFN.format(3)],  # 250 inside the window
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": main},
            {"name": "pipeline-fetch", "events": pipeline_thread},
        ]},
    ]}


def without_program_spans():
    flat = hand_made()
    for line in flat["planes"][1]["lines"]:
        line["events"] = [e for e in line["events"] if not e[0].startswith("mlops:")]
    return flat


def test_jobs_phases_clipped_to_the_window():
    program = pt.reduce_profile(hand_made(), PID)
    assert program["window_s"] == pytest.approx(1000e-6)
    first, second = program["jobs"]
    assert first["attrs"]["job"] == 1 and second["attrs"]["job"] == 2
    assert first["seconds"] == pytest.approx(
        {"job": 460e-6, "build": 30e-6, "warmup": 150e-6, "sweep": 200e-6, "drift": 70e-6})
    # job 2: clipped at the window's end, its drift sample outside it
    assert second["seconds"] == pytest.approx(
        {"job": 480e-6, "build": 20e-6, "warmup": 160e-6, "sweep": 300e-6})
    assert first["compile_events"]["cache_hits"] == 1
    assert second["compile_events"] is None
    assert program["harness_job_s"] == pytest.approx([490e-6])
    assert pt.mean_per_job(program, ("build", "warmup")) == pytest.approx(180e-6)
    assert pt.mean_per_job(program, ("drift",)) == pytest.approx(70e-6)


def test_busy_inside_a_span_and_device_time_by_scope():
    program = pt.reduce_profile(hand_made(), PID)
    assert program["devices"] == 1
    assert program["busy_s"] == pytest.approx(590e-6)
    assert [job["warmup_busy_s"] for job in program["jobs"]] == pytest.approx([80e-6, 90e-6])
    assert dict(program["device_by_scope"]) == pytest.approx({
        "jit(fused)/BertEncoder/block_*/ffn/Dense_0": 320e-6,
        "jit(fused)/BertEncoder/block_*/MultiHeadSelfAttention_0/attend": 170e-6,
        "jit(fused)/BertEncoder/block_*/MultiHeadSelfAttention_0/qkv": 80e-6,
        "jit(_one_hot)/drift": 10e-6,
        "jit(_one_hot)/drift/while/body": 10e-6,
    })
    assert sum(dict(program["device_by_scope"]).values()) == pytest.approx(program["busy_s"])
    assert pt.busy_inside([(0, 10), (20, 30)], 5, 25) == 10


def test_idle_goes_to_the_innermost_span_other_threads_included():
    program = pt.reduce_profile(hand_made(), PID)
    idle = dict(program["idle_by_span"])
    assert idle == pytest.approx({
        "bulk.warmup": 140e-6,  # 50..100, 180..200, 540..600, 690..700
        "bulk.sweep": 70e-6,  # 200..210, 390..400, 700..750
        pt.NO_SPAN: 60e-6,  # 0..20 and between the jobs, 480..520
        "bulk.build": 50e-6,
        "bulk.drift": 50e-6,  # 400..410, 430..470
        "pipe.fetch": 20e-6,  # 220..230, 380..390: inside the sweep, another thread
        "pipe.compute": 10e-6,
        "bulk.job": 9e-6,  # 471..480: in the job, in no phase
        "bulk.compile_events": 1e-6,
    })
    assert sum(idle.values()) + program["busy_s"] == pytest.approx(program["window_s"])


def test_nothing_to_read_gives_nothing():
    assert pt.reduce_profile(without_program_spans(), PID) is None
    assert pt.reduce_profile(hand_made(), pid=7) is None  # another process's jobs
    flat = hand_made()
    flat["planes"][1]["lines"][0]["events"].pop(0)  # no bench:window
    assert pt.reduce_profile(flat, PID) is None
    no_device = {"planes": hand_made()["planes"][1:]}
    program = pt.reduce_profile(no_device, PID)
    assert program["busy_s"] is None and program["device_by_scope"] == []
    assert "warmup_busy_s" not in program["jobs"][0]


def test_scope_folding():
    assert pt.fold_scope(ATTEND.format(11)) == (
        "jit(fused)/BertEncoder/block_*/MultiHeadSelfAttention_0/attend")
    assert pt.fold_scope("jit(fused)/outlier/ni,ij,nj->n:") == "jit(fused)/outlier"
    assert pt.fold_scope("jit(sort)/sort:") == "jit(sort)"  # an eager operator
    assert pt.fold_scope("") == "(no scope)"  # copies and buffer allocations


def _reader(name):
    return run.load_module(run.HERE / "layer_metrics" / f"{name}.py")


EXPECTED = {
    "bulk_job_start_s": 180e-6,
    "bulk_job_retrace_s": 6e-5,  # job 1's marker alone; retrieval is in the compile
    "bulk_warmup_device_pct": 100 * 170 / 590,
    "bulk_drift_s": 70e-6,
    "bulk_attn_device_pct": 100 * 250 / 590,
}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_the_hand_made_profile(monkeypatch, name):
    program = pt.reduce_profile(hand_made(), PID)
    monkeypatch.setattr(pt, "load", lambda facts: program)
    assert _reader(name).read({"trace": {}}) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_the_programs_spans(monkeypatch, name):
    """The parent of PR 25 writes no ``mlops:`` span: every reader returns
    ``None`` and raises nothing; so does a run whose reduction is ``None``
    (no device plane: a rehearsal), without looking for a profile."""
    monkeypatch.setattr(
        pt, "load", lambda facts: pt.reduce_profile(without_program_spans(), PID)
    )
    assert _reader(name).read({"trace": {}}) is None
    monkeypatch.undo()
    assert _reader(name).read({"trace": None}) is None


def test_tables_name_spans_and_scopes(capsys):
    pt.print_tables(pt.reduce_profile(hand_made(), PID))
    err = capsys.readouterr().err
    assert "idle seconds by program span" in err and "bulk.warmup" in err
    assert "device seconds by scope" in err
    assert "jit(fused)/BertEncoder/block_*/MultiHeadSelfAttention_0/attend" in err
    assert "job 1: job 0.000 build" in err and "cache_hits 1" in err


def test_profile_recorded_on_a_v5e(monkeypatch):
    """Two jobs of a 2-layer bert, flattened on the chip's host as
    ``load_profile`` flattens (the fixture's ``recorded`` says how)."""
    flat = json.loads(RECORDED.read_text())
    assert "TPU v5" in flat["recorded"]
    program = pt.reduce_profile(flat)
    assert pt.reduce_profile(flat, pid=1) is None
    assert len(program["jobs"]) == 2 and program["devices"] == 1
    scopes = dict(program["device_by_scope"])
    # every instant of busy time goes to one scope, `while` bodies included
    assert sum(scopes.values()) == pytest.approx(program["busy_s"], rel=1e-9)
    idle = dict(program["idle_by_span"])
    assert sum(idle.values()) + program["busy_s"] == pytest.approx(program["window_s"])
    assert idle.get(pt.NO_SPAN, 0.0) < 0.1 * sum(idle.values())
    assert max(idle, key=idle.get) == "bulk.warmup"  # trace, lower, cache load
    # flax's module scopes and the program's own, as XLA's op_name carries them
    block = "jit(fused)/BertEncoder/block_*/"
    for scope in (block + "MultiHeadSelfAttention_0/attend", block + "ffn/Dense_0",
                  "jit(fused)/BertEncoder/embed/tok_embed", "jit(fused)/outlier",
                  "jit(fused)/BertEncoder/head/pooler"):
        assert any(name.startswith(scope) for name in scopes), scope
    # the drift sample runs as eager operators, each a program of its own:
    # the `drift` scope is not on them, the `bulk.drift` span is around them
    assert not any("drift" in name for name in scopes)
    assert any(name.startswith("jit(searchsorted)") for name in scopes)
    for job in program["jobs"]:
        seconds = job["seconds"]
        assert sum(seconds[p] for p in pt.PHASES) == pytest.approx(seconds["job"], rel=0.01)
        assert job["compile_events"]["cache_hits"] == 1
        assert "fused" in job["compile_events"]["programs"].split("|")
        # the warm-up call runs the chunk program once: a third of a sweep
        # of three chunks, to a few per cent
        assert 0 < job["warmup_busy_s"] < seconds["warmup"]
    monkeypatch.setattr(pt, "load", lambda facts: program)
    values = {name: _reader(name).read({"trace": {}}) for name in READERS}
    assert all(value is not None and value > 0 for value in values.values()), values
    assert values["bulk_warmup_device_pct"] == pytest.approx(
        100 * sum(job["warmup_busy_s"] for job in program["jobs"]) / program["busy_s"])
    attention = sum(v for name, v in scopes.items() if "MultiHeadSelfAttention" in name)
    assert values["bulk_attn_device_pct"] == pytest.approx(100 * attention / program["busy_s"])


@pytest.mark.parametrize("workload", CELLS)
def test_cpu_profile_of_the_rehearsals_jobs(tiny_root, workload):
    """The rehearsal's jobs under a real profiler session, kept where
    ``run.py`` keeps a traced run's profile: ``load`` finds it by this
    process's pid and the host spans' readers read it; the two readers that
    need a device plane find none. (``run.py`` itself reports none of the
    five on the CPU: its reduction is ``None`` there, see ``load``.)"""
    import jax

    loaded = run.load_cell(tiny_root, workload)
    ctx = run.Context(3_000_000_017, loaded["cell"], loaded["config"], loaded["traffic"])
    driver = run.load_module(loaded["driver_file"]).build(ctx)
    driver.setup()
    driver.warmup()
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        ctx.tracing = True
        try:
            with ctx.span("window"):
                driver.window(0.3, max_units=2)
        finally:
            ctx.tracing = False
            jax.profiler.stop_trace()
        pt._reduced.cache_clear()
        facts = {"trace": {}}  # as if the reduction had found a device
        program = pt.load(facts)
        assert program is not None and program["busy_s"] is None
        assert [job["attrs"]["pid"] for job in program["jobs"]] == [os.getpid()] * 2
        rows = int(loaded["traffic"]["rows_per_file"])
        assert program["jobs"][0]["attrs"]["rows"] == rows
        values = {name: _reader(name).read(facts) for name in READERS}
    assert values["bulk_job_start_s"] > 0 and values["bulk_drift_s"] > 0
    assert values["bulk_job_retrace_s"] > 0
    assert values["bulk_warmup_device_pct"] is None
    assert values["bulk_attn_device_pct"] is None
    for job, mine in zip(program["jobs"], driver.jobs):
        assert set(job["seconds"]) == {"job", *pt.PHASES}
        # the sweep's span and the program's own sweep time, two clocks
        assert job["seconds"]["sweep"] == pytest.approx(mine["sweep_s"], rel=0.2, abs=2e-3)
        assert "fused" in job["compile_events"]["programs"].split("|")

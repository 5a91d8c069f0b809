"""The trace reduction, on a hand-made trace whose answers can be worked
out on paper and on a small trace recorded on a TPU v5e."""

import json
from pathlib import Path

import pytest

from benchmark import trace_reduce as tr

RECORDED = Path(__file__).resolve().parent / "recorded_trace_tpu_v5e.json"
US = 1_000


def hand_made():
    """Window 0..100 us, two jobs; device ops overlap inside job 1."""
    ops = [
        ["%fusion.1 = f32[8]{0} fusion(...)", 10 * US, 20 * US],  # 10..30
        ["%fusion.2 = f32[8]{0} fusion(...)", 25 * US, 15 * US],  # 25..40 overlaps
        ["%copy.7 = f32[8]{0} copy(...)", 60 * US, 10 * US],  # 60..70
        ["%fusion.1 = f32[8]{0} fusion(...)", 80 * US, 10 * US],  # 80..90
        ["%late = f32[] add(...)", 120 * US, 5 * US],  # after the window
    ]
    modules = [
        ["jit_a(1)", 10 * US, 30 * US],  # 10..40
        ["jit_b(2)", 60 * US, 10 * US],
        ["jit_a(1)", 80 * US, 10 * US],
    ]
    host = [
        ["bench:window", 0, 100 * US],
        ["bench:job", 5 * US, 45 * US],  # 5..50
        ["bench:job", 55 * US, 40 * US],  # 55..95
        ["something else", 0, 7],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules},
            {"name": "Steps", "events": [["1", 0, 100 * US]]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]}


def test_union_of_overlapping_intervals():
    assert tr.union_intervals([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]


def test_busy_idle_top_operations_and_gap_attribution():
    out = tr.reduce_trace(hand_made())
    assert out["window_s"] == pytest.approx(100e-6)
    assert out["busy_s"] == pytest.approx(50e-6)  # 10..40, 60..70, 80..90
    assert out["devices"] == 1
    ops = dict(out["device_ops"])
    assert ops == pytest.approx({"fusion": 45e-6, "copy": 10e-6})
    gaps = dict(out["idle_gaps"])
    assert gaps == pytest.approx({
        # the gap 0..10 has its middle at 5, where the first job begins
        "job:start>jit_a": 10e-6,
        # 40..60: its middle, 50, is between the jobs
        "window:jit_a>jit_b": 20e-6,
        "job:jit_b>jit_a": 10e-6,
        # 90..100: its middle, 95, is where the second job has just ended
        "window:jit_a>end": 10e-6,
    })
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(out["window_s"])


def test_nothing_to_read_gives_nothing():
    trace = hand_made()
    assert tr.reduce_trace({"planes": trace["planes"][1:]}) is None  # no device
    trace["planes"][1]["lines"][0]["events"] = []
    assert tr.reduce_trace(trace) is None  # no window span


def test_operation_and_program_names():
    raw = "%reshape.266 = bf16[4096,48,3,12,64]{1,0,4,3,2:T(8,128)(2,1)} reshape(bf16[196608,3,12,64] %x)"
    assert tr.op_kind(raw) == "reshape"
    assert tr.op_kind("%convolution_add_fusion.17 = bf16[8] fusion()") == "convolution_add_fusion"
    assert tr._module_name("jit_fused(11550058643168936960)") == "jit_fused"


def test_recorded_tpu_trace():
    trace = json.loads(RECORDED.read_text())
    out = tr.reduce_trace(trace)
    device = trace["planes"][0]
    ops = tr._events(device, "XLA Ops")
    # operations nest (a while loop and its body) and overlap (async copies),
    # so busy time is not the sum of durations; count it a second way, by a
    # sweep over starts (+1) and ends (-1) that adds up the time at depth > 0
    assert sum(d for _, _, d in ops) / 1e9 > out["busy_s"]
    marks = sorted([(s, 1) for _, s, d in ops] + [(s + d, -1) for _, s, d in ops])
    depth, busy, last = 0, 0, marks[0][0]
    for t, step in marks:
        busy += (t - last) if depth > 0 else 0
        depth, last = depth + step, t
    assert out["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0.5 < out["busy_s"] / out["window_s"] < 0.6  # 1.45 s of chunks in 2.7 s
    names = [name for name, _ in out["device_ops"]]
    assert names[:2] == ["fusion", "reshape"] and len(names) == 10
    gaps = dict(out["idle_gaps"])
    # the job's own set-up, before its first chunk program, is the longest gap
    assert max(gaps, key=gaps.get) == "job:start>jit_fused"
    assert gaps["job:start>jit_fused"] == pytest.approx(1.335, abs=0.01)
    assert sum(gaps.values()) <= out["window_s"] - out["busy_s"] + 1e-9


def test_load_xplane_reads_a_trace_this_process_writes(tmp_path):
    """On the CPU there is no device plane: the spans are found, and the
    reduction says there is nothing to read."""
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench:window"):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    flat = tr.load_xplane(tr.find_xplane(tmp_path))
    assert [s[0] for s in tr.harness_spans(flat)] == ["window"]
    assert tr.reduce_trace(flat) is None


# ------------------------------------------------- readers of the reduction
def reader(name):
    from benchmark import run

    return run.load_module(Path(tr.__file__).parent / "layer_metrics" / f"{name}.py")


def one_job_trace():
    """Window 0..100 us, one job: the in-call warm-up run of the chunk
    program, a long wait, a sweep of three chunks, then a drift operator."""
    modules = [
        ["jit_fused(9)", 5 * US, 10 * US],  # 5..15, the warm-up chunk
        ["jit_fused(9)", 40 * US, 10 * US],  # 40..50
        ["jit_fused(9)", 52 * US, 10 * US],  # 52..62: waited 2 us
        ["jit_fused(9)", 62 * US, 10 * US],  # 62..72: waited 0
        ["jit__where(3)", 80 * US, 2 * US],
    ]
    ops = [[f"%fusion.{i} = f32[8] fusion()", s, d] for i, (_, s, d) in enumerate(modules)]
    host = [["bench:window", 0, 100 * US], ["bench:job", 2 * US, 90 * US]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]}


def facts_for(trace, rows=700, chunk=256, flops_rows=700):
    return {
        "trace": trace,
        "traffic": {"rows_per_file": rows},
        "config": {
            "deployment": {"score_chunk_rows": chunk},
            "model_config": {"family": "bert", "token_dim": 768, "depth": 12},
            "intermediate_size": 3072, "seq_len": 48,
        },
        "window": {"units": flops_rows},
        "cell": {"chips": 1},
        "peaks": {"bf16_flops_per_s": 197e12},
    }


def test_spans_and_program_runs_are_handed_on():
    out = tr.reduce_trace(one_job_trace())
    assert out["spans"] == [["job", pytest.approx(2e-6), pytest.approx(92e-6)]]
    assert [p[0] for p in out["programs"]] == ["jit_fused"] * 4 + ["jit__where"]
    assert out["programs"][1][1:] == [pytest.approx(40e-6), pytest.approx(50e-6)]


def test_sweep_idle_leaves_out_the_wait_after_the_warm_up_chunk():
    out = tr.reduce_trace(one_job_trace())
    # 3 chunks: the LAST three runs are the sweep; 2 us + 0 us of 100 us
    assert reader("bulk_sweep_idle_pct").read(facts_for(out)) == pytest.approx(2.0)
    # the 25 us between the warm-up run and the sweep is in the breakdown
    assert dict(out["idle_gaps"])["job:jit_fused>jit_fused"] == pytest.approx(27e-6)
    # fewer runs than the file has chunks: nothing to read, not 0
    assert reader("bulk_sweep_idle_pct").read(facts_for(out, rows=2000)) is None
    assert reader("bulk_sweep_idle_pct").read(facts_for(None)) is None


def test_program_mfu_is_operations_over_busy_time_and_peak():
    from benchmark import flops

    out = tr.reduce_trace(one_job_trace())
    assert out["busy_s"] == pytest.approx(42e-6)
    facts = facts_for(out)
    want = 100 * flops.forward_flops_per_row(facts["config"]) * 700 / 42e-6 / 197e12
    assert reader("bulk_program_mfu_pct").read(facts) == pytest.approx(want)
    assert reader("bulk_program_mfu_pct").read({**facts, "peaks": None}) is None
    assert reader("bulk_program_mfu_pct").read({**facts, "trace": None}) is None


def test_readers_on_the_recorded_trace():
    """The recorded job ran the chunk program twice: the warm-up run and a
    sweep of one chunk. The 18 ms between them is start-up, not sweep."""
    out = tr.reduce_trace(json.loads(RECORDED.read_text()))
    runs = [p for p in out["programs"] if p[0] == "jit_fused"]
    assert len(out["spans"]) == 1 and len(runs) == 2
    chunks = len(runs) - 1
    idle = reader("bulk_sweep_idle_pct").read(facts_for(out, rows=chunks * 256, chunk=256))
    between = sum(b[1] - a[2] for a, b in zip(runs[1:], runs[2:]))
    assert idle == pytest.approx(100 * between / out["window_s"])
    assert idle == 0.0
    assert dict(out["idle_gaps"])["job:jit_fused>jit_fused"] == pytest.approx(0.018, abs=0.001)

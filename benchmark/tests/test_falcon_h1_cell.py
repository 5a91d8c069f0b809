"""What ISSUE 39 adds to the benchmark: the operation and byte counts of
the ``falcon_h1`` family against hand counts at a tiny shape, the three
readers of its scopes on a hand-made profile (and the two readers the cell
is appended to), the driver's per-head leaves, and the cell end to end at
its rehearsal size with the timed path broken underneath once for each
fault that is this family's own: the scan's state not carried, the gate
after the norm, a multiplier dropped. (The cell's rehearsal, the five
faults every bulk cell can have, its control and the reference against
the program run through the files that are parametrised over
``BENCHMARK.json``: ``test_rehearsal.py``, ``test_reference.py``.)"""

import json
from pathlib import Path

import numpy as np
import pytest
from conftest import CELLS, CONFIGS
from test_rehearsal import drive

from benchmark import faulted_readings, flops, run
from benchmark import program_trace as pt
from benchmark.faults.falcon_h1 import OWN_FAULTS
from benchmark.flops import falcon_h1 as counts
from benchmark.rooflines import falcon_h1 as roofs
from benchmark.rooflines import kimi_k2 as scopes

CONFIG = json.loads(
    (Path(__file__).resolve().parents[1] / "configs" / "falcon-h1-34b.json").read_text()
)
CELL = "falcon-h1-34b.bulk-hist"
# hidden 8; 4 query heads over 2 key/value heads of 3 (4 x 3 is not 8); a mixer of
# 6 in 3 heads of 2 over 1 group with a state of 5; an MLP of 12; 2 layers;
# records of 4 tokens, 3 a history
TINY = {
    "model_config": {
        "family": "falcon_h1", "token_dim": 8, "depth": 2, "heads": 4, "kv_heads": 2,
        "head_dim": 3, "ffn_dim": 12, "ssm_dim": 6, "ssm_heads": 3, "ssm_state": 5,
        "ssm_groups": 1,
    },
    "records_per_history": 3,
    "tokens_per_record": 4,
}
US = 1_000


def test_the_cell_and_the_configuration_are_in_the_benchmark():
    assert CELL in CELLS and "falcon-h1-34b" in CONFIGS
    bench = json.loads((Path(run.CHECKOUT) / "BENCHMARK.json").read_text())
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["bert-base.bulk-dp4"]  # one in eight: what the 25% rule allows
    entry = next(c for c in bench["configs"] if c["name"] == "falcon-h1-34b")
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/falcon-h1-34b.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["traffic"] == "bulk-ssm-token-histories-1228" and cell["chips"] == 1
    traffic = json.loads((run.HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    like = json.loads((run.HERE / "traffic" / "bulk-token-histories-1228.json").read_text())
    assert traffic["driver"] == "bulk_ssm_token_histories"
    for key in ("rows_per_file", "data", "check_histories", "outlier_check_rows",
                "traced_units", "tokens_per_record", "rehearsal"):
        assert traffic[key] == like[key], key  # every traffic parameter is that mix's


def test_macs_match_the_hand_count_at_a_tiny_shape():
    mc = TINY["model_config"]
    assert counts.mixer_columns(mc) == (6 + 5 + 3, 6 + 5)  # x, B, dt | z, C
    assert counts.recurrence_macs(mc) == 6 * 5  # 3 heads x 2 channels x a state of 5
    kv, rest = 2 * 8 * 2 * 3, 2 * 8 * 4 * 3  # k, v: 2 heads of 3; q, o: 4 heads of 3
    assert counts.attention_macs(mc) == (kv, rest) == (96, 192)
    assert counts.attention_macs_per_key(mc) == 2 * 4 * 3
    seq, records = 12, 3
    mlp = 3 * 8 * 12
    every = sum(range(1, seq + 1))  # 78 keys over a whole layer's queries
    # a whole layer: in_proj 8 x 25, three products of 30, out_proj 6 x 8
    whole = seq * (8 * 25 + 3 * 30 + 6 * 8 + kv + rest + mlp) + 24 * every
    # the last: x, B, dt, two of the three products and k, v at every position;
    # z, C, the third product, out_proj, q, o and the MLP at positions 3, 7, 11
    last = seq * (8 * 14 + 2 * 30 + kv) + records * (8 * 11 + 30 + 6 * 8 + rest + mlp)
    last += 24 * (4 + 8 + 12)
    assert counts.history_macs(TINY, records) == whole + last + records * 8
    assert flops.forward_flops_per_row(TINY) == 2 * (counts.history_macs(TINY, 3) // 3)
    one_layer = {**TINY, "model_config": {**mc, "depth": 1}}
    assert counts.history_macs(one_layer, records) == last + records * 8


def test_the_real_configuration_counts_what_the_issue_reckoned():
    mc = CONFIG["model_config"]
    assert sum(counts.mixer_columns(mc)) == 9248  # z 4096 | x 4096 | B 512 | C 512 | dt 32
    assert counts.recurrence_macs(mc) == 4096 * 256
    assert sum(counts.attention_macs(mc)) == 31_457_280  # the four projections
    # a token of a whole layer: the projections and the MLP are the parameters'
    # 430.1 M less the norms, the taps and the per-head leaves, plus the recurrence
    token = 5120 * 9248 + 4096 * 5120 + 31_457_280 + 3 * 5120 * 21504 + 3 * 4096 * 256
    assert token == 433_225_728
    assert 3 * 5120 * 21504 / token == pytest.approx(0.76, abs=0.01)  # the issue's 77%
    whole = counts.history_macs(CONFIG, 64)
    square = sum(range(1, 3073))
    assert whole == pytest.approx(5 * (3072 * token + 5120 * square), rel=0.02)  # + the last
    assert flops.forward_flops_per_row(CONFIG) == pytest.approx(215.5e9, rel=1e-3)
    # a job of 1,228 rows: 264.7 TFLOP, 1.34 s at 197 TFLOP/s
    assert 1228 * flops.forward_flops_per_row(CONFIG) / 197e12 == pytest.approx(1.34, abs=0.01)
    # the mixer's projections and the recurrence: 16% of the required operations
    mixer = 5 * 3072 * (5120 * 9248 + 4096 * 5120 + 3 * 4096 * 256)
    assert mixer / whole == pytest.approx(0.16, abs=0.01)
    assert 5 * 3072 * 3 * 4096 * 256 / whole < 0.008  # the recurrence itself: under 1%


def test_roofline_operations_and_bytes_match_the_hand_count():
    ops, moved = roofs.scan_layer_work(TINY, 3, 0)
    assert ops == 2 * 30 * 3 * 12  # three products a position
    assert moved == 2 * (12 * (6 + 5 + 3) + 12 * (5 + 6))  # x, B, dt; C, y
    last_ops, last_moved = roofs.scan_layer_work(TINY, 3, 1)
    assert last_ops == 2 * 30 * (2 * 12 + 3)  # the state at 12, the answers at 3
    assert last_moved == 2 * (12 * 14 + 3 * 11)
    a_ops, a_moved = roofs.attend_layer_work(TINY, 3, 0)
    assert a_ops == 2 * 24 * 78 and a_moved == 2 * (2 * 12 * 12 + 2 * 12 * 6)
    a_last_ops, a_last_moved = roofs.attend_layer_work(TINY, 3, 1)
    assert a_last_ops == 2 * 24 * (4 + 8 + 12) and a_last_moved == 2 * (2 * 3 * 12 + 2 * 12 * 6)
    slow = {"bf16_flops_per_s": 1e4}
    assert roofs.history_seconds(TINY, 3, slow, roofs.scan_layer_work) == pytest.approx(
        (ops + last_ops) / 1e4)
    assert roofs.history_seconds(TINY, 3, slow, roofs.attend_layer_work) == pytest.approx(
        (a_ops + a_last_ops) / 1e4)
    assert roofs.binds((ops, moved), slow) == "compute"
    assert roofs.binds((ops, moved), {"bf16_flops_per_s": 1e15}) == "memory"
    # the real shape: both bound by compute in a whole layer
    peaks = {"bf16_flops_per_s": 197e12}
    real = roofs.scan_layer_work(CONFIG, 64, 0)
    assert real == (6 * 4096 * 256 * 3072, 2 * 3072 * (4096 + 512 + 32 + 512 + 4096))
    assert real[0] / 197e12 == pytest.approx(0.0981e-3, rel=1e-2)  # the issue's 0.1 ms
    assert real[1] / 819e9 == pytest.approx(0.0694e-3, rel=1e-2)
    assert roofs.binds(real, peaks) == "compute"
    attend = roofs.attend_layer_work(CONFIG, 64, 0)
    assert attend[0] == 2 * 2 * 2560 * sum(range(1, 3073))
    assert attend[1] == 2 * (2 * 3072 * 2560 + 2 * 3072 * 512)
    assert roofs.binds(attend, peaks) == "compute"
    assert roofs.binds(roofs.attend_layer_work(CONFIG, 64, 5), peaks) == "memory"  # 64 queries
    assert roofs.history_seconds(CONFIG, 64, peaks, roofs.scan_layer_work) == pytest.approx(
        5 * real[0] / 197e12 + roofs.scan_layer_work(CONFIG, 64, 5)[0] / 197e12)


# ------------------------------------------------------------ the readers
BLOCK = "jit(fused)/FalconH1Scorer/block_{0}/block_{0}."


def hand_made():
    """Window 0..1000 us, one job, two runs of the chunk program. Device: the
    mixer 200 us (in 70, conv 20, scan 60: a product 40 and a while 20, out
    50), the attention 100 us (qkv 30, the kernel 50, o 20), 200 of ffn:
    busy 500."""
    def span(name, lo, hi, **attrs):
        return [name, lo * US, (hi - lo) * US, attrs]

    host = [
        span("bench:window", 0, 1000),
        span("bench:job", 10, 900),
        span("mlops:bulk.job", 20, 880, job=1, pid=7, rows=10, chunks=2),
    ]
    timeline = [
        ("fusion", 70, BLOCK.format(1) + "_ssm/ssm_in/in_proj/dot_general:"),
        ("fusion", 20, BLOCK.format(1) + "_ssm/ssm_conv/mul:"),
        ("fusion", 40, BLOCK.format(1) + "_ssm/ssm_scan/bcgrik,bckgrp->bcigrp/dot_general:"),
        ("fusion", 20, BLOCK.format(1) + "_ssm/ssm_scan/while/body/add:"),
        ("fusion", 50, BLOCK.format(1) + "_ssm/ssm_out/out_proj/dot_general:"),
        ("fusion", 30, BLOCK.format(1) + "_attention/gqa_qkv/q/dot_general:"),
        ("custom-call", 50, BLOCK.format(1) + "_attention/gqa_attend/jit(_kernel_or_xla)/gqa_attend_fwd:"),
        ("fusion", 20, BLOCK.format(1) + "_attention/gqa_o/o/dot_general:"),
        ("fusion", 200, "jit(fused)/FalconH1Scorer/block_0/ffn/gate/dot_general:"),
    ]
    ops, at = [], 100
    for kind, dur, scope in timeline:
        ops.append([kind, at * US, dur * US, scope])
        at += dur + 5
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]}


def _reader(name):
    return run.load_module(run.HERE / "layer_metrics" / f"{name}.py")


def _facts(peaks, spec=TINY):
    spec = {**spec, "deployment": {"score_chunk_rows": 6}}  # 2 histories a chunk
    trace = {"programs": [["jit_fused", 0.0, 0.1], ["jit_add", 0.1, 0.2], ["jit_fused", 0.2, 0.3]]}
    return {"trace": trace, "peaks": peaks, "config": spec, "driver": None,
            "traffic": {"rows_per_file": 10}}


NEW = ["bulk_ssm_device_pct", "ssd_scan_roofline_pct", "falcon_h1_gqa_attend_roofline_pct"]
APPENDED = ["bulk_gqa_device_pct", "bulk_sweep_span_idle_pct"]


def test_readers_on_the_hand_made_profile(monkeypatch):
    program = pt.reduce_profile(hand_made(), 7)
    monkeypatch.setattr(pt, "load", lambda facts: program)
    assert scopes.scope_seconds(program, roofs.SSM_SCOPES) == pytest.approx(200e-6)
    assert _reader("bulk_ssm_device_pct").read(_facts(None)) == pytest.approx(100 * 200 / 500)
    # lfm2_moe's reader, unedited, reads this family's attention
    assert _reader("bulk_gqa_device_pct").read(_facts(None)) == pytest.approx(100 * 100 / 500)
    peaks = {"bf16_flops_per_s": 1e9}
    allowed = 2 * 2 * roofs.history_seconds(TINY, 3, peaks, roofs.scan_layer_work)
    assert _reader("ssd_scan_roofline_pct").read(_facts(peaks)) == pytest.approx(
        100 * allowed / 60e-6  # the scope whole: the products and the hand-over's loop
    )
    allowed = 2 * 2 * roofs.history_seconds(TINY, 3, peaks, roofs.attend_layer_work)
    assert _reader("falcon_h1_gqa_attend_roofline_pct").read(_facts(peaks)) == pytest.approx(
        100 * allowed / 50e-6
    )
    for name in NEW[1:]:
        assert _reader(name).read(_facts(None)) is None  # no peak: a CPU
    # another family's configuration under the same scope names: nothing, not a wrong share
    other = {**TINY, "model_config": {**TINY["model_config"], "family": "exaone_moe"}}
    for name in NEW[1:]:
        assert _reader(name).read(_facts(peaks, spec=other)) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_where_the_scopes_are_missing(monkeypatch, name):
    """A program without the scopes (the parent's, which has no such
    family); a rehearsal without a device; a trace without a run of the
    chunk program: ``None``, never 0, nothing raised."""
    flat = hand_made()
    ops = flat["planes"][0]["lines"][0]["events"]
    flat["planes"][0]["lines"][0]["events"] = [op for op in ops if "block_0" in op[3]]
    program = pt.reduce_profile(flat, 7)
    monkeypatch.setattr(pt, "load", lambda facts: program)
    peaks = {"bf16_flops_per_s": 1e9}
    assert _reader(name).read(_facts(peaks)) is None
    monkeypatch.setattr(pt, "load", lambda facts: None)
    assert _reader(name).read(_facts(peaks)) is None
    if name.endswith("roofline_pct"):
        whole = pt.reduce_profile(hand_made(), 7)
        monkeypatch.setattr(pt, "load", lambda facts: whole)
        none_ran = {**_facts(peaks), "trace": {"programs": [["jit_add", 0.0, 0.1]]}}
        assert _reader(name).read(none_ran) is None


def test_the_cells_metrics_are_the_three_new_ones_and_the_attentions():
    bench = json.loads((Path(run.CHECKOUT) / "BENCHMARK.json").read_text())
    names = {m["name"] for m in run.cell_metrics(bench, CELL, "per_layer")}
    assert set(NEW) | set(APPENDED) <= names
    assert not names & {
        "bulk_mla_device_pct", "mla_attend_roofline_pct", "bulk_attn_device_pct",
        "bulk_eva_attn_device_pct", "bulk_sweep_idle_pct", "bulk_short_conv_device_pct",
        "short_conv_roofline_pct", "gqa_attend_roofline_pct", "bulk_moe_device_pct",
        "moe_experts_roofline_pct", "bulk_swa_device_pct", "swa_attend_roofline_pct",
        "exaone_gqa_attend_roofline_pct",
    }
    for entry in bench["per_layer"]:
        if entry["name"] in NEW:
            assert entry["workloads"] == [CELL] and entry["moves"] == "bulk_rows_per_s"
            assert (entry["layer"], entry["source"]) == ("programs", "device_trace")
        if entry["name"] in APPENDED:
            assert CELL in entry["workloads"] and "lfm2-8b-a1b.bulk-hist" in entry["workloads"]
    for other in CELLS:
        if other != CELL:
            assert not set(NEW) & {m["name"] for m in run.cell_metrics(bench, other, "per_layer")}
    # every metric with no list of cells is reported here too, as in the other cells
    unlisted = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert unlisted <= names


# -------------------------------------------------------------- the driver
def test_the_driver_sets_the_per_head_leaves_and_fits_nothing(tiny_root):
    loaded = run.load_cell(tiny_root, CELL)
    ctx = run.Context(2**31 + 9, loaded["cell"], loaded["config"], loaded["traffic"])
    module = run.load_module(loaded["driver_file"])
    driver = module.build(ctx)
    driver.setup()
    mc = loaded["config"]["model_config"]
    assert (mc["family"], mc["depth"], mc["heads"] // mc["kv_heads"]) == ("falcon_h1", 3, 5)
    assert mc["key_multiplier"] == CONFIG["source_config"]["key_multiplier"]  # as published
    first = driver.weights["params"]["block_0"]
    np.testing.assert_allclose(np.asarray(first["a_log"]["bias"]), np.log(np.arange(1, 7)), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(first["skip"]["scale"]), 1.0)  # D = 1, the issue's
    dt = np.log1p(np.exp(np.asarray(first["dt_bias"]["bias"], np.float64)))
    assert (dt > 0.00099).all() and (dt < 0.101).all()
    assert driver.bundle.variables is driver.weights  # the program reads what the reference reads
    driver.warmup()
    driver.window(0.0, max_units=2)
    expected = driver.reference_outputs()
    for job in driver.jobs:
        assert "routing" not in job
        assert driver.compare(job, expected)["pred_max_gap"] < 1e-5


def test_the_driver_prints_the_record_of_a_slow_job(capsys, monkeypatch, tiny_root):
    loaded = run.load_cell(tiny_root, CELL)
    ctx = run.Context(7, loaded["cell"], loaded["config"], loaded["traffic"])
    module = run.load_module(loaded["driver_file"])
    driver = module.build(ctx)
    driver.setup()
    driver.warmup()
    capsys.readouterr()
    monkeypatch.setattr(module, "SLOW_JOB", 0.0)  # every job is slow
    driver.window(0.0, max_units=2)
    lines = [line for line in capsys.readouterr().err.splitlines()
             if line.startswith("slow job record: ")]
    records = [json.loads(line.split(": ", 1)[1]) for line in lines]
    assert len(records) == 2  # the window's two jobs, not set-up's
    assert [r["wall_s"] for r in records] == pytest.approx(
        [job["wall_s"] for job in driver.jobs], rel=0.2, abs=5e-3)
    assert all({"phases", "stages", "pauses", "compile_events"} <= set(r) for r in records)


# ------------------- this family's own three faults (`benchmark/faults/falcon_h1.py`)
@pytest.fixture(autouse=True)
def fresh_chunk_programs():
    """`parallel/bulk.py` keeps a model's chunk program from job to job: a
    fault under the model has to be traced anew, and may not outlive its
    test."""
    from mlops_tpu.parallel import bulk

    bulk.CHUNK_PROGRAMS.clear()
    yield
    bulk.CHUNK_PROGRAMS.clear()


def test_the_rehearsal_is_correct_before_any_fault(capsys, tiny_root):
    result, _ = drive(capsys, tiny_root, CELL)
    assert result["correct"] is True
    assert result["compared"]["pred_rms_gap"]["value"] < 1e-6  # float32: the limits are tight


@pytest.mark.parametrize("fault", sorted(OWN_FAULTS))
def test_a_fault_of_the_familys_own_is_not_correct(capsys, monkeypatch, tiny_root, fault):
    OWN_FAULTS[fault](monkeypatch)
    result, _ = drive(capsys, tiny_root, CELL)
    assert result["correct"] is False, fault
    failed = [n for n, c in result["compared"].items() if c["value"] > c["limit"]]
    assert set(failed) <= {"pred_rms_gap", "pred_max_gap"} and failed, (fault, result["compared"])


def test_faulted_readings_holds_the_faulted_program_to_the_cells_limits(capsys, tiny_root):
    """`benchmark/faulted_readings.py`, the chip's trial, at the rehearsal's
    size: the state not carried reads false under ``correct.program``, the
    fault is gone when it returns."""
    from mlops_tpu.models import falcon_h1

    real = falcon_h1.ssd_scan
    rc = faulted_readings.main(
        ["--family", "falcon_h1", "--fault", "state_not_carried", "--workload", CELL,
         "--seeds", "3000000017", "--seconds", "0", "--control", "0"],
        bench_root=tiny_root, require_chip=False,
    )
    assert rc == 0 and falcon_h1.ssd_scan is real
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] == {"program": False}
    assert set(line["fails"]["program"]) <= {"pred_rms_gap", "pred_max_gap"}

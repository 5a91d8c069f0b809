"""What the ``nemotron_h`` family adds to the benchmark: the operation and
byte counts of its three kinds of layer against hand counts at a tiny
shape, its three readers on a hand-made profile, the driver that both
sets the mixers' per-head leaves and fits the routers' bias, and the cell
end to end at its rehearsal size with the timed path broken underneath
once for each fault that is this family's own: SiLU for ReLU², the
routed scaling dropped, rotary positions applied, the scan's state not
carried. (The cell's rehearsal, the five faults every bulk cell can have,
its control and the reference against the program run through the files
that are parametrised over ``BENCHMARK.json``: ``test_rehearsal.py``,
``test_reference.py``.)"""

import json
from pathlib import Path

import numpy as np
import pytest
from conftest import CELLS, CONFIGS
from test_rehearsal import drive

from benchmark import faulted_readings, flops, run
from benchmark import program_trace as pt
from benchmark.faults.nemotron_h import OWN_FAULTS
from benchmark.flops import nemotron_h as counts
from benchmark.rooflines import falcon_h1 as falcon_roofs
from benchmark.rooflines import nemotron_h as roofs

CONFIG = json.loads(
    (Path(__file__).resolve().parents[1] / "configs" / "nemotron-labs-twotower-30b-a3b.json")
    .read_text()
)
CELL = "nemotron-labs-twotower-30b-a3b.bulk-hist"
NEW = [
    "bulk_hybrid_moe_layer_device_pct", "relu2_experts_roofline_pct",
    "nemotron_ssd_scan_roofline_pct", "bulk_hybrid_mamba_layer_device_pct",
    "nemotron_gqa_attend_roofline_pct",
]
ROOFLINES = [name for name in NEW if name.endswith("roofline_pct")]
# metrics of other cells whose readers read this family's scopes unedited
APPENDED = ["bulk_sweep_span_idle_pct", "bulk_gqa_device_pct"]
# hidden 8; a mixer of 6 in 3 heads of 2 over 1 group with a state of 5;
# 4 query heads over 2 key/value heads of 3; 4 routed experts of 5, 2 a
# token, 2 held, beside a shared one of 7; the pattern's M E *; records of
# 4 tokens, 3 a history
TINY = {
    "model_config": {
        "family": "nemotron_h", "token_dim": 8, "depth": 3, "heads": 4, "kv_heads": 2,
        "head_dim": 3, "ssm_dim": 6, "ssm_heads": 3, "ssm_state": 5, "ssm_groups": 1,
        "moe_ffn_dim": 5, "shared_ffn_dim": 7, "num_experts": 4, "experts_per_token": 2,
        "experts_held": 2,
    },
    "hybrid_override_pattern": "ME*EM",
    "records_per_history": 3,
    "tokens_per_record": 4,
}
US = 1_000


def test_the_cell_and_the_configuration_are_in_the_benchmark():
    assert CELL in CELLS and "nemotron-labs-twotower-30b-a3b" in CONFIGS
    bench = json.loads((Path(run.CHECKOUT) / "BENCHMARK.json").read_text())
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["bert-base.bulk-dp4"]  # one in nine: what the 25% rule allows
    entry = next(c for c in bench["configs"] if c["name"] == "nemotron-labs-twotower-30b-a3b")
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/nemotron-labs-twotower-30b-a3b.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["traffic"] == "bulk-moe-ssm-token-histories-1228" and cell["chips"] == 1
    assert "1,152 assignments a run" in cell["why"]  # 512 x 48 x 6 / 128
    assert 512 * 48 * 6 // 128 == 1152 == CONFIG["deployment"]["score_chunk_rows"] * 48 * 6 // 128
    traffic = json.loads((run.HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    assert traffic["driver"] == "bulk_moe_ssm_token_histories"
    for like in ("bulk-token-histories-1228", "bulk-ssm-token-histories-1228"):
        other = json.loads((run.HERE / "traffic" / f"{like}.json").read_text())
        for key in ("rows_per_file", "data", "check_histories", "outlier_check_rows",
                    "traced_units", "tokens_per_record", "rehearsal"):
            assert traffic[key] == other[key], (like, key)  # every traffic parameter is theirs


def test_macs_match_the_hand_count_at_a_tiny_shape():
    mc = TINY["model_config"]
    assert counts.kinds(TINY) == ["mamba", "moe", "attention"]
    seq, records = 12, 3
    # a mixer at every position: in_proj 8 x (6 + 6 + 5 + 5 + 3), three products of 30,
    # out_proj 6 x 8
    mamba = seq * (8 * 25 + 3 * 30 + 6 * 8)
    assert counts.layer_macs(TINY, "mamba", seq, range(seq)) == mamba
    # a token's expert layer: the router 8 x 4, one of two held experts' share of
    # a token's two choices (up and down, 8 x 5 each), the shared one (8 x 7 twice)
    token = 8 * 4 + 1.0 * 2 * 8 * 5 + 2 * 8 * 7
    assert counts.experts_macs(mc) == token
    assert counts.layer_macs(TINY, "moe", seq, range(seq)) == seq * token
    # the last layer, attention: k, v at every position, q, o and the keys at 3, 7, 11
    kv, rest = 2 * 8 * 2 * 3, 2 * 8 * 4 * 3
    last = seq * kv + records * rest + 2 * 4 * 3 * (4 + 8 + 12)
    assert counts.history_macs(TINY, records) == mamba + seq * token + last + records * 8
    assert flops.forward_flops_per_row(TINY) == 2 * (counts.history_macs(TINY, 3) // 3)
    # ending on an expert layer: the experts at the read positions alone
    ending = {**TINY, "model_config": {**mc, "depth": 4}}
    whole_attention = seq * (kv + rest) + 2 * 4 * 3 * sum(range(1, seq + 1))
    assert counts.history_macs(ending, records) == (
        mamba + seq * token + whole_attention + records * token + records * 8
    )


def test_the_real_configuration_counts_what_its_file_reckons():
    assert counts.kinds(CONFIG) == ["mamba", "moe"] * 2 + ["mamba", "attention"] + [
        "moe", "mamba"] * 3 + ["attention"]
    mc = CONFIG["model_config"]
    # a token of an E layer: the router, 3 of its 6 choices held, the shared expert
    token = 2688 * 128 + 3 * 2 * 2688 * 1856 + 2 * 2688 * 3712
    assert counts.experts_macs(mc) == token
    whole = counts.history_macs(CONFIG, 64)
    moe = 5 * 3072 * token
    assert moe / whole == pytest.approx(0.473, abs=0.005)  # the expert layers' share
    assert flops.forward_flops_per_row(CONFIG) == pytest.approx(50.97e9, rel=1e-3)
    # a job of 1,228 rows: 62.6 TFLOP, 0.32 s at 197 TFLOP/s
    assert 1228 * flops.forward_flops_per_row(CONFIG) / 197e12 == pytest.approx(0.318, abs=0.005)


def test_roofline_operations_and_bytes_match_the_hand_count():
    ops, moved = falcon_roofs.scan_layer_work(TINY, 3, 0)
    assert ops == 2 * 30 * 3 * 12 and moved == 2 * (12 * (6 + 5 + 3) + 12 * (5 + 6))
    slow = {"bf16_flops_per_s": 1e4}
    # the one mixer layer of M E *, and its one attention layer, the last
    assert scan_history_seconds(TINY, 3, slow) == pytest.approx(ops / 1e4)
    a_ops, a_moved = falcon_roofs.attend_layer_work(TINY, 3, 2)
    assert a_ops == 2 * 4 * 3 * 2 * (4 + 8 + 12)  # keys before each of 3 read positions
    assert a_moved == 2 * (2 * 3 * 4 * 3 + 2 * 12 * 2 * 3)
    attend = roofs.history_seconds(TINY, 3, slow, "attention", falcon_roofs.attend_layer_work)
    assert attend == pytest.approx(a_ops / 1e4)
    e_ops, e_moved = roofs.experts_layer_work(TINY, 10, 3)
    assert e_ops == 2 * 2 * 8 * 5 * 10  # two products, no gate
    assert e_moved == 2 * (2 * 8 * 5 * 3 + 2 * 8 * 10)
    jobs = [{"routing": {"per_layer": [[4, 6]], "expert_runs_per_layer": [[1, 2]]}}, {}]
    assert roofs.experts_seconds(TINY, jobs, slow) == pytest.approx(e_ops / 1e4)
    assert roofs.experts_seconds(TINY, [{}], slow) is None
    # the real shapes: the scan bound by memory, a run's experts by compute
    peaks = {"bf16_flops_per_s": 197e12}
    real = falcon_roofs.scan_layer_work(CONFIG, 64, 0)
    assert real == (6 * 4096 * 128 * 3072, 2 * 3072 * (4096 + 1024 + 64 + 1024 + 4096))
    assert falcon_roofs.binds(real, peaks) == "memory"
    assert real[1] / 819e9 / (real[0] / 197e12) == pytest.approx(1.58, abs=0.01)
    assert scan_history_seconds(CONFIG, 64, peaks) == pytest.approx(6 * real[1] / 819e9)
    # attention at a group of 16: compute binds in the full layer (5) and the last (12)
    for layer in (5, 12):
        assert falcon_roofs.binds(falcon_roofs.attend_layer_work(CONFIG, 64, layer), peaks) == "compute"
    run_of_eight = roofs.experts_layer_work(CONFIG, 73728, 64)
    assert falcon_roofs.binds(run_of_eight, peaks) == "compute"


def scan_history_seconds(spec, records, peaks):
    return roofs.history_seconds(spec, records, peaks, "mamba", falcon_roofs.scan_layer_work)


# ------------------------------------------------------------ the readers
BLOCK = "jit(fused_counting)/NemotronHScorer/block_{0}/{1}/block_{0}._mixed/"


def hand_made():
    """Window 0..1000 us, one job, two runs of the chunk program. Device: a
    mixer layer 150 us (its scan 60), an expert layer 200 us (the ReLU²
    kernels 120 of it, the shared expert 50, the router 30), an attention
    layer 50 us, 100 of embedding: busy 500."""
    def span(name, lo, hi, **attrs):
        return [name, lo * US, (hi - lo) * US, attrs]

    host = [
        span("bench:window", 0, 1000),
        span("bench:job", 10, 900),
        span("mlops:bulk.job", 20, 880, job=1, pid=7, rows=10, chunks=2),
    ]
    mixer, experts, attention = (
        BLOCK.format(0, "mamba_mixer"), BLOCK.format(1, "moe_layer"),
        BLOCK.format(2, "attention_layer"),
    )
    timeline = [
        ("fusion", 90, mixer + "ssm_in/in_proj/dot_general:"),
        ("fusion", 60, mixer + "ssm_scan/bcgrik,bckgrp->bcigrp/dot_general:"),
        ("fusion", 30, experts + "router/dot_general:"),
        ("custom-call", 80, experts + "cond/branch_1_fun/relu2_experts/jit(_kernel_or_xla)/experts_up_fwd:"),
        ("custom-call", 40, experts + "cond/branch_1_fun/relu2_experts/jit(_kernel_or_xla)/experts_down_fwd:"),
        ("fusion", 50, experts + "shared_expert/shared_up/dot_general:"),
        ("custom-call", 50, attention + "gqa_attend/jit(_kernel_or_xla)/gqa_attend_fwd:"),
        ("fusion", 100, "jit(fused_counting)/NemotronHScorer/embed/gather:"),
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


class _Driver:
    jobs = [{"routing": {"per_layer": [[40, 60]], "expert_runs_per_layer": [[2, 2]]}}]


# one job of 10 rows: a run of 6 (2 histories) and a padded tail of 3 (1)
WINDOW = {"setup": [], "window": [{"rows": 10, "chunks": 2, "chunk_rows": 6, "rows_run": 9}]}


def _facts(peaks, spec=TINY, driver=_Driver):
    spec = {**spec, "deployment": {"score_chunk_rows": 6}}  # 2 histories a chunk
    trace = {"programs": [["jit_fused_counting", 0.0, 0.1], ["jit_add", 0.1, 0.2],
                          ["jit_fused_counting", 0.2, 0.3]]}
    return {"trace": trace, "peaks": peaks, "config": spec, "driver": driver,
            "traffic": {"rows_per_file": 10}}


def test_readers_on_the_hand_made_profile(monkeypatch):
    program = pt.reduce_profile(hand_made(), 7)
    monkeypatch.setattr(pt, "load", lambda facts: program)
    monkeypatch.setattr(roofs, "kind_seconds", lambda facts, prefix: 0.0)
    monkeypatch.setattr(roofs.job_log, "load", lambda facts: WINDOW)
    moe = _reader("bulk_hybrid_moe_layer_device_pct")
    monkeypatch.setattr(moe, "kind_seconds", lambda facts, prefix: 0.0)
    assert moe.read(_facts(None)) == pytest.approx(100 * 200 / 500)
    assert _reader("bulk_hybrid_mamba_layer_device_pct").read(_facts(None)) == pytest.approx(
        100 * 150 / 500
    )
    # the other decoders' readers, unedited, read this family's attention and sweep
    assert _reader("bulk_gqa_device_pct").read(_facts(None)) == pytest.approx(100 * 50 / 500)
    peaks = {"bf16_flops_per_s": 1e9}
    # the run of 2 histories and the tail of 1, each at its own size: 3, not 2 runs x 2
    assert roofs.histories_run(_facts(peaks)) == 3
    allowed = 3 * scan_history_seconds(TINY, 3, peaks)
    assert _reader("nemotron_ssd_scan_roofline_pct").read(_facts(peaks)) == pytest.approx(
        100 * allowed / 60e-6
    )
    allowed = 3 * roofs.history_seconds(TINY, 3, peaks, "attention", falcon_roofs.attend_layer_work)
    assert _reader("nemotron_gqa_attend_roofline_pct").read(_facts(peaks)) == pytest.approx(
        100 * allowed / 50e-6
    )
    # a record from before ``rows_run``: every run a whole chunk
    older = {"setup": [], "window": [{"rows": 10, "chunks": 2, "chunk_rows": 6}]}
    monkeypatch.setattr(roofs.job_log, "load", lambda facts: older)
    assert roofs.histories_run(_facts(peaks)) == 4
    experts = roofs.experts_seconds(TINY, _Driver.jobs, peaks)
    assert _reader("relu2_experts_roofline_pct").read(_facts(peaks)) == pytest.approx(
        100 * experts / 120e-6
    )
    for name in ROOFLINES:
        assert _reader(name).read(_facts(None)) is None  # no peak: a CPU
    # another family's configuration under the same scope names: nothing, not a wrong share
    other = {**TINY, "model_config": {**TINY["model_config"], "family": "falcon_h1"}}
    for name in ROOFLINES:
        assert _reader(name).read(_facts(peaks, spec=other)) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_where_the_scopes_are_missing(monkeypatch, name):
    """A program without the scopes (the parent's, which has no such
    family); a rehearsal without a device; a driver without a counter; a
    window without a job log: ``None``, never 0, nothing raised."""
    flat = hand_made()
    ops = flat["planes"][0]["lines"][0]["events"]
    flat["planes"][0]["lines"][0]["events"] = [op for op in ops if "embed" in op[3]]
    program = pt.reduce_profile(flat, 7)
    monkeypatch.setattr(pt, "load", lambda facts: program)
    monkeypatch.setattr(roofs, "kind_seconds", lambda facts, prefix: 0.0)
    monkeypatch.setattr(roofs.job_log, "load", lambda facts: WINDOW)
    peaks = {"bf16_flops_per_s": 1e9}
    assert _reader(name).read(_facts(peaks)) is None
    monkeypatch.setattr(pt, "load", lambda facts: None)
    assert _reader(name).read(_facts(peaks)) is None
    whole = pt.reduce_profile(hand_made(), 7)
    monkeypatch.setattr(pt, "load", lambda facts: whole)
    if name == "relu2_experts_roofline_pct":
        assert _reader(name).read(_facts(peaks, driver=object())) is None  # no jobs
    if name in ("nemotron_ssd_scan_roofline_pct", "nemotron_gqa_attend_roofline_pct"):
        monkeypatch.setattr(roofs.job_log, "load", lambda facts: None)
        assert _reader(name).read(_facts(peaks)) is None


def test_the_cells_metrics_are_the_three_new_ones_and_every_unlisted_one():
    bench = json.loads((Path(run.CHECKOUT) / "BENCHMARK.json").read_text())
    names = {m["name"] for m in run.cell_metrics(bench, CELL, "per_layer")}
    assert set(NEW) | set(APPENDED) <= names
    for entry in bench["per_layer"]:
        if entry["name"] in NEW:
            assert entry["workloads"] == [CELL] and entry["moves"] == "bulk_rows_per_s"
            assert (entry["layer"], entry["source"]) == ("programs", "device_trace")
        elif entry["name"] in APPENDED:
            assert entry["workloads"][-1] == CELL  # appended, the cells before it kept
        elif "workloads" in entry:
            assert CELL not in entry["workloads"]  # nothing else was edited
    for other in CELLS:
        if other != CELL:
            assert not set(NEW) & {m["name"] for m in run.cell_metrics(bench, other, "per_layer")}
    # every metric with no list of cells is reported here too, as in the other cells
    unlisted = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert unlisted <= names and names == unlisted | set(NEW) | set(APPENDED)
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == NEW  # appended, in order


# -------------------------------------------------------------- the driver
def test_the_driver_sets_the_leaves_then_fits_the_bias(tiny_root):
    """The per-head leaves as Mamba-2's, the router's bias fitted to the
    reference's scores UNDER those leaves (a fit before them would be a
    fit to another model), and the program reading what the reference
    reads."""
    from benchmark.reference import nemotron_h as reference

    loaded = run.load_cell(tiny_root, CELL)
    ctx = run.Context(2**31 + 9, loaded["cell"], loaded["config"], loaded["traffic"])
    module = run.load_module(loaded["driver_file"])
    driver = module.build(ctx)
    driver.setup()
    mc = loaded["config"]["model_config"]
    assert (mc["family"], mc["depth"], mc["heads"] // mc["kv_heads"]) == ("nemotron_h", 6, 16)
    params = driver.weights["params"]
    first = params["block_0"]
    np.testing.assert_allclose(np.asarray(first["a_log"]["bias"]), np.log(np.arange(1, 17)), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(first["skip"]["scale"]), 1.0)
    for name in ("block_1", "block_3"):  # the E layers of MEMEM*
        bias = np.asarray(params[name]["router"]["bias"])
        assert np.abs(bias).max() > 0 and abs(bias.mean()) < 1e-6  # fitted, centred
    assert driver.bundle.variables is driver.weights
    # refitting from the driver's own weights changes nothing: the fit was the last step
    per = driver.per_history
    again = module._token.balance_selection_bias(
        reference, driver.weights, driver.cat[:per], driver.num[:per], driver.spec)
    for name in ("block_1", "block_3"):
        np.testing.assert_allclose(
            np.asarray(again["params"][name]["router"]["bias"]),
            np.asarray(params[name]["router"]["bias"]), atol=1e-6)
    driver.warmup()
    driver.window(0.0, max_units=2)
    expected = driver.reference_outputs()
    for job in driver.jobs:
        assert len(job["routing"]["per_layer"]) == 2  # the two E layers, in pattern order
        assert driver.compare(job, expected)["pred_max_gap"] < 1e-5


def test_the_driver_prints_the_record_of_a_slow_job(capsys, monkeypatch, tiny_root):
    loaded = run.load_cell(tiny_root, CELL)
    ctx = run.Context(7, loaded["cell"], loaded["config"], loaded["traffic"])
    module = run.load_module(loaded["driver_file"])
    driver = module.build(ctx)
    driver.setup()
    driver.warmup()
    capsys.readouterr()
    monkeypatch.setattr(module._ssm, "SLOW_JOB", 0.0)  # every job is slow
    driver.window(0.0, max_units=2)
    err = capsys.readouterr().err
    records = [json.loads(line.split(": ", 1)[1]) for line in err.splitlines()
               if line.startswith("slow job record: ")]
    assert len(records) == 2  # the window's two jobs, not set-up's
    assert all("routing" in r for r in records)  # the counter's scalars in each record
    assert "routing of the window's first job" in err


# ------------------- this family's own four faults (`benchmark/faults/nemotron_h.py`)
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
    assert {"pred_rms_gap", "pred_max_gap"} == set(failed), (fault, result["compared"])


def test_faulted_readings_holds_the_faulted_program_to_the_cells_limits(capsys, tiny_root):
    """`benchmark/faulted_readings.py`, the chip's trial, at the rehearsal's
    size: rotary positions applied read false under ``correct.program``,
    the fault is gone when it returns."""
    from mlops_tpu.models import nemotron_h

    real = nemotron_h.grouped_query_attention
    rc = faulted_readings.main(
        ["--family", "nemotron_h", "--fault", "rope_applied", "--workload", CELL,
         "--seeds", "3000000017", "--seconds", "0", "--control", "0"],
        bench_root=tiny_root, require_chip=False,
    )
    assert rc == 0 and nemotron_h.grouped_query_attention is real
    assert not hasattr(nemotron_h.NemotronHBlock, "rope_theta")
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] == {"program": False}
    assert set(line["fails"]["program"]) == {"pred_rms_gap", "pred_max_gap"}

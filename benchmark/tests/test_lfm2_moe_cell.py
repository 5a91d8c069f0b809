"""What ISSUE 33 adds to the benchmark: the operation and byte counts of
the ``lfm2_moe`` family against hand counts at a tiny shape, the four
readers of its scopes on a hand-made profile (and the expert layer's
readers of ``kimi-k2-5l``, which the cell is appended to), and the
token-history driver on a family with every expert held. (The new cell's
rehearsal end to end, its five faults, its control and the reference
against the program run through the files that are parametrised over
``BENCHMARK.json``: ``test_rehearsal.py``, ``test_reference.py``.)"""

import json
from pathlib import Path

import pytest
from conftest import CELLS, CONFIGS

from benchmark import flops, run
from benchmark import program_trace as pt
from benchmark.flops import lfm2_moe
from benchmark.rooflines import kimi_k2 as moe_roofs
from benchmark.rooflines import lfm2_moe as roofs

CONFIG = json.loads(
    (Path(__file__).resolve().parents[1] / "configs" / "lfm2-8b-a1b.json").read_text()
)
CELL = "lfm2-8b-a1b.bulk-hist"
# hidden 8, 4 query heads over 2 key/value heads of 2, dense 12, 8 experts of
# width 3, 2 a token, all held; conv conv attn conv, 1 dense layer; records of 4
# tokens, 3 a history
TINY = {
    "model_config": {
        "family": "lfm2_moe", "token_dim": 8, "heads": 4, "kv_heads": 2, "depth": 4,
        "ffn_dim": 12, "moe_ffn_dim": 3, "num_experts": 8, "experts_per_token": 2,
        "first_expert": 0, "experts_held": 8, "dense_layers": 1, "conv_width": 3,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv"],
    },
    "records_per_history": 3,
    "tokens_per_record": 4,
}
US = 1_000


def test_the_cell_and_the_configuration_are_in_the_benchmark():
    assert CELL in CELLS and "lfm2-8b-a1b" in CONFIGS
    bench = json.loads((Path(run.CHECKOUT) / "BENCHMARK.json").read_text())
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["bert-base.bulk-dp4"]  # one in six: what the 25% rule allows
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b")
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CONFIG["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["traffic"] == "bulk-token-histories-1228" and cell["chips"] == 1


def test_macs_match_the_hand_count_at_a_tiny_shape():
    mc = TINY["model_config"]
    assert lfm2_moe.conv_macs(mc) == (8 * 24, 8 * 8)
    assert lfm2_moe.attention_macs(mc) == (2 * 8 * 2 * 2, 2 * 8 * 8)  # k, v; q, o
    assert lfm2_moe.attention_macs_per_key(mc) == 4 * 2 * 2  # 4 heads, 2 wide, 2 products
    dense, expert = 3 * 8 * 12, 3 * 8 * 3
    sparse = 8 * 8 + 2 * expert  # the router and two experts a token: all are held
    assert lfm2_moe.ffn_macs(mc, 0) == dense and lfm2_moe.ffn_macs(mc, 1) == sparse == 208
    half = {**mc, "first_expert": 4, "experts_held": 4}
    assert lfm2_moe.ffn_macs(half, 1) == 8 * 8 + 1 * expert  # a share gets its part
    seq, records = 12, 3
    every = sum(range(1, seq + 1))  # 78 keys over a full layer's queries
    conv = seq * (192 + 64)
    attention = seq * (64 + 128) + 16 * every
    # the last layer, a convolution: the input projection at each read position
    # and the two before it, the rest at the three read positions
    last = 3 * 3 * 192 + records * (64 + sparse)
    want = (conv + seq * dense) + (conv + seq * sparse) + (attention + seq * sparse) + last
    assert lfm2_moe.history_macs(TINY, records) == want + records * 8
    assert flops.forward_flops_per_row(TINY) == 2 * (lfm2_moe.history_macs(TINY, 3) // 3)
    # a last layer of attention: keys and values whole, the rest at the reads
    ends_on_attention = {**TINY, "model_config": {**mc, "depth": 3}}
    read = 4 + 8 + 12
    last = seq * 64 + records * (128 + sparse) + 16 * read
    assert lfm2_moe.history_macs(ends_on_attention, records) == (
        (conv + seq * dense) + (conv + seq * sparse) + last + records * 8
    )


def test_the_real_configuration_counts_what_the_issue_reckoned():
    whole = lfm2_moe.history_macs(CONFIG, 64)
    mc = CONFIG["model_config"]
    assert whole / 3072 == pytest.approx(915e6, rel=2e-3)  # MACs a token
    assert flops.forward_flops_per_row(CONFIG) == pytest.approx(87.8e9, rel=2e-3)
    # an expert layer's FFN: the issue's "88 of each layer's ~120 MFLOP" in MACs
    assert lfm2_moe.ffn_macs(mc, 2) == pytest.approx(44.1e6, rel=2e-3)
    assert sum(lfm2_moe.conv_macs(mc)) == 16_777_216
    assert sum(lfm2_moe.attention_macs(mc)) == 10_485_760
    # 13 expert layers whole and the last at its 64 read positions: 63% of the
    # required operations (two thirds of what the program, which runs 14, executes)
    experts = (13 * 3072 + 64) * lfm2_moe.ffn_macs(mc, 2)
    assert experts / whole == pytest.approx(0.63, abs=0.01)


def test_roofline_operations_and_bytes_match_the_hand_count():
    assert roofs.mixers(TINY) == ["conv", "conv", "full_attention", "conv"]
    # three convolution layers, one pass each over [T, 24] in and [T, 8] out, bfloat16
    assert roofs.short_conv_run_seconds(TINY, 24) == pytest.approx(3 * 24 * 32 * 2 / 819e9)
    ops, moved = roofs.attend_layer_work(TINY, 3, last=False)
    assert ops == 2 * 16 * 78
    assert moved == 2 * (2 * 12 * 8 + 2 * 12 * 4)  # q, o 8 wide; k, v 4 wide (two heads of 2)
    ops_last, moved_last = roofs.attend_layer_work(TINY, 3, last=True)
    assert ops_last == 2 * 16 * (4 + 8 + 12) and moved_last == 2 * (2 * 3 * 8 + 2 * 12 * 4)
    slow = {"bf16_flops_per_s": 1e4}
    assert roofs.attend_history_seconds(TINY, 3, slow) == pytest.approx(ops / 1e4)
    ends_on_attention = {**TINY, "model_config": {**TINY["model_config"], "depth": 3}}
    assert roofs.attend_history_seconds(ends_on_attention, 3, slow) == pytest.approx(ops_last / 1e4)
    # the real shape: four attention layers, compute-bound, 38.7 GFLOP and 0.196 ms each
    real_ops, real_moved = roofs.attend_layer_work(CONFIG, 64, last=False)
    assert real_ops == 2 * 2 * 2048 * 3072 * 3073 // 2
    assert real_moved == 2 * (2 * 3072 * 2048 + 2 * 3072 * 512)
    assert roofs.attend_history_seconds(CONFIG, 64, {"bf16_flops_per_s": 197e12}) == pytest.approx(
        4 * real_ops / 197e12, rel=1e-9
    )
    assert roofs.mixers(CONFIG).count("conv") == 12
    assert roofs.short_conv_run_seconds(CONFIG, 12288) == pytest.approx(2.95e-3, rel=1e-2)
    # the experts at full load: compute-bound where kimi-k2-5l's share is memory-bound
    e_ops, e_moved = moe_roofs.experts_layer_work(CONFIG, 4 * 12288, 32)
    assert e_ops / 197e12 > e_moved / 819e9
    assert e_ops == 2 * 3 * 2048 * 1792 * 49152


# ------------------------------------------------------------ the readers
BLOCK = "jit(fused_counting)/Lfm2MoeScorer/block_{}/"


def hand_made():
    """Window 0..1000 us, one job, two runs of the chunk program. Device:
    convolution 130 us (in 60, the operator 20, out 50), attention 100 us
    (qkv 30, attend 50: the products 40, the softmax 10; o 20), moe 100 us under
    scopes (router 10, dispatch 20, experts' activation 10, combine 60) and
    70 us of ragged-dot with no scope, 100 of ffn: busy 500."""
    def span(name, lo, hi, **attrs):
        return [name, lo * US, (hi - lo) * US, attrs]

    host = [
        span("bench:window", 0, 1000),
        span("bench:job", 10, 900),
        span("mlops:bulk.job", 20, 880, job=1, pid=7, rows=10, chunks=2),
    ]
    timeline = [
        ("fusion", 60, BLOCK.format(1) + "block_1._conv/conv_in/in_proj/dot_general:"),
        ("fusion", 20, BLOCK.format(1) + "block_1._conv/short_conv/mul:"),
        ("fusion", 50, BLOCK.format(1) + "block_1._conv/conv_out/out_proj/dot_general:"),
        ("fusion", 30, BLOCK.format(2) + "block_2._attention/gqa_qkv/rope/mul:"),
        ("fusion", 40, BLOCK.format(2) + "block_2._attention/gqa_attend/bqhe,bkhe->bhqk/dot_general:"),
        ("fusion", 10, BLOCK.format(2) + "block_2._attention/gqa_attend/exp:"),
        ("fusion", 20, BLOCK.format(2) + "block_2._attention/gqa_o/o/dot_general:"),
        ("fusion", 10, BLOCK.format(1) + "router/dot_general:"),
        ("fusion", 20, BLOCK.format(1) + "moe_dispatch/sort:"),
        ("ragged-dot-none", 70, "ragged-dot-none:"),
        ("fusion", 10, BLOCK.format(1) + "cond/branch_1_fun/experts/mul:"),
        ("fusion", 60, BLOCK.format(1) + "cond/branch_1_fun/moe_combine/scatter-add:"),
        ("fusion", 100, BLOCK.format(0) + "ffn/block_0._swiglu/gate/dot_general:"),
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
    jobs = [{"routing": {"per_layer": [[4, 6, 0, 0, 1, 1, 0, 0]],
                         "expert_runs_per_layer": [[2, 1, 0, 0, 1, 1, 0, 0]]}}]


def _facts(peaks, driver=_Driver):
    spec = {**TINY, "deployment": {"score_chunk_rows": 6}}  # 2 histories, 24 tokens a chunk
    trace = {"programs": [["jit_fused_counting", 0.0, 0.1], ["jit_add", 0.1, 0.2],
                          ["jit_fused_counting", 0.2, 0.3]]}
    return {"trace": trace, "peaks": peaks, "config": spec, "driver": driver,
            "traffic": {"rows_per_file": 10}}


NEW = ["bulk_short_conv_device_pct", "bulk_gqa_device_pct", "short_conv_roofline_pct",
       "gqa_attend_roofline_pct"]
APPENDED = ["bulk_moe_device_pct", "moe_experts_roofline_pct", "bulk_sweep_span_idle_pct"]


def test_readers_on_the_hand_made_profile(monkeypatch):
    program = pt.reduce_profile(hand_made(), 7)
    monkeypatch.setattr(pt, "load", lambda facts: program)
    monkeypatch.setattr(moe_roofs, "kind_seconds", lambda facts, prefix: 70e-6)
    assert moe_roofs.scope_seconds(program, roofs.CONV_SCOPES) == pytest.approx(130e-6)
    assert moe_roofs.scope_seconds(program, roofs.GQA_SCOPES) == pytest.approx(100e-6)
    assert _reader("bulk_short_conv_device_pct").read(_facts(None)) == pytest.approx(100 * 130 / 500)
    assert _reader("bulk_gqa_device_pct").read(_facts(None)) == pytest.approx(100 * 100 / 500)
    peaks = {"bf16_flops_per_s": 1e9}
    allowed = 2 * roofs.short_conv_run_seconds(TINY, 24)  # 2 runs of 24 tokens
    assert _reader("short_conv_roofline_pct").read(_facts(peaks)) == pytest.approx(
        100 * allowed / 20e-6
    )
    allowed = 2 * 2 * roofs.attend_history_seconds(TINY, 3, peaks)  # 2 runs x 2 histories
    assert _reader("gqa_attend_roofline_pct").read(_facts(peaks)) == pytest.approx(
        100 * allowed / 50e-6  # the scope whole: the products and the softmax
    )
    # the expert layer's readers, which this family shares with kimi_k2
    moe = _reader("bulk_moe_device_pct")
    monkeypatch.setattr(moe, "kind_seconds", lambda facts, prefix: 70e-6)
    assert moe.read(_facts(None)) == pytest.approx(100 * 170 / 500)
    experts = moe_roofs.experts_seconds(TINY, _Driver.jobs, peaks)
    assert _reader("moe_experts_roofline_pct").read(_facts(peaks)) == pytest.approx(
        100 * experts / (10e-6 + 70e-6)
    )
    for name in ("short_conv_roofline_pct", "gqa_attend_roofline_pct"):
        assert _reader(name).read(_facts(None)) is None  # no peak: a CPU


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_where_the_scopes_are_missing(monkeypatch, name):
    """A program without the scopes; a rehearsal without a device; a trace
    without a run of the chunk program: ``None``, never 0, nothing raised."""
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


def test_the_cells_metrics_are_the_four_new_ones_and_the_expert_layers():
    bench = json.loads((Path(run.CHECKOUT) / "BENCHMARK.json").read_text())
    names = {m["name"] for m in run.cell_metrics(bench, CELL, "per_layer")}
    assert set(NEW) | set(APPENDED) <= names and len(names) == 16
    assert not names & {"bulk_mla_device_pct", "mla_attend_roofline_pct", "bulk_attn_device_pct",
                        "bulk_eva_attn_device_pct", "bulk_sweep_idle_pct"}
    for entry in bench["per_layer"]:
        if entry["name"] in NEW:
            assert entry["workloads"] == [CELL] and entry["moves"] == "bulk_rows_per_s"
            assert (entry["layer"], entry["source"]) == ("programs", "device_trace")
        if entry["name"] in APPENDED:
            assert entry["workloads"][-1] == CELL and "kimi-k2-5l.bulk-hist" in entry["workloads"]
    for other in CELLS:
        if other != CELL:
            assert not set(NEW) & {m["name"] for m in run.cell_metrics(bench, other, "per_layer")}
    kimi = {m["name"] for m in run.cell_metrics(bench, "kimi-k2-5l.bulk-hist", "per_layer")}
    assert len(kimi) == 14  # what it reported before this cell came


# -------------------------------------------------------------- the driver
def test_the_driver_fits_the_bias_and_counts_every_assignment(tiny_root):
    """``bulk_token_histories`` as it stands on a second family: the weights
    by group, the selection bias fitted from THIS family's reference (found
    by ``model_config.family``), the routing counter in every job record;
    with all experts held a full layer's counts add up to every choice of
    every token."""
    import numpy as np

    loaded = run.load_cell(tiny_root, CELL)
    ctx = run.Context(2**31 + 9, loaded["cell"], loaded["config"], loaded["traffic"])
    driver = run.load_module(loaded["driver_file"]).build(ctx)
    driver.setup()
    mc = loaded["config"]["model_config"]
    assert (mc["family"], mc["experts_held"], mc["num_experts"]) == ("lfm2_moe", 8, 8)
    bias = np.asarray(driver.weights["params"]["block_2"]["router"]["bias"])
    assert abs(bias.mean()) < 1e-6 and bias.std() > 0.005  # centred, and fitted
    taps = np.asarray(driver.weights["params"]["block_0"]["conv"]["kernel"])
    assert taps.shape == (3, 64) and 0.3 < taps.std() < 0.9  # by their fan-in of 3
    driver.warmup()
    driver.window(0.0, max_units=2)
    expected = driver.reference_outputs()
    for job in driver.jobs:
        routing = job["routing"]
        assert routing["tokens"] == 7 * 48 * 48  # 301 rows: 7 chunks of 16 histories
        per_layer = np.asarray(routing["per_layer"])
        assert per_layer.shape == (6, 8)
        assert (per_layer[:-1].sum(axis=1) == routing["tokens"] * 2).all()  # nothing left out
        assert per_layer[-1].sum() == 7 * 48 * 2  # the last layer: the read positions
        assert per_layer[0].max() < 2 * per_layer[0].mean()  # the fit evens the loads
        assert driver.compare(job, expected)["pred_max_gap"] < 1e-4

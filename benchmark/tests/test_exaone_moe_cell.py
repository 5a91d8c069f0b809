"""What ISSUE 37 adds to the benchmark: the operation and byte counts of
the ``exaone_moe`` family against hand counts at a tiny shape, the three
readers of its scopes on a hand-made profile (and the readers of the
other decoders' scopes which the cell is appended to), and the
token-history driver on a third family. (The new cell's rehearsal end to
end, its five faults, its control and the reference against the program
run through the files that are parametrised over ``BENCHMARK.json``:
``test_rehearsal.py``, ``test_reference.py``.)"""

import json
from pathlib import Path

import pytest
from conftest import CELLS, CONFIGS

from benchmark import flops, run
from benchmark import program_trace as pt
from benchmark.flops import exaone_moe
from benchmark.rooflines import exaone_moe as roofs
from benchmark.rooflines import kimi_k2 as moe_roofs

CONFIG = json.loads(
    (Path(__file__).resolve().parents[1] / "configs" / "k-exaone-236b-a23b.json").read_text()
)
CELL = "k-exaone-236b-a23b.bulk-hist"
SWA, FULL = "sliding_attention", "full_attention"
# hidden 8, 4 query heads over 2 key/value heads of 3 (4 x 3 is not 8), window 5,
# dense 12, 8 experts of width 3, 2 a token, 4 held, beside a shared one;
# L L L G L, 1 dense layer; records of 4 tokens, 3 a history
TINY = {
    "model_config": {
        "family": "exaone_moe", "token_dim": 8, "heads": 4, "kv_heads": 2, "head_dim": 3,
        "attn_window": 5, "depth": 5, "ffn_dim": 12, "moe_ffn_dim": 3, "num_experts": 8,
        "experts_per_token": 2, "first_expert": 0, "experts_held": 4, "dense_layers": 1,
        "layer_types": [SWA, SWA, SWA, FULL, SWA, SWA, SWA, FULL],
    },
    "records_per_history": 3,
    "tokens_per_record": 4,
}
US = 1_000


def test_the_cell_and_the_configuration_are_in_the_benchmark():
    assert CELL in CELLS and "k-exaone-236b-a23b" in CONFIGS
    bench = json.loads((Path(run.CHECKOUT) / "BENCHMARK.json").read_text())
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["bert-base.bulk-dp4"]  # one in seven: what the 25% rule allows
    entry = next(c for c in bench["configs"] if c["name"] == "k-exaone-236b-a23b")
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == CONFIG["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["traffic"] == "bulk-token-histories-1228" and cell["chips"] == 1
    assert bench["workloads"][-1] == cell and bench["configs"][-1] == entry  # appended


def test_macs_match_the_hand_count_at_a_tiny_shape():
    mc = TINY["model_config"]
    kv, rest = 2 * 8 * 2 * 3, 2 * 8 * 4 * 3  # k, v: 2 heads of 3; q, o: 4 heads of 3
    assert exaone_moe.attention_macs(mc) == (kv, rest) == (96, 192)
    assert exaone_moe.attention_macs_per_key(mc) == 2 * 4 * 3  # 4 heads, 3 wide, 2 products
    dense, expert = 3 * 8 * 12, 3 * 8 * 3
    sparse = 8 * 8 + (2 * 4 / 8) * expert + expert  # router, one assignment a token, shared
    assert exaone_moe.ffn_macs(mc, 0) == dense and exaone_moe.ffn_macs(mc, 1) == sparse == 208
    all_held = {**mc, "experts_held": 8}
    assert exaone_moe.ffn_macs(all_held, 1) == 8 * 8 + 2 * expert + expert
    seq, records = 12, 3
    every = sum(range(1, seq + 1))  # 78 keys over a full layer's queries
    banded = 1 + 2 + 3 + 4 + 8 * 5  # min(position + 1, 5): 50 over a window layer's
    assert exaone_moe.keys_seen(mc, 0, range(seq)) == banded
    assert exaone_moe.keys_seen(mc, 3, range(seq)) == every
    window = lambda ffn: seq * (kv + rest + ffn) + 24 * banded  # noqa: E731
    full = seq * (kv + rest + sparse) + 24 * every
    # the last layer, a window layer: keys and values whole, the rest at the
    # three read positions (3, 7, 11), which see 4, 5 and 5 keys
    last = seq * kv + records * (rest + sparse) + 24 * (4 + 5 + 5)
    want = window(dense) + 2 * window(sparse) + full + last
    assert exaone_moe.history_macs(TINY, records) == want + records * 8
    assert flops.forward_flops_per_row(TINY) == 2 * (exaone_moe.history_macs(TINY, 3) // 3)
    # a last layer of full attention: the read positions see 4, 8 and 12 keys
    ends_on_full = {**TINY, "model_config": {**mc, "depth": 4}}
    last = seq * kv + records * (rest + sparse) + 24 * (4 + 8 + 12)
    assert exaone_moe.history_macs(ends_on_full, records) == (
        window(dense) + 2 * window(sparse) + last + records * 8
    )
    # a window as a mask over every key would not count lower: the count is the band's
    masked = {**TINY, "model_config": {**mc, "attn_window": 12}}
    assert exaone_moe.history_macs(masked, records) > exaone_moe.history_macs(TINY, records)


def test_the_real_configuration_counts_what_the_issue_reckoned():
    mc = CONFIG["model_config"]
    assert sum(exaone_moe.attention_macs(mc)) == 113_246_208  # the four projections
    assert exaone_moe.ffn_macs(mc, 0) == 3 * 6144 * 18432
    # a sparse layer's FFN a token: the router, 1 routed assignment of 8, the shared expert
    assert exaone_moe.ffn_macs(mc, 1) == 6144 * 128 + 2 * 37_748_736
    # the issue's per layer and history: 12.62 and 154.7 GFLOP
    band = exaone_moe.keys_seen(mc, 0, range(3072))
    assert band == 128 * 129 // 2 + (3072 - 128) * 128
    assert 2 * exaone_moe.attention_macs_per_key(mc) * band == pytest.approx(12.62e9, rel=1e-3)
    square = exaone_moe.keys_seen(mc, 3, range(3072))
    assert 2 * exaone_moe.attention_macs_per_key(mc) * square == pytest.approx(154.7e9, rel=1e-3)
    whole = exaone_moe.history_macs(CONFIG, 64)
    assert 2 * whole / 3072 == pytest.approx(2.138e9, rel=1e-3)  # operations a token at depth 5
    assert flops.forward_flops_per_row(CONFIG) == pytest.approx(102.6e9, rel=1e-3)
    # three window layers' projections whole and the last's keys and values:
    # a third of the required operations; their attention under 1%
    kv, rest = exaone_moe.attention_macs(mc)
    projections = 3 * 3072 * (kv + rest) + 3072 * kv + 64 * rest
    assert projections / whole == pytest.approx(0.33, abs=0.01)
    assert 3 * band * exaone_moe.attention_macs_per_key(mc) / whole < 0.01


def test_roofline_operations_and_bytes_match_the_hand_count():
    assert roofs.kinds(TINY) == [SWA, SWA, SWA, FULL, SWA]
    ops, moved = roofs.attend_layer_work(TINY, 3, 0)
    assert ops == 2 * 24 * 50
    assert moved == 2 * (2 * 12 * 12 + 2 * 12 * 6)  # q, o 12 wide; k, v 6 wide (two heads of 3)
    full_ops, full_moved = roofs.attend_layer_work(TINY, 3, 3)
    assert full_ops == 2 * 24 * 78 and full_moved == moved
    last_ops, last_moved = roofs.attend_layer_work(TINY, 3, 4)
    assert last_ops == 2 * 24 * 14 and last_moved == 2 * (2 * 3 * 12 + 2 * 12 * 6)
    slow = {"bf16_flops_per_s": 1e4}
    assert roofs.attend_history_seconds(TINY, 3, slow, SWA) == pytest.approx(
        (3 * ops + last_ops) / 1e4
    )
    assert roofs.attend_history_seconds(TINY, 3, slow, FULL) == pytest.approx(full_ops / 1e4)
    # the real shape: a window layer bound by memory, the full layer by compute
    peaks = {"bf16_flops_per_s": 197e12}
    real_ops, real_moved = roofs.attend_layer_work(CONFIG, 64, 0)
    assert real_moved == 2 * (2 * 3072 * 8192 + 2 * 3072 * 1024) == 113_246_208
    assert real_ops / 197e12 < real_moved / 819e9 == pytest.approx(0.138e-3, rel=5e-3)
    full_ops, full_moved = roofs.attend_layer_work(CONFIG, 64, 3)
    assert full_moved == real_moved and full_ops / 197e12 == pytest.approx(0.785e-3, rel=5e-3)
    last_ops, last_moved = roofs.attend_layer_work(CONFIG, 64, 4)
    assert last_ops < real_ops / 40 and last_moved == 2 * (2 * 64 * 8192 + 2 * 3072 * 1024)
    assert roofs.attend_history_seconds(CONFIG, 64, peaks, SWA) == pytest.approx(
        3 * real_moved / 819e9 + last_moved / 819e9
    )
    assert roofs.attend_history_seconds(CONFIG, 64, peaks, FULL) == pytest.approx(full_ops / 197e12)
    # the experts at 1,536 rows each: compute-bound, as lfm2-8b-a1b's
    e_ops, e_moved = moe_roofs.experts_layer_work(CONFIG, 24576, 16)
    assert e_ops / 197e12 > e_moved / 819e9 and e_ops == 2 * 3 * 6144 * 2048 * 24576


# ------------------------------------------------------------ the readers
BLOCK = "jit(fused_counting)/ExaoneMoeScorer/block_{}/"


def hand_made():
    """Window 0..1000 us, one job, two runs of the chunk program. Device:
    window layers 150 us (qkv 60, attend 40: the products 30, the softmax 10;
    o 50), full layer 100 us (qkv 30, attend 50, o 20), moe 130 us under
    scopes (router 10, dispatch 20, experts' kernels 30, combine 40, shared
    expert 30), 120 of ffn: busy 500."""
    def span(name, lo, hi, **attrs):
        return [name, lo * US, (hi - lo) * US, attrs]

    host = [
        span("bench:window", 0, 1000),
        span("bench:job", 10, 900),
        span("mlops:bulk.job", 20, 880, job=1, pid=7, rows=10, chunks=2),
    ]
    timeline = [
        ("fusion", 60, BLOCK.format(1) + "block_1._attention/swa_qkv/rope/mul:"),
        ("fusion", 30, BLOCK.format(1) + "block_1._attention/swa_attend/bnqhe,bnkhe->bnhqk/dot_general:"),
        ("fusion", 10, BLOCK.format(1) + "block_1._attention/swa_attend/exp:"),
        ("fusion", 50, BLOCK.format(1) + "block_1._attention/swa_o/o/dot_general:"),
        ("fusion", 30, BLOCK.format(3) + "block_3._attention/gqa_qkv/q/dot_general:"),
        ("fusion", 50, BLOCK.format(3) + "block_3._attention/gqa_attend/bqhe,bkhe->bhqk/dot_general:"),
        ("fusion", 20, BLOCK.format(3) + "block_3._attention/gqa_o/o/dot_general:"),
        ("fusion", 10, BLOCK.format(1) + "router/dot_general:"),
        ("fusion", 20, BLOCK.format(1) + "moe_dispatch/sort:"),
        ("custom-call", 30, BLOCK.format(1) + "while/body/experts/experts_gate_up_fwd:"),
        ("fusion", 40, BLOCK.format(1) + "while/body/moe_combine/scatter-add:"),
        ("fusion", 30, BLOCK.format(1) + "shared_expert/shared_gate/dot_general:"),
        ("fusion", 120, BLOCK.format(0) + "ffn/gate/dot_general:"),
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
    jobs = [{"routing": {"per_layer": [[4, 6, 1, 1]], "expert_runs_per_layer": [[2, 1, 1, 1]]}}]


def _facts(peaks, driver=_Driver, spec=TINY):
    spec = {**spec, "deployment": {"score_chunk_rows": 6}}  # 2 histories, 24 tokens a chunk
    trace = {"programs": [["jit_fused_counting", 0.0, 0.1], ["jit_add", 0.1, 0.2],
                          ["jit_fused_counting", 0.2, 0.3]]}
    return {"trace": trace, "peaks": peaks, "config": spec, "driver": driver,
            "traffic": {"rows_per_file": 10}}


NEW = ["bulk_swa_device_pct", "swa_attend_roofline_pct", "exaone_gqa_attend_roofline_pct"]
APPENDED = ["bulk_gqa_device_pct", "bulk_moe_device_pct", "moe_experts_roofline_pct",
            "bulk_sweep_span_idle_pct"]


def test_readers_on_the_hand_made_profile(monkeypatch):
    program = pt.reduce_profile(hand_made(), 7)
    monkeypatch.setattr(pt, "load", lambda facts: program)
    monkeypatch.setattr(moe_roofs, "kind_seconds", lambda facts, prefix: 0.0)
    assert moe_roofs.scope_seconds(program, roofs.SWA_SCOPES) == pytest.approx(150e-6)
    assert _reader("bulk_swa_device_pct").read(_facts(None)) == pytest.approx(100 * 150 / 500)
    # lfm2_moe's reader, unedited, reads this family's full layers
    assert _reader("bulk_gqa_device_pct").read(_facts(None)) == pytest.approx(100 * 100 / 500)
    peaks = {"bf16_flops_per_s": 1e9}
    allowed = 2 * 2 * roofs.attend_history_seconds(TINY, 3, peaks, SWA)  # 2 runs x 2 histories
    assert _reader("swa_attend_roofline_pct").read(_facts(peaks)) == pytest.approx(
        100 * allowed / 40e-6  # the scope whole: the products and the softmax
    )
    allowed = 2 * 2 * roofs.attend_history_seconds(TINY, 3, peaks, FULL)
    assert _reader("exaone_gqa_attend_roofline_pct").read(_facts(peaks)) == pytest.approx(
        100 * allowed / 50e-6
    )
    # the expert layer's readers, which this family shares with the other two
    moe = _reader("bulk_moe_device_pct")
    monkeypatch.setattr(moe, "kind_seconds", lambda facts, prefix: 0.0)
    assert moe.read(_facts(None)) == pytest.approx(100 * 130 / 500)
    experts = moe_roofs.experts_seconds(TINY, _Driver.jobs, peaks)
    assert _reader("moe_experts_roofline_pct").read(_facts(peaks)) == pytest.approx(
        100 * experts / 30e-6
    )
    for name in ("swa_attend_roofline_pct", "exaone_gqa_attend_roofline_pct"):
        assert _reader(name).read(_facts(None)) is None  # no peak: a CPU
    # another family's configuration under the same scope names: nothing, not a wrong share
    other = {**TINY, "model_config": {**TINY["model_config"], "family": "lfm2_moe"}}
    assert _reader("exaone_gqa_attend_roofline_pct").read(_facts(peaks, spec=other)) is None


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


def test_the_cells_metrics_are_the_three_new_ones_and_the_other_decoders():
    bench = json.loads((Path(run.CHECKOUT) / "BENCHMARK.json").read_text())
    names = {m["name"] for m in run.cell_metrics(bench, CELL, "per_layer")}
    assert set(NEW) | set(APPENDED) <= names
    assert not names & {"bulk_mla_device_pct", "mla_attend_roofline_pct", "bulk_attn_device_pct",
                        "bulk_eva_attn_device_pct", "bulk_sweep_idle_pct",
                        "bulk_short_conv_device_pct", "short_conv_roofline_pct",
                        "gqa_attend_roofline_pct"}  # lfm2's: a head of hidden // heads
    for entry in bench["per_layer"]:
        if entry["name"] in NEW:
            assert entry["workloads"] == [CELL] and entry["moves"] == "bulk_rows_per_s"
            assert (entry["layer"], entry["source"]) == ("programs", "device_trace")
        if entry["name"] in APPENDED:
            assert entry["workloads"][-1] == CELL and "lfm2-8b-a1b.bulk-hist" in entry["workloads"]
    assert [m["name"] for m in bench["per_layer"][-3:]] == NEW  # appended, in order
    for other in CELLS:
        if other != CELL:
            assert not set(NEW) & {m["name"] for m in run.cell_metrics(bench, other, "per_layer")}
    # every metric with no list of cells is reported here too, as in the other cells
    unlisted = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert unlisted <= names


# -------------------------------------------------------------- the driver
def test_the_driver_fits_the_bias_and_counts_the_held_share(tiny_root):
    """``bulk_token_histories`` as it stands on a third family: the weights
    by group, the selection bias fitted from THIS family's reference (found
    by ``model_config.family``), the routing counter in every job record;
    with half the experts held, about half of every token's choices land."""
    import numpy as np

    loaded = run.load_cell(tiny_root, CELL)
    ctx = run.Context(2**31 + 9, loaded["cell"], loaded["config"], loaded["traffic"])
    driver = run.load_module(loaded["driver_file"]).build(ctx)
    driver.setup()
    mc = loaded["config"]["model_config"]
    assert (mc["family"], mc["first_expert"], mc["experts_held"], mc["num_experts"]) == (
        "exaone_moe", 4, 8, 16,
    )
    assert (mc["depth"], mc["heads"] * mc["head_dim"], mc["token_dim"]) == (5, 128, 64)
    bias = np.asarray(driver.weights["params"]["block_2"]["router"]["bias"])
    assert bias.shape == (16,) and abs(bias.mean()) < 1e-6 and bias.std() > 0.005
    assert "router" not in driver.weights["params"]["block_0"]  # the dense layer
    driver.warmup()
    driver.window(0.0, max_units=2)
    expected = driver.reference_outputs()
    for job in driver.jobs:
        routing = job["routing"]
        assert routing["tokens"] == 7 * 48 * 48  # 301 rows: 7 chunks of 16 histories
        per_layer = np.asarray(routing["per_layer"])
        assert per_layer.shape == (4, 8)
        # 8 of 16 experts held: about half of the 4 choices of every token
        share = per_layer[:-1].sum(axis=1) / (routing["tokens"] * 4)
        assert (0.3 < share).all() and (share < 0.7).all()
        assert 0 < per_layer[-1].sum() <= 7 * 48 * 4  # the last layer: the read positions
        assert per_layer[0].max() < 2.5 * per_layer[0].mean()  # the fit evens the loads
        assert driver.compare(job, expected)["pred_max_gap"] < 1e-4

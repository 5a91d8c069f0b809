"""What ISSUE 27 adds to the benchmark: the operation and byte counts of
the ``evabyte`` family against hand counts at a tiny shape, the two readers
of the EVA attention's scopes on a hand-made profile, and the history
driver's sample. (The new cell's rehearsal, its five faults, its control
and the reference against the program run through the files that are
parametrised over ``BENCHMARK.json``: ``test_rehearsal.py``,
``test_reference.py``.)"""

import json
from pathlib import Path

import pytest
from conftest import CELLS, CONFIGS

from benchmark import flops, run
from benchmark import program_trace as pt
from benchmark.flops import evabyte
from benchmark.rooflines import eva_attention

CONFIG = json.loads(
    (Path(__file__).resolve().parents[1] / "configs" / "evabyte-8l.json").read_text()
)
CELL = "evabyte-8l.bulk-hist"
# hidden 8, FFN 12, 2 layers, window 4, chunk 2, records of 4 bytes, 3 a history: S = 12
TINY = {
    "model_config": {"family": "evabyte", "token_dim": 8, "ffn_dim": 12, "depth": 2,
                     "attn_window": 4, "attn_chunk": 2},
    "records_per_history": 3,
    "record_bytes": 4,
}
US = 1_000


def test_the_cell_and_its_configuration_are_in_the_benchmark():
    assert CELL in CELLS and "evabyte-8l" in CONFIGS


def test_keys_a_query_attends():
    # window 4, chunk 2: positions 0..3 see 1..4 local keys; position 4 opens
    # window 1 and sees itself and the 2 summaries of window 0
    assert [evabyte.attention_keys(p, 4, 2) for p in range(9)] == [1, 2, 3, 4, 3, 4, 5, 6, 5]
    # the real shape: 1,024.5 local and 448 remote keys a query on average
    total = sum(evabyte.attention_keys(p, 2048, 16) for p in range(16384))
    assert total == 16384 * (1024.5 + 448)


def test_macs_match_the_hand_count_at_a_tiny_shape():
    d, f, seq, records = 8, 12, 12, 3
    keys = [1, 2, 3, 4, 3, 4, 5, 6, 5, 6, 7, 8]
    assert [evabyte.attention_keys(p, 4, 2) for p in range(seq)] == keys
    full = seq * (4 * d * d + 3 * d * f) + 2 * d * sum(keys)  # 6528 + 864
    read = keys[3] + keys[7] + keys[11]  # the records' last bytes: 3, 7, 11
    last = seq * 2 * d * d + records * (2 * d * d + 3 * d * f + d) + 2 * d * read
    assert (full, last) == (7392, 1536 + 1272 + 288)
    assert evabyte.history_macs(TINY, records) == full + last == 10488
    assert evabyte.forward_macs_per_row(TINY) == 10488 // 3
    assert flops.forward_flops_per_row(TINY) == 2 * 3496


def test_the_real_configuration_counts_what_the_issue_reckoned():
    whole = 2 * evabyte.history_macs(CONFIG, 64)
    assert whole == pytest.approx(50.31e12, rel=1e-3)  # 56.2 less the skipped last layer
    assert flops.forward_flops_per_row(CONFIG) == pytest.approx(0.786e12, rel=1e-3)
    every, _ = eva_attention.layer_work(CONFIG, 64, last=False)
    assert 8 * every == pytest.approx(3.16e12, rel=1e-2)  # eva_attend, 8 full layers


def test_roofline_operations_and_bytes_match_the_hand_count():
    d, seq, chunk = 8, 12, 2
    ops, moved = eva_attention.layer_work(TINY, 3, last=False)
    assert ops == 2 * 2 * d * 54  # two products, two operations a MAC, 54 keys
    assert moved == 4 * seq * d * 2 + 2 * 2 * (seq // chunk) * d * 2
    ops_last, moved_last = eva_attention.layer_work(TINY, 3, last=True)
    assert ops_last == 2 * 2 * d * (4 + 6 + 8)
    assert moved_last == (2 * seq + 2 * 3) * d * 2 + 2 * 2 * (seq // chunk) * d * 2
    peaks = {"bf16_flops_per_s": 1e4}  # a slow chip, so that both bounds show
    seconds = eva_attention.history_seconds(TINY, 3, peaks)
    assert seconds == pytest.approx(
        max(ops / 1e4, moved / 819e9) + max(ops_last / 1e4, moved_last / 819e9)
    )
    real = eva_attention.history_seconds(CONFIG, 64, {"bf16_flops_per_s": 197e12})
    assert real == pytest.approx(0.01442, rel=1e-2)  # compute-bound, 2.0 ms a full layer


# ------------------------------------------------------------ the readers
BLOCK = "jit(fused)/EvaByteScorer/block_{}/"


def hand_made():
    """Window 0..1000 us, one job. Device: 100 us of ``eva_attend`` (a
    ``while`` of 80 holding a fusion of 50, and 20 outside it), 30 of
    ``eva_prep_kv``, 70 of ``rope`` and ``ffn``: busy 200."""
    def span(name, lo, hi, **attrs):
        return [name, lo * US, (hi - lo) * US, attrs]

    host = [
        span("bench:window", 0, 1000),
        span("bench:job", 10, 900),
        span("mlops:bulk.job", 20, 880, job=1, pid=7, rows=10, chunks=3),
        span("mlops:bulk.warmup", 30, 200, job=1),
    ]
    ops = [
        ["fusion", 100 * US, 30 * US, BLOCK.format(0) + "eva_prep_kv/reduce:"],
        ["while", 200 * US, 80 * US, BLOCK.format(0) + "eva_attend/while:"],
        ["fusion", 210 * US, 50 * US, BLOCK.format(0) + "eva_attend/while/body/bwqd,bwkd->bwqk/dot:"],
        ["fusion", 300 * US, 20 * US, BLOCK.format(1) + "eva_attend/transpose:"],
        ["fusion", 400 * US, 30 * US, BLOCK.format(1) + "rope/mul:"],
        ["fusion", 500 * US, 40 * US, BLOCK.format(1) + "ffn/gate/dot_general:"],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]}


def _reader(name):
    return run.load_module(run.HERE / "layer_metrics" / f"{name}.py")


def _facts(peaks):
    spec = {**TINY, "deployment": {"score_chunk_rows": 6}}  # 2 histories a chunk
    return {"trace": {}, "peaks": peaks, "config": spec, "traffic": {"rows_per_file": 10}}


def test_readers_on_the_hand_made_profile(monkeypatch):
    program = pt.reduce_profile(hand_made(), 7)
    monkeypatch.setattr(pt, "load", lambda facts: program)
    assert eva_attention.scope_seconds(program) == pytest.approx(130e-6)
    share = _reader("bulk_eva_attn_device_pct").read(_facts(None))
    assert share == pytest.approx(100 * 130 / 200)
    # one job: the warm-up chunk and ceil(10 / 6) = 2 chunks, 2 histories each
    peaks = {"bf16_flops_per_s": 1e9}
    allowed = 3 * 2 * eva_attention.history_seconds(TINY, 3, peaks)
    roofline = _reader("eva_attn_roofline_pct").read(_facts(peaks))
    assert roofline == pytest.approx(100 * allowed / 130e-6)
    assert _reader("eva_attn_roofline_pct").read(_facts(None)) is None  # no peak: a CPU


@pytest.mark.parametrize("name", ["bulk_eva_attn_device_pct", "eva_attn_roofline_pct"])
def test_readers_find_nothing_where_the_scopes_are_missing(monkeypatch, name):
    """The parent's program has no such scope; a rehearsal has no device:
    ``None``, never 0, and nothing raised."""
    flat = hand_made()
    ops = flat["planes"][0]["lines"][0]["events"]
    flat["planes"][0]["lines"][0]["events"] = [op for op in ops if "eva_" not in op[3]]
    program = pt.reduce_profile(flat, 7)
    monkeypatch.setattr(pt, "load", lambda facts: program)
    assert _reader(name).read(_facts({"bf16_flops_per_s": 1e9})) is None
    monkeypatch.setattr(pt, "load", lambda facts: None)
    assert _reader(name).read(_facts({"bf16_flops_per_s": 1e9})) is None


def test_bulk_attn_device_pct_lists_the_cells_it_can_read():
    bench = json.loads((Path(run.CHECKOUT) / "BENCHMARK.json").read_text())
    names = [m["name"] for m in run.cell_metrics(bench, CELL, "per_layer")]
    assert "bulk_attn_device_pct" not in names and len(names) == 12
    assert {"bulk_eva_attn_device_pct", "eva_attn_roofline_pct", "bulk_program_mfu_pct"} <= set(names)
    bert = [m["name"] for m in run.cell_metrics(bench, "bert-base.bulk", "per_layer")]
    assert "bulk_attn_device_pct" in bert and "eva_attn_roofline_pct" not in bert


# -------------------------------------------------------------- the driver
def test_the_checks_sample_is_whole_histories(tiny_root):
    loaded = run.load_cell(tiny_root, CELL)
    ctx = run.Context(11, loaded["cell"], loaded["config"], loaded["traffic"])
    driver = run.load_module(loaded["driver_file"]).build(ctx)
    sample = driver._check_sample()
    per, rows, chunk = driver.per_history, driver.rows, driver.chunk
    assert (per, rows, chunk) == (2, 301, 64)
    histories = sorted({int(r) // per for r in sample})
    assert histories == [0, chunk // per - 1, rows // per]  # first, end of chunk 1, last
    assert list(sample) == [0, 1, chunk - 2, chunk - 1, rows - 1]  # the last one is short


def test_weights_are_filled_subtree_by_subtree_with_streams_of_their_own(tiny_root):
    import jax
    import numpy as np

    loaded = run.load_cell(tiny_root, CELL)
    module = run.load_module(loaded["driver_file"])
    drivers = []
    for seed in (5, 5, 2**31 + 5):
        ctx = run.Context(seed, loaded["cell"], loaded["config"], loaded["traffic"])
        driver = module.build(ctx)
        driver.setup()
        drivers.append(driver.weights["params"])
    a, again, other = drivers
    leaves = jax.tree_util.tree_leaves
    assert all((x == y).all() for x, y in zip(leaves(a), leaves(again)))
    assert any((x != y).any() for x, y in zip(leaves(a), leaves(other)))
    # same shapes, different streams: no two blocks hold the same weights
    assert np.abs(a["block_0"]["gate"]["kernel"] - a["block_1"]["gate"]["kernel"]).max() > 0.1
    assert abs(float(a["block_0"]["attn_norm"]["scale"].mean()) - 1.0) < 0.1

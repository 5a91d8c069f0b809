"""What ISSUE 31 adds to the benchmark: the operation and byte counts of
the ``kimi_k2`` family against hand counts at a tiny shape, the four
readers of its scopes on a hand-made profile, and the token-history
driver's weights and routing records. (The new cells' rehearsals, their
five faults, their controls and the reference against the program run
through the files that are parametrised over ``BENCHMARK.json``:
``test_rehearsal.py``, ``test_reference.py``.)"""

import json
from pathlib import Path

import pytest
from conftest import CELLS, CONFIGS

from benchmark import flops, run
from benchmark import program_trace as pt
from benchmark.flops import kimi_k2
from benchmark.rooflines import kimi_k2 as roofs

CONFIG = json.loads(
    (Path(__file__).resolve().parents[1] / "configs" / "kimi-k2-5l.json").read_text()
)
CELL = "kimi-k2-5l.bulk-hist"
# hidden 8, 2 heads of 4 | 2 | 3, ranks 6 and 5, dense 12, 8 experts of width
# 3, 2 a token, 4 held; 1 dense + 2 expert layers; records of 4 tokens, 3 a history
TINY = {
    "model_config": {
        "family": "kimi_k2", "token_dim": 8, "heads": 2, "depth": 3, "ffn_dim": 12,
        "q_lora_rank": 6, "kv_lora_rank": 5, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2,
        "v_head_dim": 3, "moe_ffn_dim": 3, "num_experts": 8, "experts_per_token": 2,
        "first_expert": 0, "experts_held": 4,
    },
    "records_per_history": 3,
    "tokens_per_record": 4,
}
US = 1_000


def test_the_cells_and_the_configuration_are_in_the_benchmark():
    assert {CELL, "bert-base.bulk-dp4"} <= set(CELLS) and "kimi-k2-5l" in CONFIGS
    bench = json.loads((Path(run.CHECKOUT) / "BENCHMARK.json").read_text())
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["bert-base.bulk-dp4"]  # one in five: what the 25% rule allows


def test_macs_match_the_hand_count_at_a_tiny_shape():
    mc = TINY["model_config"]
    kv = 8 * (5 + 2) + 5 * 2 * (4 + 3)  # kv_a, kv_b
    rest = 8 * 6 + 6 * 2 * (4 + 2) + 2 * 3 * 8  # q_a, q_b, o
    assert kimi_k2.mla_macs(mc) == (kv, rest) == (126, 168)
    assert kimi_k2.attention_macs_per_key(mc) == 2 * (4 + 2 + 3)
    dense = 3 * 8 * 12
    expert = 3 * 8 * 3
    sparse = expert + 8 * 8 + (2 * 4 / 8) * expert  # shared + router + one assignment a token
    assert kimi_k2.ffn_macs(mc, 0) == dense and kimi_k2.ffn_macs(mc, 1) == sparse == 208
    seq, records = 12, 3
    every = sum(range(1, seq + 1))  # 78 keys over a full layer's queries
    read = 4 + 8 + 12
    full = lambda ffn: seq * (kv + rest + ffn) + 18 * every  # noqa: E731
    last = seq * kv + records * (rest + sparse + 8) + 18 * read
    assert kimi_k2.history_macs(TINY, records) == full(dense) + full(sparse) + last
    assert flops.forward_flops_per_row(TINY) == 2 * (kimi_k2.history_macs(TINY, 3) // 3)


def test_the_real_configuration_counts_what_the_issue_reckoned():
    whole = kimi_k2.history_macs(CONFIG, 64)
    assert whole / 3072 == pytest.approx(1150e6, rel=2e-3)  # MACs a token
    assert 2 * whole == pytest.approx(7.07e12, rel=2e-3)
    assert flops.forward_flops_per_row(CONFIG) == pytest.approx(110e9, rel=5e-3)
    assert sum(kimi_k2.mla_macs(CONFIG["model_config"])) == pytest.approx(101.1e6, rel=1e-3)
    assert kimi_k2.attention_macs_per_key(CONFIG["model_config"]) == 64 * 320


def test_roofline_operations_and_bytes_match_the_hand_count():
    ops, moved = roofs.attend_layer_work(TINY, 3, last=False)
    assert ops == 2 * 18 * 78
    assert moved == 2 * 2 * (2 * 12 * 6 + 2 * 12 * 3)  # q, k 6 wide; v, o 3 wide; 2 heads
    ops_last, moved_last = roofs.attend_layer_work(TINY, 3, last=True)
    assert ops_last == 2 * 18 * (4 + 8 + 12)
    assert moved_last == 2 * 2 * ((3 + 12) * 6 + (3 + 12) * 3)
    slow = {"bf16_flops_per_s": 1e4}
    assert roofs.attend_history_seconds(TINY, 3, slow) == pytest.approx(
        2 * max(ops / 1e4, moved / 819e9) + max(ops_last / 1e4, moved_last / 819e9)
    )
    # the real shape: compute-bound, 193 GFLOP and 0.98 ms a full layer
    real_ops, real_moved = roofs.attend_layer_work(CONFIG, 64, last=False)
    assert real_ops == pytest.approx(193.3e9, rel=1e-3) and real_moved == 3072 * 64 * 640 * 2
    _, last_moved = roofs.attend_layer_work(CONFIG, 64, last=True)  # k and v whole: memory-bound
    assert roofs.attend_history_seconds(CONFIG, 64, {"bf16_flops_per_s": 197e12}) == pytest.approx(
        4 * real_ops / 197e12 + last_moved / 819e9, rel=1e-6
    )
    # the experts: 10 assignments, 3 (run, expert) pairs
    e_ops, e_moved = roofs.experts_layer_work(TINY, 10, 3)
    assert e_ops == 2 * 3 * 8 * 3 * 10 and e_moved == 2 * (3 * 8 * 3 * 3 + 2 * 8 * 10)
    jobs = [{"routing": {"per_layer": [[4, 6, 0, 0], [1, 0, 0, 0]],
                         "expert_runs_per_layer": [[2, 1, 0, 0], [1, 0, 0, 0]]}},
            {"routing": None}, {}]
    one_ops, one_moved = roofs.experts_layer_work(TINY, 1, 1)
    assert roofs.experts_seconds(TINY, jobs, slow) == pytest.approx(
        max(e_ops / 1e4, e_moved / 819e9) + max(one_ops / 1e4, one_moved / 819e9)
    )
    assert roofs.experts_seconds(TINY, [{"routing": None}], slow) is None
    # the real share at an even router: memory-bound, 2.1 GB and 2.6 ms a layer and run
    r_ops, r_moved = roofs.experts_layer_work(CONFIG, 1536, 24)
    assert r_moved / 819e9 > r_ops / 197e12
    assert r_moved / 819e9 == pytest.approx(2.63e-3, rel=2e-2)


# ------------------------------------------------------------ the readers
BLOCK = "jit(fused_counting)/KimiK2Scorer/block_{}/"


def hand_made():
    """Window 0..1000 us, one job, two runs of the chunk program. Device:
    mla 150 us (q 20, kv 30, attend 60, o 40), moe 100 us under scopes
    (router 10, dispatch 20, experts' activation 10, combine 40, shared 20)
    and 50 us of ragged-dot with no scope, 100 of ffn: busy 400."""
    def span(name, lo, hi, **attrs):
        return [name, lo * US, (hi - lo) * US, attrs]

    host = [
        span("bench:window", 0, 1000),
        span("bench:job", 10, 900),
        span("mlops:bulk.job", 20, 880, job=1, pid=7, rows=10, chunks=2),
    ]
    timeline = [
        ("fusion", 20, BLOCK.format(1) + "block_1._attention/mla_q/q_b/dot_general:"),
        ("fusion", 30, BLOCK.format(1) + "block_1._attention/mla_kv/rope/mul:"),
        ("fusion", 60, BLOCK.format(1) + "block_1._attention/mla_attend/bqhe,bkhe->bhqk/dot_general:"),
        ("fusion", 40, BLOCK.format(1) + "block_1._attention/mla_o/o/dot_general:"),
        ("fusion", 10, BLOCK.format(1) + "block_1._experts/router/dot_general:"),
        ("fusion", 20, BLOCK.format(1) + "block_1._experts/moe_dispatch/sort:"),
        ("ragged-dot-none", 50, "ragged-dot-none:"),
        ("fusion", 10, BLOCK.format(1) + "block_1._experts/while/body/closed_call/cond/branch_1_fun/experts/mul:"),
        ("fusion", 40, BLOCK.format(1) + "block_1._experts/while/body/closed_call/cond/branch_1_fun/moe_combine/scatter-add:"),
        ("fusion", 20, BLOCK.format(1) + "block_1._experts/shared_expert/block_1._swiglu/shared_up/dot_general:"),
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
    jobs = [{"routing": {"per_layer": [[4, 6, 0, 0]], "expert_runs_per_layer": [[2, 1, 0, 0]]}}]


def _facts(peaks, driver=_Driver):
    spec = {**TINY, "deployment": {"score_chunk_rows": 6}}  # 2 histories a chunk
    trace = {"programs": [["jit_fused_counting", 0.0, 0.1], ["jit_add", 0.1, 0.2],
                          ["jit_fused_counting", 0.2, 0.3]]}
    return {"trace": trace, "peaks": peaks, "config": spec, "driver": driver,
            "traffic": {"rows_per_file": 10}}


@pytest.fixture
def profile(monkeypatch):
    flat = hand_made()
    program = pt.reduce_profile(flat, 7)
    monkeypatch.setattr(pt, "load", lambda facts: program)
    ragged = [0.0]
    monkeypatch.setattr(roofs, "kind_seconds", lambda facts, prefix: ragged[0])
    return program, ragged


def test_readers_on_the_hand_made_profile(profile, monkeypatch):
    program, ragged = profile
    ragged[0] = 50e-6
    assert roofs.scope_seconds(program, roofs.MLA_SCOPES) == pytest.approx(150e-6)
    assert roofs.scope_seconds(program, roofs.MOE_SCOPES) == pytest.approx(100e-6)
    assert roofs.chunk_runs(_facts(None)["trace"]) == 2
    assert _reader("bulk_mla_device_pct").read(_facts(None)) == pytest.approx(100 * 150 / 400)
    # the reader asks `kind_seconds` by the name it imported
    moe = _reader("bulk_moe_device_pct")
    monkeypatch.setattr(moe, "kind_seconds", lambda facts, prefix: 50e-6)
    assert moe.read(_facts(None)) == pytest.approx(100 * 150 / 400)
    peaks = {"bf16_flops_per_s": 1e9}
    allowed = 2 * 2 * roofs.attend_history_seconds(TINY, 3, peaks)  # 2 runs x 2 histories
    assert _reader("mla_attend_roofline_pct").read(_facts(peaks)) == pytest.approx(
        100 * allowed / 60e-6
    )
    experts = roofs.experts_seconds(TINY, _Driver.jobs, peaks)
    assert _reader("moe_experts_roofline_pct").read(_facts(peaks)) == pytest.approx(
        100 * experts / (10e-6 + 50e-6)
    )
    for name in ("mla_attend_roofline_pct", "moe_experts_roofline_pct"):
        assert _reader(name).read(_facts(None)) is None  # no peak: a CPU


def test_kind_seconds_reads_the_operations_that_carry_no_scope(monkeypatch, tmp_path):
    flat = hand_made()
    monkeypatch.setattr(pt, "_reduced", lambda path, pid: {"jobs": []})
    monkeypatch.setattr(pt, "load_profile", lambda path: flat)
    monkeypatch.setattr(roofs.tempfile, "gettempdir", lambda: str(tmp_path))
    found = tmp_path / "bench-trace-x" / "plugins" / "profile" / "1"
    found.mkdir(parents=True)
    (found / "host.xplane.pb").write_bytes(b"")
    assert roofs.kind_seconds({"trace": {}}, roofs.GROUPED_PRODUCT) == pytest.approx(50e-6)
    assert roofs.kind_seconds({"trace": None}, roofs.GROUPED_PRODUCT) == 0.0
    monkeypatch.setattr(pt, "_reduced", lambda path, pid: None)  # another run's profile
    assert roofs.kind_seconds({"trace": {}}, roofs.GROUPED_PRODUCT) == 0.0


@pytest.mark.parametrize("name", [
    "bulk_mla_device_pct", "bulk_moe_device_pct", "mla_attend_roofline_pct",
    "moe_experts_roofline_pct",
])
def test_readers_find_nothing_where_the_scopes_are_missing(monkeypatch, name):
    """The parent's program has no such scope; a rehearsal has no device:
    ``None``, never 0, and nothing raised."""
    flat = hand_made()
    ops = flat["planes"][0]["lines"][0]["events"]
    flat["planes"][0]["lines"][0]["events"] = [op for op in ops if "block_0" in op[3]]
    program = pt.reduce_profile(flat, 7)
    monkeypatch.setattr(pt, "load", lambda facts: program)
    monkeypatch.setattr(roofs, "kind_seconds", lambda facts, prefix: 0.0)
    peaks = {"bf16_flops_per_s": 1e9}
    assert _reader(name).read(_facts(peaks)) is None
    assert _reader(name).read(_facts(peaks, driver=object())) is None  # a driver with no jobs
    monkeypatch.setattr(pt, "load", lambda facts: None)
    assert _reader(name).read(_facts(peaks)) is None


def test_the_new_metrics_list_the_one_cell_they_can_read():
    bench = json.loads((Path(run.CHECKOUT) / "BENCHMARK.json").read_text())
    new = {"bulk_mla_device_pct", "bulk_moe_device_pct", "mla_attend_roofline_pct",
           "moe_experts_roofline_pct"}
    names = {m["name"] for m in run.cell_metrics(bench, CELL, "per_layer")}
    assert new <= names and "bulk_attn_device_pct" not in names and len(names) == 14
    for other in ("bert-base.bulk", "bert-base.bulk-dp4", "evabyte-8l.bulk-hist"):
        assert not new & {m["name"] for m in run.cell_metrics(bench, other, "per_layer")}
    dp4 = [m["name"] for m in run.cell_metrics(bench, "bert-base.bulk-dp4", "per_layer")]
    # those without a list, `bulk_attn_device_pct` (the cell appended to its
    # list) and `bulk_sweep_span_idle_pct`, which reads the sweep's idle share
    # from the program's spans in both new cells; `bulk_sweep_idle_pct` counts
    # the runs of a job by the configuration's one-chip chunk and finds
    # nothing under the mesh, so it lists the accepted cells, which it can read
    assert len(dp4) == 11 and "bulk_program_mfu_pct" in dp4 and "bulk_sweep_idle_pct" not in dp4
    assert {"bulk_sweep_span_idle_pct", "bulk_attn_device_pct"} <= set(dp4)
    assert "bulk_sweep_span_idle_pct" in names


def test_the_sweeps_idle_share_is_read_from_the_programs_spans(monkeypatch):

    reader = _reader("bulk_sweep_span_idle_pct")
    program = {
        "busy_s": 3.6, "window_s": 4.0,
        "idle_by_span": [["bulk.drift", 0.3], ["pipe.fetch", 0.012], ["bulk.sweep", 0.004],
                         ["bulk.build", 0.08], ["pipe.slice", 0.004]],
    }
    monkeypatch.setattr(pt, "load", lambda facts: program)
    assert reader.read({}) == pytest.approx(100.0 * 0.02 / 4.0)
    monkeypatch.setattr(pt, "load", lambda facts: {**program, "busy_s": None})
    assert reader.read({}) is None  # no device in the profile
    monkeypatch.setattr(pt, "load", lambda facts: None)
    assert reader.read({}) is None  # a program without the spans


# -------------------------------------------------------------- the driver
def test_weights_are_filled_group_by_group_with_streams_of_their_own(tiny_root):
    import jax
    import numpy as np

    loaded = run.load_cell(tiny_root, CELL)
    module = run.load_module(loaded["driver_file"])
    trees = []
    for seed in (5, 5, 2**31 + 5):
        ctx = run.Context(seed, loaded["cell"], loaded["config"], loaded["traffic"])
        driver = module.build(ctx)
        driver.setup()
        trees.append(driver.weights["params"])
    a, again, other = trees
    leaves = jax.tree_util.tree_leaves
    assert all((x == y).all() for x, y in zip(leaves(a), leaves(again)))
    assert any((x != y).any() for x, y in zip(leaves(a), leaves(other)))
    # same shapes, different streams: no two blocks hold the same weights
    gap = np.abs(a["block_1"]["experts_gate"]["kernel"] - a["block_2"]["experts_gate"]["kernel"])
    assert gap.max() > 0.1
    assert abs(float(a["block_0"]["attn_norm"]["scale"].mean()) - 1.0) < 0.1
    assert abs(float(a["block_1"]["router"]["bias"].mean())) < 0.1
    # every leaf is in exactly one group; a leaf over the bound is alone
    shapes = {"params": jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), a)}
    groups = module.leaf_groups(shapes)
    assert sorted(i for g in groups for i in g) == list(range(len(leaves(a))))
    big = {"params": {"block_1": {
        "experts_up": {"kernel": jax.ShapeDtypeStruct((24, 7168, 2048), np.float32)},
        "router": {"kernel": jax.ShapeDtypeStruct((8, 4), np.float32),
                   "bias": jax.ShapeDtypeStruct((4,), np.float32)}}}}
    assert module.leaf_groups(big) == [[0], [1, 2]]


def test_each_job_record_keeps_the_routing_counter(tiny_root):
    loaded = run.load_cell(tiny_root, CELL)
    ctx = run.Context(11, loaded["cell"], loaded["config"], loaded["traffic"])
    driver = run.load_module(loaded["driver_file"]).build(ctx)
    driver.setup()
    driver.warmup()
    driver.window(0.0, max_units=2)
    assert len(driver.jobs) == 2
    for job in driver.jobs:
        routing = job["routing"]
        assert routing["tokens"] == 7 * 48 * 48  # 301 rows: 7 chunks of 16 histories
        assert len(routing["per_layer"]) == 2 and len(routing["per_layer"][0]) == 4
        assert routing["assignments_held"] == sum(map(sum, routing["per_layer"]))
        assert routing["expert_runs"] <= 7 * 2 * 4
    assert driver.jobs[0]["routing"] == driver.jobs[1]["routing"]  # the same file


def test_the_fitted_selection_bias_evens_the_loads(tiny_root):
    """With the bias the generator draws, a few experts take most tokens;
    fitted (`balance_selection_bias`), every expert of every layer gets
    tokens and the most loaded one of a full layer stays under twice an
    even share. The fit reads the float32 reference's scores and never
    runs the program under test: the weights are the seed's alone. The
    program and the reference are handed the fitted bias."""
    from unittest import mock

    import numpy as np

    from benchmark.reference import kimi_k2 as reference

    loaded = run.load_cell(tiny_root, CELL)
    module = run.load_module(loaded["driver_file"])
    ctx = run.Context(2**31 + 9, loaded["cell"], loaded["config"], loaded["traffic"])
    driver = module.build(ctx)
    with mock.patch(
        "flax.linen.Module.apply", side_effect=AssertionError("the program ran in set-up")
    ):
        driver.setup()
    assert driver.bundle.variables is driver.weights
    model, per = driver.bundle.model, driver.per_history
    cat, num = driver.cat[: 40 * per], driver.num[: 40 * per]
    _, routed = reference.forward(driver.weights, cat, num, driver.spec)
    chosen = np.concatenate([np.asarray(choices[0]) for choices in routed])
    loads = np.bincount(chosen.reshape(-1), minlength=model.num_experts)
    even = chosen.size / model.num_experts
    assert loads.min() > 0 and loads.max() < 2 * even, loads
    bias = np.asarray(driver.weights["params"]["block_1"]["router"]["bias"])
    assert abs(bias.mean()) < 1e-6 and bias.std() > 0.01  # centred, and fitted
    expected = driver.reference_outputs()  # the reference reads the same weights
    driver.warmup()
    driver.window(0.0, max_units=1)
    assert driver.compare(driver.jobs[0], expected)["pred_max_gap"] < 1e-4

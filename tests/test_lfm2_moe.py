"""Family ``lfm2_moe`` (ISSUE 33): an LFM2-MoE-style hybrid sparse decoder
as a token-level history scorer. The program against the plain reference
the benchmark keeps (``benchmark/reference/lfm2_moe.py``: the harness finds
it there, it is not copied) through ``score_dataset``; the gated short
convolution against a loop; grouped-query attention against repeated keys
and values; the router's normaliser as the family's argument (and
``kimi_k2`` unmoved by it, bit for bit); the uncut expert layer as the sum
of two shares; padding; the bfloat16 bundle; the routing counter; the
guards; the commands. All on the CPU, seeded random weights, tiny widths
that keep every ratio, float32 unless a test says otherwise."""

import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import inputs
from benchmark.reference import lfm2_moe as reference
from mlops_tpu.bundle.bundle import Bundle, load_bundle, save_bundle
from mlops_tpu.config import HISTORY_FAMILIES, ModelConfig
from mlops_tpu.data.encode import EncodedDataset, Preprocessor
from mlops_tpu.models import FAMILIES, abstract_variables, build_model
from mlops_tpu.monitor.state import fit_monitor
from mlops_tpu.ops import moe_dispatch
from mlops_tpu.ops.causal_attention import causal_attend
from mlops_tpu.ops.short_conv import short_conv
from mlops_tpu.parallel.bulk import make_bulk_jit, score_dataset
from mlops_tpu.schema import SCHEMA

REAL = json.loads(
    (Path(__file__).resolve().parents[1] / "benchmark/configs/lfm2-8b-a1b.json").read_text()
)
PER = 3  # records a history in the bulk tests: S = 144 tokens
PERIODS = ("conv", "conv", "full_attention", "conv") * 2  # the published list's start


def tiny_config(**over) -> ModelConfig:
    fields = dict(
        family="lfm2_moe", token_dim=64, depth=8, heads=4, kv_heads=2, ffn_dim=224,
        moe_ffn_dim=56, num_experts=8, experts_per_token=2, first_expert=0, experts_held=0,
        vocab_rows=1200, doc_records=PER, layer_types=PERIODS, dense_layers=2, conv_width=3,
        rope_theta=1000000.0, precision="f32", dropout=0.0,
    )
    return ModelConfig(**{**fields, **over})


def spec_of(config: ModelConfig) -> dict:
    """The configuration file's keys that the reference reads, for a tiny
    ``ModelConfig``; the source's constants are the real file's."""
    return {
        **{k: REAL[k] for k in (
            "norm_eps", "routed_scaling_factor", "tokens_per_record", "record_vocab_size",
            "num_bins", "schema",
        )},
        "model_config": dataclasses.asdict(config),
        "records_per_history": config.doc_records,
    }


def rows(n, seed=0):
    rng = np.random.default_rng(seed)
    cat = np.stack([rng.integers(0, c, n) for c in SCHEMA.cards], 1).astype(np.int32)
    return cat, (1.5 * rng.normal(size=(n, SCHEMA.num_numeric))).astype(np.float32)


def seeded(config: ModelConfig, seed=2**31 + 7):
    model = build_model(config)
    return model, inputs.make_weights(abstract_variables(model), seed)


def bundle_of(config: ModelConfig, ds: EncodedDataset) -> Bundle:
    model, weights = seeded(config)
    zeros = np.zeros(SCHEMA.num_numeric, np.float32)
    return Bundle(
        manifest={"flavor": "flax", "model_config": dataclasses.asdict(config),
                  "calibration": {"temperature": 1.5}},
        model=model,
        variables=weights,
        preprocessor=Preprocessor(zeros, zeros, zeros + 1, SCHEMA.fingerprint()),
        monitor=fit_monitor(ds),
    )


@pytest.fixture(scope="module")
def tiny_bundle():
    """A hand-made ``lfm2_moe`` bundle and a file of five whole histories
    and one of two records."""
    cat, num = rows(5 * PER + 2)
    ds = EncodedDataset(cat, num)
    return bundle_of(tiny_config(), ds), ds


def score(bundle, ds, chunk_rows=2 * PER, mesh=None):
    return score_dataset(
        bundle, ds, mesh=mesh, chunk_rows=chunk_rows, exact=True, pipeline_depth=2
    )


def logit(p):
    return 1.5 * np.log(p / (1.0 - p))  # undo sigmoid(logit / 1.5)


# ------------------------------------------------------- the configuration
def test_the_family_is_listed_and_keeps_histories_whole():
    assert "lfm2_moe" in FAMILIES and "lfm2_moe" in HISTORY_FAMILIES
    history = ModelConfig(family="lfm2_moe", doc_records=64)
    assert (history.reads_documents, history.history_rows) == (False, 64)
    assert not history.uses_layout_trainer


@pytest.mark.parametrize("over,match", [
    (dict(layer_types=PERIODS[:5]), "layer_types names 5 of 8"),
    (dict(layer_types=("conv", "sliding_attention") * 4), "each one of"),
    (dict(kv_heads=3), "4 query heads over 3"),
    (dict(token_dim=66), "hidden size of 66"),
    (dict(first_expert=6, experts_held=4), "experts 6..10 of 8"),
    (dict(experts_per_token=9), "9 experts a token of 8"),
    (dict(vocab_rows=500), "500 embedding rows"),
], ids=["short-list", "unknown-mixer", "ragged-groups", "ragged-heads", "experts-past-the-end",
        "too-many-a-token", "too-few-rows"])
def test_build_models_guards(over, match):
    model = build_model(tiny_config(**over))
    with pytest.raises(ValueError, match=match):
        abstract_variables(model)


@pytest.mark.parametrize("family,ok", [
    ("lfm2_moe", True), ("kimi_k2", True), ("mlp", False), ("evabyte", False),
])
def test_only_the_sparse_decoders_store_bfloat16_parameters(family, ok):
    config = ModelConfig(family=family, param_dtype="bf16", layer_types=PERIODS)
    if not ok:
        with pytest.raises(ValueError, match="keeps float32 parameters"):
            build_model(config)
    else:
        assert build_model(config).param_dtype == jnp.bfloat16


def test_the_real_configuration_is_the_published_widths():
    mc = REAL["model_config"]
    model = build_model(ModelConfig(**{**mc, "hidden_dims": tuple(mc["hidden_dims"])}))
    source = REAL["source_config"]
    assert (model.hidden, model.heads, model.kv_heads, model.ffn_dim, model.moe_ffn_dim) == (
        source["hidden_size"], source["num_attention_heads"], source["num_key_value_heads"],
        source["intermediate_size"], source["moe_intermediate_size"],
    )
    assert (model.num_experts, model.experts_per_token, model.first_expert, model.experts_held) == (
        source["num_experts"], source["num_experts_per_tok"], 0, source["num_experts"],
    )
    assert (model.vocab_rows, model.dense_layers, model.conv_width, model.rope_theta) == (
        source["vocab_size"], source["num_dense_layers"], source["conv_L_cache"],
        source["rope_theta"],
    )
    # depth is the one cut: layers 0..15 of the published list, four whole periods
    assert REAL["reduced"] == ["num_hidden_layers"] and model.depth == 16
    assert {k: REAL[k] for k in source} == {**source, "num_hidden_layers": 16}
    assert tuple(model.layer_types) == tuple(source["layer_types"])
    assert ModelConfig().layer_types == tuple(source["layer_types"])  # the family's default
    assert build_model(ModelConfig(family="lfm2_moe", heads=4)).kv_heads == 4  # 0: no grouping
    assert model.layer_types[:16] == ("conv", "conv", "full_attention", "conv") * 4
    shapes = abstract_variables(model)["params"]
    sizes = jax.tree_util.tree_map(lambda leaf: leaf.size, shapes)
    count = lambda tree: sum(jax.tree_util.tree_leaves(tree))  # noqa: E731
    # the issue's arithmetic: a layer's two norms ride on each figure
    conv, attention, dense, experts = 16_783_360, 10_485_888, 44_040_192, 352_387_104
    assert count(sizes["block_0"]) == conv + dense + 4096
    assert count(sizes["block_2"]) == attention + experts + 4096
    assert count(sizes["block_3"]) == conv + experts + 4096
    assert count(sizes["tok_embed"]) == 65536 * 2048
    assert count(sizes) == 5_399_131_073  # 10.80 GB at 2 bytes, 63.9% of the chip
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(shapes)} == {jnp.dtype("bfloat16")}
    assert set(shapes["block_3"]) == {
        "operator_norm", "in_proj", "conv", "out_proj", "ffn_norm", "router",
        "experts_gate", "experts_up", "experts_down",
    }
    assert set(shapes["block_2"]) - set(shapes["block_3"]) == {
        "q", "k", "v", "o", "q_norm", "k_norm",
    }
    assert shapes["block_2"]["k"]["kernel"].shape == (2048, 8 * 64)
    assert shapes["block_2"]["q_norm"]["scale"].shape == (64,)
    assert shapes["block_3"]["conv"]["kernel"].shape == (3, 2048)
    assert shapes["block_3"]["experts_up"]["kernel"].shape == (32, 2048, 1792)


# ------------------------------------------------- the short convolution
def conv_by_the_loop(bcu, taps):
    """The definition, position by position, in float64."""
    bcu, taps = np.asarray(bcu, np.float64), np.asarray(taps, np.float64)
    width, channels = taps.shape
    in_gate, out_gate, signal = np.split(bcu, 3, axis=-1)
    out = np.zeros_like(signal)
    for b in range(bcu.shape[0]):
        for t in range(bcu.shape[1]):
            z = np.zeros(channels)
            for j in range(width):
                at = t - (width - 1) + j
                if at >= 0:  # zeros left of the history's start
                    z += taps[j] * in_gate[b, at] * signal[b, at]
            out[b, t] = out_gate[b, t] * z
    return out


@pytest.mark.parametrize("width", [3, 1, 4])
def test_short_conv_matches_a_numpy_loop(width):
    rng = np.random.default_rng(width)
    bcu = rng.normal(size=(2, 11, 3 * 8)).astype(np.float32)
    taps = rng.normal(size=(width, 8)).astype(np.float32)
    out = short_conv(jnp.asarray(bcu), jnp.asarray(taps))
    assert out.shape == (2, 11, 8) and out.dtype == jnp.float32
    np.testing.assert_allclose(out, conv_by_the_loop(bcu, taps), atol=1e-5)
    low = short_conv(jnp.asarray(bcu, jnp.bfloat16), jnp.asarray(taps, jnp.bfloat16))
    assert low.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="projected channels"):
        short_conv(jnp.asarray(bcu[..., :-1]), jnp.asarray(taps))


def test_short_conv_is_causal_and_never_reads_across_a_historys_start():
    rng = np.random.default_rng(5)
    bcu = jnp.asarray(rng.normal(size=(2, 9, 12)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(3, 4)), jnp.float32)
    base = np.asarray(short_conv(bcu, taps))
    moved = np.asarray(short_conv(bcu.at[0, 5].add(1.0), taps))
    assert (moved[0, :5] == base[0, :5]).all()  # nothing before position 5
    assert np.abs(moved[0, 5:8] - base[0, 5:8]).max(axis=-1).min() > 1e-6  # its three readers
    assert (moved[0, 8:] == base[0, 8:]).all() and (moved[1] == base[1]).all()
    # a history's first positions read zeros, whatever the history before it holds
    other = np.asarray(short_conv(bcu.at[0].multiply(3.0), taps))
    assert (other[1] == base[1]).all()
    first = np.asarray(bcu[1, 0])
    np.testing.assert_allclose(
        base[1, 0], first[4:8] * np.asarray(taps[2]) * first[:4] * first[8:], rtol=1e-5
    )


# ------------------------------------------------- grouped-query attention
@pytest.mark.parametrize("block", [512, 48, 40])
def test_grouped_attention_matches_repeated_keys_and_values(block):
    """Four query heads over two key/value heads against (a) the same form
    with each key/value head handed to its two query heads (``repeat``: what
    the grouped form must not do in HBM), (b) a per-head loop."""
    rng = np.random.default_rng(1)
    q = rng.normal(size=(2, 144, 4, 24)).astype(np.float32)
    k = rng.normal(size=(2, 144, 2, 24)).astype(np.float32)
    v = rng.normal(size=(2, 144, 2, 16)).astype(np.float32)
    out = np.asarray(causal_attend(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 0.3,
                                   query_block=block))
    assert out.shape == (2, 144, 4, 16)
    repeated = causal_attend(
        jnp.asarray(q), jnp.repeat(jnp.asarray(k), 2, axis=2), jnp.repeat(jnp.asarray(v), 2, axis=2),
        0.3, query_block=block,
    )
    np.testing.assert_allclose(out, repeated, atol=2e-6)
    for b in range(2):
        for h in range(4):
            for i in (0, 1, 47, 48, 100, 143):
                s = 0.3 * k[b, : i + 1, h // 2] @ q[b, i, h]
                w = np.exp(s - s.max())
                np.testing.assert_allclose(
                    out[b, i, h], (w / w.sum()) @ v[b, : i + 1, h // 2], atol=2e-5
                )
    read = np.array([47, 95, 143])
    some = causal_attend(jnp.asarray(q[:, read]), jnp.asarray(k), jnp.asarray(v), 0.3, read=read)
    np.testing.assert_allclose(some, out[:, read], atol=2e-6)
    # no repeat in the program either: the lowered form holds no key or value
    # of the query heads' count
    text = jax.jit(lambda *xs: causal_attend(*xs, 0.3)).lower(q, k, v).as_text()
    assert "2x144x4x24" in text and "2x144x4x16xf32>) ->" not in text.split("dot_general")[0]
    with pytest.raises(ValueError, match="4 query heads over 3"):
        causal_attend(jnp.asarray(q), jnp.asarray(k[:, :, :1].repeat(3, 2)), jnp.asarray(v), 0.3)


def test_the_attention_layer_matches_the_references_one_head_at_a_time():
    config = tiny_config(depth=3, dense_layers=3)  # conv conv attention, all dense
    model, weights = seeded(config)
    cat, num = rows(2 * PER)
    served = model.apply(weights, cat, num, train=False)
    np.testing.assert_allclose(
        served, reference.logits(weights, cat, num, spec_of(config)), atol=1e-5
    )


# ---------------------------------------------------------------- the router
@pytest.mark.parametrize("eps", [1e-20, 1e-6])
def test_the_routers_normaliser_is_the_callers(eps):
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(50, 16)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(16, 8)) / 4 - 3.0, jnp.float32)  # small scores
    bias = jnp.asarray(rng.normal(size=8) * 0.1, jnp.float32)
    routing = moe_dispatch.route(h, gate, bias, 2, 1.5, eps)
    scores = 1.0 / (1.0 + np.exp(-(np.asarray(h, np.float64) @ np.asarray(gate, np.float64))))
    chosen = np.argsort(-(scores + np.asarray(bias, np.float64)), axis=-1)[:, :2]
    np.testing.assert_array_equal(np.sort(routing.experts, -1), np.sort(chosen, -1))
    picked = np.take_along_axis(scores, np.asarray(routing.experts), axis=-1)
    np.testing.assert_allclose(
        routing.weights, 1.5 * picked / (picked.sum(-1, keepdims=True) + eps), rtol=2e-5
    )
    # the epsilon is seen where the scores are small: 1e-6 against sums of some 1e-1
    other = moe_dispatch.route(h, gate, bias, 2, 1.5, 1e-2).weights
    assert float(jnp.abs(other - routing.weights).max()) > 1e-3


# `tests/test_kimi_k2.py`'s tiny configuration, its seed and its rows on the
# parent commit (ca55e38, before `route` took its epsilon from the caller and
# the causal block form moved to `ops/causal_attention.py`): the logits' bits
KIMI_BITS = [
    1027509294, 1058066839, 1051937200, 1068339793, 1042754592, 1065202568, 1067850070,
    1056782438, 1060996940, 3212999890, 1064616071, 1066652187, 1059250238, 1059955630,
    3179845681, 3199476100, 1066229573,
]


def test_kimi_k2_answers_as_the_parent_did_bit_for_bit():
    config = ModelConfig(
        family="kimi_k2", token_dim=64, depth=3, heads=4, ffn_dim=160,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, moe_ffn_dim=32, num_experts=16, experts_per_token=4,
        first_expert=4, experts_held=4, vocab_rows=1200, doc_records=3,
        rope_theta=50000.0, precision="f32", dropout=0.0,
    )
    model, weights = seeded(config)
    cat, num = rows(17)
    out = np.asarray(model.apply(weights, cat, num, train=False))
    assert out.view(np.uint32).tolist() == KIMI_BITS


# ------------------------------------------------------- the expert layer
def expert_inputs(tokens=150, dim=32, experts=32, width=24, seed=3):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape) / math.sqrt(shape[-2]), jnp.float32)  # noqa: E731
    h = jnp.asarray(rng.normal(size=(tokens, dim)), jnp.float32)
    return h, draw(dim, experts), draw(experts, dim, width), draw(experts, dim, width), draw(experts, width, dim)


def test_the_two_halves_add_up_to_the_layer_with_every_expert_held():
    """The guide's share test at this family's counts: experts 0..15 and
    16..31 as two shares add up to the layer with all 32 held, which is
    the reference's whole layer, expert by expert; no shared expert."""
    h, router, gate, up, down = expert_inputs()
    bias = jnp.asarray(np.random.default_rng(4).normal(size=32) * 0.1, jnp.float32)
    routing = moe_dispatch.route(h, router, bias, 4, 1.0, 1e-6)

    def part(first, held):
        planned = moe_dispatch.plan(routing.experts, first, held)
        sl = slice(first, first + held)
        rows_ = moe_dispatch.segment_rows(150, 4, 32, held)
        return moe_dispatch.grouped_swiglu(
            h, routing, planned, gate[sl], up[sl], down[sl], rows_
        ), planned

    (low, low_plan), (high, high_plan), (whole, plan) = part(0, 16), part(16, 16), part(0, 32)
    assert int(plan.counts.sum()) == 150 * 4  # all held: every choice lands
    assert int(low_plan.counts.sum()) + int(high_plan.counts.sum()) == 150 * 4
    np.testing.assert_array_equal(
        np.concatenate([low_plan.counts, high_plan.counts]), plan.counts
    )
    assert moe_dispatch.segment_rows(150, 4, 32, 32) == 600  # one segment, the worst case
    assert moe_dispatch.segment_rows(6144, 4, 32, 32) == 24576
    np.testing.assert_allclose(low + high, whole, atol=2e-5)
    want = jnp.zeros_like(h)
    for i in range(32):
        mine = jnp.where(routing.experts == i, routing.weights, 0.0).sum(-1)[:, None]
        want = want + mine * reference.swiglu(h, gate[i], up[i], down[i], "f32")
    np.testing.assert_allclose(whole, want, atol=3e-5)
    np.testing.assert_allclose(routing.weights.sum(-1), 1.0, rtol=1e-4)  # scaled by 1
    assert float(jnp.abs(low).max()) > 0 and float(jnp.abs(whole - low).max()) > 0.05


# --------------------------------------------------------------- the combine
# One segment holds every held assignment: every token gathers its own
# experts' rows by the inverse of the dispatch's permutation and sums them.
# (first, held, rows, bias): all 32 held; half of them (absent slots are
# masked); two, fewer than a token chooses (positions past the segment's
# rows are clamped); all held with expert 6 never chosen.
SHARES = {
    "all": (0, 32, 600, None),
    "half": (16, 16, 600, None),
    "two": (3, 2, 300, None),
    "one_expert_idle": (0, 32, 600, (6, -5.0)),
}


def share_inputs(case):
    first, held, rows_, skew = SHARES[case]
    h, router, gate, up, down = expert_inputs()
    bias = np.random.default_rng(4).normal(size=32) * 0.1
    if skew:
        bias[skew[0]] = skew[1]
    routing = moe_dispatch.route(h, router, jnp.asarray(bias, jnp.float32), 4, 1.0, 1e-6)
    sl = slice(first, first + held)
    return h, routing, (gate[sl], up[sl], down[sl]), first, held, rows_


def scattered(h, weights, planned, gate, up, down):
    """The combine as a scatter-add, written plainly: every sorted row
    times its weight, the rows past the last held assignment zeroed, added
    at its token."""
    top_k = weights.shape[1]
    token = planned.order // top_k
    out = moe_dispatch.segment_products_xla(h[token], gate, up, down, planned.counts)
    live = jnp.arange(token.shape[0]) < planned.offsets[-1]
    weighted = jnp.where(live[:, None], out * weights.reshape(-1)[planned.order][:, None], 0.0)
    return jnp.zeros_like(h).at[token].add(weighted)


@pytest.mark.parametrize("case", list(SHARES))
def test_one_segments_gather_is_the_scatter_add(case):
    h, routing, stacked, first, held, rows_ = share_inputs(case)
    planned = moe_dispatch.plan(routing.experts, first, held)
    counts = np.asarray(planned.counts)
    assert -(-min(4, held) * 150 // rows_) == 1  # one segment: the gather form
    assert case != "one_expert_idle" or (counts[6] == 0 and counts.sum() == 600)
    assert (int(counts.sum()) < 600) == (held < 32)  # a share leaves slots absent
    got = moe_dispatch.grouped_swiglu(h, routing, planned, *stacked, rows_)
    want = scattered(h, routing.weights, planned, *stacked)
    assert float(jnp.abs(want).max()) > 0.05
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("case", ["half", "two"])
def test_undefined_rows_never_reach_a_token(case, monkeypatch):
    """A grouped product leaves the rows past its sizes' sum undefined. With
    NaN there, the result is what it was: they are selected away (a
    ``where``), not multiplied by 0."""
    h, routing, stacked, first, held, rows_ = share_inputs(case)
    planned = moe_dispatch.plan(routing.experts, first, held)
    assert int(planned.offsets[-1]) < rows_
    clean = moe_dispatch.grouped_swiglu(h, routing, planned, *stacked, rows_)

    def undefined_past_the_sizes(taken, gate, up, down, sizes):
        out = moe_dispatch.segment_products_xla(taken, gate, up, down, sizes)
        return jnp.where((jnp.arange(out.shape[0]) < sizes.sum())[:, None], out, jnp.nan)

    monkeypatch.setattr(moe_dispatch, "segment_products", undefined_past_the_sizes)
    got = moe_dispatch.grouped_swiglu(h, routing, planned, *stacked, rows_)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(got, clean)


@pytest.mark.parametrize("case", ["all", "half"])
def test_landed_is_the_sorted_orders_inverse(case):
    h, routing, _, first, held, _ = share_inputs(case)
    planned = moe_dispatch.plan(routing.experts, first, held)
    pos = np.asarray(moe_dispatch.landed(planned.order))
    np.testing.assert_array_equal(np.asarray(planned.order)[pos], np.arange(600))
    # a held assignment landed under the total, at its expert's run
    local = np.asarray(routing.experts).reshape(-1) - first
    mine = (local >= 0) & (local < held)
    np.testing.assert_array_equal(pos < int(planned.offsets[-1]), mine)
    offsets = np.asarray(planned.offsets)
    assert (pos[mine] >= offsets[local[mine]]).all() and (pos[mine] < offsets[local[mine] + 1]).all()


@pytest.mark.parametrize("case", ["all", "one_expert_idle"])
def test_several_segments_answer_as_one(case):
    """The walk in segments of 128 rows (five of them: the scatter-add under
    the loop) against one segment of 600 (the gather)."""
    h, routing, stacked, first, held, rows_ = share_inputs(case)
    planned = moe_dispatch.plan(routing.experts, first, held)
    walked = moe_dispatch.grouped_swiglu(h, routing, planned, *stacked, 128)
    one = moe_dispatch.grouped_swiglu(h, routing, planned, *stacked, rows_)
    np.testing.assert_allclose(walked, one, atol=1e-6)


@pytest.mark.parametrize("case", ["all", "half"])
def test_the_gathers_gradient_is_the_scatter_adds(case):
    """The trainers differentiate `grouped_swiglu`: through one segment's
    gather the gradients of the rows, of the weights and of the three
    stacked projections are those through the scatter-add."""
    h, routing, stacked, first, held, rows_ = share_inputs(case)
    planned = moe_dispatch.plan(routing.experts, first, held)
    tilt = jnp.asarray(np.random.default_rng(5).normal(size=h.shape), jnp.float32)

    def through_the_gather(h, weights, gate, up, down):
        routed = routing._replace(weights=weights)
        return (moe_dispatch.grouped_swiglu(h, routed, planned, gate, up, down, rows_) * tilt).sum()

    def through_the_scatter(h, weights, gate, up, down):
        return (scattered(h, weights, planned, gate, up, down) * tilt).sum()

    operands = (h, routing.weights, *stacked)
    got = jax.grad(through_the_gather, argnums=(0, 1, 2, 3, 4))(*operands)
    want = jax.grad(through_the_scatter, argnums=(0, 1, 2, 3, 4))(*operands)
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 1e-3
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


# ----------------------------------------------- the model and the bulk job
@pytest.mark.parametrize("held", [(0, 0), (2, 4)], ids=["uncut", "share"])
def test_score_dataset_matches_the_reference(held):
    """A file of five whole histories and a short last one of two records,
    in chunks of two histories: three runs, the last padded. To 1e-5: the
    program and the reference are float32 throughout and differ in the
    order of their sums only (the blocks of queries, the grouped products'
    segments, a fused convolution): a logit of order 1 after 8 layers
    reads 1e-6 to 3e-6 apart."""
    first, count = held
    config = tiny_config(first_expert=first, experts_held=count)
    cat, num = rows(5 * PER + 2)
    ds = EncodedDataset(cat, num)
    bundle = bundle_of(config, ds)
    result = score(bundle, ds)
    spec = spec_of(config)
    expected, _ = reference.forward(bundle.variables, cat, num, spec)
    np.testing.assert_allclose(logit(result.predictions), np.asarray(expected), atol=1e-5)
    assert np.abs(np.asarray(expected)).max() > 0.05
    # the counter, exactly: the job's runs were three chunks of two histories,
    # the last history two records and a padding row of zeros
    given_cat = np.concatenate([cat, np.zeros((1, 9), np.int32)])
    given_num = np.concatenate([num, np.zeros((1, 14), np.float32)])
    want = reference.held_assignments(
        reference.forward(bundle.variables, given_cat, given_num, spec)[1], spec
    )
    got = np.asarray(result.routing["per_layer"])
    assert got.shape == (6, count or 8)  # 6 expert layers of 8
    # every layer but the last routes every token; the last the read positions
    np.testing.assert_array_equal(got[:-1], want[:-1])
    assert result.routing["tokens"] == 6 * PER * 48
    assert result.routing["assignments_held"] == got.sum()
    assert 0 < got[-1].sum() <= 6 * PER * 2
    if not count:  # all held: every choice of every token is counted, nothing left out
        assert (got[:-1].sum(axis=1) == 6 * PER * 48 * 2).all() and got[-1].sum() == 6 * PER * 2


def test_a_historys_answers_do_not_depend_on_its_neighbours_or_its_padding(tiny_bundle):
    bundle, ds = tiny_bundle
    whole = score(bundle, ds).predictions
    short = EncodedDataset(ds.cat_ids[: 5 * PER + 1], ds.numeric[: 5 * PER + 1])
    np.testing.assert_allclose(score(bundle, short).predictions, whole[: 5 * PER + 1], atol=2e-6)
    # the history before another one altered: the convolution pads on the left
    cat, num = ds.cat_ids.copy(), ds.numeric.copy()
    num[:PER] += 1.0
    moved = score(bundle, EncodedDataset(cat, num)).predictions
    assert np.abs(moved[:PER] - whole[:PER]).max() > 1e-4
    np.testing.assert_allclose(moved[PER:], whole[PER:], atol=2e-6)
    # causality: a record's answer never depends on the records after it
    first = bundle.model.apply(bundle.variables, ds.cat_ids[:1], ds.numeric[:1], train=False)
    np.testing.assert_allclose(logit(whole[:1]), first, atol=1e-5)


def test_chunks_of_any_number_of_histories_give_the_same_answers(tiny_bundle):
    bundle, ds = tiny_bundle
    np.testing.assert_allclose(
        score(bundle, ds, chunk_rows=PER).predictions,
        score(bundle, ds, chunk_rows=4 * PER).predictions, atol=2e-6,
    )


@pytest.mark.parametrize("depth", [7, 8], ids=["ends-on-attention", "ends-on-a-convolution"])
def test_the_last_layer_at_the_read_positions_answers_as_the_whole_layer(depth):
    """The program's last layer runs behind its mixer's inputs at the read
    positions only; the reference runs every layer whole."""
    config = tiny_config(depth=depth)
    model, weights = seeded(config)
    cat, num = rows(2 * PER + 1)
    served = model.apply(weights, cat, num, train=False)
    np.testing.assert_allclose(
        served, reference.logits(weights, cat, num, spec_of(config)), atol=1e-5
    )


@pytest.mark.parametrize("scope", [
    "conv_in", "short_conv", "conv_out", "gqa_qkv", "gqa_attend", "gqa_o", "rope", "router",
    "moe_dispatch", "experts", "moe_combine", "embed", "ffn", "head",
])
def test_lowered_chunk_program_holds_the_scope(tiny_bundle, scope):
    bundle, _ = tiny_bundle
    chunk = 2 * PER
    lowered = make_bulk_jit(bundle.model, None).lower(
        bundle.variables, bundle.monitor, np.float32(1.5),
        np.zeros((chunk, SCHEMA.num_categorical), np.int8),
        np.zeros((chunk, SCHEMA.num_numeric), np.float32), np.ones(chunk, bool),
    )
    text = lowered.as_text(debug_info=True)
    assert f"/{scope}/" in text or f"/{scope}\"" in text, scope
    assert "shared_expert" not in text and "mla_attend" not in text and "pallas" not in text


def test_the_routing_marker_is_written_once_a_job(tiny_bundle, tmp_path):
    from conftest import program_spans

    bundle, ds = tiny_bundle
    with program_spans(tmp_path / "profile") as spans:
        result = score(bundle, ds)
    (marker,) = [attrs for name, _, _, attrs in spans if name == "mlops:bulk.routing"]
    assert marker["assignments_held"] == result.routing["assignments_held"]
    assert marker["tokens"] == 6 * PER * 48
    assert marker["layer_0"] == "|".join(map(str, result.routing["per_layer"][0]))
    assert "routing" in result.summary()


# ------------------------------------------------------ bfloat16 parameters
def test_a_bfloat16_bundle_round_trips_bit_for_bit(tmp_path):
    config = tiny_config(param_dtype="bf16", precision="bf16")
    cat, num = rows(2 * PER)
    ds = EncodedDataset(cat, num)
    bundle = bundle_of(config, ds)
    leaves = jax.tree_util.tree_leaves(bundle.variables)
    assert {leaf.dtype for leaf in leaves} == {jnp.dtype("bfloat16")}
    save_bundle(tmp_path / "b", bundle.model_config, bundle.variables["params"],
                bundle.preprocessor, bundle.monitor, calibration={"temperature": 1.5})
    loaded = load_bundle(tmp_path / "b")
    assert loaded.model_config == bundle.model_config
    assert loaded.model_config.layer_types == PERIODS  # a list on disk, a tuple again
    for a, b in zip(leaves, jax.tree_util.tree_leaves(loaded.variables)):
        assert b.dtype == jnp.dtype("bfloat16")
        np.testing.assert_array_equal(np.asarray(a).view(np.uint16), np.asarray(b).view(np.uint16))
    np.testing.assert_array_equal(score(loaded, ds).predictions, score(bundle, ds).predictions)
    # nothing casts the tree: no parameter-shaped float32 copy in the program
    chunk = 2 * PER
    text = make_bulk_jit(bundle.model, None).lower(
        bundle.variables, bundle.monitor, np.float32(1.5),
        np.zeros((chunk, SCHEMA.num_categorical), np.int8),
        np.zeros((chunk, SCHEMA.num_numeric), np.float32), np.ones(chunk, bool),
    ).as_text()
    assert "tensor<1200x64xf32>" not in text and "tensor<8x64x56xf32>" not in text
    assert "tensor<64x192xf32>" not in text  # the convolution's input projection
    # against the float32 reference the bfloat16 program is near, not equal
    expected = reference.logits(bundle.variables, cat, num, spec_of(config))
    # (a flipped choice of 2 experts in 8 is half a token's FFN at this size)
    gap = np.abs(logit(score(bundle, ds).predictions) - np.asarray(expected))
    assert 1e-6 < gap.max() < 1.5 and np.sqrt((gap**2).mean()) < 0.5


# ------------------------------------------------- training, the commands
def test_gradients_are_finite_and_reach_every_kind_of_layer():
    config = tiny_config(doc_records=2)
    model, weights = seeded(config)
    cat, num = rows(8)
    labels = jnp.asarray(np.arange(8) % 2, jnp.float32)

    def loss(params):
        logits = model.apply({"params": params}, cat, num, train=False)
        return jnp.mean(jnp.logaddexp(0.0, logits) - labels * logits)

    value, grads = jax.jit(jax.value_and_grad(loss))(weights["params"])
    assert np.isfinite(float(value))
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree_util.tree_leaves(grads))
    for block, name in (("block_0", "in_proj"), ("block_1", "conv"), ("block_2", "k"),
                        ("block_2", "q_norm"), ("block_3", "experts_gate"),
                        ("block_6", "experts_down")):
        leaf = jax.tree_util.tree_leaves(grads[block][name])[0]
        assert np.abs(np.asarray(leaf)).max() > 0, (block, name)


def test_score_batch_scores_a_lfm2_moe_bundle(tmp_path, capsys):
    from mlops_tpu.cli import main
    from mlops_tpu.data import generate_synthetic, write_csv_columns

    config = tiny_config(doc_records=2)
    cat, num = rows(10)
    bundle = bundle_of(config, EncodedDataset(cat, num))
    save_bundle(tmp_path / "b", config, bundle.variables["params"], bundle.preprocessor,
                bundle.monitor, calibration={"temperature": 1.5})
    columns, labels = generate_synthetic(37, seed=3)  # 18 histories of 2 and one of 1
    write_csv_columns(tmp_path / "in.csv", columns, labels)
    assert main(["score-batch", f"data.train_path={tmp_path / 'in.csv'}",
                 f"serve.model_directory={tmp_path / 'b'}", "score.chunk_rows=8",
                 "score.exact=true", f"score.output_path={tmp_path / 'out.npz'}"]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["rows"] == 37 and summary["path"] == "exact"
    # the command shards over the test's eight devices: a chunk is a history
    # a device, 16 rows, and the job three chunks
    assert summary["routing"]["tokens"] == 3 * 16 * 48
    scored = np.load(tmp_path / "out.npz")["predictions"]
    assert scored.shape == (37,) and np.isfinite(scored).all()

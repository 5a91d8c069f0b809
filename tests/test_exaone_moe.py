"""Family ``exaone_moe`` (ISSUE 37): a K-EXAONE-style sparse decoder as a
token-level history scorer. The program against the plain reference the
benchmark keeps (``benchmark/reference/exaone_moe.py``: the harness finds
it there, it is not copied) through ``score_dataset``, uncut and as a
share; the shares adding up to the uncut layer with the shared expert
counted once; `causal_attend`'s window against a masked dense softmax and
the work it skips counted in the lowered program; which layers turn; the
last layer of either kind; padding; the bfloat16 bundle; the routing
counter; the guards; the commands; and `lfm2_moe`'s logits against the
parent's recorded bits. All on the CPU, seeded random weights,
tiny widths that keep every ratio, float32 unless a test says otherwise."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmark import inputs
from benchmark.reference import exaone_moe as reference
from mlops_tpu.bundle.bundle import Bundle, load_bundle, save_bundle
from mlops_tpu.config import HISTORY_FAMILIES, ModelConfig
from mlops_tpu.data.encode import EncodedDataset, Preprocessor
from mlops_tpu.models import BF16_PARAM_FAMILIES, FAMILIES, abstract_variables, build_model
from mlops_tpu.models import exaone_moe
from mlops_tpu.models.routed_experts import experts_beside_a_shared_one
from mlops_tpu.monitor.state import fit_monitor
from mlops_tpu.ops.causal_attention import causal_attend
from mlops_tpu.parallel.bulk import make_bulk_jit, score_dataset
from mlops_tpu.schema import SCHEMA

REAL = json.loads(
    (Path(__file__).resolve().parents[1] / "benchmark/configs/k-exaone-236b-a23b.json").read_text()
)
PER = 3  # records a history in the bulk tests: S = 144 tokens
SWA, FULL = "sliding_attention", "full_attention"
LLLG = (SWA, SWA, SWA, FULL)


def tiny_config(**over) -> ModelConfig:
    """8 query heads over 2 key/value heads of 16 in a hidden size of 64
    (8 x 16 is not 64), a window of 40 (shorter than the 144-token history,
    no divisor of it or of a query block), L L L G twice, 1 dense layer, 16
    experts, 4 a token, beside the shared one."""
    fields = dict(
        family="exaone_moe", token_dim=64, depth=8, heads=8, kv_heads=2, head_dim=16,
        attn_window=40, ffn_dim=192, moe_ffn_dim=24, num_experts=16, experts_per_token=4,
        first_expert=0, experts_held=0, vocab_rows=1200, doc_records=PER,
        layer_types=LLLG * 2, dense_layers=1, rope_theta=1000000.0, precision="f32",
        dropout=0.0,
    )
    return ModelConfig(**{**fields, **over})


def spec_of(config: ModelConfig) -> dict:
    """The configuration file's keys that the reference reads, for a tiny
    ``ModelConfig``; the source's constants are the real file's."""
    return {
        **{k: REAL[k] for k in (
            "rms_norm_eps", "routed_scaling_factor", "tokens_per_record", "record_vocab_size",
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
    """A hand-made ``exaone_moe`` bundle and a file of five whole histories
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


def lowered_chunk(bundle, chunk=2 * PER):
    return make_bulk_jit(bundle.model, None).lower(
        bundle.variables, bundle.monitor, np.float32(1.5),
        np.zeros((chunk, SCHEMA.num_categorical), np.int8),
        np.zeros((chunk, SCHEMA.num_numeric), np.float32), np.ones(chunk, bool),
    )


# ------------------------------------------------------- the configuration
def test_the_family_is_listed_and_keeps_histories_whole():
    assert "exaone_moe" in FAMILIES and "exaone_moe" in HISTORY_FAMILIES
    assert "exaone_moe" in BF16_PARAM_FAMILIES
    history = ModelConfig(family="exaone_moe", doc_records=64)
    assert (history.reads_documents, history.history_rows) == (False, 64)
    assert not history.uses_layout_trainer
    assert ModelConfig().head_dim == 0  # 0: the hidden size over the heads
    assert build_model(tiny_config(head_dim=0)).head_dim == 8
    assert build_model(tiny_config(param_dtype="bf16")).param_dtype == jnp.bfloat16


@pytest.mark.parametrize("over,match", [
    (dict(layer_types=LLLG), "layer_types names 4 of 8"),
    (dict(layer_types=("conv", SWA) * 4), "each one of"),
    (dict(layer_types=ModelConfig().layer_types), "each one of"),  # lfm2_moe's default list
    (dict(kv_heads=3), "8 query heads over 3"),
    (dict(head_dim=15), "key/value heads of 15"),
    (dict(attn_window=0), "a window of 0"),
    (dict(first_expert=12, experts_held=8), "experts 12..20 of 16"),
    (dict(experts_per_token=17), "17 experts a token of 16"),
    (dict(vocab_rows=500), "500 embedding rows"),
], ids=["short-list", "unknown-layer", "another-familys-list", "ragged-groups", "odd-head",
        "no-window", "experts-past-the-end", "too-many-a-token", "too-few-rows"])
def test_build_models_guards(over, match):
    model = build_model(tiny_config(**over))
    with pytest.raises(ValueError, match=match):
        abstract_variables(model)


def test_the_real_configuration_is_the_published_widths():
    mc = REAL["model_config"]
    model = build_model(ModelConfig(**{**mc, "hidden_dims": tuple(mc["hidden_dims"])}))
    source = REAL["source_config"]
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():  # the catalog row's config, key for key
        row = next(
            json.loads(line) for line in catalog.read_text().splitlines()
            if json.loads(line)["name"] == "K-EXAONE-236B-A23B"
        )
        assert source == row["config"] and REAL["source"] == row["source_url"]
    assert (model.hidden, model.heads, model.kv_heads, model.head_dim, model.window) == (
        source["hidden_size"], source["num_attention_heads"], source["num_key_value_heads"],
        source["head_dim"], source["sliding_window"],
    ) == (6144, 64, 8, 128, 128)
    assert model.heads * model.head_dim == 8192 != model.hidden
    assert (model.ffn_dim, model.moe_ffn_dim, model.num_experts, model.experts_per_token) == (
        source["intermediate_size"], source["moe_intermediate_size"], source["num_experts"],
        source["num_experts_per_tok"],
    ) == (18432, 2048, 128, 8)
    assert (model.dense_layers, model.rope_theta) == (
        source["first_k_dense_replace"], source["rope_parameters"]["rope_theta"],
    )
    assert exaone_moe.ROUTED_SCALING == source["routed_scaling_factor"] == 2.5
    assert source["num_shared_experts"] == 1 and source["n_group"] == source["topk_group"] == 1
    # three cuts: depth, the experts held, the vocabulary's slice; nothing else
    assert REAL["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    held = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 19200}
    assert {k: REAL[k] for k in source} == {**source, **held}
    assert (model.depth, model.first_expert, model.experts_held, model.vocab_rows) == (5, 0, 16, 19200)
    assert source["num_experts"] // 8 == 16 and source["vocab_size"] // 8 == 19200  # 8 chips a layer
    assert tuple(model.layer_types) == tuple(source["layer_types"]) == LLLG * 12
    assert model.layer_types[:5] == (SWA, SWA, SWA, FULL, SWA)
    assert [w for w in source["sliding_windows"]] == [128, 128, 128, 0] * 12
    shapes = abstract_variables(model)["params"]
    sizes = jax.tree_util.tree_map(lambda leaf: leaf.size, shapes)
    count = lambda tree: sum(jax.tree_util.tree_leaves(tree))  # noqa: E731
    # the issue's arithmetic, to the parameter
    attention, expert, router, norms = 113_246_464, 37_748_736, 786_560, 12_288
    assert count(sizes["block_0"]) == attention + 3 * 6144 * 18432 + norms == 452_997_376
    sparse = attention + expert + router + 16 * expert + norms
    assert sparse == 755_773_824
    assert all(count(sizes[f"block_{i}"]) == sparse for i in range(1, 5))
    assert count(sizes["tok_embed"]) == 19200 * 6144 == 117_964_800
    assert count(sizes) == 3_594_069_761  # 7.19 GB at 2 bytes, 42.5% of the chip
    assert count(sizes) + 3 * sparse == 5_861_391_233  # layers 0-7, the depth tried first
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(shapes)} == {jnp.dtype("bfloat16")}
    assert set(shapes["block_1"]) == {
        "attn_norm", "q", "k", "v", "o", "q_norm", "k_norm", "ffn_norm", "router",
        "experts_gate", "experts_up", "experts_down", "shared_gate", "shared_up", "shared_down",
    }
    assert set(shapes["block_0"]) == {
        "attn_norm", "q", "k", "v", "o", "q_norm", "k_norm", "ffn_norm", "gate", "up", "down",
    }
    assert shapes["block_3"]["q"]["kernel"].shape == (6144, 8192)
    assert shapes["block_3"]["k"]["kernel"].shape == (6144, 1024)
    assert shapes["block_3"]["o"]["kernel"].shape == (8192, 6144)
    assert shapes["block_3"]["q_norm"]["scale"].shape == (128,)
    assert shapes["block_2"]["router"]["kernel"].shape == (6144, 128)  # the published width
    assert shapes["block_2"]["experts_up"]["kernel"].shape == (16, 6144, 2048)
    assert shapes["block_2"]["shared_down"]["kernel"].shape == (2048, 6144)


# ------------------------------------------------------------- the window
def masked_softmax(q, k, v, scale, window):
    """The definition in float64: a window is a mask over every score."""
    share = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, share, axis=2), np.repeat(v, share, axis=2)
    scores = scale * np.einsum("bqhe,bkhe->bhqk", q.astype(np.float64), k.astype(np.float64))
    at = np.arange(q.shape[1])
    seen = (at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - window)
    scores = np.where(seen, scores, -np.inf)
    weights = np.exp(scores - scores.max(-1, keepdims=True))
    weights /= weights.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", weights, v.astype(np.float64))


@pytest.mark.parametrize("groups", [2, 8], ids=["grouped", "ungrouped"])
@pytest.mark.parametrize("window", [1, 40, 144, 200], ids=["itself", "non-divisor", "history", "longer"])
def test_the_window_matches_a_masked_dense_softmax(window, groups):
    rng = np.random.default_rng(window)
    q = rng.normal(size=(2, 144, 8, 24)).astype(np.float32)
    k = rng.normal(size=(2, 144, groups, 24)).astype(np.float32)
    v = rng.normal(size=(2, 144, groups, 16)).astype(np.float32)
    want = masked_softmax(q, k, v, 0.3, window)
    for block in (512, 48, 25):
        out = causal_attend(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 0.3,
                            window=window, query_block=block)
        assert out.shape == (2, 144, 8, 16)
        np.testing.assert_allclose(out, want, atol=3e-6)
    read = np.array([0, 47, 95, 143])
    some = causal_attend(jnp.asarray(q[:, read]), jnp.asarray(k), jnp.asarray(v), 0.3,
                         read=read, window=window)
    np.testing.assert_allclose(some, want[:, read], atol=3e-6)
    if window >= 144:  # no window at all: the full form's answers, bit for bit
        full = causal_attend(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 0.3)
        np.testing.assert_array_equal(
            causal_attend(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 0.3, window=window), full
        )
    else:  # a query never reads left of its window
        moved = causal_attend(jnp.asarray(q), jnp.asarray(k).at[:, 20].add(5.0), jnp.asarray(v),
                              0.3, window=window)
        base = np.asarray(causal_attend(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 0.3,
                                        window=window))
        np.testing.assert_array_equal(np.asarray(moved)[:, 20 + window:], base[:, 20 + window:])
        if window > 1:  # (alone under the softmax, a key's weight is 1 whatever it holds)
            assert np.abs(np.asarray(moved)[:, 20] - base[:, 20]).max() > 1e-3
    with pytest.raises(ValueError, match="a window of 0"):
        causal_attend(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 0.3, window=0)


def matmul_flops(seq: int, window):
    """The matrix-multiply operations XLA's cost analysis counts in the
    lowered attention of one history of ``seq`` tokens, 8 heads over 2 of
    64; the elementwise work rides along and is small beside them."""
    shapes = [jax.ShapeDtypeStruct((1, seq, heads, 64), jnp.float32) for heads in (8, 2, 2)]
    attend = jax.jit(lambda q, k, v: causal_attend(q, k, v, 0.125, window=window))
    cost = attend.lower(*shapes).compile().cost_analysis()
    return float((cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"])


def test_the_window_skips_the_work_left_of_the_band():
    """Held by the count, not by a comment: at window 128 the work grows
    with the history's length, where the full form's grows with its
    square."""
    short, long = matmul_flops(1536, 128), matmul_flops(3072, 128)
    assert long / short < 2.2, (short, long)
    full_short, full_long = matmul_flops(1536, None), matmul_flops(3072, None)
    assert 3.3 < full_long / full_short < 4.2, (full_short, full_long)
    # a block of 128 queries against a tile of 256 keys: a twelfth of the
    # full form's half square, twice over
    assert long < full_long / 4.5, (long, full_long)


# `tests/test_lfm2_moe.py`'s tiny configuration, its seed and its rows on the
# parent commit (a728de4, before `causal_attend` learned its window and the
# attention and the SwiGLU moved out of the blocks): the logits' bits. (`kimi_k2`
# is held to its own by that file's `KIMI_BITS`, which this PR leaves as they are.)
LFM2_BITS = [
    3202163114, 3205887518, 1016019268, 3195822022, 1054017187, 3208169643, 1046132712,
    3204773501, 3206274481, 3214440822, 3212722127, 1057277856, 1050652461, 3191798842,
    3208570033, 3200057298, 1068459533,
]


def test_lfm2_moe_answers_as_the_parent_did_bit_for_bit():
    config = ModelConfig(
        family="lfm2_moe", token_dim=64, depth=8, heads=4, kv_heads=2, ffn_dim=224,
        moe_ffn_dim=56, num_experts=8, experts_per_token=2, first_expert=0, experts_held=0,
        vocab_rows=1200, doc_records=3, layer_types=("conv", "conv", FULL, "conv") * 2,
        dense_layers=2, conv_width=3, rope_theta=1000000.0, precision="f32", dropout=0.0,
    )
    model, weights = seeded(config)
    cat, num = rows(17)
    out = np.asarray(model.apply(weights, cat, num, train=False))
    assert out.view(np.uint32).tolist() == LFM2_BITS


# ---------------------------------------------------------- which layers turn
@pytest.mark.parametrize("kind,turned", [(SWA, False), (FULL, True)],
                         ids=["unturned-window-layer", "turned-full-layer"])
def test_rotary_turns_the_window_layers_only(kind, turned, monkeypatch):
    """The program as it is answers as the reference; with one kind of
    layer's turn the other way round it does not."""
    config = tiny_config(depth=4)
    model, weights = seeded(config)
    cat, num = rows(2 * PER)
    expected = np.asarray(reference.logits(weights, cat, num, spec_of(config)))
    served = model.apply(weights, cat, num, train=False)
    np.testing.assert_allclose(served, expected, atol=1e-5)
    real = exaone_moe.grouped_query_attention

    def wrong(block, h, read, **kw):
        if block.layer_type == kind:
            kw["turn"] = turned
        return real(block, h, read, **kw)

    monkeypatch.setattr(exaone_moe, "grouped_query_attention", wrong)
    moved = model.apply(weights, cat, num, train=False)
    assert np.abs(np.asarray(moved) - expected).max() > 1e-3


# ------------------------------------------------------- the expert layer
class _Layer(exaone_moe.ExaoneBlock):
    """A block's expert layer alone, as the block calls it."""

    @nn.compact
    def __call__(self, h):
        return experts_beside_a_shared_one(
            self, h, scaling=exaone_moe.ROUTED_SCALING, eps=exaone_moe.ROUTE_EPS
        )


def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    """The guide's share test: experts 0..7 and 8..15 as two chips' shares,
    the shared expert (which every chip computes alike) counted once, add
    up to what the uncut reference gives for the whole layer."""
    def layer(first, held):
        return _Layer(
            layer_type=SWA, heads=8, kv_heads=2, head_dim=16, window=40, ffn_dim=0,
            moe_ffn_dim=24, num_experts=16, experts_per_token=4, first_expert=first,
            experts_held=held, rope_theta=1e6, dtype=jnp.float32,
        )

    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(150, 64)), jnp.float32)
    whole = inputs.make_weights(jax.eval_shape(layer(0, 16).init, jax.random.PRNGKey(0), h), 11)
    p = whole["params"]

    def part(first, held):
        cut = {
            name: {"kernel": leaf["kernel"][first : first + held]} if name.startswith("experts_")
            else leaf
            for name, leaf in p.items()
        }
        return layer(first, held).apply({"params": cut}, h)

    # what the uncut reference's layer adds to the stream, expert by expert
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])
    _, chosen = jax.lax.top_k(scores + p["router"]["bias"], 4)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = 2.5 * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    shared = reference.swiglu(
        h, p["shared_gate"]["kernel"], p["shared_up"]["kernel"], p["shared_down"]["kernel"], "f32"
    )
    want = shared
    for i in range(16):
        mine = jnp.where(chosen == i, weights, 0.0).sum(-1)[:, None]
        want = want + mine * reference.swiglu(
            h, p["experts_gate"]["kernel"][i], p["experts_up"]["kernel"][i],
            p["experts_down"]["kernel"][i], "f32",
        )
    low, high, uncut = part(0, 8), part(8, 8), part(0, 16)
    np.testing.assert_allclose(uncut, want, atol=3e-5)
    np.testing.assert_allclose(low + high - shared, want, atol=3e-5)  # the shared one once
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)  # normalised, then scaled
    assert float(jnp.abs(shared).max()) > 0.05 and float(jnp.abs(low - shared).max()) > 0.05
    assert float(jnp.abs(uncut - low).max()) > 0.05


# ----------------------------------------------- the model and the bulk job
@pytest.mark.parametrize("held", [(0, 0), (4, 8)], ids=["uncut", "share"])
def test_score_dataset_matches_the_reference(held):
    """A file of five whole histories and a short last one of two records,
    in chunks of two histories: three runs, the last padded. To 1e-5: the
    program and the reference are float32 throughout and differ in the
    order of their sums only (the band's tiles, the blocks of queries, the
    grouped products' segments)."""
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
    assert got.shape == (7, count or 16)  # 7 sparse layers
    # every layer but the last routes every token; the last the read positions
    np.testing.assert_array_equal(got[:-1], want[:-1])
    assert result.routing["tokens"] == 6 * PER * 48
    assert result.routing["assignments_held"] == got.sum()
    assert 0 < got[-1].sum() <= 6 * PER * 4
    if not count:  # all held: every choice of every token is counted
        assert (got[:-1].sum(axis=1) == 6 * PER * 48 * 4).all() and got[-1].sum() == 6 * PER * 4


def test_a_historys_answers_do_not_depend_on_its_neighbours_or_its_padding(tiny_bundle):
    bundle, ds = tiny_bundle
    whole = score(bundle, ds).predictions
    short = EncodedDataset(ds.cat_ids[: 5 * PER + 1], ds.numeric[: 5 * PER + 1])
    np.testing.assert_allclose(score(bundle, short).predictions, whole[: 5 * PER + 1], atol=2e-6)
    # the history before another one altered: a window never reaches across a start
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


@pytest.mark.parametrize("depth", [5, 7, 8], ids=[
    "the-cells-cut-ends-on-a-window-layer", "ends-on-a-window-layer", "ends-on-a-full-layer",
])
def test_the_last_layer_at_the_read_positions_answers_as_the_whole_layer(depth):
    """The program's last layer runs behind its keys and values at the read
    positions only (a window layer: each against the keys that end at it);
    the reference runs every layer whole."""
    config = tiny_config(depth=depth)
    assert config.layer_types[depth - 1] == (FULL if depth == 8 else SWA)
    model, weights = seeded(config)
    cat, num = rows(2 * PER + 1)
    served = model.apply(weights, cat, num, train=False)
    np.testing.assert_allclose(
        served, reference.logits(weights, cat, num, spec_of(config)), atol=1e-5
    )


@pytest.mark.parametrize("scope", [
    "swa_qkv", "swa_attend", "swa_o", "gqa_qkv", "gqa_attend", "gqa_o", "rope", "router",
    "moe_dispatch", "experts", "moe_combine", "shared_expert", "embed", "ffn", "head",
])
def test_lowered_chunk_program_holds_the_scope(tiny_bundle, scope):
    bundle, _ = tiny_bundle
    text = lowered_chunk(bundle).as_text(debug_info=True)
    assert f"/{scope}/" in text or f"/{scope}\"" in text, scope
    assert "short_conv" not in text and "mla_attend" not in text and "pallas" not in text
    if scope == "rope":  # the window layers turn, the full layers do not
        assert "swa_qkv/rope" in text and "gqa_qkv/rope" not in text


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
    assert loaded.model_config.layer_types == LLLG * 2  # a list on disk, a tuple again
    assert loaded.model_config.head_dim == 16
    for a, b in zip(leaves, jax.tree_util.tree_leaves(loaded.variables)):
        assert b.dtype == jnp.dtype("bfloat16")
        np.testing.assert_array_equal(np.asarray(a).view(np.uint16), np.asarray(b).view(np.uint16))
    np.testing.assert_array_equal(score(loaded, ds).predictions, score(bundle, ds).predictions)
    # nothing casts the tree: no parameter-shaped float32 copy in the program
    text = lowered_chunk(bundle).as_text()
    assert "tensor<1200x64xf32>" not in text and "tensor<16x64x24xf32>" not in text
    assert "tensor<64x128xf32>" not in text  # the query projection
    # against the float32 reference the bfloat16 program is near, not equal
    expected = reference.logits(bundle.variables, cat, num, spec_of(config))
    gap = np.abs(logit(score(bundle, ds).predictions) - np.asarray(expected))
    assert 1e-6 < gap.max() < 1.5 and np.sqrt((gap**2).mean()) < 0.5


# ------------------------------------------------- training, the commands
def test_gradients_are_finite_and_reach_both_kinds_of_layer():
    config = tiny_config(doc_records=2)  # S = 96: the window of 40 cuts it
    model, weights = seeded(config)
    cat, num = rows(8)
    labels = jnp.asarray(np.arange(8) % 2, jnp.float32)

    def loss(params):
        logits = model.apply({"params": params}, cat, num, train=False)
        return jnp.mean(jnp.logaddexp(0.0, logits) - labels * logits)

    value, grads = jax.jit(jax.value_and_grad(loss))(weights["params"])
    assert np.isfinite(float(value))
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree_util.tree_leaves(grads))
    for block, name in (("block_0", "gate"), ("block_1", "k"), ("block_2", "q_norm"),
                        ("block_3", "k"), ("block_3", "q_norm"), ("block_3", "experts_gate"),
                        ("block_4", "shared_up"), ("block_6", "experts_down"), ("block_7", "o")):
        leaf = jax.tree_util.tree_leaves(grads[block][name])[0]
        assert np.abs(np.asarray(leaf)).max() > 0, (block, name)


def test_score_batch_scores_an_exaone_moe_bundle(tmp_path, capsys):
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

"""Family ``nemotron_h``: a Nemotron-H-style hybrid decoder (a Mamba-2
mixer, routed ReLU² experts beside a shared one, or unturned grouped-query
attention, one kind a layer by the published pattern) as a token-level
history scorer. The program against the plain reference the benchmark
keeps (``benchmark/reference/nemotron_h.py``: the recurrence position by
position, the experts dense over the held ones) through ``score_dataset``,
uncut and as a share; ONE mixer layer's carried state; the expert shares
adding up to the uncut layer with the shared expert counted once; the
ReLU² grouped products, kernel (interpret mode) and XLA form, against a
dense loop; attention at a group of 16 with no rotation against a plain
softmax; the family's four faults told apart; the last layer of each
kind; padding; the routing counter; the bfloat16 bundle; the guards; the
configuration file; ``score-batch``. All on the CPU, seeded random
weights, tiny widths that keep every ratio (8 mixer heads a group, 16
query heads a key/value head, a scan chunk shorter than the history),
float32 unless a test says otherwise."""

import dataclasses
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_exaone_moe import masked_softmax  # the definition in float64, a window a mask

from benchmark import run
from benchmark.faults.nemotron_h import OWN_FAULTS
from benchmark.reference import nemotron_h as reference
from mlops_tpu.bundle.bundle import Bundle, load_bundle, save_bundle
from mlops_tpu.config import HISTORY_FAMILIES, ModelConfig
from mlops_tpu.data.encode import EncodedDataset, Preprocessor
from mlops_tpu.models import (
    BF16_PARAM_FAMILIES,
    FAMILIES,
    abstract_variables,
    build_model,
    init_params,
    nemotron_h,
)
from mlops_tpu.monitor.state import fit_monitor
from mlops_tpu.ops.causal_attention import causal_attend
from mlops_tpu.ops.expert_products import (
    Tiles,
    grouped_relu2_kernels,
    grouped_tiles,
    wants_grouped_kernel,
)
from mlops_tpu.ops.gqa_attention import _tiling, gqa_attend_blockwise, wants_gqa_kernel
from mlops_tpu.ops.moe_dispatch import segment_relu2_xla
from mlops_tpu.parallel.bulk import make_bulk_jit, score_dataset
from mlops_tpu.parallel.sharding import PARAM_RULES
from mlops_tpu.schema import SCHEMA

BENCHMARK = Path(__file__).resolve().parents[1] / "benchmark"
REAL = json.loads((BENCHMARK / "configs/nemotron-labs-twotower-30b-a3b.json").read_text())
SOURCE = REAL["source_config"]
DRIVER = run.load_module(BENCHMARK / "drivers/bulk_moe_ssm_token_histories.py")
PER = 3  # records a history in the bulk tests: S = 144 tokens = 4.5 scan chunks of 32


def tiny_config(**over) -> ModelConfig:
    """A hidden size of 64; 32 query heads over 2 key/value heads of 16 (a
    group of 16, as published; 32 x 16 is not 64); a mixer of 128 in 16
    heads of 8 over 2 groups (8 heads a group, as published) with a state
    of 16 and a scan chunk of 32; 16 routed experts of 24, 6 a token,
    beside a shared one of 48 (twice a routed one's, as published); the
    published pattern's first ``depth`` letters (MEMEM* at 6)."""
    fields = dict(
        family="nemotron_h", token_dim=64, depth=6, heads=32, kv_heads=2, head_dim=16,
        ssm_dim=128, ssm_heads=16, ssm_state=16, ssm_groups=2, ssm_chunk=32, conv_width=4,
        moe_ffn_dim=24, shared_ffn_dim=48, num_experts=16, experts_per_token=6,
        first_expert=0, experts_held=0, vocab_rows=1200, doc_records=PER,
        layer_types=nemotron_h.layer_types_of(SOURCE["hybrid_override_pattern"]),
        precision="f32", dropout=0.0,
    )
    return ModelConfig(**{**fields, **over})


def spec_of(config: ModelConfig) -> dict:
    """The configuration file's keys that the reference reads, for a tiny
    ``ModelConfig``; the source's constants are the real file's."""
    return {
        **{k: REAL[k] for k in (
            "layer_norm_epsilon", "routed_scaling_factor", "hybrid_override_pattern",
            "tokens_per_record", "record_vocab_size", "num_bins", "schema",
        )},
        "model_config": dataclasses.asdict(config),
        "records_per_history": config.doc_records,
    }


def rows(n, seed=0):
    rng = np.random.default_rng(seed)
    cat = np.stack([rng.integers(0, c, n) for c in SCHEMA.cards], 1).astype(np.int32)
    return cat, (1.5 * rng.normal(size=(n, SCHEMA.num_numeric))).astype(np.float32)


def initialised(config: ModelConfig, seed=7):
    """The model and ITS OWN initial parameters, the mixers' per-head leaves
    as the benchmark's driver sets them."""
    model = build_model(config)
    return model, DRIVER._ssm.state_space_leaves(init_params(model, jax.random.PRNGKey(seed)), seed)


def bundle_of(config: ModelConfig, ds: EncodedDataset) -> Bundle:
    model, weights = initialised(config)
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
    """A hand-made ``nemotron_h`` bundle and a file of five whole histories
    and one of two records."""
    cat, num = rows(5 * PER + 2)
    ds = EncodedDataset(cat, num)
    return bundle_of(tiny_config(), ds), ds


def score(bundle, ds, chunk_rows=2 * PER):
    return score_dataset(bundle, ds, chunk_rows=chunk_rows, exact=True, pipeline_depth=2)


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
    assert "nemotron_h" in FAMILIES and "nemotron_h" in HISTORY_FAMILIES
    assert "nemotron_h" in BF16_PARAM_FAMILIES
    history = ModelConfig(family="nemotron_h", doc_records=64)
    assert history.history_rows == 64 and not history.reads_documents


@pytest.mark.parametrize("over,match", [
    (dict(layer_types=("mamba", "moe", "conv", "moe", "mamba", "attention")), "layer_types"),
    (dict(layer_types=("mamba", "moe", "attention")), "layer_types"),
    (dict(layer_types=("mamba", "attention") * 3), "no expert layer"),
    (dict(first_expert=12, experts_held=8), "experts 12..20"),
    (dict(heads=31), "query heads"),
    (dict(ssm_heads=12), "state-space mixer"),
], ids=["unknown-kind", "short-list", "no-experts", "share-past-the-end", "heads", "mixer"])
def test_build_models_guards(over, match):
    config = tiny_config(**over)
    with pytest.raises(ValueError, match=match):
        abstract_variables(build_model(config))


def test_the_pattern_is_read_letter_by_letter():
    assert nemotron_h.layer_types_of("ME*") == ("mamba", "moe", "attention")
    with pytest.raises(ValueError, match="-"):
        nemotron_h.layer_types_of("M-M")


def test_the_real_configuration_is_the_published_widths():
    """Every published width as the catalog gives it; the layers and the
    experts held the two cuts `reduced` names; the pattern's first 13
    letters; the parameters the file counts."""
    mc = REAL["model_config"]
    assert REAL["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    for key, value in SOURCE.items():
        if key not in REAL["reduced"]:
            assert REAL[key] == value, key
    assert (REAL["num_hidden_layers"], REAL["n_routed_experts"]) == (13, 64)
    assert SOURCE["num_hidden_layers"] == 52 and SOURCE["n_routed_experts"] == 128
    assert mc["token_dim"] == SOURCE["hidden_size"] == 2688
    assert (mc["ssm_heads"], mc["ssm_dim"] // mc["ssm_heads"]) == (
        SOURCE["mamba_num_heads"], SOURCE["mamba_head_dim"]) == (64, 64)
    assert (mc["ssm_state"], mc["ssm_groups"], mc["ssm_chunk"], mc["conv_width"]) == (
        SOURCE["ssm_state_size"], SOURCE["n_groups"], SOURCE["chunk_size"], SOURCE["conv_kernel"])
    assert (mc["moe_ffn_dim"], mc["shared_ffn_dim"]) == (
        SOURCE["moe_intermediate_size"], SOURCE["moe_shared_expert_intermediate_size"]) == (1856, 3712)
    assert (mc["num_experts"], mc["experts_per_token"], mc["experts_held"], mc["first_expert"]) == (
        128, SOURCE["num_experts_per_tok"], 64, 0)
    assert (mc["heads"], mc["kv_heads"], mc["head_dim"]) == (
        SOURCE["num_attention_heads"], SOURCE["num_key_value_heads"], SOURCE["head_dim"])
    assert tuple(mc["layer_types"]) == nemotron_h.layer_types_of(
        SOURCE["hybrid_override_pattern"][:13])
    assert "".join(SOURCE["hybrid_override_pattern"][:13]) == "MEMEM*EMEMEM*"
    assert nemotron_h.ROUTED_SCALING == SOURCE["routed_scaling_factor"]
    assert mc["vocab_rows"] == SOURCE["vocab_size"] and mc["depth"] == 13
    counts = REAL["published_counts"]
    assert (counts["chips"], counts["pipeline_stages"], counts["chips_sharing_a_layer"]) == (8, 4, 2)
    fields = {**mc, "layer_types": tuple(mc["layer_types"]), "hidden_dims": tuple(mc["hidden_dims"])}
    tree = abstract_variables(build_model(ModelConfig(**fields)))
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree)) == 3_926_021_249
    assert "3,926,021,249" in REAL["parameter_arithmetic"]


def test_the_experts_alone_are_laid_over_the_expert_axis():
    """`parallel/sharding.py PARAM_RULES` reads the pattern's E layers by
    their leaves' names: the routed experts' two stacked matrices take the
    expert-parallel rule, nothing else does (the shared expert is whole on
    every chip)."""
    tree = abstract_variables(build_model(tiny_config()))["params"]
    ruled = {
        "/".join(str(k.key) for k in path)
        for path, _ in jax.tree_util.tree_leaves_with_path(tree)
        if re.search(PARAM_RULES[-1][0], "/".join(str(k.key) for k in path))
    }
    assert PARAM_RULES[-1][0] == r"experts_"
    assert ruled == {
        f"block_{i}/experts_{name}/kernel" for i in (1, 3) for name in ("up", "down")
    }


def test_the_per_head_leaves_are_the_mixers_alone():
    weights = init_params(build_model(tiny_config()), jax.random.PRNGKey(7))["params"]
    for i, kind in enumerate(tiny_config().layer_types[:6]):
        block = weights[f"block_{i}"]
        assert ("a_log" in block) == (kind == "mamba") and ("router" in block) == (kind == "moe")
        if kind == "mamba":
            np.testing.assert_allclose(block["a_log"]["bias"], np.log(np.arange(1, 17)), rtol=1e-6)
            np.testing.assert_array_equal(block["skip"]["scale"], np.ones(16))
        if kind == "attention":
            assert set(block) == {"norm", "q", "k", "v", "o"}  # no head norm, no bias
        if kind == "moe":
            assert set(block) == {
                "norm", "router", "experts_up", "experts_down", "shared_up", "shared_down"}


# ----------------------------------------------- the model and the bulk job
@pytest.mark.parametrize("held", [(0, 0), (8, 8)], ids=["uncut", "share"])
def test_score_dataset_matches_the_reference(held):
    """A file of five whole histories and a short last one of two records,
    in chunks of two histories: three runs, the last padded. To 1e-5: the
    program and the reference are float32 throughout and differ in the
    order of their sums only (the scan's chunks against the recurrence's
    positions, the grouped products' segments against a dense loop)."""
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
    # the counter, exactly, in the pattern's order of the two E layers (1, 3):
    # the job's runs were three chunks of two histories, the last history two
    # records and a padding row of zeros
    given_cat = np.concatenate([cat, np.zeros((1, 9), np.int32)])
    given_num = np.concatenate([num, np.zeros((1, 14), np.float32)])
    want = reference.held_assignments(
        reference.forward(bundle.variables, given_cat, given_num, spec)[1], spec
    )
    got = np.asarray(result.routing["per_layer"])
    assert got.shape == (2, count or 16)
    np.testing.assert_array_equal(got, want)
    assert result.routing["tokens"] == 6 * PER * 48
    if not count:  # all held: every choice of every token is counted
        assert (got.sum(axis=1) == 6 * PER * 48 * 6).all()


def test_a_historys_answers_do_not_depend_on_its_neighbours_or_its_padding(tiny_bundle):
    bundle, ds = tiny_bundle
    whole = score(bundle, ds).predictions
    short = EncodedDataset(ds.cat_ids[: 5 * PER + 1], ds.numeric[: 5 * PER + 1])
    np.testing.assert_allclose(score(bundle, short).predictions, whole[: 5 * PER + 1], atol=2e-6)
    # the history before another one altered: no state and no key crosses a start
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


@pytest.mark.parametrize("depth", [4, 5, 6, 13], ids=[
    "ends-on-an-expert-layer", "ends-on-a-mixer", "ends-on-attention",
    "the-cells-thirteen-layers",
])
def test_the_last_layer_at_the_read_positions_answers_as_the_whole_layer(depth):
    """The program's last layer, of each kind, answers at the read positions
    only (attention: keys and values at every position; the mixer: its
    state at every position; the experts: the read positions alone); the
    reference runs every layer whole."""
    config = tiny_config(depth=depth)
    model, weights = initialised(config)
    cat, num = rows(2 * PER + 1)
    served = model.apply(weights, cat, num, train=False)
    np.testing.assert_allclose(
        served, reference.logits(weights, cat, num, spec_of(config)), atol=1e-5
    )


# ------------------------------------------------------ the carried state
def test_one_mixer_layers_carried_state_is_the_references():
    """ONE mixer layer on a residual stream of two histories of 144
    positions: position 5, in chunk 0 of the scan, altered in the first
    history. Every later chunk's output moves (the state is the only way
    back past the convolution's three positions), by what the reference's
    recurrence says; the other history's not at all."""
    config = tiny_config()
    scorer = build_model(config)
    fields = [f.name for f in dataclasses.fields(nemotron_h.NemotronHBlock)
              if f.name not in ("parent", "name", "kind")]
    block = nemotron_h.NemotronHBlock(
        kind="mamba", **{name: getattr(scorer, name) for name in fields}
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 144, 64)), jnp.float32)
    params = block.init(jax.random.PRNGKey(3), x)["params"]
    params = DRIVER._ssm.state_space_leaves({"params": {"block_0": params}}, 3)["params"]["block_0"]
    dims = tuple(sorted(reference.sizes(spec_of(config)).items()))

    def both(stream):
        served = np.asarray(block.apply({"params": params}, stream))
        expected = np.stack([
            np.asarray(reference.layer(stream[i], params, dims=dims, kind="mamba",
                                       precision="f32")[0])
            for i in range(2)
        ])
        np.testing.assert_allclose(served, expected, atol=2e-6)
        return served, expected

    served, expected = both(x)
    moved_served, moved_expected = both(x.at[0, 5].add(3.0))
    got, want = moved_served - served, moved_expected - expected
    for start in (32, 64, 96, 128):  # chunks 1 to 4
        assert np.abs(want[0, start:start + 16]).max() > 1e-5, start
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_array_equal(got[0, :5], 0.0)  # causal
    np.testing.assert_array_equal(got[1], 0.0)  # the other history


@pytest.mark.parametrize("fault", sorted(OWN_FAULTS))
def test_each_of_the_familys_faults_disagrees_with_the_reference(fault, monkeypatch):
    """The four faults of `benchmark/faults/nemotron_h.py`, planted under the
    program, the reference sound: each moves an answer by over 30 times
    what the sound program reads (float32: 1e-5 at most)."""
    config = tiny_config()
    model, weights = initialised(config)
    cat, num = rows(2 * PER)
    expected = np.asarray(reference.logits(weights, cat, num, spec_of(config)))
    np.testing.assert_allclose(model.apply(weights, cat, num, train=False), expected, atol=1e-5)
    OWN_FAULTS[fault](monkeypatch)
    moved = np.asarray(model.apply(weights, cat, num, train=False))
    assert np.abs(moved - expected).max() > 3e-4, fault


# ----------------------------------------------------- the experts' share
def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    """The guide's share test: experts 0..7 and 8..15 as two chips' shares,
    the shared expert (which every chip computes alike) and the residual
    counted once, add up to what the uncut reference gives for the whole
    layer."""
    scorer = build_model(tiny_config())
    fields = [f.name for f in dataclasses.fields(nemotron_h.NemotronHBlock)
              if f.name not in ("parent", "name", "kind", "first_expert", "experts_held")]

    def layer(first, held):
        return nemotron_h.NemotronHBlock(
            kind="moe", first_expert=first, experts_held=held,
            **{name: getattr(scorer, name) for name in fields},
        )

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, 150, 64)), jnp.float32)
    p = layer(0, 16).init(jax.random.PRNGKey(5), x)["params"]

    def part(first, held):
        cut = {
            name: {"kernel": leaf["kernel"][first : first + held]} if name.startswith("experts_")
            else leaf
            for name, leaf in p.items()
        }
        return np.asarray(layer(first, held).apply({"params": cut}, x))

    dims = tuple(sorted(reference.sizes(spec_of(tiny_config(experts_held=16))).items()))
    want, _, _ = reference.layer(x[0], p, dims=dims, kind="moe", precision="f32")
    h = reference.rms_norm(x[0], p["norm"]["scale"], 1e-5)
    shared = reference.relu2_expert(h, p["shared_up"]["kernel"], p["shared_down"]["kernel"], "f32")
    low, high, uncut = part(0, 8)[0], part(8, 8)[0], part(0, 16)[0]
    np.testing.assert_allclose(uncut, want, atol=3e-5)
    np.testing.assert_allclose(low + high - x[0] - shared, want, atol=3e-5)  # shared once
    assert float(jnp.abs(shared).max()) > 0.05
    assert np.abs(low - x[0] - shared).max() > 0.05 and np.abs(uncut - low).max() > 0.05


# -------------------------------------------------- the grouped ReLU² products
@pytest.mark.parametrize("f,columns", [(96, 96), (256, 128)], ids=["f-taken-whole", "f-in-lane-tiles"])
def test_the_relu2_grouped_products_are_a_dense_loop(f, columns):
    """One segment of 256 rows over 4 experts (70, 0, 100 and 50 rows: the
    last 36 belong to none), the kernels in interpret mode and the XLA form,
    against each row's own expert's ``down(relu(x up)^2)``."""
    rng = np.random.default_rng(f)
    rows_, held, d = 256, 4, 128
    sizes = np.array([70, 0, 100, 50], np.int32)
    x = rng.normal(size=(rows_, d)).astype(np.float32)
    up = (rng.normal(size=(held, d, f)) * d**-0.5).astype(np.float32)
    down = (rng.normal(size=(held, f, d)) * f**-0.5).astype(np.float32)
    owner = np.repeat(np.arange(held), sizes)
    want = np.stack([
        (np.maximum(x[r] @ up[e], 0.0) ** 2) @ down[e] for r, e in enumerate(owner)
    ])
    real = sizes.sum()
    xla = segment_relu2_xla(jnp.asarray(x), jnp.asarray(up), jnp.asarray(down), jnp.asarray(sizes))
    np.testing.assert_allclose(np.asarray(xla)[:real], want, rtol=1e-5, atol=1e-5)
    kernel = grouped_relu2_kernels(
        jnp.asarray(x), jnp.asarray(up), jnp.asarray(down), jnp.asarray(sizes),
        tiles=Tiles(128, columns, 128), interpret=True,
    )
    np.testing.assert_allclose(np.asarray(kernel)[:real], want, rtol=1e-5, atol=1e-5)


def test_the_relu2_tiling_takes_an_expert_width_of_half_a_lane_tile_whole():
    """Nemotron's 1,856 is 14.5 lane tiles: the ReLU² kernels take it as one
    block; the SwiGLU's rule is as it was (no such width)."""
    segment = (6 * 256 * 48, 64, 2688, 1856)  # a chunk of 4 histories, 6 of a token's choices
    assert wants_grouped_kernel(*segment, gated=False)
    assert grouped_tiles(*segment, gated=False) == Tiles(256, 1856, 2688)
    assert not wants_grouped_kernel(*segment)  # the SwiGLU needs whole lane tiles
    assert grouped_tiles(512, 4, 128, 96, gated=False) == Tiles(128, 96, 128)
    assert not wants_grouped_kernel(512, 4, 128, 100, gated=False)  # no whole sublanes
    assert not wants_grouped_kernel(600, 4, 32, 24, gated=False)  # the tiny models'


# ------------------------------------------- attention at a group of 16
def test_the_tiling_admits_the_published_group_of_sixteen():
    """32 query heads over 2 key/value heads of 128: 16 lane tiles a group;
    at 3,072 keys one a step (512 rows against 3,072 keys are the scores
    VMEM holds)."""
    assert _tiling(3072, 32, 2, 128, None) == (None, 512, 1)
    assert _tiling(512, 32, 2, 128, None) == (None, 512, 8)
    assert _tiling(1024, 32, 2, 128, None) == (None, 512, 4)
    assert wants_gqa_kernel(3072, 32, 2, 128) and not wants_gqa_kernel(3000, 32, 2, 128)


@pytest.mark.parametrize("seq", [512, 1024], ids=["eight-tiles-a-step", "four-tiles-a-step"])
def test_attention_at_thirty_two_over_two_heads_of_128_matches_a_plain_softmax(seq):
    """No rotation anywhere: the operands go in as projected."""
    rng = np.random.default_rng(seq)
    q = rng.normal(size=(1, seq, 32, 128)).astype(np.float32)
    k = rng.normal(size=(1, seq, 2, 128)).astype(np.float32)
    v = rng.normal(size=(1, seq, 2, 128)).astype(np.float32)
    want = masked_softmax(q, k, v, 128**-0.5, window=seq)  # every key so far
    xla = causal_attend(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 128**-0.5)
    np.testing.assert_allclose(xla, want, atol=5e-6)
    kernel = gqa_attend_blockwise(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 128**-0.5, interpret=True
    )
    np.testing.assert_allclose(kernel, want, atol=5e-6)
    read = np.array([47, 95, seq - 1])
    some = causal_attend(jnp.asarray(q[:, read]), jnp.asarray(k), jnp.asarray(v), 128**-0.5,
                         read=read)
    np.testing.assert_allclose(some, want[:, read], atol=5e-6)


# ------------------------------------------------------------- the scopes
@pytest.mark.parametrize("scope", [
    "mamba_mixer", "moe_layer", "attention_layer", "ssm_in", "ssm_conv", "ssm_scan",
    "ssm_out", "router", "moe_dispatch", "relu2_experts", "moe_combine", "shared_expert",
    "gqa_qkv", "gqa_attend", "gqa_o", "embed", "head",
])
def test_lowered_chunk_program_holds_the_scope(tiny_bundle, scope):
    bundle, _ = tiny_bundle
    text = lowered_chunk(bundle).as_text(debug_info=True)
    assert f"/{scope}/" in text or f"/{scope}\"" in text, scope
    # no rotation, no SwiGLU experts, no other family's mixer
    assert "/rope/" not in text and "/experts/" not in text and "/short_conv/" not in text
    assert "pallas" not in text


@pytest.mark.parametrize("outer,inner", [
    ("mamba_mixer", "ssm_scan"), ("moe_layer", "relu2_experts"), ("moe_layer", "shared_expert"),
    ("moe_layer", "router"), ("attention_layer", "gqa_attend"),
])
def test_each_kinds_scope_holds_its_own_parts(tiny_bundle, outer, inner):
    bundle, _ = tiny_bundle
    names = re.findall(r'"([^"]*)"', lowered_chunk(bundle).as_text(debug_info=True))
    paths = [p for p in (name.split("/") for name in names) if inner in p and len(p) > 1]
    assert paths and all(outer in path[: path.index(inner)] for path in paths), (outer, inner)


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
    assert loaded.model_config.layer_types == config.layer_types  # a list on disk, a tuple again
    assert loaded.model_config.shared_ffn_dim == 48
    for a, b in zip(leaves, jax.tree_util.tree_leaves(loaded.variables)):
        np.testing.assert_array_equal(np.asarray(a).view(np.uint16), np.asarray(b).view(np.uint16))
    np.testing.assert_array_equal(score(loaded, ds).predictions, score(bundle, ds).predictions)
    # nothing casts the tree: no parameter-shaped float32 copy in the program
    text = lowered_chunk(bundle).as_text()
    assert "tensor<1200x64xf32>" not in text and "tensor<16x64x24xf32>" not in text
    # against the float32 reference the bfloat16 program is near, not equal
    expected = reference.logits(bundle.variables, cat, num, spec_of(config))
    gap = np.abs(logit(score(bundle, ds).predictions) - np.asarray(expected))
    assert 1e-6 < gap.max() < 1.5 and np.sqrt((gap**2).mean()) < 0.5


# ------------------------------------------------- training, the commands
def test_gradients_are_finite_and_reach_every_kind_of_layer():
    config = tiny_config(doc_records=2)
    model, weights = initialised(config)
    cat, num = rows(8)
    labels = jnp.asarray(np.arange(8) % 2, jnp.float32)

    def loss(params):
        logits = model.apply({"params": params}, cat, num, train=False)
        return jnp.mean(jnp.logaddexp(0.0, logits) - labels * logits)

    value, grads = jax.jit(jax.value_and_grad(loss))(weights["params"])
    assert np.isfinite(float(value))
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree_util.tree_leaves(grads))
    for block, name in (("block_0", "in_proj"), ("block_0", "a_log"), ("block_1", "experts_up"),
                        ("block_1", "experts_down"), ("block_3", "shared_up"),
                        ("block_4", "conv"), ("block_5", "k"), ("block_5", "o")):
        leaf = jax.tree_util.tree_leaves(grads[block][name])[0]
        assert np.abs(np.asarray(leaf)).max() > 0, (block, name)


def test_score_batch_scores_a_nemotron_h_bundle(tmp_path, capsys):
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
    assert summary["routing"]["tokens"] > 0  # the two E layers' counter

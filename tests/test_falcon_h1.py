"""Family ``falcon_h1`` (ISSUE 39): a Falcon-H1-style hybrid decoder as a
token-level history scorer. The program against the plain reference the
benchmark keeps (``benchmark/reference/falcon_h1.py``: the recurrence,
position by position; the harness finds it there, it is not copied)
through ``score_dataset`` with a padded tail; the state carried across
the scan's chunks and seen by the comparison; every muP multiplier seen;
the last layer's ``read`` form; padding and neighbours; the bfloat16
bundle; the guards; the commands; and `gqa_attend` at the published group
of five. All on the CPU, the model's OWN initialisers (which follow
Mamba-2's for the three per-head leaves) with the per-head leaves then
set as the benchmark's driver sets them (the same three, from the
driver's seed), tiny widths that keep every ratio, float32 unless a test
says otherwise."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_exaone_moe import masked_softmax  # the definition in float64, a window a mask

from benchmark import run
from benchmark.faults.falcon_h1 import state_not_carried
from benchmark.reference import falcon_h1 as reference
from mlops_tpu.bundle.bundle import Bundle, load_bundle, save_bundle
from mlops_tpu.config import HISTORY_FAMILIES, ModelConfig
from mlops_tpu.data.encode import EncodedDataset, Preprocessor
from mlops_tpu.models import (
    BF16_PARAM_FAMILIES,
    FAMILIES,
    abstract_variables,
    build_model,
    falcon_h1,
    init_params,
)
from mlops_tpu.monitor.state import fit_monitor
from mlops_tpu.ops.causal_attention import causal_attend
from mlops_tpu.ops.gqa_attention import _tiling, gqa_attend_blockwise, wants_gqa_kernel
from mlops_tpu.parallel.bulk import make_bulk_jit, score_dataset
from mlops_tpu.schema import SCHEMA

BENCHMARK = Path(__file__).resolve().parents[1] / "benchmark"
REAL = json.loads((BENCHMARK / "configs/falcon-h1-34b.json").read_text())
DRIVER = run.load_module(BENCHMARK / "drivers/bulk_ssm_token_histories.py")
SOURCE = REAL["source_config"]
PER = 3  # records a history in the bulk tests: S = 144 tokens = 4.5 scan chunks of 32
MULTIPLIERS = {  # the published ones, which the tiny model keeps
    name: SOURCE[name] for name in (
        "embedding_multiplier", "attention_in_multiplier", "attention_out_multiplier",
        "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
    )
}


def tiny_config(**over) -> ModelConfig:
    """10 query heads over 2 key/value heads of 16 in a hidden size of 64
    (a group of FIVE; 10 x 16 is not 64), a mixer of 96 in 6 heads of 16
    over 2 groups with a state of 24 and a scan chunk of 32, an MLP of
    160, every multiplier as published."""
    fields = dict(
        family="falcon_h1", token_dim=64, depth=3, heads=10, kv_heads=2, head_dim=16,
        ffn_dim=160, ssm_dim=96, ssm_heads=6, ssm_state=24, ssm_groups=2, ssm_chunk=32,
        conv_width=4, vocab_rows=1200, doc_records=PER, rope_theta=SOURCE["rope_theta"],
        precision="f32", dropout=0.0, **MULTIPLIERS,
        ssm_multipliers=tuple(SOURCE["ssm_multipliers"]),
        mlp_multipliers=tuple(SOURCE["mlp_multipliers"]),
    )
    return ModelConfig(**{**fields, **over})


def spec_of(config: ModelConfig) -> dict:
    """The configuration file's keys that the reference reads, for a tiny
    ``ModelConfig``; the source's constants are the real file's."""
    return {
        **{k: REAL[k] for k in (
            "rms_norm_eps", "tokens_per_record", "record_vocab_size", "num_bins", "schema",
        )},
        "model_config": dataclasses.asdict(config),
        "records_per_history": config.doc_records,
    }


def rows(n, seed=0):
    rng = np.random.default_rng(seed)
    cat = np.stack([rng.integers(0, c, n) for c in SCHEMA.cards], 1).astype(np.int32)
    return cat, (1.5 * rng.normal(size=(n, SCHEMA.num_numeric))).astype(np.float32)


def initialised(config: ModelConfig, seed=7):
    """The model and ITS OWN initial parameters, the per-head leaves as the
    benchmark's driver sets them."""
    model = build_model(config)
    return model, DRIVER.state_space_leaves(init_params(model, jax.random.PRNGKey(seed)), seed)


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
    """A hand-made ``falcon_h1`` bundle and a file of five whole histories
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
    assert "falcon_h1" in FAMILIES and "falcon_h1" in HISTORY_FAMILIES
    assert "falcon_h1" in BF16_PARAM_FAMILIES
    history = ModelConfig(family="falcon_h1", doc_records=64)
    assert (history.reads_documents, history.history_rows) == (False, 64)
    assert not history.uses_layout_trainer
    assert build_model(tiny_config(param_dtype="bf16")).param_dtype == jnp.bfloat16
    model = build_model(tiny_config())
    assert not hasattr(model, "routing_collection")  # no router: two outputs a chunk


@pytest.mark.parametrize("over,match", [
    (dict(kv_heads=3), "10 query heads over 3"),
    (dict(head_dim=15), "key/value heads of 15"),
    (dict(ssm_heads=5), "a state-space mixer of 96 in 5 heads"),
    (dict(ssm_groups=4), "6 heads over 4 groups"),
    (dict(ssm_multipliers=(1.0, 1.0)), "2 ssm_multipliers"),
    (dict(mlp_multipliers=(1.0,)), "1 mlp_multipliers"),
    (dict(vocab_rows=500), "500 embedding rows"),
], ids=["ragged-groups", "odd-head", "ragged-mixer-heads", "ragged-state-groups",
        "too-few-ssm-multipliers", "too-few-mlp-multipliers", "too-few-rows"])
def test_build_models_guards(over, match):
    model = build_model(tiny_config(**over))
    with pytest.raises(ValueError, match=match):
        abstract_variables(model)


def test_the_real_configuration_is_the_published_widths():
    mc = REAL["model_config"]
    model = build_model(ModelConfig(**{
        **mc, "hidden_dims": tuple(mc["hidden_dims"]),
        "ssm_multipliers": tuple(mc["ssm_multipliers"]),
        "mlp_multipliers": tuple(mc["mlp_multipliers"]),
    }))
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():  # the catalog row's config, key for key
        row = next(
            json.loads(line) for line in catalog.read_text().splitlines()
            if json.loads(line)["name"] == "Falcon-H1-34B-Instruct"
        )
        assert SOURCE == row["config"] and REAL["source"] == row["source_url"]
    assert (model.hidden, model.heads, model.kv_heads, model.head_dim, model.ffn_dim) == (
        SOURCE["hidden_size"], SOURCE["num_attention_heads"], SOURCE["num_key_value_heads"],
        SOURCE["head_dim"], SOURCE["intermediate_size"],
    ) == (5120, 20, 4, 128, 21504)
    assert (model.ssm_dim, model.ssm_heads, model.ssm_state, model.ssm_groups) == (
        SOURCE["mamba_d_ssm"], SOURCE["mamba_n_heads"], SOURCE["mamba_d_state"],
        SOURCE["mamba_n_groups"],
    ) == (4096, 32, 256, 2)
    assert model.ssm_dim // model.ssm_heads == SOURCE["mamba_d_head"] == 128
    assert (model.ssm_chunk, model.conv_width) == (
        SOURCE["mamba_chunk_size"], SOURCE["mamba_d_conv"]) == (128, 4)
    assert model.rope_theta == SOURCE["rope_theta"] == 1e11
    for name in MULTIPLIERS:
        assert getattr(model, name) == SOURCE[name], name
    assert list(model.ssm_multipliers) == SOURCE["ssm_multipliers"]
    assert list(model.mlp_multipliers) == SOURCE["mlp_multipliers"]
    assert SOURCE["mamba_rms_norm"] and not SOURCE["mamba_norm_before_gate"]
    assert SOURCE["mamba_conv_bias"] and not SOURCE["mamba_proj_bias"] and not SOURCE["mlp_bias"]
    assert falcon_h1.RMS_EPS == SOURCE["rms_norm_eps"] == REAL["rms_norm_eps"]
    # ONE cut: the depth; every width and the whole vocabulary as published
    assert REAL["reduced"] == ["num_hidden_layers"]
    assert {k: REAL[k] for k in SOURCE} == {**SOURCE, "num_hidden_layers": 6}
    assert (model.depth, model.vocab_rows) == (6, SOURCE["vocab_size"]) == (6, 261120)
    assert SOURCE["num_hidden_layers"] == 72 == 12 * model.depth  # 12 stages of 6
    shapes = abstract_variables(model)["params"]
    sizes = jax.tree_util.tree_map(lambda leaf: leaf.size, shapes)
    count = lambda tree: sum(jax.tree_util.tree_leaves(tree))  # noqa: E731
    # the configuration file's arithmetic, to the parameter
    mixer = 5120 * 9248 + 4096 * 5120 + (4 * 5120 + 5120) + 3 * 32 + 4096
    attention = 2 * 5120 * 2560 + 2 * 5120 * 512
    assert (mixer, attention) == (68_351_072, 31_457_280)
    layer = mixer + attention + 3 * 5120 * 21504 + 2 * 5120
    assert layer == 430_120_032
    assert all(count(sizes[f"block_{i}"]) == layer for i in range(6))
    assert count(sizes["tok_embed"]) == 261120 * 5120 == 1_336_934_400
    assert count(sizes) == 3_917_664_833  # 7.84 GB at 2 bytes
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(shapes)} == {jnp.dtype("bfloat16")}
    assert set(shapes["block_0"]) == {
        "input_norm", "in_proj", "conv", "dt_bias", "a_log", "skip", "ssm_norm", "out_proj",
        "q", "k", "v", "o", "ffn_norm", "gate", "up", "down",
    }  # no q_norm, no k_norm: the heads are not normed
    assert shapes["block_3"]["in_proj"]["kernel"].shape == (5120, 9248)  # z | x | B | C | dt
    assert shapes["block_3"]["conv"]["kernel"].shape == (4, 5120)
    assert shapes["block_3"]["conv"]["bias"].shape == (5120,)
    assert shapes["block_3"]["ssm_norm"]["scale"].shape == (4096,)
    assert shapes["block_3"]["out_proj"]["kernel"].shape == (4096, 5120)
    assert shapes["block_3"]["q"]["kernel"].shape == (5120, 2560)
    assert shapes["block_3"]["k"]["kernel"].shape == (5120, 512)
    assert shapes["block_3"]["o"]["kernel"].shape == (2560, 5120)
    assert shapes["block_3"]["gate"]["kernel"].shape == (5120, 21504)
    # every leaf ends in a name `benchmark/inputs.py make_weights` has a rule for
    names = {str(path[-1].key) for path, _ in jax.tree_util.tree_leaves_with_path(shapes)}
    assert names == {"kernel", "embedding", "scale", "bias"}


def test_the_per_head_leaves_are_initialised_as_mamba_2s():
    weights = init_params(build_model(tiny_config()), jax.random.PRNGKey(7))
    for i in range(3):
        block = weights["params"][f"block_{i}"]
        np.testing.assert_allclose(block["a_log"]["bias"], np.log(np.arange(1, 7)), rtol=1e-6)
        np.testing.assert_array_equal(block["skip"]["scale"], np.ones(6))
        dt = np.asarray(jax.nn.softplus(block["dt_bias"]["bias"]))
        assert (dt >= 0.001 * (1 - 1e-4)).all() and (dt <= 0.1 * (1 + 1e-4)).all()
    first, second = (np.asarray(weights["params"][f"block_{i}"]["dt_bias"]["bias"]) for i in (0, 1))
    assert np.abs(first - second).max() > 0  # a draw a layer


def test_the_driver_sets_the_per_head_leaves_so_that_the_state_is_seen():
    """`benchmark/drivers/bulk_ssm_token_histories.py state_space_leaves`:
    A_log, dt and the skip D = 1 as Mamba-2's, from the seed, a draw a
    layer; every other leaf as it was."""
    plain = init_params(build_model(tiny_config()), jax.random.PRNGKey(7))
    for tree in (plain, jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.bfloat16), plain)):
        out = DRIVER.state_space_leaves(tree, 3_000_000_017)["params"]
        again = DRIVER.state_space_leaves(tree, 3_000_000_017)["params"]
        other = DRIVER.state_space_leaves(tree, 3_000_000_018)["params"]
        for i in range(3):
            block, was = out[f"block_{i}"], tree["params"][f"block_{i}"]
            assert block["a_log"]["bias"].dtype == was["a_log"]["bias"].dtype
            np.testing.assert_allclose(
                np.asarray(block["a_log"]["bias"], np.float32), np.log(np.arange(1, 7)), rtol=4e-3)
            np.testing.assert_array_equal(np.asarray(block["skip"]["scale"], np.float32), 1.0)
            dt = np.asarray(jax.nn.softplus(block["dt_bias"]["bias"].astype(jnp.float32)))
            assert (dt > 0.00099).all() and (dt < 0.101).all()
            np.testing.assert_array_equal(
                np.asarray(block["dt_bias"]["bias"], np.float32),
                np.asarray(again[f"block_{i}"]["dt_bias"]["bias"], np.float32))
            assert (np.asarray(block["dt_bias"]["bias"], np.float32)
                    != np.asarray(other[f"block_{i}"]["dt_bias"]["bias"], np.float32)).any()
            assert block["in_proj"] is was["in_proj"] and block["conv"] is was["conv"]
        assert out["tok_embed"] is tree["params"]["tok_embed"]
        first, second = (np.asarray(out[f"block_{i}"]["dt_bias"]["bias"], np.float32) for i in (0, 1))
        assert (first != second).any()


# ----------------------------------------------- the model and the bulk job
def test_score_dataset_matches_the_reference_with_a_padded_tail(tiny_bundle):
    """A file of five whole histories and a short last one of two records,
    in chunks of two histories: three runs, the last padded. To 1e-5: the
    program and the reference are float32 throughout and differ in the
    order of their sums only (the scan's chunks against the recurrence's
    positions, the blocks of queries)."""
    bundle, ds = tiny_bundle
    result = score(bundle, ds)
    expected = reference.forward(bundle.variables, ds.cat_ids, ds.numeric, spec_of(tiny_config()))
    np.testing.assert_allclose(logit(result.predictions), np.asarray(expected), atol=1e-5)
    assert np.abs(np.asarray(expected)).max() > 0.05
    assert result.routing is None and "routing" not in result.summary()


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


@pytest.mark.parametrize("depth", [1, 2, 4], ids=lambda d: f"depth-{d}")
def test_the_last_layer_at_the_read_positions_answers_as_the_whole_layer(depth):
    """The program's last layer moves its state and writes its keys and
    values at every position and answers at the read positions only; the
    reference runs every layer whole."""
    config = tiny_config(depth=depth)
    model, weights = initialised(config)
    cat, num = rows(2 * PER + 1)
    served = model.apply(weights, cat, num, train=False)
    np.testing.assert_allclose(
        served, reference.forward(weights, cat, num, spec_of(config)), atol=1e-5
    )


@pytest.mark.parametrize("chunk", [8, 32, 48, 128, 256], ids=lambda c: f"scan-chunk-{c}")
def test_the_scans_chunk_changes_no_answer(chunk):
    """144 positions in chunks of 8 (18 of them), of a record, of 128 (one
    and a ragged second) and of more than the history."""
    config = tiny_config(ssm_chunk=chunk)
    model, weights = initialised(config)
    cat, num = rows(PER + 2)
    np.testing.assert_allclose(
        model.apply(weights, cat, num, train=False),
        reference.forward(weights, cat, num, spec_of(config)), atol=1e-5,
    )


# ------------------------------------------------------ the carried state
def block_of(config: ModelConfig) -> falcon_h1.FalconH1Block:
    """ONE layer of the scorer ``config`` builds."""
    scorer = build_model(config)
    fields = [f.name for f in dataclasses.fields(falcon_h1.FalconH1Block)
              if f.name not in ("parent", "name")]
    return falcon_h1.FalconH1Block(**{name: getattr(scorer, name) for name in fields})


def test_a_token_of_the_first_chunk_moves_later_chunks_answers_as_the_reference_says():
    """ONE layer on a residual stream of two histories of 144 positions, the
    attention taken out (the state is the only way back past the
    convolution's three positions): position 5, in chunk 0 of the scan,
    altered in the first history. Every later chunk's output moves, by what
    the reference's recurrence says (float32: the two agree to 3e-8 where
    the least of these moves is 2e-6); the other history's not at all."""
    config = tiny_config(attention_out_multiplier=0.0)
    block = block_of(config)
    rng = np.random.default_rng(0)
    x = jnp.asarray(0.08 * rng.normal(size=(2, 144, 64)), jnp.float32)
    params = block.init(jax.random.PRNGKey(3), x)["params"]
    params = DRIVER.state_space_leaves({"params": {"block_0": params}}, 3)["params"]["block_0"]
    dims = tuple(sorted(reference.sizes(spec_of(config)).items()))

    def both(stream):
        served = np.asarray(block.apply({"params": params}, stream))
        expected = np.stack([
            np.asarray(reference.layer(stream[i], params, dims=dims, precision="f32"))
            for i in range(2)
        ])
        np.testing.assert_allclose(served, expected, atol=5e-7)
        return served, expected

    served, expected = both(x)
    moved_served, moved_expected = both(x.at[0, 5].add(0.3))
    got, want = moved_served - served, moved_expected - expected
    for start in (32, 64, 96, 128):  # chunks 1 to 4
        assert np.abs(want[0, start:start + 16]).max() > 1e-6, start
    np.testing.assert_allclose(got, want, atol=2e-7)
    np.testing.assert_array_equal(got[0, :5], 0.0)  # causal
    np.testing.assert_array_equal(got[1], 0.0)  # the other history


def test_a_scan_whose_chunks_start_from_a_zero_state_disagrees_with_the_reference(monkeypatch):
    """The hand-over dropped: float32 at this size sees it; the cell's
    bfloat16 limits at the published widths do not (its ``blind_spots``)."""
    config = tiny_config()
    model, weights = initialised(config)
    cat, num = rows(2 * PER)
    expected = np.asarray(reference.forward(weights, cat, num, spec_of(config)))
    np.testing.assert_allclose(model.apply(weights, cat, num, train=False), expected, atol=1e-5)
    state_not_carried(monkeypatch)  # every chunk scanned as a history of its own
    moved = np.asarray(model.apply(weights, cat, num, train=False))
    # float32: the sound program is within 1e-5 of the reference (3e-7 in fact)
    assert np.abs(moved - expected).max() > 3e-4
    # the first record is read at position 47, in chunk 1: its answer too has moved
    assert np.abs(moved[0] - expected[0]) > 1e-4


# -------------------------------------------------------- the multipliers
def _unit(name, index=None):
    if index is None:
        # attention_in_multiplier is published as 1: the test moves it to 1/2
        return {name: 0.5 if MULTIPLIERS.get(name) == 1 else 1.0}
    values = list(SOURCE[name])
    values[index] = 1.0
    return {name: tuple(values)}


@pytest.mark.parametrize("over", [
    *(_unit(name) for name in MULTIPLIERS),
    *(_unit("ssm_multipliers", i) for i in range(5)),
    *(_unit("mlp_multipliers", i) for i in range(2)),
], ids=[*MULTIPLIERS, "ssm-z", "ssm-x", "ssm-B", "ssm-C", "ssm-dt", "mlp-gate", "mlp-output"])
def test_every_multiplier_is_seen_by_the_comparison(over):
    """A model built with ONE multiplier off its published value disagrees
    with the reference, which reads the published one."""
    config = tiny_config()
    _, weights = initialised(config)
    cat, num = rows(2 * PER)
    expected = np.asarray(reference.forward(weights, cat, num, spec_of(config)))
    served = build_model(tiny_config(**over)).apply(weights, cat, num, train=False)
    # the sound one reads 3e-7; the least of these, the dt column's (it scales the
    # state's part alone, which sits beside the skip D = 1), reads 1.9e-5
    assert np.abs(np.asarray(served) - expected).max() > 1e-5, over
    # and the reference reads the same field: handed the altered one it agrees
    np.testing.assert_allclose(
        served, reference.forward(weights, cat, num, spec_of(tiny_config(**over))), atol=1e-5
    )


def test_the_gate_comes_before_the_norm(monkeypatch):
    """``mamba_norm_before_gate: false``: with the norm first and the gate
    after it the program disagrees with the reference."""
    config = tiny_config()
    model, weights = initialised(config)
    cat, num = rows(2 * PER)
    expected = np.asarray(reference.forward(weights, cat, num, spec_of(config)))
    np.testing.assert_allclose(model.apply(weights, cat, num, train=False), expected, atol=1e-5)
    real = falcon_h1._GatedGroupNorm.__call__

    def norm_then_gate(self, y, z):
        # a gate of silu(1e4) = 1e4 everywhere leaves the norm's input y but for a scale
        return real(self, y, jnp.full_like(z, 1e4)) * jax.nn.silu(z)

    monkeypatch.setattr(falcon_h1._GatedGroupNorm, "__call__", norm_then_gate)
    moved = model.apply(weights, cat, num, train=False)
    assert np.abs(np.asarray(moved) - expected).max() > 1e-3


# ------------------------------------------------------------- the scopes
@pytest.mark.parametrize("scope", [
    "ssm_in", "ssm_conv", "ssm_scan", "ssm_out", "gqa_qkv", "gqa_attend", "gqa_o", "rope",
    "embed", "ffn", "head",
])
def test_lowered_chunk_program_holds_the_scope(tiny_bundle, scope):
    bundle, _ = tiny_bundle
    text = lowered_chunk(bundle).as_text(debug_info=True)
    assert f"/{scope}/" in text or f"/{scope}\"" in text, scope
    assert "/short_conv/" not in text and "swa_attend" not in text and "/router/" not in text
    assert "pallas" not in text
    if scope == "rope":  # every layer turns its queries and keys
        assert "gqa_qkv/rope" in text


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
    assert loaded.model_config.ssm_multipliers == tuple(SOURCE["ssm_multipliers"])  # a tuple again
    for a, b in zip(leaves, jax.tree_util.tree_leaves(loaded.variables)):
        assert b.dtype == jnp.dtype("bfloat16")
        np.testing.assert_array_equal(np.asarray(a).view(np.uint16), np.asarray(b).view(np.uint16))
    np.testing.assert_array_equal(score(loaded, ds).predictions, score(bundle, ds).predictions)
    # nothing casts the tree: no parameter-shaped float32 copy in the program
    text = lowered_chunk(bundle).as_text()
    assert "tensor<1200x64xf32>" not in text and "tensor<64x294xf32>" not in text
    assert "tensor<64x160xf32>" not in text  # the query projection, the MLP's gate
    # against the float32 reference the bfloat16 program is near, not equal
    expected = reference.forward(bundle.variables, cat, num, spec_of(config))
    gap = np.abs(logit(score(bundle, ds).predictions) - np.asarray(expected))
    assert 1e-6 < gap.max() < 0.5 and np.sqrt((gap**2).mean()) < 0.2


# ------------------------------------------------- training, the commands
def test_gradients_are_finite_and_reach_both_mixers():
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
    for block, name in (("block_0", "in_proj"), ("block_0", "conv"), ("block_0", "a_log"),
                        ("block_0", "dt_bias"), ("block_1", "skip"), ("block_1", "ssm_norm"),
                        ("block_1", "out_proj"), ("block_0", "k"), ("block_1", "q"),
                        ("block_2", "o"), ("block_2", "gate"), ("block_2", "a_log")):
        leaf = jax.tree_util.tree_leaves(grads[block][name])[0]
        assert np.abs(np.asarray(leaf)).max() > 0, (block, name)


def test_score_batch_scores_a_falcon_h1_bundle(tmp_path, capsys):
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
    assert "routing" not in summary
    scored = np.load(tmp_path / "out.npz")["predictions"]
    assert scored.shape == (37,) and np.isfinite(scored).all()


# ------------------------------------------- attention at a group of five
def test_the_tiling_admits_the_published_group_of_five():
    """20 query heads over 4 key/value heads of 128: five lane tiles a
    group, which divide into steps of five or of one; at 3,072 keys one
    (512 rows against 3,072 keys are the scores VMEM holds)."""
    assert _tiling(3072, 20, 4, 128, None) == (None, 512, 1)
    assert _tiling(512, 20, 4, 128, None) == (None, 512, 5)  # a short history: all five
    assert _tiling(1024, 20, 4, 128, None) == (None, 512, 1)
    assert wants_gqa_kernel(3072, 20, 4, 128) and not wants_gqa_kernel(3000, 20, 4, 128)


@pytest.mark.parametrize("seq", [512, 1024], ids=["five-tiles-a-step", "one-tile-a-step"])
def test_attention_at_twenty_over_four_heads_of_128_matches_a_masked_softmax(seq):
    rng = np.random.default_rng(seq)
    q = rng.normal(size=(1, seq, 20, 128)).astype(np.float32)
    k = rng.normal(size=(1, seq, 4, 128)).astype(np.float32)
    v = rng.normal(size=(1, seq, 4, 128)).astype(np.float32)
    want = masked_softmax(q, k, v, 128**-0.5, window=seq)  # no window: every key so far
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


def test_unnormed_heads_have_no_norm_leaves_and_the_normed_callers_keep_theirs():
    """`grouped_query_attention(normed=False)`: no ``q_norm`` / ``k_norm``;
    the two callers that norm keep their trees."""
    ours = abstract_variables(build_model(tiny_config()))["params"]["block_0"]
    assert "q_norm" not in ours and "k_norm" not in ours
    exaone = abstract_variables(build_model(ModelConfig(
        family="exaone_moe", token_dim=64, depth=2, heads=8, kv_heads=2, head_dim=16,
        attn_window=40, ffn_dim=192, moe_ffn_dim=24, num_experts=16, experts_per_token=4,
        vocab_rows=1200, doc_records=PER, dense_layers=1, precision="f32",
        layer_types=("sliding_attention", "full_attention"),
    )))["params"]["block_1"]
    assert {"q", "k", "v", "o", "q_norm", "k_norm"} <= set(exaone)
    lfm2 = abstract_variables(build_model(ModelConfig(
        family="lfm2_moe", token_dim=64, depth=3, heads=4, kv_heads=2, ffn_dim=224,
        moe_ffn_dim=56, num_experts=8, experts_per_token=2, vocab_rows=1200, doc_records=PER,
        layer_types=("conv", "conv", "full_attention"), dense_layers=2, precision="f32",
    )))["params"]["block_2"]
    assert {"q", "k", "v", "o", "q_norm", "k_norm"} <= set(lfm2)

"""Family ``kimi_k2`` (ISSUE 31): a DeepSeek-V3-style sparse decoder as a
token-level history scorer. The program against the plain reference the
benchmark keeps (``benchmark/reference/kimi_k2.py``: the harness finds it
there, it is not copied) through ``score_dataset``; the expert layer's
share of an expert-parallel layer, its dropless dispatch and its router;
latent attention and YaRN; padding; the bfloat16 bundle; the routing
counter; the commands. All on the CPU, seeded random weights, tiny widths
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
from benchmark.reference import kimi_k2 as reference
from mlops_tpu.bundle.bundle import Bundle, load_bundle, save_bundle
from mlops_tpu.config import ModelConfig
from mlops_tpu.data.encode import EncodedDataset, Preprocessor
from mlops_tpu.models import FAMILIES, abstract_variables, build_model
from mlops_tpu.monitor.state import fit_monitor
from mlops_tpu.ops import moe_dispatch
from mlops_tpu.ops.causal_attention import causal_attend
from mlops_tpu.ops.eva_attention import rope, rope_inv_freq
from mlops_tpu.ops.mla import (
    mla_attend,
    mla_attend_blockwise,
    mla_attend_xla,
    softmax_scale,
    wants_mla_kernel,
    yarn_inv_freq,
)
from mlops_tpu.parallel.bulk import make_bulk_jit, score_dataset
from mlops_tpu.schema import SCHEMA

REAL = json.loads(
    (Path(__file__).resolve().parents[1] / "benchmark/configs/kimi-k2-5l.json").read_text()
)
PER = 3  # records a history in the bulk tests: S = 144 tokens


def tiny_config(**over) -> ModelConfig:
    fields = dict(
        family="kimi_k2", token_dim=64, depth=3, heads=4, ffn_dim=160,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, moe_ffn_dim=32, num_experts=16, experts_per_token=4,
        first_expert=4, experts_held=4, vocab_rows=1200, doc_records=PER,
        rope_theta=50000.0, precision="f32", dropout=0.0,
    )
    return ModelConfig(**{**fields, **over})


def spec_of(config: ModelConfig) -> dict:
    """The configuration file's keys that the reference reads, for a tiny
    ``ModelConfig``; the source's constants are the real file's."""
    return {
        **{k: REAL[k] for k in (
            "rms_norm_eps", "rope_scaling", "routed_scaling_factor", "tokens_per_record",
            "record_vocab_size", "num_bins", "schema",
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
    """A hand-made ``kimi_k2`` bundle and a file of five whole histories
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
    assert "kimi_k2" in FAMILIES
    history = ModelConfig(family="kimi_k2", doc_records=64)
    assert (history.reads_documents, history.history_rows) == (False, 64)
    assert not history.uses_layout_trainer
    with pytest.raises(ValueError, match="kimi_k2"):
        build_model(ModelConfig(family="mlp", param_dtype="bf16"))


def test_the_real_configuration_is_the_published_widths():
    mc = REAL["model_config"]
    model = build_model(ModelConfig(**{**mc, "hidden_dims": tuple(mc["hidden_dims"])}))
    source = REAL["source_config"]
    assert (model.hidden, model.heads, model.ffn_dim, model.moe_ffn_dim) == (
        source["hidden_size"], source["num_attention_heads"],
        source["intermediate_size"], source["moe_intermediate_size"],
    )
    assert (model.q_lora_rank, model.kv_lora_rank, model.qk_nope_head_dim,
            model.qk_rope_head_dim, model.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (model.num_experts, model.experts_per_token) == (384, 8)
    assert (model.first_expert, model.experts_held, model.vocab_rows, model.depth) == (0, 24, 20480, 5)
    assert model.dense_layers == source["first_k_dense_replace"]
    assert model.routed_scaling == source["routed_scaling_factor"]
    assert (model.rope_theta, model.rope_factor, model.rope_original_positions) == (
        source["rope_theta"], source["rope_scaling"]["factor"],
        source["rope_scaling"]["original_max_position_embeddings"],
    )
    assert REAL["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    published = {**source, "num_hidden_layers": 5, "n_routed_experts": 24, "vocab_size": 20480}
    assert {k: REAL[k] for k in published} == published
    shapes = abstract_variables(model)["params"]
    sizes = jax.tree_util.tree_map(lambda leaf: leaf.size, shapes)
    count = lambda tree: sum(jax.tree_util.tree_leaves(tree))  # noqa: E731
    # the issue's arithmetic, plus the norms' and the router bias's few thousand
    assert count(sizes["block_0"]) == pytest.approx(497.5e6, rel=1e-3)
    assert count(sizes["block_1"]) == pytest.approx(1204.9e6, rel=1e-3)
    assert count(sizes) == pytest.approx(5463.9e6, rel=1e-3)
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(shapes)} == {jnp.dtype("bfloat16")}
    assert max(leaf.size for leaf in jax.tree_util.tree_leaves(shapes)) == 24 * 7168 * 2048


# ------------------------------------------------------- YaRN and the scale
def test_yarn_frequencies_and_the_scale_match_hand_computed_values():
    freqs = yarn_inv_freq(64, 50000.0, 64.0, 4096)
    plain = rope_inv_freq(64, 50000.0)
    # correction dimensions: 64 ln(4096 / (32 * 2 pi)) / (2 ln 50000) = 8.91 -> 8;
    # 64 ln(4096 / (2 pi)) / (2 ln 50000) = 19.16 -> 20
    low = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(50000.0))
    high = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(50000.0))
    assert (math.floor(low), math.ceil(high)) == (8, 20)
    np.testing.assert_allclose(freqs[:9], plain[:9], rtol=1e-6)  # fast: left alone
    np.testing.assert_allclose(freqs[20:], plain[20:] / 64.0, rtol=1e-6)  # slow: / factor
    ramp = (14 - 8) / (20 - 8)  # dimension 14 lies halfway
    np.testing.assert_allclose(
        freqs[14], plain[14] / 64.0 * ramp + plain[14] * (1 - ramp), rtol=1e-6
    )
    np.testing.assert_allclose(
        freqs, reference.yarn_inverse_frequencies(64, 50000.0, REAL["rope_scaling"]), rtol=1e-6
    )
    assert softmax_scale(192, 64.0) == pytest.approx(192**-0.5 * (0.1 * math.log(64) + 1) ** 2)
    assert softmax_scale(192, 64.0) == pytest.approx(0.14468, rel=1e-4)
    assert softmax_scale(192, 1.0) == pytest.approx(192**-0.5)


def test_rope_takes_a_base_or_the_frequencies_themselves():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 9, 3, 8)), jnp.float32)
    np.testing.assert_array_equal(rope(x, 100000.0), rope(x, rope_inv_freq(8, 100000.0)))
    some = np.array([2, 5, 8])
    np.testing.assert_allclose(
        rope(x[:, some], 100000.0, positions=some), rope(x, 100000.0)[:, some], atol=1e-6
    )
    with pytest.raises(ValueError, match="frequencies"):
        rope(x, np.ones(3, np.float32))


# ------------------------------------------------------------------- MLA
@pytest.mark.parametrize("block", [512, 48, 40])
def test_causal_attention_matches_a_per_head_loop(block):
    rng = np.random.default_rng(1)
    q, k = (rng.normal(size=(2, 144, 4, 24)).astype(np.float32) for _ in range(2))
    v = rng.normal(size=(2, 144, 4, 16)).astype(np.float32)
    out = np.asarray(causal_attend(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 0.3,
                                   query_block=block))
    for b in range(2):
        for h in range(4):
            for i in (0, 1, 47, 48, 100, 143):
                s = 0.3 * k[b, : i + 1, h] @ q[b, i, h]
                w = np.exp(s - s.max())
                np.testing.assert_allclose(
                    out[b, i, h], (w / w.sum()) @ v[b, : i + 1, h], atol=2e-5
                )
    read = np.array([47, 95, 143])
    some = causal_attend(jnp.asarray(q[:, read]), jnp.asarray(k), jnp.asarray(v), 0.3, read=read)
    np.testing.assert_allclose(some, out[:, read], atol=2e-6)


# ------------------------------ the blockwise kernel, against the XLA form
SCALE = softmax_scale(192, 64.0)  # the published 0.14468


def kernel_operands(blocks, dtype, batch=2, heads=2, seed=0, block=128):
    """``q_nope``, ``q_rot``, ``kv``, ``k_rot`` of the published head widths
    (128 + 64 against 128), as the projections write them."""
    seq = blocks * block
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)

    return (
        draw(batch, seq, heads, 128), draw(batch, seq, heads, 64),
        draw(batch, seq, heads * 256), draw(batch, seq, 64),
    )


@pytest.mark.parametrize("dtype,atol", [("float32", 1e-5), ("bfloat16", 2.0**-7)])
@pytest.mark.parametrize("blocks,heads", [(1, 2), (2, 3), (3, 4), (6, 2)])
def test_the_kernel_matches_the_xla_form(blocks, heads, dtype, atol):
    """float32 to 1e-5 (the order of a score's and a row's sums is all
    that differs); bfloat16 to the rounding of one bfloat16 weight or
    output: both forms round the weights once before the second product."""
    operands = kernel_operands(blocks, jnp.dtype(dtype), heads=heads, seed=blocks)
    with jax.default_matmul_precision("highest"):
        expected = mla_attend_xla(*operands, SCALE)
        out = mla_attend_blockwise(*operands, SCALE, block=128, interpret=True)
    assert out.shape == expected.shape == (2, blocks * 128, heads * 128)
    assert out.dtype == expected.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32), atol=atol
    )


def test_the_kernel_never_lets_a_query_see_a_later_key():
    """A key and value moved at position ``j`` change no answer before
    ``j``, bit for bit (in the block of ``j`` by the mask, in the blocks
    before it because the kernel never reads past a block's own keys), and
    every answer from ``j`` on."""
    q_nope, q_rot, kv, k_rot = kernel_operands(3, jnp.float32, batch=1, seed=7)

    def run(kv, k_rot):
        return np.asarray(
            mla_attend_blockwise(q_nope, q_rot, kv, k_rot, SCALE, block=128, interpret=True)
        )

    base = run(kv, k_rot)
    for j in (1, 127, 128, 200, 383):
        moved = run(kv.at[:, j].add(1.0), k_rot.at[:, j].add(1.0))
        assert (moved[:, :j] == base[:, :j]).all(), j
        assert np.abs(moved[:, j:] - base[:, j:]).max(axis=(0, 2)).min() > 1e-6, j


def test_which_shapes_take_the_mla_kernel():
    mc = REAL["model_config"]
    widths = (mc["qk_nope_head_dim"], mc["qk_rope_head_dim"], mc["v_head_dim"])
    assert widths == (128, 64, 128)
    seq = REAL["records_per_history"] * REAL["tokens_per_record"]
    assert seq == 3072 and wants_mla_kernel(seq, *widths)
    assert wants_mla_kernel(512, *widths) and wants_mla_kernel(4096, *widths)
    # each tiny configuration of this file takes the XLA form, everywhere
    for config in (tiny_config(), tiny_config(doc_records=64), tiny_config(heads=2)):
        assert not wants_mla_kernel(
            config.doc_records * 48, config.qk_nope_head_dim, config.qk_rope_head_dim,
            config.v_head_dim,
        )
    assert wants_mla_kernel(384, 128, 64, 128, block=128)  # the kernel tests' shape
    assert not wants_mla_kernel(3072, 64, 64, 64)  # half a lane tile a head
    assert not wants_mla_kernel(3072, 128, 64, 256)  # values of two lane tiles
    assert not wants_mla_kernel(3072, 128, 192, 128)  # a rotary part past a lane tile
    assert not wants_mla_kernel(3072, 128, 64, 128, block=192)  # no whole lane tiles a block
    assert not wants_mla_kernel(3072 - 48, *widths)  # a ragged history: no whole blocks
    assert not wants_mla_kernel(8192, *widths)  # one visit of 8,192 keys: past VMEM
    with pytest.raises(ValueError, match="no tiling"):
        mla_attend_blockwise(*kernel_operands(3, jnp.float32), SCALE, interpret=True)


def test_the_read_path_is_the_xla_form_and_the_full_results_rows():
    """With ``read`` the queries are those positions' alone and the form is
    XLA's whatever the shape (no `custom_vjp`, no kernel in the trace);
    the answers are the full result's at those positions."""
    q_nope, q_rot, kv, k_rot = kernel_operands(1, jnp.float32, batch=1, seed=5, block=512)
    read = np.array([47, 95, 300, 511])
    full = mla_attend(q_nope, q_rot, kv, k_rot, SCALE)
    some = mla_attend(q_nope[:, read], q_rot[:, read], kv, k_rot, SCALE, read=read)
    assert some.shape == (1, 4, 2 * 128)
    np.testing.assert_allclose(np.asarray(some), np.asarray(full[:, read]), atol=2e-6)
    traced = str(jax.make_jaxpr(
        lambda *xs: mla_attend(*xs, SCALE, read=read)
    )(q_nope[:, read], q_rot[:, read], kv, k_rot))
    assert "custom_vjp" not in traced and "pallas_call" not in traced
    # without ``read`` the same shape is the kernel's wherever a TPU is lowered for
    assert "custom_vjp" in str(jax.make_jaxpr(
        lambda *xs: mla_attend(*xs, SCALE)
    )(q_nope, q_rot, kv, k_rot))


def test_the_backward_of_the_mla_kernels_shape_is_the_xla_forms():
    """At a shape the kernel takes, `mla_attend` is a `custom_vjp` whose
    backward differentiates the XLA form: the same gradients as autodiff
    of the XLA form itself (on the CPU the forward is that form too)."""
    operands = kernel_operands(1, jnp.float32, batch=1, seed=3, block=512)
    weights = jnp.asarray(np.random.default_rng(4).normal(size=(1, 512, 256)), jnp.float32)

    def loss(attend):
        return lambda *xs: (attend(*xs, SCALE) * weights).sum()

    got = jax.grad(loss(mla_attend), argnums=(0, 1, 2, 3))(*operands)
    expected = jax.grad(loss(mla_attend_xla), argnums=(0, 1, 2, 3))(*operands)
    for g, e in zip(got, expected):
        assert np.abs(np.asarray(e)).max() > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=1e-6, atol=1e-7)


# ------------------------------------------------------- the expert layer
def expert_inputs(tokens=150, dim=32, experts=16, width=24, seed=3):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape) / math.sqrt(shape[-2]), jnp.float32)  # noqa: E731
    h = jnp.asarray(rng.normal(size=(tokens, dim)), jnp.float32)
    return h, draw(dim, experts), draw(experts, dim, width), draw(experts, dim, width), draw(experts, width, dim)


def routed_part(h, routing, gate, up, down, first, held, rows=None):
    planned = moe_dispatch.plan(routing.experts, first, held)
    tokens, top_k = routing.experts.shape
    rows = rows or moe_dispatch.segment_rows(tokens, top_k, gate.shape[0], held)
    sl = slice(first, first + held)
    return (
        moe_dispatch.grouped_swiglu(h, routing, planned, gate[sl], up[sl], down[sl], rows),
        planned,
    )


def test_the_shares_add_up_to_the_uncut_layer():
    """Every chip's routed part + the shared expert once = the whole layer,
    as the reference's own expert function computes it expert by expert."""
    h, router, gate, up, down = expert_inputs()
    bias = jnp.asarray(np.random.default_rng(4).normal(size=16) * 0.1, jnp.float32)
    routing = moe_dispatch.route(h, router, bias, 4, 2.827, 1e-20)
    shares = [routed_part(h, routing, gate, up, down, first, 4)[0] for first in (0, 4, 8, 12)]
    uncut, planned = routed_part(h, routing, gate, up, down, 0, 16)
    assert int(planned.counts.sum()) == 150 * 4  # all held: every choice lands
    np.testing.assert_allclose(sum(shares), uncut, atol=2e-5)
    shared = reference.swiglu(h, gate[0], up[0], down[0], "f32")  # any one SwiGLU as "shared"
    whole = shared
    for i in range(16):
        mine = jnp.where(routing.experts == i, routing.weights, 0.0).sum(-1)[:, None]
        whole = whole + mine * reference.swiglu(h, gate[i], up[i], down[i], "f32")
    np.testing.assert_allclose(sum(shares) + shared, whole, atol=3e-5)
    # a share is a PART: weights are normalised over all four chosen, held or not
    np.testing.assert_allclose(routing.weights.sum(-1), 2.827, rtol=1e-5)
    assert float(jnp.abs(shares[1]).max()) > 0 and float(jnp.abs(uncut - shares[1]).max()) > 0.1


def test_no_token_is_dropped_under_a_skewed_router():
    """One held expert takes (nearly) every token, another none: the
    segments are walked to the end and every assignment is computed."""
    h, router, gate, up, down = expert_inputs()
    bias = jnp.zeros(16).at[5].set(5.0).at[6].set(-5.0)  # 5 always chosen, 6 never
    routing = moe_dispatch.route(h, router, bias, 4, 2.827, 1e-20)
    rows = 128  # far under the 4 x 150 worst case: several segments
    got, planned = routed_part(h, routing, gate, up, down, 4, 4, rows=rows)
    counts = np.asarray(planned.counts)
    assert counts[1] == 150 and counts[2] == 0 and counts.sum() > rows
    want = jnp.zeros_like(h)
    for i in range(4, 8):
        mine = jnp.where(routing.experts == i, routing.weights, 0.0).sum(-1)[:, None]
        want = want + mine * reference.swiglu(h, gate[i], up[i], down[i], "f32")
    np.testing.assert_allclose(got, want, atol=3e-5)
    one = routed_part(h, routing, gate, up, down, 4, 4, rows=600)[0]  # one segment: the worst case
    np.testing.assert_allclose(got, one, atol=2e-5)
    assert moe_dispatch.segment_rows(3072, 8, 384, 24) == 3072
    assert moe_dispatch.segment_rows(150, 4, 16, 16) == 600  # all held: the worst case is the case


@pytest.mark.parametrize("held, segments", [(4, 2), (8, 1)], ids=["quarter", "half"])
def test_the_combines_two_forms_agree_at_a_shares_own_rows(held, segments):
    """A quarter of the experts is walked in two segments (the scatter-add
    under the loop, this family's cell), half of them in one (every token
    gathers its own experts' rows): each at its own `segment_rows` against
    the other form, and against the reference expert by expert."""
    h, router, gate, up, down = expert_inputs()
    bias = jnp.asarray(np.random.default_rng(4).normal(size=16) * 0.1, jnp.float32)
    routing = moe_dispatch.route(h, router, bias, 4, 2.827, 1e-20)
    rows = moe_dispatch.segment_rows(150, 4, 16, held)
    assert -(-4 * 150 // rows) == segments
    own, planned = routed_part(h, routing, gate, up, down, 4, held)
    other = routed_part(h, routing, gate, up, down, 4, held, rows=600 if segments > 1 else 128)[0]
    assert 0 < int(planned.counts.sum()) < 600
    np.testing.assert_allclose(own, other, atol=1e-6)
    want = jnp.zeros_like(h)
    for i in range(4, 4 + held):
        mine = jnp.where(routing.experts == i, routing.weights, 0.0).sum(-1)[:, None]
        want = want + mine * reference.swiglu(h, gate[i], up[i], down[i], "f32")
    np.testing.assert_allclose(own, want, atol=3e-5)


def test_the_bias_moves_the_choice_and_not_the_weights():
    h, router, *_ = expert_inputs()
    plain = moe_dispatch.route(h, router, jnp.zeros(16), 4, 2.827, 1e-20)
    bias = jnp.zeros(16).at[3].set(5.0)
    biased = moe_dispatch.route(h, router, bias, 4, 2.827, 1e-20)
    assert bool((biased.experts == 3).any(axis=-1).all())
    assert not bool((plain.experts == 3).any(axis=-1).all())
    scores = np.asarray(jax.nn.sigmoid(h @ router))
    picked = np.take_along_axis(scores, np.asarray(biased.experts), axis=-1)
    np.testing.assert_allclose(
        biased.weights, 2.827 * picked / picked.sum(-1, keepdims=True), rtol=1e-5
    )  # sigmoid scores alone, the bias nowhere


# ----------------------------------------------- the model and the bulk job
@pytest.mark.parametrize("held", [(4, 4), (0, 0)], ids=["share", "uncut"])
def test_score_dataset_matches_the_reference(held):
    first, count = held
    config = tiny_config(first_expert=first, experts_held=count)
    cat, num = rows(5 * PER + 2)
    ds = EncodedDataset(cat, num)
    result = score(bundle_of(config, ds), ds)
    bundle = bundle_of(config, ds)
    expected, routed = reference.forward(bundle.variables, cat, num, spec_of(config))
    np.testing.assert_allclose(logit(result.predictions), np.asarray(expected), atol=1e-5)
    assert np.abs(np.asarray(expected)).max() > 0.05
    # the counter: the job's chunk runs were three chunks of two histories,
    # the last history two records and a padding row of zeros
    given_cat = np.concatenate([cat, np.zeros((1, 9), np.int32)])
    given_num = np.concatenate([num, np.zeros((1, 14), np.float32)])
    spec = spec_of(config)
    want = reference.held_assignments(
        reference.forward(bundle.variables, given_cat, given_num, spec)[1], spec
    )
    got = np.asarray(result.routing["per_layer"])
    # every layer but the last routes every token; the last the read positions
    np.testing.assert_array_equal(got[:-1], want[:-1])
    assert result.routing["tokens"] == 6 * PER * 48
    assert result.routing["assignments_held"] == got.sum()
    assert result.routing["max_expert_load"] == got.max()
    assert 0 < got[-1].sum() <= 6 * PER * 4
    if not count:  # all held: every choice of every token is counted
        assert got[0].sum() == 6 * PER * 48 * 4 and got[-1].sum() == 6 * PER * 4
    assert result.routing["expert_runs"] <= 3 * got.size


def test_padded_rows_behind_a_short_history_change_no_answer(tiny_bundle):
    bundle, ds = tiny_bundle
    whole = score(bundle, ds).predictions
    short = EncodedDataset(ds.cat_ids[: 5 * PER + 1], ds.numeric[: 5 * PER + 1])
    np.testing.assert_allclose(score(bundle, short).predictions, whole[: 5 * PER + 1], atol=2e-6)
    # causality: a record's answer never depends on the records after it
    model = bundle.model
    first = model.apply(bundle.variables, ds.cat_ids[:1], ds.numeric[:1], train=False)
    np.testing.assert_allclose(logit(whole[:1]), first, atol=1e-5)


def test_chunks_of_any_number_of_histories_give_the_same_answers(tiny_bundle):
    bundle, ds = tiny_bundle
    np.testing.assert_allclose(
        score(bundle, ds, chunk_rows=PER).predictions,
        score(bundle, ds, chunk_rows=4 * PER).predictions, atol=2e-6,
    )


def test_a_family_without_experts_counts_nothing():
    cat, num = rows(40)
    ds = EncodedDataset(cat, num)
    zeros = np.zeros(SCHEMA.num_numeric, np.float32)
    config = ModelConfig(family="mlp", hidden_dims=(16,))
    model = build_model(config)
    bundle = Bundle(
        manifest={"flavor": "flax", "model_config": dataclasses.asdict(config)},
        model=model, variables=inputs.make_weights(abstract_variables(model), 3),
        preprocessor=Preprocessor(zeros, zeros, zeros + 1, SCHEMA.fingerprint()),
        monitor=fit_monitor(ds),
    )
    result = score_dataset(bundle, ds, chunk_rows=16, exact=True)
    assert result.routing is None and "routing" not in result.summary()


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
    # the job's record (and so ``job_log()``) holds the scalars, no table
    scalars = result.record["routing"]
    assert set(scalars) == {"tokens", "assignments_held", "max_expert_load",
                            "mean_expert_load", "expert_runs"}
    assert all(scalars[k] == result.routing[k] == marker[k] for k in scalars)


@pytest.mark.parametrize("scope", [
    "mla_q", "mla_kv", "mla_attend", "mla_o", "router", "moe_dispatch", "experts",
    "moe_combine", "shared_expert", "embed", "ffn", "head",
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
    assert "tensor<1200x64xf32>" not in text and "tensor<4x64x32xf32>" not in text
    # against the float32 reference the bfloat16 program is near, not equal
    expected = reference.logits(bundle.variables, cat, num, spec_of(config))
    gap = np.abs(logit(score(bundle, ds).predictions) - np.asarray(expected)).max()
    assert 1e-6 < gap < 0.3


# ------------------------------------------------- training, the commands
def test_gradients_are_finite_and_reach_the_experts_and_the_latents():
    config = tiny_config(doc_records=2)  # block_1: an expert layer at every position
    model, weights = seeded(config)
    cat, num = rows(8)
    labels = jnp.asarray(np.arange(8) % 2, jnp.float32)

    def loss(params):
        logits = model.apply({"params": params}, cat, num, train=False)
        return jnp.mean(jnp.logaddexp(0.0, logits) - labels * logits)

    value, grads = jax.jit(jax.value_and_grad(loss))(weights["params"])
    assert np.isfinite(float(value))
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in leaves)
    for name in ("experts_gate", "experts_down", "shared_up", "q_a", "kv_b"):
        assert np.abs(np.asarray(grads["block_1"][name]["kernel"])).max() > 0, name


def test_score_batch_scores_a_kimi_k2_bundle(tmp_path, capsys):
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

"""Family ``evabyte`` (ISSUE 27): EvaByte's decoder as a byte-level history
scorer. The program against the plain reference the benchmark keeps
(``benchmark/reference/evabyte.py``: the harness finds it there, it is not
copied), for the whole model and for the EVA attention alone; the rendered
bytes; causality through ``score_dataset``; chunking; the bundle; the
commands. All on the CPU, seeded random weights, tiny widths, float32."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import inputs
from benchmark.reference import evabyte as reference
from mlops_tpu.bundle.bundle import Bundle, load_bundle, save_bundle
from mlops_tpu.config import Config, ModelConfig
from mlops_tpu.data.encode import EncodedDataset, Preprocessor
from mlops_tpu.models import FAMILIES, abstract_variables, build_model
from mlops_tpu.models.evabyte import FIELD_NAMES, RECORD_BYTES, RECORD_END, render_bytes
from mlops_tpu.monitor.state import fit_monitor
from mlops_tpu.ops.eva_attention import (
    eva_attend,
    eva_attend_blockwise,
    eva_attend_xla,
    eva_prep_kv,
    rope,
    wants_eva_kernel,
)
from mlops_tpu.parallel.bulk import make_bulk_jit, mesh_chunk_rows, score_dataset
from mlops_tpu.schema import SCHEMA

REAL = json.loads(
    (Path(__file__).resolve().parents[1] / "benchmark/configs/evabyte-8l.json").read_text()
)
PER = 4  # records a history in the bulk tests


def tiny_config(records=PER, window=256, chunk=8, **over) -> ModelConfig:
    fields = dict(
        family="evabyte", token_dim=32, depth=2, heads=2, ffn_dim=64,
        attn_window=window, attn_chunk=chunk, rope_theta=100000.0,
        doc_records=records, precision="f32", dropout=0.0,
    )
    return ModelConfig(**{**fields, **over})


def spec_of(config: ModelConfig) -> dict:
    """The configuration file's keys that the reference reads, for a tiny
    ``ModelConfig``; the record format is the real file's."""
    return {
        "model_config": {
            "token_dim": config.token_dim, "depth": config.depth,
            "heads": config.heads, "ffn_dim": config.ffn_dim,
            "attn_window": config.attn_window, "attn_chunk": config.attn_chunk,
            "rope_theta": config.rope_theta,
        },
        "records_per_history": config.doc_records,
        "record_bytes": REAL["record_bytes"],
        "byte_offset": REAL["byte_offset"],
        "record_format": REAL["record_format"],
    }


def rows(n, seed=0):
    rng = np.random.default_rng(seed)
    cat = np.stack([rng.integers(0, c, n) for c in SCHEMA.cards], 1).astype(np.int32)
    return cat, (1.5 * rng.normal(size=(n, SCHEMA.num_numeric))).astype(np.float32)


def seeded(config: ModelConfig, seed=2**31 + 7):
    model = build_model(config)
    return model, inputs.make_weights(abstract_variables(model), seed)


@pytest.fixture(scope="module")
def tiny_bundle():
    """A hand-made ``evabyte`` bundle and a file of five whole histories
    and one of two records."""
    config = tiny_config()
    model, weights = seeded(config)
    cat, num = rows(5 * PER + 2)
    ds = EncodedDataset(cat, num)
    zeros = np.zeros(SCHEMA.num_numeric, np.float32)
    bundle = Bundle(
        manifest={"flavor": "flax", "model_config": dataclasses.asdict(config),
                  "calibration": {"temperature": 1.5}},
        model=model,
        variables=weights,
        preprocessor=Preprocessor(zeros, zeros, zeros + 1, SCHEMA.fingerprint()),
        monitor=fit_monitor(ds),
    )
    return bundle, ds


def score(bundle, ds, chunk_rows=2 * PER, mesh=None):
    return score_dataset(
        bundle, ds, mesh=mesh, chunk_rows=chunk_rows, exact=True, pipeline_depth=2
    )


# ------------------------------------------------------- the configuration
def test_the_family_is_listed_and_told_apart_from_the_doc_flavour():
    assert "evabyte" in FAMILIES
    history = ModelConfig(family="evabyte", doc_records=64)
    document = ModelConfig(family="bert", doc_records=11)
    assert (history.reads_documents, history.history_rows) == (False, 64)
    assert (document.reads_documents, document.history_rows) == (True, 1)
    assert not history.uses_layout_trainer and document.uses_layout_trainer
    assert ModelConfig().history_rows == 1 and not ModelConfig().reads_documents


def test_the_real_configuration_is_the_published_widths():
    mc = REAL["model_config"]
    model = build_model(ModelConfig(**{**mc, "hidden_dims": tuple(mc["hidden_dims"])}))
    assert (model.hidden, model.heads, model.ffn_dim) == (4096, 32, 11008)
    assert (model.window, model.chunk, model.rope_theta) == (2048, 16, 100000.0)
    assert (model.depth, model.records_per_history) == (8, 64)
    assert REAL["reduced"] == ["num_hidden_layers"]
    published = {**REAL["source_config"], "num_hidden_layers": 8}
    assert {k: REAL[k] for k in published} == published
    sizes = jax.tree_util.tree_map(lambda leaf: leaf.size, abstract_variables(model))
    assert sum(jax.tree_util.tree_leaves(sizes["params"]["block_0"])) == 202_391_552


# ------------------------------------------------------------ the rendering
def test_rendered_bytes_equal_the_references_byte_for_byte():
    cat, num = rows(300, seed=4)
    num[0, :4] = [0.0, -0.04, 12.0, -12.0]  # +00, -00 as "+00", both clips
    num[1, :3] = [0.25, 0.35, -0.05]  # halves round to even
    rendered = np.asarray(render_bytes(cat, num))
    assert rendered.shape == (300, RECORD_BYTES) and RECORD_BYTES == REAL["record_bytes"]
    assert (rendered == np.asarray(reference.render(cat, num, spec_of(tiny_config())))).all()
    text = bytes(rendered[0].astype(np.uint8)).decode()
    assert text.endswith(RECORD_END) and text.count(",") == len(FIELD_NAMES)
    fields = dict(f.split("=") for f in text[: -len(RECORD_END)].rstrip(",").split(","))
    assert list(fields) == list(FIELD_NAMES) == REAL["record_format"]["field_names"]
    assert fields["sex___"] == f"{cat[0, 0]:03d}" and fields["repay6"] == f"{cat[0, 8]:03d}"
    assert [fields[k] for k in ("climit", "age___", "bill_1", "bill_2")] == [
        "+00", "+00", "+99", "-99"]
    second = bytes(rendered[1].astype(np.uint8)).decode()
    assert "climit=+02," in second and "age___=+04," in second and "bill_1=+00," in second


# ------------------------------------------- the attention, against the reference
@pytest.mark.parametrize("windows", [1, 2, 3.5])
def test_eva_attention_matches_the_reference(windows):
    window, chunk, heads, head_dim = 64, 8, 3, 16
    seq = int(windows * window)
    rng = np.random.default_rng(int(10 * windows))
    q, k, v = (rng.normal(size=(2, seq, heads, head_dim)).astype(np.float32) for _ in range(3))
    phi, mu = (rng.normal(size=(heads, head_dim)).astype(np.float32) for _ in range(2))
    with jax.default_matmul_precision("highest"):
        qr, kr = rope(jnp.asarray(q), 100000.0), rope(jnp.asarray(k), 100000.0)
        k_sum, v_sum = eva_prep_kv(kr, jnp.asarray(v), phi, mu, chunk)
        out = np.asarray(eva_attend(qr, kr, jnp.asarray(v), k_sum, v_sum, window, chunk))
    assert out.shape == (2, seq, heads, head_dim)
    for b in range(2):
        qb, kb = reference.rotary(q[b], 100000.0), reference.rotary(k[b], 100000.0)
        np.testing.assert_allclose(np.asarray(qr[b]), np.asarray(qb), atol=1e-6)
        for h in range(heads):
            ks, vs = reference.summaries(kb[:, h], v[b, :, h], phi[h], mu[h], chunk)
            np.testing.assert_allclose(np.asarray(k_sum[b, :, h]), np.asarray(ks), atol=2e-6)
            np.testing.assert_allclose(np.asarray(v_sum[b, :, h]), np.asarray(vs), atol=2e-6)
            expected = reference.eva_head(
                qb[:, h], kb[:, h], v[b, :, h], phi[h], mu[h], window, chunk, "f32"
            )
            np.testing.assert_allclose(out[b, :, h], np.asarray(expected), atol=5e-6)


def test_a_summary_shows_only_once_its_window_is_past():
    """Changing the keys of window 0 moves nothing in window 0 beyond the
    causal reach, and reaches window 1 only through the summaries."""
    window, chunk, seq = 32, 8, 64
    rng = np.random.default_rng(3)
    q, k, v = (rng.normal(size=(1, seq, 1, 8)).astype(np.float32) for _ in range(3))
    phi, mu = np.zeros((1, 8), np.float32), np.zeros((1, 8), np.float32)

    def run(values, with_summaries=True):
        k_sum, v_sum = eva_prep_kv(k, values, phi, mu, chunk)
        if not with_summaries:
            v_sum = jnp.zeros_like(v_sum)
        return np.asarray(eva_attend(q, k, values, k_sum, v_sum, window, chunk))

    moved = v.copy()
    moved[0, 20] += 1.0  # a value in window 0
    base, after = run(v), run(moved)
    assert (base[0, :20] == after[0, :20]).all()  # causal inside the window
    assert np.abs(base[0, 20:32] - after[0, 20:32]).max() > 1e-3
    assert np.abs(base[0, 32:] - after[0, 32:]).max() > 1e-4  # through v~ of chunk 2
    cut, cut_after = run(v, False), run(moved, False)
    assert (cut[0, 32:] == cut_after[0, 32:]).all()  # and through nothing else


# ------------------------------ the blockwise kernel, against the XLA form
KERNEL = dict(window=512, chunk=4)  # 128 summaries a window, as EvaByte has
# blocks of 128 at window 512: four query blocks a window, so a grid step
# meets local blocks before its own, its diagonal and past windows' summaries


def kernel_operands(windows, dtype, batch=2, heads=2, seed=0, window=512, chunk=4):
    seq = int(windows * window)
    rng = np.random.default_rng(seed)
    q, k, v = (
        jnp.asarray(rng.normal(size=(batch, seq, heads, 128)), jnp.float32).astype(dtype)
        for _ in range(3)
    )
    phi, mu = (rng.normal(size=(heads, 128)).astype(np.float32) for _ in range(2))
    k_sum, v_sum = eva_prep_kv(k, v, phi, mu, chunk)
    return q, k, v, k_sum, v_sum


@pytest.mark.parametrize("dtype,atol", [("float32", 1e-5), ("bfloat16", 2.0**-7)])
@pytest.mark.parametrize("windows", [1, 2, 3.5])
def test_the_kernel_matches_the_xla_form(windows, dtype, atol):
    """float32 to 1e-5 (the order of a row's sum is all that differs);
    bfloat16 to the rounding of one bfloat16 weight or output: both forms
    round the weights once, to 8 bits, before the second product."""
    operands = kernel_operands(windows, jnp.dtype(dtype), seed=int(10 * windows))
    with jax.default_matmul_precision("highest"):
        expected = eva_attend_xla(*operands, **KERNEL)
        out = eva_attend_blockwise(*operands, **KERNEL, block=128, interpret=True)
    assert out.shape == expected.shape and out.dtype == expected.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32), atol=atol
    )


def test_the_kernel_at_its_own_block_size_matches_the_xla_form():
    """The default block against a window of two of them: the widest
    tiles the kernel forms, one history, one head."""
    window, chunk = 1024, 8
    q, k, v, k_sum, v_sum = kernel_operands(
        2, jnp.float32, batch=1, heads=1, seed=5, window=window, chunk=chunk
    )
    with jax.default_matmul_precision("highest"):
        expected = eva_attend_xla(q, k, v, k_sum, v_sum, window, chunk)
        out = eva_attend_blockwise(q, k, v, k_sum, v_sum, window, chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-5)


def test_the_kernel_never_visits_a_summary_block_before_its_window_is_past():
    """The block-skipping twin of the test above: the summaries of window
    ``j`` change no answer in windows ``0..j`` (their block is not visited
    there) and every answer after them; the last window's change none."""
    q, k, v, k_sum, v_sum = kernel_operands(3, jnp.float32, batch=1, seed=7)
    per_window = KERNEL["window"] // KERNEL["chunk"]

    def run(ks, vs):
        return np.asarray(
            eva_attend_blockwise(q, k, v, ks, vs, **KERNEL, block=128, interpret=True)
        )

    base = run(k_sum, v_sum)
    for j in range(3):
        blanked = slice(j * per_window, (j + 1) * per_window)
        moved = run(k_sum.at[:, blanked].add(1.0), v_sum.at[:, blanked].add(1.0))
        seen_from = (j + 1) * KERNEL["window"]
        assert (moved[:, :seen_from] == base[:, :seen_from]).all(), j
        if j < 2:  # every later position, through that one block
            later = np.abs(moved[:, seen_from:] - base[:, seen_from:])
            assert later.max(axis=(0, 2, 3)).min() > 1e-4, j


def test_which_shapes_take_the_kernel():
    mc = REAL["model_config"]
    head = mc["token_dim"] // mc["heads"]
    assert (head, mc["attn_window"], mc["attn_chunk"]) == (128, 2048, 16)
    for seq in (256, 3072, 16384, 32768):  # any history of the real configuration
        assert wants_eva_kernel(seq, head, mc["attn_window"], mc["attn_chunk"])
    # each tiny configuration of this file takes the XLA form, everywhere
    for config in (tiny_config(), tiny_config(window=512), tiny_config(records=2, window=128),
                   tiny_config(token_dim=16, heads=2, window=128)):
        assert not wants_eva_kernel(
            config.doc_records * RECORD_BYTES, config.token_dim // config.heads,
            config.attn_window, config.attn_chunk,
        )
    assert wants_eva_kernel(1792, 128, 512, 4)  # the kernel tests' shape
    assert not wants_eva_kernel(1792, 64, 512, 4)  # half a lane tile a head
    assert not wants_eva_kernel(1792, 128, 512, 8)  # 64 summaries a window
    assert not wants_eva_kernel(1792, 128, 640, 5)  # no whole blocks in a window
    assert not wants_eva_kernel(65536, 128, 2048, 16)  # 4,096 summaries: past VMEM
    assert not wants_eva_kernel(8192, 128, 4096, 32)  # a 4,096-key window: past VMEM
    with pytest.raises(ValueError, match="no tiling"):
        eva_attend_blockwise(*kernel_operands(1, jnp.float32), 512, 8, interpret=True)


def test_the_backward_of_the_kernels_shape_is_the_xla_forms():
    """At a shape the kernel takes, `eva_attend` is a `custom_vjp` whose
    backward differentiates the XLA form: the same gradients as autodiff
    of the XLA form itself (on the CPU the forward is that form too)."""
    operands = kernel_operands(1.5, jnp.float32, batch=1, heads=1, seed=3)
    weights = jnp.asarray(np.random.default_rng(4).normal(size=operands[0].shape), jnp.float32)

    def loss(attend):
        return lambda *xs: (attend(*xs, **KERNEL) * weights).sum()

    got = jax.grad(loss(eva_attend), argnums=(0, 1, 2, 3, 4))(*operands)
    expected = jax.grad(loss(eva_attend_xla), argnums=(0, 1, 2, 3, 4))(*operands)
    for g, e in zip(got, expected):
        assert np.abs(np.asarray(e)).max() > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=1e-6, atol=1e-7)
    assert "custom_vjp" in str(jax.make_jaxpr(loss(eva_attend))(*operands))


@pytest.mark.parametrize("records,window", [(1, 256), (2, 256), (7, 512)])
def test_model_matches_the_reference(records, window):
    """S of 1, 2 and 3 1/2 windows; several histories and a short last one."""
    config = tiny_config(records=records, window=window)
    model, weights = seeded(config)
    cat, num = rows(2 * records + max(1, records // 2), seed=records)
    with jax.default_matmul_precision("highest"):
        served = np.asarray(model.apply(weights, cat, num, train=False))
    expected = np.asarray(reference.logits(weights, cat, num, spec_of(config)))
    assert served.shape == (cat.shape[0],) and np.abs(served).max() > 0.05
    np.testing.assert_allclose(served, expected, atol=3e-5)
    low = np.asarray(reference.logits(weights, cat, num, spec_of(config), precision="fp8"))
    assert np.abs(low - expected).max() > 30 * np.abs(served - expected).max()


# ------------------------------------------------ causality, through the bulk job
@pytest.mark.parametrize("how", ["changed", "padded"])
def test_records_after_r_never_change_answers_up_to_r(tiny_bundle, how):
    bundle, ds = tiny_bundle
    whole = score(bundle, ds)
    assert whole.rows == ds.n == 22 and np.isfinite(whole.predictions).all()
    r = 2 * PER + 1  # the second record of the third history
    if how == "changed":
        cat, num = ds.cat_ids.copy(), ds.numeric.copy()
        cat[r + 1 :], num[r + 1 :] = rows(ds.n - r - 1, seed=9)
        after = score(bundle, EncodedDataset(cat, num))
        assert (after.predictions[r + 1 : 3 * PER] != whole.predictions[r + 1 : 3 * PER]).all()
    else:  # the file ends after record r: zeros are padded behind it
        after = score(bundle, EncodedDataset(ds.cat_ids[: r + 1], ds.numeric[: r + 1]))
        assert after.rows == r + 1
    assert (after.predictions[: r + 1] == whole.predictions[: r + 1]).all()  # the same bits
    assert (after.outliers[: r + 1] == whole.outliers[: r + 1]).all()


def test_a_history_starts_anew(tiny_bundle):
    """Record 0 of every history sees no record before it: the same record
    at the head of two histories gets the same answer."""
    bundle, ds = tiny_bundle
    cat, num = ds.cat_ids.copy(), ds.numeric.copy()
    cat[2 * PER], num[2 * PER] = cat[0], num[0]
    result = score(bundle, EncodedDataset(cat, num))
    np.testing.assert_allclose(result.predictions[2 * PER], result.predictions[0], rtol=1e-6)
    assert abs(result.predictions[1] - result.predictions[2 * PER + 1]) > 1e-6


@pytest.mark.parametrize("histories", [1, 2, 4])
def test_chunks_of_any_number_of_histories_give_the_same_answers(tiny_bundle, histories):
    bundle, ds = tiny_bundle
    direct = jax.nn.sigmoid(
        bundle.model.apply(bundle.variables, ds.cat_ids, ds.numeric, train=False) / 1.5
    )
    result = score(bundle, ds, chunk_rows=histories * PER)
    np.testing.assert_allclose(result.predictions, np.asarray(direct), atol=2e-6)
    assert result.pipeline["stages"]["compute"]["items"] == -(-ds.n // (histories * PER))


@pytest.mark.parametrize(
    "asked,data_axis,history_rows,chunk",
    [(4096, None, 1, 4096), (0, None, 1, 1), (10, 4, 1, 12), (1, 8, 1, 8),
     (128, None, 64, 128), (100, None, 64, 128), (1, None, 64, 64),
     (131072, None, 64, 131072), (128, 4, 64, 256), (7, 2, 4, 8)],
)
def test_the_one_rounding_rule_keeps_histories_whole(asked, data_axis, history_rows, chunk):
    mesh = None
    if data_axis:
        from mlops_tpu.parallel import make_mesh

        mesh = make_mesh(data_axis)
    assert mesh_chunk_rows(asked, mesh, history_rows) == chunk


def test_a_chunk_that_would_cut_a_history_is_rounded_up(tiny_bundle):
    bundle, ds = tiny_bundle
    cut = score(bundle, ds, chunk_rows=PER + 1)  # -> 2 histories a chunk
    np.testing.assert_array_equal(cut.predictions, score(bundle, ds).predictions)


def test_sharded_over_a_mesh_matches_one_device(tiny_bundle):
    from mlops_tpu.parallel import make_mesh

    bundle, ds = tiny_bundle
    sharded = score(bundle, ds, chunk_rows=PER, mesh=make_mesh(2))
    np.testing.assert_allclose(sharded.predictions, score(bundle, ds).predictions, atol=2e-6)


# ----------------------------------------------------- spans and scopes
def test_the_job_span_counts_histories_and_bytes(tiny_bundle, tmp_path):
    from conftest import program_spans

    bundle, ds = tiny_bundle
    with program_spans(tmp_path / "profile") as spans:
        score(bundle, ds)
    (job,) = [attrs for name, _, _, attrs in spans if name == "mlops:bulk.job"]
    assert (job["rows"], job["histories"], job["bytes"]) == (22, 6, 22 * RECORD_BYTES)
    assert (job["chunk_rows"], job["chunks"]) == (2 * PER, 3)


@pytest.mark.parametrize("scope", ["eva_prep_kv", "eva_attend", "rope", "ffn", "embed", "head"])
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


# ------------------------------------------------- training, bundle, commands
def test_gradients_are_finite_and_the_loss_falls():
    import optax

    config = tiny_config(records=2, window=128)
    model = build_model(config)
    cat, num = rows(16, seed=5)
    labels = (np.arange(16) % 3 == 0).astype(np.float32)
    params = model.init({"params": jax.random.PRNGKey(1)}, cat, num, train=False)

    def loss_fn(p):
        logits = model.apply(p, cat, num, train=True)
        return optax.sigmoid_binary_cross_entropy(logits, labels).mean()

    tx = optax.adam(3e-3)
    state = tx.init(params)
    step = jax.jit(jax.value_and_grad(loss_fn))
    losses = []
    for _ in range(8):
        loss, grads = step(params)
        leaves = jax.tree_util.tree_leaves(grads)
        assert all(np.isfinite(np.asarray(g)).all() for g in leaves)
        assert any(np.abs(np.asarray(g)).max() > 0 for g in leaves)
        updates, state = tx.update(grads, state)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.01, losses
    phi = grads["params"]["block_0"]["adaptive_phi"]["bias"]
    assert np.abs(np.asarray(phi)).max() > 0  # the summaries are trained through


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """`train` at a tiny size through the normal entry point."""
    from mlops_tpu.train.pipeline import run_training

    root = tmp_path_factory.mktemp("evabyte-train")
    config = Config()
    config.data.rows = 600
    config.model = tiny_config(records=2, window=128, token_dim=16, depth=1, ffn_dim=32)
    config.train.steps = 6
    config.train.eval_every = 3
    config.train.batch_size = 32
    config.train.warmup_steps = 1
    config.registry.root = str(root / "registry")
    config.registry.run_root = str(root / "runs")
    return run_training(config)


def test_train_packages_a_flax_bundle(trained):
    bundle = load_bundle(trained.bundle_dir)
    assert bundle.flavor == "flax" and bundle.model_config.family == "evabyte"
    assert bundle.model_config.history_rows == 2
    assert np.isfinite(trained.train_result.metrics["validation_roc_auc_score"])


def test_bundle_round_trip_gives_the_same_answers(tiny_bundle, tmp_path):
    bundle, ds = tiny_bundle
    save_bundle(tmp_path / "b", bundle.model_config, bundle.variables["params"],
                bundle.preprocessor, bundle.monitor, calibration={"temperature": 1.5})
    loaded = load_bundle(tmp_path / "b")
    assert loaded.flavor == "flax" and loaded.model_config == bundle.model_config
    np.testing.assert_array_equal(score(loaded, ds).predictions, score(bundle, ds).predictions)


def test_score_batch_and_predict_file_score_an_evabyte_bundle(trained, tmp_path, capsys):
    from mlops_tpu.cli import main
    from mlops_tpu.data import generate_synthetic, write_csv_columns

    columns, labels = generate_synthetic(37, seed=3)  # 18 histories of 2 and one of 1
    write_csv_columns(tmp_path / "in.csv", columns, labels)
    common = [f"data.train_path={tmp_path / 'in.csv'}",
              f"serve.model_directory={trained.bundle_dir}"]
    assert main(["score-batch", *common, "score.chunk_rows=8", "score.exact=true",
                 f"score.output_path={tmp_path / 'out.npz'}"]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["rows"] == 37 and summary["path"] == "exact"
    scored = np.load(tmp_path / "out.npz")["predictions"]
    assert scored.shape == (37,) and np.isfinite(scored).all()

    assert main(["predict-file", *common]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    np.testing.assert_allclose(out["predictions"], scored, atol=2e-5)


def test_score_batch_says_which_flavour_it_refuses(tmp_path, monkeypatch):
    from mlops_tpu import commands

    doc = type("B", (), {"flavor": "doc"})()
    monkeypatch.setattr("mlops_tpu.bundle.load_bundle", lambda path: doc)
    config = Config()
    config.serve.model_directory = str(tmp_path)
    with pytest.raises(SystemExit, match="3-D.*evabyte"):
        commands._score_batch(config)

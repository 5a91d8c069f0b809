"""Sharded bulk scoring (BASELINE config 4) on the fake 8-device mesh."""

import numpy as np
import pytest

from mlops_tpu.bundle import load_bundle
from mlops_tpu.parallel import make_mesh
from mlops_tpu.parallel.bulk import score_dataset


@pytest.fixture(scope="module")
def flax_bundle(tiny_pipeline):
    _, result = tiny_pipeline
    return load_bundle(result.bundle_dir)


@pytest.fixture(scope="module")
def score_ds(flax_bundle):
    from mlops_tpu.data import generate_synthetic

    columns, _ = generate_synthetic(10_000, seed=99)
    return flax_bundle.preprocessor.encode(columns)


def test_sharded_matches_unsharded(flax_bundle, score_ds):
    """8-way data-parallel scoring must agree with the single-device path —
    the mesh changes layout, not math."""
    local = score_dataset(flax_bundle, score_ds, mesh=None, chunk_rows=4096)
    sharded = score_dataset(
        flax_bundle, score_ds, mesh=make_mesh(8), chunk_rows=4096
    )
    np.testing.assert_allclose(
        local.predictions, sharded.predictions, rtol=2e-2, atol=2e-3
    )
    np.testing.assert_array_equal(local.outliers, sharded.outliers)
    assert sharded.rows == 10_000
    assert sharded.rows_per_s > 0


def test_tail_chunk_padding_exact(flax_bundle, score_ds):
    """A chunk size that doesn't divide N exercises the padded tail; padded
    rows must not leak into outputs."""
    a = score_dataset(flax_bundle, score_ds, mesh=make_mesh(8), chunk_rows=4096)
    b = score_dataset(flax_bundle, score_ds, mesh=make_mesh(8), chunk_rows=2048)
    np.testing.assert_allclose(a.predictions, b.predictions, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(a.outliers, b.outliers)


def test_bulk_matches_serving_engine(flax_bundle, score_ds):
    """Bulk predictions agree with the serving engine's fused path on the
    same rows (one model, two execution surfaces)."""
    from mlops_tpu.serve import InferenceEngine

    take = 256
    engine = InferenceEngine(
        flax_bundle, buckets=(take,), enable_grouping=False
    )
    served = engine.predict_arrays(
        score_ds.cat_ids[:take], score_ds.numeric[:take]
    )
    bulk = score_dataset(
        flax_bundle, score_ds.slice(np.arange(take)), chunk_rows=take
    )
    np.testing.assert_allclose(
        np.asarray(served["predictions"], np.float32),
        bulk.predictions,
        rtol=1e-4,
        atol=1e-5,
    )


def test_bulk_empty_dataset(flax_bundle, score_ds):
    import json

    empty = score_dataset(flax_bundle, score_ds.slice(np.arange(0)))
    assert empty.rows == 0
    summary = empty.summary()
    json.dumps(summary)  # no NaN leaks into the JSON contract
    assert summary["default_rate"] == 0.0
    assert set(summary["feature_drift_batch"]) and all(
        v == 0.0 for v in summary["feature_drift_batch"].values()
    )


def test_bulk_sklearn_flavor(score_ds, encoded_small, tmp_path):
    from mlops_tpu.bundle import save_bundle
    from mlops_tpu.config import Config, ModelConfig, TrainConfig
    from mlops_tpu.models.gbm import SklearnBaseline
    from mlops_tpu.monitor import fit_monitor

    config = Config()
    model_config = ModelConfig(family="gbm", n_estimators=20, max_tree_depth=3)
    _, ds = encoded_small
    baseline = SklearnBaseline.train(model_config, TrainConfig(), ds)
    monitor = fit_monitor(ds, config.monitor, seed=0)
    prep, _ = encoded_small
    save_bundle(tmp_path / "b", model_config, baseline, prep, monitor)
    bundle = load_bundle(tmp_path / "b")

    result = score_dataset(bundle, score_ds, chunk_rows=4096)
    assert result.predictions.shape == (10_000,)
    assert ((result.predictions >= 0) & (result.predictions <= 1)).all()
    direct = baseline.predict_proba(score_ds.cat_ids, score_ds.numeric)
    np.testing.assert_allclose(result.predictions, direct, rtol=1e-6)


# ------------------------------------ the chunk program is kept (ISSUE 28)
def _tiny_mlp_bundle(width, ds):
    """A hand-made one-layer `mlp` bundle ``width`` wide: cheap to compile,
    and another architecture (another entry of the keep) per width."""
    import jax

    from mlops_tpu.bundle.bundle import Bundle
    from mlops_tpu.config import ModelConfig
    from mlops_tpu.models import build_model, init_params
    from mlops_tpu.monitor import fit_monitor

    model = build_model(ModelConfig(family="mlp", hidden_dims=(width,)))
    return Bundle(
        manifest={"flavor": "flax", "model_config": {}},
        model=model,
        variables=init_params(model, jax.random.PRNGKey(width)),
        preprocessor=None,
        monitor=fit_monitor(ds),
    )


def test_the_keep_is_bounded_and_a_dropped_program_compiles_again(score_ds):
    import gc
    import weakref

    from mlops_tpu.parallel import bulk

    ds = score_ds.slice(np.arange(600))
    widths = [8 + i for i in range(bulk.KEPT_CHUNK_PROGRAMS + 1)]
    bundles = [_tiny_mlp_bundle(width, ds) for width in widths]
    bulk.CHUNK_PROGRAMS.clear()
    oldest = bulk.make_bulk_jit(bundles[0].model, None)
    first = score_dataset(bundles[0], ds, chunk_rows=256, exact=True)
    again = score_dataset(bundles[0], ds, chunk_rows=256, exact=True)
    assert first.compile_events["chunk_program_reused"] == 0
    assert again.compile_events["chunk_program_reused"] == 1
    assert bulk.make_bulk_jit(bundles[0].model, None) is oldest
    for bundle in bundles[1:]:  # as many more models as the keep holds
        assert bulk.make_bulk_jit(bundle.model, None) is not oldest
    # the newest are all there, the least recently used is gone ...
    assert all(
        bulk.CHUNK_PROGRAMS.holding(bulk.make_bulk_jit(bundle.model, None))
        for bundle in bundles[1:]
    )
    assert bulk.CHUNK_PROGRAMS.holding(oldest) is None
    # ... its jax.jit with it, once nothing else holds that
    gone = weakref.ref(oldest)
    del oldest
    gc.collect()
    assert gone() is None
    # and a job of it compiles again, inside its warm-up, to the same bits
    back = score_dataset(bundles[0], ds, chunk_rows=256, exact=True)
    assert back.compile_events["chunk_program_reused"] == 0
    assert "fused" in back.compile_events["programs"]
    np.testing.assert_array_equal(back.predictions, first.predictions)


def test_sharded_and_unsharded_programs_never_share_an_entry(flax_bundle):
    from mlops_tpu.parallel.bulk import make_bulk_jit, make_bulk_quant_jit

    model = flax_bundle.model
    assert make_bulk_jit(model, None) is make_bulk_jit(model, None)
    assert make_bulk_jit(model, make_mesh(8)) is make_bulk_jit(model, make_mesh(8))
    assert make_bulk_jit(model, make_mesh(8)) is not make_bulk_jit(model, None)
    assert make_bulk_jit(model, make_mesh(8)) is not make_bulk_jit(model, make_mesh(4))
    assert make_bulk_quant_jit(None) is make_bulk_quant_jit(None)
    assert make_bulk_quant_jit(None) is not make_bulk_quant_jit(make_mesh(8))
    assert make_bulk_quant_jit(None) is not make_bulk_jit(model, None)


def test_second_stream_call_on_one_bundle_traces_nothing(flax_bundle, tmp_path):
    """`score_csv_stream` goes through the same warm-up rule as
    `score_dataset`: called per file, only the first call compiles."""
    from mlops_tpu.compilecache.events import compile_counter
    from mlops_tpu.data import generate_synthetic, write_csv_columns
    from mlops_tpu.data.stream import score_csv_stream
    from mlops_tpu.parallel import bulk

    columns, labels = generate_synthetic(1_500, seed=5)
    write_csv_columns(tmp_path / "in.csv", columns, labels)
    bulk.CHUNK_PROGRAMS.clear()
    counter = compile_counter()
    deltas, outputs = [], []
    for name in ("first.csv", "second.csv"):
        before = counter.snapshot()
        stats = score_csv_stream(
            flax_bundle, tmp_path / "in.csv", tmp_path / name,
            chunk_rows=512, exact=True,
        )
        deltas.append(counter.delta(before, counter.snapshot()))
        outputs.append((tmp_path / name).read_text())
        assert stats["rows"] == 1_500
    assert "fused" in deltas[0]["programs"] and deltas[0]["lower_s"] > 0
    assert deltas[1]["programs"] == [] and deltas[1]["programs_traced"] == 0
    assert deltas[1]["lower_s"] == 0 and deltas[1]["backend_compile_s"] == 0
    assert outputs[0] == outputs[1]

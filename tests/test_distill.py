"""Ensemble -> bulk-student distillation (train/distill.py) and the
CPU-backend bulk routing it enables (parallel/bulk.py use_distilled_bulk).

Addresses the measured gap: the 8-member flagship's bulk throughput loses
~9x to the reference's sklearn GBM floor on CPU (BASELINE.md config 1);
the distilled student buys it back while the fidelity record keeps the
substitution auditable.
"""

import numpy as np
import pytest

from mlops_tpu.bundle import load_bundle
from mlops_tpu.config import Config, ModelConfig, TrainConfig
from mlops_tpu.data import generate_synthetic
from mlops_tpu.parallel.bulk import score_dataset, use_distilled_bulk
from mlops_tpu.train.pipeline import run_training


@pytest.fixture(scope="module")
def ensemble_bundle_dir(tmp_path_factory):
    """A small 4-member ensemble trained through the real pipeline, which
    packages the distilled bulk student alongside."""
    root = tmp_path_factory.mktemp("distill")
    config = Config()
    config.data.rows = 4000
    config.model = ModelConfig(
        family="mlp", hidden_dims=(32, 32), embed_dim=4, ensemble_size=4
    )
    config.train = TrainConfig(steps=150, eval_every=150, batch_size=256)
    config.registry.root = str(root / "registry")
    config.registry.run_root = str(root / "runs")
    result = run_training(config, register=False)
    return result.bundle_dir


@pytest.fixture(scope="module")
def ensemble_bundle(ensemble_bundle_dir):
    return load_bundle(ensemble_bundle_dir)


def test_bundle_carries_bulk_student(ensemble_bundle):
    assert ensemble_bundle.has_bulk
    assert ensemble_bundle.bulk_variables is not None
    manifest = ensemble_bundle.manifest["bulk"]
    assert manifest["model_config"]["ensemble_size"] == 1
    fidelity = ensemble_bundle.bulk_fidelity
    assert 0.0 <= fidelity["mean_abs_prob_delta"] <= 0.2
    assert "roc_auc_delta" in fidelity


def test_student_tracks_teacher_probs(ensemble_bundle):
    """Distillation fidelity: student probabilities stay close to the
    ensemble's on fresh data (mean |delta| under a few points)."""
    columns, _ = generate_synthetic(2000, seed=41)
    ds = ensemble_bundle.preprocessor.encode(columns)
    exact = score_dataset(ensemble_bundle, ds, chunk_rows=2048, exact=True)
    distilled = score_dataset(ensemble_bundle, ds, chunk_rows=2048, exact=False)
    assert exact.path == "exact" and distilled.path == "distilled"
    assert np.mean(np.abs(exact.predictions - distilled.predictions)) < 0.05
    # Outlier flags don't depend on the classifier: identical either way.
    np.testing.assert_array_equal(exact.outliers, distilled.outliers)


def test_auto_routing_uses_student_on_cpu(ensemble_bundle):
    """Tests run on the CPU backend, so the auto route must pick the
    student — and exact=True must still force the ensemble."""
    assert use_distilled_bulk(ensemble_bundle) is True
    assert use_distilled_bulk(ensemble_bundle, exact=True) is False
    columns, _ = generate_synthetic(500, seed=42)
    ds = ensemble_bundle.preprocessor.encode(columns)
    auto = score_dataset(ensemble_bundle, ds, chunk_rows=512)
    assert auto.path == "distilled"
    assert auto.summary()["path"] == "distilled"


def test_single_model_bundle_has_no_student(tiny_pipeline):
    _, result = tiny_pipeline
    bundle = load_bundle(result.bundle_dir)
    assert not bundle.has_bulk
    assert use_distilled_bulk(bundle) is False
    columns, _ = generate_synthetic(300, seed=43)
    ds = bundle.preprocessor.encode(columns)
    assert score_dataset(bundle, ds, chunk_rows=512).path == "exact"


def test_distill_opt_out(tmp_path):
    config = Config()
    config.data.rows = 2000
    config.model = ModelConfig(
        family="mlp", hidden_dims=(16,), embed_dim=4, ensemble_size=2
    )
    config.train = TrainConfig(
        steps=60, eval_every=60, batch_size=256, distill_bulk=False
    )
    config.registry.root = str(tmp_path / "registry")
    config.registry.run_root = str(tmp_path / "runs")
    result = run_training(config, register=False)
    bundle = load_bundle(result.bundle_dir)
    assert not bundle.has_bulk


def test_serving_engine_never_uses_student(ensemble_bundle):
    """The serving engine is wired to the exact model: its predictions
    match the exact bulk path, not the student's."""
    from mlops_tpu.serve import InferenceEngine

    columns, _ = generate_synthetic(64, seed=44)
    ds = ensemble_bundle.preprocessor.encode(columns)
    engine = InferenceEngine(
        ensemble_bundle, buckets=(64,), enable_grouping=False
    )
    served = engine.predict_arrays(ds.cat_ids, ds.numeric)
    exact = score_dataset(ensemble_bundle, ds, chunk_rows=64, exact=True)
    np.testing.assert_allclose(
        served["predictions"], exact.predictions, rtol=1e-4, atol=1e-5
    )


def test_score_exact_flag_forces_ensemble(
    ensemble_bundle_dir, tmp_path, capsys
):
    """score-batch score.exact=true reports path=exact; default reports
    distilled (CPU backend) — the substitution is always visible and
    overridable from the CLI."""
    import json

    from mlops_tpu.commands import _score_batch
    from mlops_tpu.data import write_csv_columns

    columns, labels = generate_synthetic(400, seed=45)
    path = tmp_path / "in.csv"
    write_csv_columns(path, columns, labels)

    for exact, want in ((True, "exact"), (False, "distilled")):
        config = Config()
        config.data.train_path = str(path)
        config.serve.model_directory = str(ensemble_bundle_dir)
        config.score.exact = exact
        config.score.chunk_rows = 256
        assert _score_batch(config) == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["path"] == want
        # the printed summary says where the job's time went and what it
        # re-traced (the chunk program, a new jax.jit every job)
        assert list(out["phases"]) == ["build", "warmup", "sweep", "drift"]
        assert "fused" in out["compile_events"]["programs"]


# Heaviest end-to-end path (~60s serial on CPU): excluded from the
# timed tier-1 gate; CI's parallel pytest job still runs it.
@pytest.mark.slow
def test_transformer_families_also_distill(tmp_path):
    """The FT-Transformer (best measured AUC) loses CPU bulk to the
    sklearn floor just like ensembles do — the distillation gate covers
    the transformer families too."""
    config = Config()
    config.data.rows = 2000
    config.model = ModelConfig(
        family="ft_transformer", token_dim=16, depth=1, heads=2
    )
    config.train = TrainConfig(steps=60, eval_every=60, batch_size=256)
    config.registry.root = str(tmp_path / "registry")
    config.registry.run_root = str(tmp_path / "runs")
    result = run_training(config, register=False)
    bundle = load_bundle(result.bundle_dir)
    assert bundle.has_bulk
    assert bundle.manifest["bulk"]["model_config"]["family"] == "mlp"
    assert use_distilled_bulk(bundle) is True  # CPU test backend
    # Student tracks the transformer teacher on fresh rows.
    columns, _ = generate_synthetic(800, seed=46)
    ds = bundle.preprocessor.encode(columns)
    exact = score_dataset(bundle, ds, chunk_rows=512, exact=True)
    distilled = score_dataset(bundle, ds, chunk_rows=512, exact=False)
    assert np.mean(np.abs(exact.predictions - distilled.predictions)) < 0.06


def test_distilled_path_shards_over_mesh(ensemble_bundle):
    """Distilled routing composes with data-parallel scoring: the student
    sharded over the 8-device mesh matches its single-device output."""
    from mlops_tpu.parallel import make_mesh

    columns, _ = generate_synthetic(1000, seed=47)
    ds = ensemble_bundle.preprocessor.encode(columns)
    solo = score_dataset(ensemble_bundle, ds, chunk_rows=512, exact=False)
    sharded = score_dataset(
        ensemble_bundle, ds, mesh=make_mesh(8), chunk_rows=512, exact=False
    )
    assert solo.path == sharded.path == "distilled"
    np.testing.assert_allclose(
        solo.predictions, sharded.predictions, rtol=2e-2, atol=2e-3
    )
    np.testing.assert_array_equal(solo.outliers, sharded.outliers)

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


# ------------------- the tail at the smallest whole-history power of two
TAIL_RULE = {  # rows of a job, its chunk, the unit, the tail's run size
    # the benchmark's cells
    "k-exaone-236b-a23b.bulk-hist": (1228, 512, 64, 256),
    "falcon-h1-34b.bulk-hist": (1228, 512, 64, 256),
    "nemotron-labs-twotower-30b-a3b.bulk-hist": (1228, 512, 64, 256),
    "bert-base.bulk-uci": (30_000, 4096, 1, 2048),
    "bert-base.bulk": (65_000, 4096, 1, 4096),
    "bert-base.bulk-dp4": (260_000, 16_384, 4, 16_384),
    "lfm2-8b-a1b.bulk-hist": (1228, 256, 64, 256),
    "evabyte-8l.bulk-hist": (1228, 128, 64, 128),
    "kimi-k2-5l.bulk-hist": (1228, 128, 64, 128),
    # and the edges
    "rows_divide_evenly": (3 * 4096, 4096, 1, 4096),
    "histories_divide_evenly": (20 * 64, 256, 64, 256),
    "one_span": (100, 131_072, 1, 128),
    "a_history_is_never_cut": (512 + 65, 512, 64, 128),
}


@pytest.mark.parametrize("rows,chunk,unit,tail_chunk", TAIL_RULE.values(), ids=TAIL_RULE)
def test_the_tail_runs_at_the_smallest_whole_history_power_of_two(
    rows, chunk, unit, tail_chunk
):
    from mlops_tpu.parallel.bulk import tail_chunk_rows

    assert tail_chunk_rows(rows, chunk, unit) == tail_chunk


def test_the_tail_rule_is_its_definition():
    """Against the rule written as a search: the smallest ``unit × 2**k``
    that holds the last span, capped at the chunk."""
    from mlops_tpu.parallel.bulk import tail_chunk_rows

    for unit in (1, 3, 4, 64):
        for chunk in range(unit, 12 * unit + 1, unit):
            for rows in range(1, 3 * chunk + 1):
                tail = rows - (rows - 1) // chunk * chunk
                size = unit
                while size < tail:
                    size *= 2
                assert tail_chunk_rows(rows, chunk, unit) == min(size, chunk), (
                    rows, chunk, unit)


@pytest.mark.parametrize("sharded", [False, True], ids=["one_chip", "mesh8"])
def test_a_smaller_tail_answers_as_a_chunk_that_divides_the_rows(
    flax_bundle, score_ds, sharded
):
    """10,000 rows at 4,096: two runs and a tail of 1,808 rows, run at
    2,048; at 2,000 five runs and no padding. Same rows, same answers."""
    mesh = make_mesh(8) if sharded else None
    tail = score_dataset(flax_bundle, score_ds, mesh=mesh, chunk_rows=4096)
    even = score_dataset(flax_bundle, score_ds, mesh=mesh, chunk_rows=2000)
    assert (tail.record["chunks"], tail.record["tail_chunk_rows"],
            tail.record["rows_run"]) == (3, 2048, 10_240)
    assert (even.record["chunks"], even.record["tail_chunk_rows"],
            even.record["rows_run"]) == (5, 2000, 10_000)
    np.testing.assert_allclose(tail.predictions, even.predictions, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(tail.outliers, even.outliers)


def test_the_tails_program_is_made_behind_the_body_and_kept(
    flax_bundle, score_ds, monkeypatch
):
    """The first job runs a chunk of zeros at the body's size alone; its
    tail's size is traced by the tail's own first dispatch, after the
    body's chunks are dispatched; a second job of the same size finds both
    sizes ready and traces neither."""
    import functools

    from mlops_tpu.compilecache.events import compile_counter
    from mlops_tpu.parallel import bulk

    counter = compile_counter()
    runs = []
    make = bulk.make_chunk_scorer

    def counted(*args, **kwargs):
        scorer = make(*args, **kwargs)

        @functools.wraps(scorer)  # what the scorer says of itself goes along
        def score_chunk(cat, num, mask):
            runs.append((cat.shape[0], int(np.asarray(mask).sum())))
            return scorer(cat, num, mask)

        return score_chunk

    def fused_traced(before):
        return counter.snapshot()["programs"].get("fused", 0) - before.get("fused", 0)

    monkeypatch.setattr(bulk, "make_chunk_scorer", counted)
    bulk.CHUNK_PROGRAMS.clear()  # whatever ran before: this process has not seen it
    before = counter.snapshot()["programs"]
    first = score_dataset(flax_bundle, score_ds, chunk_rows=4096)
    # zeros at 4,096, then the body's two chunks, then the tail's 1,808 rows
    assert runs == [(4096, 4096), (4096, 4096), (4096, 4096), (2048, 1808)]
    assert fused_traced(before) == 2  # the body's size and the tail's
    assert first.compile_events["chunk_program_reused"] == 0
    assert first.elapsed_s >= first.phases["sweep"]
    runs.clear()
    before = counter.snapshot()["programs"]
    again = score_dataset(flax_bundle, score_ds, chunk_rows=4096)
    assert runs == [(4096, 4096), (4096, 4096), (2048, 1808)]
    assert fused_traced(before) == 0
    assert again.compile_events["chunk_program_reused"] == 1
    assert "fused" not in again.compile_events["programs"]
    np.testing.assert_array_equal(first.predictions, again.predictions)
    np.testing.assert_array_equal(first.outliers, again.outliers)


def test_a_one_span_job_runs_at_its_tail_size(flax_bundle, score_ds):
    """100 rows at the default chunk of 131,072 run as 128 rows."""
    rows = score_ds.slice(np.arange(100))
    small = score_dataset(flax_bundle, rows)
    assert (small.record["chunks"], small.record["tail_chunk_rows"],
            small.record["rows_run"]) == (1, 128, 128)
    exact = score_dataset(flax_bundle, rows, chunk_rows=100)
    assert exact.record["rows_run"] == 100
    np.testing.assert_allclose(small.predictions, exact.predictions, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(small.outliers, exact.outliers)


@pytest.mark.parametrize("data_axis", [None, 2], ids=["one_chip", "mesh2"])
def test_a_history_models_tail_runs_at_fewer_histories(data_axis):
    """A tiny `falcon_h1` (histories of 3 records): 6 histories, the last
    of 2 records, at a chunk of 4 histories. The tail (2 histories) runs
    at 2, not 4, and answers as one run of all 6."""
    from test_falcon_h1 import PER, bundle_of, rows, tiny_config

    from mlops_tpu.data.encode import EncodedDataset

    ds = EncodedDataset(*rows(5 * PER + 2))
    bundle = bundle_of(tiny_config(), ds)
    mesh = make_mesh(data_axis) if data_axis else None
    tail = score_dataset(bundle, ds, mesh=mesh, chunk_rows=4 * PER, exact=True)
    whole = score_dataset(bundle, ds, mesh=mesh, chunk_rows=6 * PER, exact=True)
    assert (tail.record["chunk_rows"], tail.record["tail_chunk_rows"],
            tail.record["rows_run"]) == (4 * PER, 2 * PER, 6 * PER)
    assert whole.record["rows_run"] == 6 * PER  # 8 histories would pass the chunk
    np.testing.assert_allclose(tail.predictions, whole.predictions, atol=2e-6)
    np.testing.assert_array_equal(tail.outliers, whole.outliers)


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


def test_a_first_job_longer_than_a_wave_answers_as_a_later_one(score_ds):
    """600 rows at 16: 37 runs and a tail of 8. A process's first job of
    that tail dispatches a wave of the body (`FETCH_WAVE`) and the tail on
    its own thread, the executor the 5 runs left; a later job sends all 38
    through the executor. Same answers, as those of a chunk that divides
    the rows."""
    from mlops_tpu.parallel import bulk

    ds = score_ds.slice(np.arange(600))
    bundle = _tiny_mlp_bundle(12, ds)
    bulk.CHUNK_PROGRAMS.clear()
    first = score_dataset(bundle, ds, chunk_rows=16, exact=True)
    later = score_dataset(bundle, ds, chunk_rows=16, exact=True)
    even = score_dataset(bundle, ds, chunk_rows=24, exact=True)
    assert (first.record["chunks"], first.record["tail_chunk_rows"],
            first.record["rows_run"]) == (38, 8, 600)
    assert first.record["stages"]["compute"]["items"] == 38 - bulk.FETCH_WAVE - 1
    assert later.record["stages"]["compute"]["items"] == 38
    assert later.compile_events["chunk_program_reused"] == 1
    for result in (first, later):
        np.testing.assert_allclose(result.predictions, even.predictions, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(result.outliers, even.outliers)


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

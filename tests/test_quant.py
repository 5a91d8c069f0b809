"""ISSUE 17 quantized-tier contracts: the int8/bf16 student's Pallas
kernel is BIT-IDENTICAL to its jnp composite at every serve bucket and
group geometry, the distilled tier's fidelity sits numerically inside the
promotion gates it shipped with, bundles round-trip the quant tree
losslessly (and refuse foreign packing formats), and the serving/bulk
tier selectors honor demand-vs-preference semantics end to end.
"""

import dataclasses
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlops_tpu.monitor import init_accumulator
from mlops_tpu.ops.predict import packed_layout
from mlops_tpu.ops.quant import (
    QUANT_EMBED_DIM,
    QUANT_FORMAT,
    QUANT_HIDDEN,
    abstract_quant_params,
    dequantize_dense,
    quant_params_from_arrays,
    quant_params_geometry,
    quant_params_to_arrays,
    quantize_dense,
)
from mlops_tpu.ops.quant_kernel import (
    QUANT_KERNEL_MAX_ROWS,
    make_quant_grouped_base,
    KERNEL_COMPOSITE_ATOL,
    make_quant_packed_base,
)
from mlops_tpu.schema import SCHEMA, records_to_columns
from mlops_tpu.serve.engine import (
    GROUP_ROW_BUCKET,
    GROUP_ROW_BUCKETS,
    GROUP_SLOT_BUCKETS,
    InferenceEngine,
)
from mlops_tpu.serve.wire import format_response


@pytest.fixture(scope="module")
def quant_pipeline(tmp_path_factory):
    """One training run with the quant tier opted in (the tiny_pipeline
    geometry + ``train.distill_quant``): teacher, monitors, AND the
    graded int8/bf16 student in one bundle."""
    from mlops_tpu.config import Config, ModelConfig, TrainConfig
    from mlops_tpu.train.pipeline import run_training

    root = tmp_path_factory.mktemp("quant_pipeline")
    config = Config()
    config.data.rows = 3000
    config.model = ModelConfig(family="mlp", hidden_dims=(32, 32), embed_dim=4)
    config.train = TrainConfig(
        steps=100, eval_every=100, batch_size=256, distill_quant=True
    )
    config.registry.root = str(root / "registry")
    config.registry.run_root = str(root / "runs")
    result = run_training(config)
    return config, result


@pytest.fixture(scope="module")
def quant_bundle(quant_pipeline):
    from mlops_tpu.bundle import load_bundle

    _, result = quant_pipeline
    return load_bundle(result.bundle_dir)


@pytest.fixture(scope="module")
def quant_engine(quant_bundle):
    """Quant-tier serving engine, warmed on demand (novel shapes compile
    into the exec table on first sight — no warmup() needed)."""
    return InferenceEngine(quant_bundle, buckets=(1, 8), serve_tier="quant")


@pytest.fixture(scope="module")
def encoded_batch(quant_bundle):
    """A held-out encoded batch through the BUNDLE's preprocessor (the
    arrays every tier consumes)."""
    from mlops_tpu.data import generate_synthetic

    columns, labels = generate_synthetic(512, seed=3)
    return quant_bundle.preprocessor.encode(columns, labels)


# ----------------------------------------------------------- quantization
def test_quantize_dense_roundtrip_properties():
    """Per-output-channel symmetric int8: dequant error is bounded by half
    a quantization step per column, the column absmax maps to the ±127
    rail exactly, and all-zero columns stay exactly zero (scale 1)."""
    rng = np.random.default_rng(0)
    w = rng.normal(size=(40, 8)).astype(np.float32) * rng.uniform(
        0.1, 30.0, size=(1, 8)
    ).astype(np.float32)
    w[:, 3] = 0.0
    q, s = quantize_dense(w)
    assert q.dtype == np.int8 and s.dtype == np.float32
    assert s[3] == 1.0 and not q[:, 3].any()
    live = [j for j in range(8) if j != 3]
    assert all(np.abs(q[:, j]).max() == 127 for j in live)
    deq = np.asarray(dequantize_dense(jnp.asarray(q), jnp.asarray(s)))
    assert np.all(np.abs(deq - w) <= s[None, :] * 0.5 + 1e-6)
    assert not deq[:, 3].any()


def test_quant_tree_matches_abstract_twin(quant_bundle):
    """The fitted tree's shapes/dtypes ARE the abstract cache-key twin
    (`abstract_quant_params`) — a drift here silently forks the AOT cache
    keys from the programs production dispatches."""
    qp = quant_bundle.quant_params
    twin = abstract_quant_params()
    assert set(qp) == set(twin)
    for key, aval in twin.items():
        assert qp[key].shape == aval.shape, key
        assert qp[key].dtype == aval.dtype, key
    assert quant_params_geometry(qp) == (QUANT_EMBED_DIM, QUANT_HIDDEN)


def test_quant_serialization_roundtrip_bitwise(quant_bundle):
    """npz arrays -> jnp tree -> npz arrays is lossless: bf16 -> f32 is
    exact and the f32 -> bf16 cast returns the original bits."""
    qp = quant_bundle.quant_params
    back = quant_params_from_arrays(quant_params_to_arrays(qp))
    assert set(back) == set(qp)
    for key in qp:
        assert back[key].dtype == qp[key].dtype, key
        np.testing.assert_array_equal(
            np.asarray(back[key].astype(jnp.float32)),
            np.asarray(qp[key].astype(jnp.float32)),
            err_msg=key,
        )


# ------------------------------------------------- kernel/composite parity
def _padded_solo(ds, n, bucket):
    cat = np.zeros((bucket, SCHEMA.num_categorical), np.int32)
    num = np.zeros((bucket, SCHEMA.num_numeric), np.float32)
    cat[:n] = ds.cat_ids[:n]
    num[:n] = ds.numeric[:n]
    return cat, num, np.arange(bucket) < n


def _assert_trees_bitwise(got, want, label):
    flat_g, _ = jax.tree_util.tree_flatten(got)
    flat_w, _ = jax.tree_util.tree_flatten(want)
    assert len(flat_g) == len(flat_w)
    for g, w in zip(flat_g, flat_w):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=label
        )


def _assert_trees_close(got, want, label):
    """Kernel vs composite: the ONE tolerance of that contract
    (`ops/quant_kernel.py KERNEL_COMPOSITE_ATOL`). The pallas_call body
    and the XLA fusion evaluate the same expressions in different orders,
    so they differ by f32 rounding (an ulp on this backend) — never
    bit-equal by contract."""
    flat_g, _ = jax.tree_util.tree_flatten(got)
    flat_w, _ = jax.tree_util.tree_flatten(want)
    assert len(flat_g) == len(flat_w)
    for g, w in zip(flat_g, flat_w):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=0, atol=KERNEL_COMPOSITE_ATOL,
            err_msg=label,
        )


def test_kernel_vs_composite_parity_every_bucket(
    quant_bundle, encoded_batch
):
    """The ISSUE 17 parity pin, solo family: the forced pallas_call
    (interpret mode, chosen here) and the jnp composite produce the same
    packed buffers and accumulator folds, to KERNEL_COMPOSITE_ATOL, at
    every serve bucket up to the kernel's row ceiling — partial masks
    included. Both routes are jitted (the serving comparison is
    compiled-vs-compiled)."""
    qp, mon = quant_bundle.quant_params, quant_bundle.monitor
    t = np.float32(quant_bundle.quant_temperature)
    kernel = jax.jit(make_quant_packed_base(use_kernel=True, interpret=True))
    composite = jax.jit(make_quant_packed_base(use_kernel=False))
    for bucket in (1, 8, 64, QUANT_KERNEL_MAX_ROWS):
        n = 1 if bucket == 1 else bucket - 3
        cat, num, mask = _padded_solo(encoded_batch, n, bucket)
        got = kernel(qp, mon, init_accumulator(), t, cat, num, mask)
        want = composite(qp, mon, init_accumulator(), t, cat, num, mask)
        _assert_trees_close(got, want, f"bucket {bucket}")
        # The packed buffer is the exact tier's layout: finite, probs in
        # [0, 1], flags in {0, 1}, padding rows zero-masked.
        arr = np.asarray(got[0])
        p, o, _ = packed_layout(bucket)
        assert np.isfinite(arr).all()
        assert (0.0 <= arr[p][:n]).all() and (arr[p][:n] <= 1.0).all()
        assert set(np.unique(arr[o])) <= {0.0, 1.0}


def test_kernel_vs_composite_parity_every_group_geometry(
    quant_bundle, encoded_batch
):
    """Grouped family: every (slots, rows) shape the engine's group grid
    serves, with per-slot partial masks — the vmapped pallas_call against
    the vmapped composite, to KERNEL_COMPOSITE_ATOL on the [S, 2R+D]
    packed stack AND the grouped accumulator fold."""
    qp, mon = quant_bundle.quant_params, quant_bundle.monitor
    t = np.float32(quant_bundle.quant_temperature)
    kernel = jax.jit(make_quant_grouped_base(use_kernel=True, interpret=True))
    composite = jax.jit(make_quant_grouped_base(use_kernel=False))
    ds = encoded_batch
    for slots in GROUP_SLOT_BUCKETS:
        for rows in GROUP_ROW_BUCKETS:
            cat = np.zeros(
                (slots, rows, SCHEMA.num_categorical), np.int32
            )
            num = np.zeros((slots, rows, SCHEMA.num_numeric), np.float32)
            mask = np.zeros((slots, rows), bool)
            for i in range(slots):
                k = (i % rows) + 1
                lo = (i * rows) % (ds.n - rows)
                cat[i, :k] = ds.cat_ids[lo : lo + k]
                num[i, :k] = ds.numeric[lo : lo + k]
                mask[i, :k] = True
            got = kernel(qp, mon, init_accumulator(), t, cat, num, mask)
            want = composite(
                qp, mon, init_accumulator(), t, cat, num, mask
            )
            _assert_trees_close(got, want, f"group {slots}x{rows}")


def test_auto_route_lowers_the_composite_off_tpu(
    quant_bundle, encoded_batch
):
    """`use_kernel=None` is the production route: lowered for the CPU it
    is the composite — and therefore equals the explicit composite
    bitwise — while a forced kernel without ``interpret`` is refused
    there rather than interpreted behind the caller's back."""
    qp, mon = quant_bundle.quant_params, quant_bundle.monitor
    t = np.float32(quant_bundle.quant_temperature)
    cat, num, mask = _padded_solo(encoded_batch, 5, 8)
    auto = jax.jit(make_quant_packed_base())(
        qp, mon, init_accumulator(), t, cat, num, mask
    )
    composite = jax.jit(make_quant_packed_base(use_kernel=False))(
        qp, mon, init_accumulator(), t, cat, num, mask
    )
    _assert_trees_bitwise(auto, composite, "auto-vs-composite")
    with pytest.raises(Exception, match="(?i)interpret|cpu|platform"):
        jax.jit(make_quant_packed_base(use_kernel=True))(
            qp, mon, init_accumulator(), t, cat, num, mask
        )


# ------------------------------------------------------------ fidelity pin
def test_quant_fidelity_pinned_inside_promotion_gates(quant_bundle):
    """The numeric acceptance pin: the shipped tier's held-out AUC delta
    and ECE sit inside the SAME promotion-gate thresholds the engine
    admits it by (`lifecycle/promote.py quant_tier_gates`), and those
    thresholds are pinned numerically so a config drift cannot quietly
    loosen the tier."""
    from mlops_tpu.config import LifecycleConfig

    gates = LifecycleConfig()
    assert gates.max_auc_drop == 0.01
    assert gates.max_ece == 0.1
    assert quant_bundle.has_quant
    assert quant_bundle.quant_gates_passed
    fidelity = quant_bundle.quant_fidelity
    assert fidelity["roc_auc_delta"] >= -gates.max_auc_drop
    assert 0.0 <= fidelity["ece"] <= gates.max_ece
    # The tier carries its OWN refit temperature (quantization shifts the
    # logit scale) — a positive, finite calibration scalar.
    assert 0.0 < quant_bundle.quant_temperature < 100.0


def test_bundle_refuses_foreign_quant_format(quant_pipeline, tmp_path):
    """A quant blob written by a different packing scheme must refuse to
    load (wrong-format params would serve garbage bit patterns), naming
    the format it found."""
    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.bundle.bundle import MANIFEST_NAME

    _, result = quant_pipeline
    clone = tmp_path / "foreign"
    shutil.copytree(result.bundle_dir, clone)
    manifest = json.loads((clone / MANIFEST_NAME).read_text())
    assert manifest["quant"]["format"] == QUANT_FORMAT
    manifest["quant"]["format"] = "int4-blockwise/v9"
    (clone / MANIFEST_NAME).write_text(json.dumps(manifest))
    with pytest.raises(ValueError, match="int4-blockwise/v9"):
        load_bundle(clone)


# ------------------------------------------------------------ serving tier
def test_engine_tier_resolution_demand_vs_preference(quant_bundle):
    """`serve_tier` semantics: "quant" on a gated bundle takes the tier,
    "auto" prefers it, a demand against an ineligible bundle RAISES
    (never a silent downgrade), "auto" falls back to exact, and an
    unknown tier name is rejected."""
    assert (
        InferenceEngine(
            quant_bundle, buckets=(1,), enable_grouping=False,
            serve_tier="auto",
        ).serve_tier
        == "quant"
    )
    with pytest.raises(ValueError, match="serve_tier"):
        InferenceEngine(quant_bundle, buckets=(1,), serve_tier="int8")
    naked = dataclasses.replace(quant_bundle, quant_params=None)
    with pytest.raises(ValueError, match="no quant params"):
        InferenceEngine(naked, buckets=(1,), serve_tier="quant")
    assert (
        InferenceEngine(
            naked, buckets=(1,), enable_grouping=False, serve_tier="auto"
        ).serve_tier
        == "exact"
    )
    # Present but ungated: the stamp is the admission check, not presence.
    ungated_manifest = json.loads(json.dumps(quant_bundle.manifest))
    ungated_manifest["quant"]["gates"]["passed"] = False
    ungated = dataclasses.replace(quant_bundle, manifest=ungated_manifest)
    with pytest.raises(ValueError, match="promotion"):
        InferenceEngine(ungated, buckets=(1,), serve_tier="quant")


def test_quant_engine_solo_bit_identical_to_composite(
    quant_engine, quant_bundle, sample_request
):
    """The quant ENGINE's wire responses (padded packed path, both warmed
    buckets) equal the jitted composite reference bit for bit — same
    packed layout, same f64 cast and round(6) drift discipline as the
    exact tier."""
    assert quant_engine.serve_tier == "quant"
    qp, mon = quant_bundle.quant_params, quant_bundle.monitor
    t = np.float32(quant_bundle.quant_temperature)
    reference = jax.jit(make_quant_packed_base(use_kernel=False))
    for bucket, n in ((1, 1), (8, 5)):
        records = []
        for i in range(n):
            rec = dict(sample_request[0])
            rec["age"] = 25.0 + 3.0 * i + bucket
            rec["bill_amount_1"] = 200.0 * (i + 1)
            records.append(rec)
        ds = quant_bundle.preprocessor.encode(records_to_columns(records))
        got = quant_engine.predict_arrays(ds.cat_ids, ds.numeric)
        cat, num, mask = (
            np.pad(ds.cat_ids, ((0, bucket - n), (0, 0))),
            np.pad(ds.numeric, ((0, bucket - n), (0, 0))),
            np.arange(bucket) < n,
        )
        packed, _ = reference(qp, mon, init_accumulator(), t, cat, num, mask)
        arr = np.asarray(jax.device_get(packed))
        p, o, d = packed_layout(bucket)
        want = format_response(
            arr[p][:n].astype(float),
            arr[o][:n].astype(float),
            arr[d].astype(float).round(6),
        )
        assert got == want, f"bucket {bucket} diverged"


def test_quant_engine_grouped_bit_identical_to_composite(
    quant_engine, quant_bundle, sample_request
):
    """Grouped quant serving: mixed-size concurrent requests through
    `predict_group` equal the vmapped composite reference assembly — per
    request, drift over each slot's OWN rows."""
    import bisect

    sizes = (1, 3, 2)
    requests = []
    for i, size in enumerate(sizes):
        rec = dict(sample_request[0])
        rec["age"] = 30.0 + 7.0 * i
        rec["credit_limit"] = 5000.0 + 900.0 * i
        requests.append([rec] * size)
    got = quant_engine.predict_group(requests)

    qp, mon = quant_bundle.quant_params, quant_bundle.monitor
    t = np.float32(quant_bundle.quant_temperature)
    slots = GROUP_SLOT_BUCKETS[
        bisect.bisect_left(GROUP_SLOT_BUCKETS, len(requests))
    ]
    rows = GROUP_ROW_BUCKETS[0] if max(sizes) == 1 else GROUP_ROW_BUCKET
    cat = np.zeros((slots, rows, SCHEMA.num_categorical), np.int32)
    num = np.zeros((slots, rows, SCHEMA.num_numeric), np.float32)
    mask = np.zeros((slots, rows), bool)
    flat = [record for records in requests for record in records]
    ds = quant_bundle.preprocessor.encode(records_to_columns(flat))
    offset = 0
    for i, k in enumerate(sizes):
        cat[i, :k] = ds.cat_ids[offset : offset + k]
        num[i, :k] = ds.numeric[offset : offset + k]
        mask[i, :k] = True
        offset += k
    packed, _ = jax.jit(make_quant_grouped_base(use_kernel=False))(
        qp, mon, init_accumulator(), t, cat, num, mask
    )
    arr = np.asarray(jax.device_get(packed))
    p, o, d = packed_layout(rows)
    want = [
        format_response(
            arr[i, p][:k].astype(float),
            arr[i, o][:k].astype(float),
            arr[i, d].astype(float).round(6),
        )
        for i, k in enumerate(sizes)
    ]
    assert got == want


# --------------------------------------------------------------- bulk tier
def test_use_quant_bulk_demand_vs_preference(quant_bundle):
    from mlops_tpu.parallel.bulk import use_quant_bulk

    assert use_quant_bulk(quant_bundle, "quant")
    assert use_quant_bulk(quant_bundle, "auto")
    assert not use_quant_bulk(quant_bundle, "exact")
    naked = dataclasses.replace(quant_bundle, quant_params=None)
    assert not use_quant_bulk(naked, "auto")
    with pytest.raises(ValueError, match="refused"):
        use_quant_bulk(naked, "quant")
    with pytest.raises(ValueError, match="tier"):
        use_quant_bulk(quant_bundle, "int8")


def test_bulk_quant_sweep_bit_identical_to_reference(
    quant_bundle, encoded_batch
):
    """`score_dataset(tier="quant")` equals the raw jitted quant chunk
    program applied chunk by chunk (int8 cat transport, padded tail) —
    and the "auto" route takes the identical path on a gated bundle."""
    from mlops_tpu.parallel.bulk import make_bulk_quant_fused, score_dataset

    ds = encoded_batch
    chunk = 256
    result = score_dataset(quant_bundle, ds, chunk_rows=chunk, tier="quant")
    assert result.path == "quant"
    assert result.rows == ds.n

    fn = jax.jit(make_bulk_quant_fused())
    qp, mon = quant_bundle.quant_params, quant_bundle.monitor
    t = np.float32(quant_bundle.quant_temperature)
    want = np.empty(ds.n, np.float32)
    for start in range(0, ds.n, chunk):
        stop = min(start + chunk, ds.n)
        cat = np.zeros((chunk, SCHEMA.num_categorical), np.int8)
        num = np.zeros((chunk, SCHEMA.num_numeric), np.float32)
        cat[: stop - start] = ds.cat_ids[start:stop].astype(np.int8)
        num[: stop - start] = ds.numeric[start:stop]
        mask = np.arange(chunk) < (stop - start)
        probs, _ = fn(qp, mon, t, cat, num, mask)
        want[start:stop] = np.asarray(probs)[: stop - start]
    np.testing.assert_array_equal(result.predictions, want)

    auto = score_dataset(quant_bundle, ds, chunk_rows=chunk, tier="auto")
    assert auto.path == "quant"
    np.testing.assert_array_equal(auto.predictions, result.predictions)
    exact = score_dataset(quant_bundle, ds, chunk_rows=chunk, tier="exact")
    assert exact.path == "exact"  # mlp teacher: no bulk student distilled


# ----------------------------------------------------- compile-cache jobs
def test_quant_warmup_jobs_carry_their_entry_ids(quant_bundle):
    """The quant tier's cache-entry family: registered ids, per-bucket
    serve jobs, grouped-grid jobs, and the bulk chunk job keyed apart
    from the exact path by the quant format + geometry fingerprint."""
    from mlops_tpu.compilecache.registry import CACHE_ENTRY_IDS
    from mlops_tpu.compilecache.warmup import (
        bulk_quant_chunk_job,
        serve_quant_group_jobs,
        serve_quant_jobs,
    )

    assert "serve-predict-quant-packed" in CACHE_ENTRY_IDS
    assert "serve-predict-quant-group-packed" in CACHE_ENTRY_IDS
    qp, mon = quant_bundle.quant_params, quant_bundle.monitor
    t = quant_bundle.quant_temperature

    jobs = serve_quant_jobs(qp, mon, buckets=(1, 8), temperature=t)
    assert [j.entry_id for j in jobs] == ["serve-predict-quant-packed"] * 2
    assert len({j.config_hash for j in jobs}) == 1  # one geometry, one key

    gjobs = serve_quant_group_jobs(qp, mon, grid=[(2, 8)], temperature=t)
    assert [j.entry_id for j in gjobs] == ["serve-predict-quant-group-packed"]

    bulk = bulk_quant_chunk_job(qp, mon, chunk_rows=4096)
    assert bulk.entry_id == "bulk-score-chunk"
    assert bulk.label == "bulk-score-chunk/quant-c4096"
    assert bulk.meta == {"chunk_rows": 4096, "path": "quant"}
    # Keyed apart from the serve family AND from any exact-path chunk job
    # (the exact path fingerprints the flax model config; quant
    # fingerprints the packing format + geometry).
    assert bulk.config_hash != jobs[0].config_hash

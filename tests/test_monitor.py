"""Monitor tests: scipy parity for the statistics, drift/outlier semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlops_tpu.config import MonitorConfig
from mlops_tpu.monitor import MonitorState, drift_scores, fit_monitor, outlier_flags
from mlops_tpu.ops.drift import chi2_two_sample, ks_two_sample
from mlops_tpu.ops.outlier import fit_mahalanobis, mahalanobis_sq
from mlops_tpu.schema import NUM_FEATURES


def test_chi2_matches_scipy():
    scipy_stats = pytest.importorskip("scipy.stats")
    rng = np.random.default_rng(0)
    ref = rng.multinomial(5000, [0.5, 0.3, 0.15, 0.05]).astype(float)
    batch = rng.multinomial(300, [0.4, 0.35, 0.15, 0.10]).astype(float)
    stat, p = chi2_two_sample(jnp.asarray(ref), jnp.asarray(batch))
    ref_stat, ref_p, _, _ = scipy_stats.chi2_contingency(
        np.stack([ref, batch]), correction=False
    )
    assert abs(float(stat) - ref_stat) < 1e-3
    assert abs(float(p) - ref_p) < 1e-5


def test_chi2_empty_categories_masked():
    # Categories observed in neither sample must not poison the statistic.
    ref = jnp.asarray([100.0, 50.0, 0.0, 0.0])
    batch = jnp.asarray([40.0, 20.0, 0.0, 0.0])
    stat, p = chi2_two_sample(ref, batch)
    assert np.isfinite(float(stat))
    assert float(p) > 0.9  # same distribution -> no drift


def test_ks_matches_scipy_asymp():
    scipy_stats = pytest.importorskip("scipy.stats")
    rng = np.random.default_rng(1)
    ref = np.sort(rng.normal(size=2048)).astype(np.float32)
    batch = rng.normal(0.3, 1.0, size=256).astype(np.float32)
    stat, p = ks_two_sample(jnp.asarray(ref), jnp.asarray(batch))
    res = scipy_stats.ks_2samp(ref, batch, method="asymp")
    assert abs(float(stat) - res.statistic) < 1e-6
    # Asymptotic formulas differ slightly (Stephens correction) — tight but
    # not exact.
    assert abs(float(p) - res.pvalue) < 5e-3


def test_ks_identical_distribution_high_p():
    rng = np.random.default_rng(2)
    sample = rng.normal(size=2048).astype(np.float32)
    stat, p = ks_two_sample(jnp.asarray(np.sort(sample)), jnp.asarray(sample))
    assert float(stat) < 1e-6
    assert float(p) > 0.99


def test_mahalanobis_flags_quantile():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(5000, 14)).astype(np.float32)
    mean, precision, threshold = fit_mahalanobis(x, quantile=0.95)
    d = mahalanobis_sq(jnp.asarray(x), jnp.asarray(mean), jnp.asarray(precision))
    frac = float((np.asarray(d) > threshold).mean())
    assert abs(frac - 0.05) < 0.01  # ~5% of training data flagged


def test_monitor_fit_and_score_in_distribution(encoded_small):
    _, ds = encoded_small
    state = fit_monitor(ds, MonitorConfig())
    scores = drift_scores(state, jnp.asarray(ds.cat_ids), jnp.asarray(ds.numeric))
    assert scores.shape == (NUM_FEATURES,)
    # Scoring the training data against itself: no drift anywhere.
    assert float(np.max(np.asarray(scores))) < 0.95
    flags = outlier_flags(state, jnp.asarray(ds.numeric))
    assert set(np.unique(np.asarray(flags))) <= {0.0, 1.0}
    assert 0.01 < float(np.mean(np.asarray(flags))) < 0.10


def test_monitor_detects_shift(encoded_small):
    from mlops_tpu.data import Preprocessor, generate_synthetic

    prep, ds = encoded_small
    state = fit_monitor(ds, MonitorConfig())
    shifted_cols, _ = generate_synthetic(1000, seed=99, drift=1.5)
    shifted = prep.encode(shifted_cols)
    scores = drift_scores(
        state, jnp.asarray(shifted.cat_ids), jnp.asarray(shifted.numeric)
    )
    # The drifted generator shifts age/credit distributions and repayment
    # behavior: a majority of features should cross 1 - p_val > 0.95.
    assert float(np.mean(np.asarray(scores) > 0.95)) > 0.5


def test_monitor_state_save_load(tmp_path, encoded_small):
    _, ds = encoded_small
    state = fit_monitor(ds, MonitorConfig())
    state.save(tmp_path / "monitor")
    state2 = MonitorState.load(tmp_path / "monitor")
    np.testing.assert_array_equal(
        np.asarray(state.cat_ref_counts), np.asarray(state2.cat_ref_counts)
    )
    np.testing.assert_array_equal(
        np.asarray(state.out_precision), np.asarray(state2.out_precision)
    )


def test_ks_small_masked_matches_pooled():
    """The dense-comparison small-batch K-S (grouped serving hot path) is
    bit-equivalent to the pooled sort/searchsorted form — incl. ties,
    padding, duplicate reference values, and the all-padded guard."""
    import numpy as np

    from mlops_tpu.monitor.state import _ref_cdf
    from mlops_tpu.ops.drift import (
        ks_two_sample_masked,
        ks_two_sample_small_masked,
    )

    rng = np.random.default_rng(5)
    ref = np.sort(
        np.round(rng.normal(size=256), 1).astype(np.float32)
    )  # rounding forces ties
    ref_cdf = _ref_cdf(ref[None, :])[0]
    for n_valid in (0, 1, 3, 8):
        batch = np.round(rng.normal(size=8), 1).astype(np.float32)
        batch[0:1] = ref[10]  # tie against the reference
        mask = np.arange(8) < n_valid
        s1, p1 = ks_two_sample_masked(ref, batch, mask)
        s2, p2 = ks_two_sample_small_masked(ref, ref_cdf, batch, mask)
        np.testing.assert_allclose(float(s1), float(s2), atol=1e-6)
        np.testing.assert_allclose(float(p1), float(p2), atol=1e-6)


def _ks_searchsorted(ref_sorted, batch, mask):
    """The masked K-S statistic by its definition: both right-continuous
    ECDFs read by ``searchsorted`` at every pooled point, padded rows +inf
    and left out of the batch's denominator."""
    r = ref_sorted.shape[0]
    ref_sorted = ref_sorted.astype(jnp.float32)
    bvals = jnp.where(mask, batch.astype(jnp.float32), jnp.inf)
    batch_sorted = jnp.sort(bvals)
    n_valid = jnp.maximum(mask.sum().astype(jnp.float32), 1.0)
    pooled = jnp.concatenate([ref_sorted, batch_sorted])
    ref_cdf = (
        jnp.searchsorted(ref_sorted, pooled, side="right") / r
    ).astype(jnp.float32)
    batch_counts = jnp.searchsorted(batch_sorted, pooled, side="right")
    batch_cdf = jnp.minimum(batch_counts.astype(jnp.float32), n_valid) / n_valid
    statistic = jnp.where(
        jnp.isfinite(pooled), jnp.abs(ref_cdf - batch_cdf), 0.0
    ).max()
    return jnp.where(mask.any(), statistic, 0.0)


# (reference rows, batch rows, real batch rows, decimals the values keep):
# one decimal or none makes runs of equal values, within and across the
# two samples. Where both denominators are powers of two every ECDF value
# and difference is exact in float32, so scipy's float64 statistic is the
# same number.
KS_CASES = {
    "ties_batch_above_ref": (256, 1024, 1024, 0),
    "ties_batch_below_ref": (512, 128, 128, 1),
    "padded": (256, 1000, 512, 1),
    "all_padded": (256, 300, 0, 1),
    "uneven_denominators": (300, 700, 555, 1),
}


def _ks_case(r, b, n_valid, decimals):
    rng = np.random.default_rng([r, b, n_valid])
    ref = np.sort(np.round(rng.normal(size=r), decimals).astype(np.float32))
    batch = np.round(rng.normal(0.2, 1.1, size=b), decimals).astype(np.float32)
    batch[: b // 4] = rng.choice(ref, b // 4)  # values both samples hold
    return ref, batch, np.arange(b) < n_valid


@pytest.mark.parametrize("case", list(KS_CASES))
def test_ks_masked_is_the_searchsorted_statistic(case):
    """The sort-and-count K-S of large batches gives the same bits as the
    ``searchsorted`` definition, eagerly and compiled, and scipy's number
    where the denominators make it exact in float32."""
    from mlops_tpu.ops.drift import ks_two_sample_masked

    r, b, n_valid, decimals = KS_CASES[case]
    ref, batch, mask = _ks_case(r, b, n_valid, decimals)
    for run in (lambda f: f, jax.jit):
        stat, p = run(ks_two_sample_masked)(ref, batch, mask)
        assert stat.dtype == p.dtype == jnp.float32
        want = run(_ks_searchsorted)(ref, batch, mask)
        assert np.asarray(stat).tobytes() == np.asarray(want).tobytes()
    if n_valid == 0:
        assert float(stat) == 0.0
    elif r & (r - 1) == 0 and n_valid & (n_valid - 1) == 0:
        scipy_stats = pytest.importorskip("scipy.stats")
        res = scipy_stats.ks_2samp(ref, batch[mask], method="asymp")
        assert float(stat) == res.statistic


def test_ks_masked_stays_float32_under_x64():
    """The gbm-tensor tier traces the monitors in an x64 context, where
    ``arange`` gives int64: the statistic and p-value stay float32 and the
    same bits as the definition there."""
    from mlops_tpu.ops.drift import ks_two_sample_masked

    ref, batch, mask = _ks_case(*KS_CASES["padded"])
    with jax.enable_x64(True):
        stat, p = jax.jit(ks_two_sample_masked)(ref, batch, mask)
        want = jax.jit(_ks_searchsorted)(ref, batch, mask)
    assert stat.dtype == p.dtype == jnp.float32
    assert np.asarray(stat).tobytes() == np.asarray(want).tobytes()


def test_ks_masked_holds_no_loop_and_no_gather():
    """Vmapped over features, as ``drift_scores`` runs it, the K-S is a
    sort and a running count: no ``while`` and no ``gather`` (what a
    vmapped ``searchsorted`` lowers to)."""
    from mlops_tpu.ops.drift import ks_two_sample_masked

    program = jax.jit(jax.vmap(ks_two_sample_masked, in_axes=(0, 0, None)))
    shapes = (
        jax.ShapeDtypeStruct((14, 2048), jnp.float32),
        jax.ShapeDtypeStruct((14, 65536), jnp.float32),
        jax.ShapeDtypeStruct((65536,), jnp.bool_),
    )
    jaxpr = str(jax.make_jaxpr(program)(*shapes))
    lowered = program.lower(*shapes).as_text()
    for text in (jaxpr, lowered):
        assert "while" not in text and "gather" not in text
    assert "sort" in lowered


def test_monitor_state_backcompat_without_ref_cdf(encoded_small):
    """Bundles saved before num_ref_cdf existed load and score identically."""
    import numpy as np

    from mlops_tpu.monitor.state import MonitorState, fit_monitor

    _, ds = encoded_small
    state = fit_monitor(ds)
    arrays = state.to_arrays()
    arrays.pop("num_ref_cdf")
    revived = MonitorState.from_arrays(arrays)
    np.testing.assert_allclose(
        np.asarray(revived.num_ref_cdf), np.asarray(state.num_ref_cdf)
    )

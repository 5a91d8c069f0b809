"""Each plain reference against the program, at a tiny width in float32 on
the CPU, on the benchmark's own seeded weights and rows."""

import numpy as np
import pytest
from conftest import CONFIGS


def program_model(spec):
    from mlops_tpu.config import ModelConfig
    from mlops_tpu.models import abstract_variables, build_model

    fields = dict(spec["model_config"])
    fields["hidden_dims"] = tuple(fields["hidden_dims"])
    model = build_model(ModelConfig(**fields))
    return model, abstract_variables(model)


@pytest.mark.parametrize("name", CONFIGS)
def test_model_reference_matches_program(tiny_files, name):
    import importlib

    import jax

    from benchmark import inputs

    spec, traffic = tiny_files[name]
    model, abstract = program_model(spec)
    weights = inputs.make_weights(abstract, seed=2**31 + 7)
    cat, num = inputs.make_file(5, spec["schema"], traffic["data"], 300)
    with jax.default_matmul_precision("highest"):
        served = np.asarray(model.apply(weights, cat, num, train=False))
    reference = importlib.import_module(f"benchmark.reference.{spec['model_config']['family']}")
    expected = np.asarray(reference.logits(weights, cat, num, spec))
    assert np.abs(served).max() > 0.05  # not a degenerate all-zero model
    np.testing.assert_allclose(served, expected, atol=3e-5)
    low = np.asarray(reference.logits(weights, cat, num, spec, precision="fp8"))
    assert np.abs(low - expected).max() > 30 * np.abs(served - expected).max()


def test_monitor_reference_matches_program(tiny_files):
    import jax.numpy as jnp

    from benchmark import inputs
    from benchmark.reference import monitors
    from mlops_tpu.monitor.state import MonitorState, drift_scores, outlier_flags

    spec, traffic = tiny_files[CONFIGS[0]]
    arrays = inputs.fit_monitor_arrays(9, spec["schema"], traffic["data"], spec["assumed"])
    cat, num = inputs.make_file(9, spec["schema"], traffic["data"], 700)
    state = MonitorState(
        **{k: jnp.asarray(v) for k, v in arrays.items()},
        num_ref_cdf=jnp.zeros_like(jnp.asarray(arrays["num_ref_sorted"])),
    )
    mask = np.ones(700, bool)
    flags, dist = monitors.outlier_flags(num, arrays)
    clear = np.abs(dist / float(arrays["out_threshold"]) - 1.0) > 1e-4
    served = np.asarray(outlier_flags(state, num, mask))
    assert (served[clear] == flags[clear]).all()
    assert 0.01 < flags.mean() < 0.3
    drift = monitors.drift_scores(cat, num, arrays, spec["schema"]["cards"])
    np.testing.assert_allclose(np.asarray(drift_scores(state, cat, num, mask)), drift, atol=2e-5)
    assert ((drift > 0.02) & (drift < 0.98)).sum() >= 5  # scores that can move
    low = monitors.drift_scores(cat, num, arrays, spec["schema"]["cards"], precision="low")
    assert np.abs(low - drift).max() > 1e-3


def test_inputs_repeat_for_a_seed_and_differ_between_seeds(tiny_files):
    from benchmark import inputs

    spec, traffic = tiny_files[CONFIGS[0]]
    big = 3_000_000_019  # more than 32 signed bits hold
    a = inputs.make_file(big, spec["schema"], traffic["data"], 900)
    b = inputs.make_file(big, spec["schema"], traffic["data"], 900)
    c = inputs.make_file(big + 1, spec["schema"], traffic["data"], 900)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert (a[1] != c[1]).any()
    assert a[0].max() < max(spec["schema"]["cards"]) and a[0].min() >= 0
    for j, card in enumerate(spec["schema"]["cards"]):
        assert a[0][:, j].max() < card

"""FLOP accounting / MFU helpers (utils/flops.py) — the bench's roofline
evidence must itself be trustworthy."""

import jax.numpy as jnp
import numpy as np
import pytest

from mlops_tpu.utils.flops import (
    compile_with_flops,
    compiled_flops,
    mfu,
    peak_flops,
)


def test_compile_with_flops_counts_a_matmul():
    n = 128
    a = jnp.ones((n, n), jnp.float32)
    exe, flops = compile_with_flops(lambda a, b: a @ b, a, a)
    assert exe is not None
    # XLA counts 2*n^3 (multiply+add) for a dense matmul.
    assert flops == 2 * n**3
    np.testing.assert_allclose(np.asarray(exe(a, a)), np.full((n, n), n))
    assert compiled_flops(lambda a, b: a @ b, a, a) == flops


def test_compile_with_flops_lets_a_compile_error_propagate():
    with pytest.raises(NameError):
        compile_with_flops(lambda x: undefined_name + x, 1.0)  # noqa: F821


def test_mfu_and_peak_lookup():
    assert mfu(None, 10.0, 1e12) is None
    assert mfu(1e9, 10.0, None) is None
    assert mfu(1e9, 100.0, 1e12) == 0.1

    class FakeDevice:
        device_kind = "TPU v5 lite"

    class UnknownDevice:
        device_kind = "mystery-asic"

    class CpuDevice:
        platform = "cpu"
        device_kind = "cpu"

    assert peak_flops(FakeDevice()) == 197e12
    assert peak_flops(FakeDevice(), "f32") == 197e12 / 2
    assert peak_flops(CpuDevice()) is None  # no published peak: no MFU


def test_unknown_device_kind_is_an_error():
    """A device that is not in the peak table raises — no override, no
    peak measured on the spot."""

    class UnknownDevice:
        platform = "tpu"
        device_kind = "mystery-asic"

    with pytest.raises(ValueError, match="mystery-asic"):
        peak_flops(UnknownDevice())

"""Test harness: force an 8-device CPU-simulated mesh before JAX imports.

Standard JAX fake-backend trick (SURVEY.md SS4 build obligation (d)): all
multi-chip logic is exercised without a TPU via
``--xla_force_host_platform_device_count=8``. The chip is reached through
``chip_smoke.py`` and ``benchmark/run.py``, never from the tests.
"""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# Persistent XLA compilation cache: the suite compiles many identical
# programs across modules (engine warmups, train steps at shared shapes);
# caching them cuts suite wall time substantially both within a run and
# across CI runs. Placed by the program's own rule
# (`compilecache/location.py`: $JAX_COMPILATION_CACHE_DIR, else the
# checkout's git-ignored .jax_cache).
from mlops_tpu.compilecache.location import enable_persistent_cache  # noqa: E402

import jax  # noqa: E402

enable_persistent_cache()

import contextlib

import numpy as np
import pytest


@contextlib.contextmanager
def persistent_cache_off():
    """JAX's persistent compilation cache fully off inside the block. The
    cache object latches on first use, so the flag flip alone is a no-op
    mid-process — reset_cache() forces re-initialization, after which the
    disabled flag is honored and every compile is real."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    compilation_cache.reset_cache()
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        compilation_cache.reset_cache()
        jax.config.update("jax_enable_compilation_cache", old)


@contextlib.contextmanager
def program_spans(profile_dir):
    """A `jax.profiler` session around the block (the tracers the benchmark
    harness uses: Python tracer off, host tracer at level 1); afterwards
    the list it yielded holds every ``mlops:`` span the program wrote into
    the profile as ``(name, start_ns, end_ns, attributes)``, by start."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    spans: list[tuple] = []
    jax.profiler.start_trace(str(profile_dir), profiler_options=options)
    try:
        yield spans
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    (found,) = Path(profile_dir).glob("plugins/profile/*/*.xplane.pb")
    for plane in ProfileData.from_file(str(found)).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("mlops:"):
                    start = int(event.start_ns)
                    spans.append(
                        (event.name, start, start + int(event.duration_ns),
                         dict(event.stats))
                    )
    spans.sort(key=lambda span: span[1])


@pytest.fixture(scope="session")
def synth_small():
    from mlops_tpu.data import generate_synthetic

    columns, labels = generate_synthetic(2000, seed=7)
    return columns, labels


@pytest.fixture(scope="session")
def encoded_small(synth_small):
    from mlops_tpu.data import Preprocessor

    columns, labels = synth_small
    prep = Preprocessor.fit(columns)
    return prep, prep.encode(columns, labels)


@pytest.fixture(scope="session")
def tiny_pipeline(tmp_path_factory):
    """One small end-to-end training run shared by bundle/serve/CLI tests."""
    from mlops_tpu.config import Config, ModelConfig, TrainConfig
    from mlops_tpu.train.pipeline import run_training

    root = tmp_path_factory.mktemp("pipeline")
    config = Config()
    config.data.rows = 3000
    config.model = ModelConfig(family="mlp", hidden_dims=(32, 32), embed_dim=4)
    config.train = TrainConfig(steps=100, eval_every=100, batch_size=256)
    config.registry.root = str(root / "registry")
    config.registry.run_root = str(root / "runs")
    result = run_training(config)
    return config, result


@pytest.fixture(scope="session")
def warm_engine(tiny_pipeline):
    """ONE fully-warmed serving engine shared by the serve/batcher modules
    (each warmup compiles 4 bucket + 6 group shapes — two identical
    engines cost ~90 s of duplicate compiles on the CI box). Tests must
    not mutate it; anything needing special buckets builds its own."""
    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.serve.engine import InferenceEngine

    _, result = tiny_pipeline
    engine = InferenceEngine(load_bundle(result.bundle_dir), buckets=(1, 8, 64))
    engine.warmup()
    return engine


@pytest.fixture(scope="session")
def sample_request():
    """The reference's exact smoke-test payload (`app/sample-request.json`)."""
    return [
        {
            "sex": "male",
            "education": "university",
            "marriage": "married",
            "repayment_status_1": "duly_paid",
            "repayment_status_2": "duly_paid",
            "repayment_status_3": "duly_paid",
            "repayment_status_4": "duly_paid",
            "repayment_status_5": "no_delay",
            "repayment_status_6": "no_delay",
            "credit_limit": 18000,
            "age": 18000,
            "bill_amount_1": 764.95,
            "bill_amount_2": 2221.95,
            "bill_amount_3": 1131.85,
            "bill_amount_4": 5074.85,
            "bill_amount_5": 18000,
            "bill_amount_6": 1419.95,
            "payment_amount_1": 2236.5,
            "payment_amount_2": 1137.55,
            "payment_amount_3": 5084.55,
            "payment_amount_4": 111.65,
            "payment_amount_5": 306.9,
            "payment_amount_6": 805.65,
        }
    ]

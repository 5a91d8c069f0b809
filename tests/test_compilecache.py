"""compilecache: key invalidation, corruption recovery, hit/miss parity
(single- and multi-device, donated programs included), the loads-but-
cannot-run report, registry sync, and the warmup CLI.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlops_tpu.compilecache import CacheJob, CompileCache
from mlops_tpu.compilecache import keys
from mlops_tpu.compilecache.registry import CACHE_ENTRY_IDS

S = jax.ShapeDtypeStruct


@pytest.fixture(autouse=True, scope="module")
def _isolated_xla_cache():
    """Fully disable JAX's persistent compilation cache for this module.
    On the CPU backend of jaxlib 0.9.0 (checked in PR 22) an executable
    whose compile was SERVED from that cache (the suite's shared
    .jax_cache) still serializes into an artifact that does not load
    ("Function ... not found"). cache.py validates round-trips, logs the
    cause and refuses to persist those (see _persist), which would turn
    the artifact-store hits expected below into 'unserializable'
    no-persists."""
    from conftest import persistent_cache_off

    with persistent_cache_off():
        yield


@pytest.fixture(scope="module")
def cc_pipeline(tmp_path_factory, _isolated_xla_cache):
    """A trained bundle with a model architecture UNIQUE to this module.

    Serving params are ARGUMENTS of the cached programs, so every engine
    over the same architecture compiles the same XLA program — and the
    session-shared warm_engine bundle's programs get disk-LOADED from the
    suite's persistent xla cache by other modules, which makes their
    in-process re-serialization unloadable (see _isolated_xla_cache). A hidden-dims
    shape no other test uses keeps this module's programs out of that
    blast radius."""
    from mlops_tpu.config import Config, ModelConfig, TrainConfig
    from mlops_tpu.train.pipeline import run_training

    root = tmp_path_factory.mktemp("cc-pipeline")
    config = Config()
    config.data.rows = 2000
    config.model = ModelConfig(family="mlp", hidden_dims=(24,), embed_dim=4)
    config.train = TrainConfig(steps=30, eval_every=30, batch_size=128)
    config.registry.root = str(root / "registry")
    config.registry.run_root = str(root / "runs")
    return config, run_training(config)


def _double(x):
    return x * 2.0


def _job(entry="test-entry", dtype=jnp.float32, **kw):
    return CacheJob(
        entry_id=entry,
        jitted=jax.jit(_double),
        abstract_args=(S((4,), dtype),),
        **kw,
    )


# ----------------------------------------------------------------- registry
def test_cache_registry_matches_entry_point_registry():
    """The cache warms exactly the tpulint Layer-2 entry points — the two
    registries can never disagree about what the hot programs are."""
    from mlops_tpu.analysis.entrypoints import registered_entry_points
    from mlops_tpu.compilecache.warmup import _WARMERS

    names = {e.name for e in registered_entry_points()}
    assert names == set(CACHE_ENTRY_IDS)
    assert names == set(_WARMERS)


# --------------------------------------------------------------------- keys
def test_cache_key_invalidation_axes():
    """Every key axis produces a distinct digest: jax/jaxlib version bump,
    backend, model-config hash, mesh shape, donation flags, dtype/shape."""
    env = keys.environment_fingerprint()
    args = (S((4,), jnp.float32),)
    _, base = keys.cache_key("e", args, config_hash="m1", env=env)

    assert keys.cache_key("e", args, config_hash="m1", env=env)[1] == base
    variants = [
        keys.cache_key("e", args, config_hash="m1", env={**env, "jax": "9.9.9"})[1],
        keys.cache_key("e", args, config_hash="m1", env={**env, "jaxlib": "9.9.9"})[1],
        keys.cache_key("e", args, config_hash="m1", env={**env, "backend": "tpu"})[1],
        keys.cache_key("e", args, config_hash="m2", env=env)[1],
        keys.cache_key("e", args, config_hash="m1", mesh_shape=(2, 4), env=env)[1],
        keys.cache_key("e", args, config_hash="m1", donated=True, env=env)[1],
        keys.cache_key("e", (S((4,), jnp.int32),), config_hash="m1", env=env)[1],
        keys.cache_key("e", (S((8,), jnp.float32),), config_hash="m1", env=env)[1],
        keys.cache_key("other", args, config_hash="m1", env=env)[1],
    ]
    assert len({base, *variants}) == len(variants) + 1


def test_model_fingerprint_tracks_config():
    from mlops_tpu.config import ModelConfig

    a = keys.model_fingerprint(ModelConfig(hidden_dims=(8,)))
    b = keys.model_fingerprint(ModelConfig(hidden_dims=(16,)))
    assert a != b
    assert a == keys.model_fingerprint(ModelConfig(hidden_dims=(8,)))


# ----------------------------------------------------------- cache behavior
def test_miss_then_hit_bit_identical(tmp_path):
    c1 = CompileCache(tmp_path)
    fn1 = c1.load_or_compile(_job())
    assert c1.stats()["misses"] == 1 and c1.stats()["hits"] == 0
    assert c1.stats()["compile_s"] > 0

    c2 = CompileCache(tmp_path)  # second process, same dir
    fn2 = c2.load_or_compile(_job())
    s2 = c2.stats()
    assert s2["hits"] == 1 and s2["misses"] == 0
    assert s2["deserialize_s"] > 0

    x = np.arange(4, dtype=np.float32)
    assert np.array_equal(np.asarray(fn1(x)), np.asarray(fn2(x)))


def test_jax_version_bump_is_a_behavioral_miss(tmp_path, monkeypatch):
    CompileCache(tmp_path).load_or_compile(_job())
    real = keys.environment_fingerprint()
    monkeypatch.setattr(
        keys, "environment_fingerprint", lambda: {**real, "jax": "99.0.0"}
    )
    c2 = CompileCache(tmp_path)
    c2.load_or_compile(_job())
    assert c2.stats()["misses"] == 1 and c2.stats()["hits"] == 0


@pytest.mark.parametrize("corruption", ["truncate", "garbage", "flip"])
def test_corrupt_artifact_discarded_and_recompiled(tmp_path, corruption):
    """A damaged cache file can cost a recompile, never a crash and never
    a stale/garbled program."""
    c1 = CompileCache(tmp_path)
    c1.load_or_compile(_job())
    [artifact] = (tmp_path / "test-entry").glob("*.jaxexe")
    raw = artifact.read_bytes()
    if corruption == "truncate":
        artifact.write_bytes(raw[: len(raw) // 2])
    elif corruption == "garbage":
        artifact.write_bytes(b"not an executable at all")
    else:  # flip payload bytes: header parses, checksum must catch it
        artifact.write_bytes(raw[:-8] + bytes(8))

    c2 = CompileCache(tmp_path)
    fn = c2.load_or_compile(_job())
    s = c2.stats()
    assert s["discards"] == 1 and s["misses"] == 1 and s["hits"] == 0
    assert np.array_equal(
        np.asarray(fn(np.arange(4, dtype=np.float32))),
        np.arange(4, dtype=np.float32) * 2,
    )
    # The bad artifact was replaced by a valid one: third process hits.
    c3 = CompileCache(tmp_path)
    c3.load_or_compile(_job())
    assert c3.stats()["hits"] == 1


def test_donated_program_rides_the_cache(tmp_path):
    """A donated program is cached like any other: the second process
    deserializes it, and it consumes its argument and returns the miss's
    bits."""
    job = CacheJob(
        entry_id="donated-entry",
        jitted=jax.jit(_double, donate_argnums=(0,)),
        abstract_args=(S((4,), jnp.float32),),
        donated=True,
    )
    c1 = CompileCache(tmp_path)
    out1 = np.asarray(c1.load_or_compile(job)(jnp.arange(4, dtype=jnp.float32)))
    assert c1.stats()["misses"] == 1
    c2 = CompileCache(tmp_path)
    out2 = np.asarray(c2.load_or_compile(job)(jnp.arange(4, dtype=jnp.float32)))
    assert c2.stats()["hits"] == 1 and c2.stats()["misses"] == 0
    assert np.array_equal(out1, out2)
    assert np.array_equal(out2, np.arange(4, dtype=np.float32) * 2)


def test_hit_executes_on_a_multi_device_backend(tmp_path):
    """The 8-device CPU mesh: a program compiled for ONE device (not the
    backend's first) and one compiled for a (2, 2) mesh must both load
    onto the devices they were compiled for, execute, and return the
    miss's bits — `deserialize_and_load`'s default (every device of the
    backend) loads executables that cannot run."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    devices = jax.devices()
    assert len(devices) == 8
    one = SingleDeviceSharding(devices[3])
    mesh = Mesh(np.array(devices[4:8]).reshape(2, 2), ("data", "model"))
    rows = NamedSharding(mesh, P("data", "model"))
    x = np.arange(16, dtype=np.float32).reshape(4, 4)
    jobs = {
        "single": CacheJob(
            entry_id="multi-single",
            jitted=jax.jit(_double),
            abstract_args=(S((4, 4), jnp.float32, sharding=one),),
            execute_args=(jax.device_put(x, one),),
        ),
        "mesh": CacheJob(
            entry_id="multi-mesh",
            jitted=jax.jit(lambda a: (a @ a.T).sum(axis=1)),
            abstract_args=(S((4, 4), jnp.float32, sharding=rows),),
            mesh_shape=(2, 2),
            execute_args=(jax.device_put(x, rows),),
        ),
    }
    for name, job in jobs.items():
        c1 = CompileCache(tmp_path)
        miss = np.asarray(c1.load_or_compile(job)(*job.execute_args))
        assert c1.stats()["misses"] == 1 and c1.stats()["unserializable"] == 0
        c2 = CompileCache(tmp_path)
        fn = c2.load_or_compile(job)
        s2 = c2.stats()
        assert s2["hits"] == 1 and s2["misses"] == 0, (name, s2)
        assert s2["discards"] == 0 and s2["unrunnable"] == 0, (name, s2)
        out = fn(*job.execute_args)
        assert np.array_equal(np.asarray(out), miss), name
        assert out.devices() <= set(job.execute_args[0].devices())


def test_artifact_that_loads_but_cannot_run_is_reported(tmp_path, caplog):
    """An artifact that deserializes and then fails to execute is an
    ERROR in the log (with its cause), counted ``unrunnable``, removed and
    recompiled — not a silent discard."""
    x = np.arange(4, dtype=np.float32)
    job = _job(execute_args=(x,))
    CompileCache(tmp_path).load_or_compile(job)

    c2 = CompileCache(tmp_path)
    real_load = c2._load

    def load_broken(*args):
        real_load(*args)  # the artifact itself is sound...

        def cannot_run(*_):
            raise RuntimeError("simulated: wrong device assignment")

        return cannot_run  # ...but what it loaded into does not execute

    c2._load = load_broken
    with caplog.at_level("ERROR", logger="mlops_tpu.compilecache.cache"):
        fn = c2.load_or_compile(job)
    s = c2.stats()
    assert s["unrunnable"] == 1 and s["hits"] == 0 and s["misses"] == 1
    assert s["discards"] == 0
    assert "loaded but cannot run" in caplog.text
    assert "wrong device assignment" in caplog.text  # the cause, not a count
    assert np.array_equal(np.asarray(fn(x)), x * 2)


def test_unvalidated_program_is_never_persisted(tmp_path, caplog):
    """Persist-time validation EXECUTES the reloaded program: one that
    does not survive is logged with its cause and never written."""
    c = CompileCache(tmp_path)

    def load_refuses(*_):
        raise RuntimeError("simulated: runtime refuses the executable")

    c._load = load_refuses
    with caplog.at_level("ERROR", logger="mlops_tpu.compilecache.cache"):
        fn = c.load_or_compile(_job(execute_args=(np.zeros(4, np.float32),)))
    assert c.stats()["unserializable"] == 1 and c.stats()["misses"] == 1
    assert "runtime refuses the executable" in caplog.text
    assert not list(tmp_path.rglob("*.jaxexe"))
    assert np.array_equal(np.asarray(fn(np.ones(4, np.float32))), np.full(4, 2.0))


# ------------------------------------------------------------ engine warmup
def test_engine_cold_then_warm_parity(tmp_path, cc_pipeline, monkeypatch):
    """The acceptance contract at unit scale: a second engine against a
    populated cache warms all-hits and serves BIT-IDENTICAL responses —
    bucketed and grouped paths both."""
    import mlops_tpu.serve.engine as engine_mod
    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.serve.engine import InferenceEngine

    # Shrink the group grid so the test warms 2 bucket + 2 group programs.
    monkeypatch.setattr(engine_mod, "GROUP_SLOT_BUCKETS", (2,))
    monkeypatch.setattr(engine_mod, "GROUP_ROW_BUCKETS", (1, 8))

    _, result = cc_pipeline
    bundle = load_bundle(result.bundle_dir)
    cache_dir = tmp_path / "cc"

    e1 = InferenceEngine(
        bundle, buckets=(1, 8), compile_cache=CompileCache(cache_dir)
    )
    e1.warmup()
    s1 = e1.warmup_stats
    assert s1["programs"] == 4
    assert s1["cache"]["misses"] == 4 and s1["cache"]["hits"] == 0

    e2 = InferenceEngine(
        bundle, buckets=(1, 8), compile_cache=CompileCache(cache_dir)
    )
    e2.warmup()
    s2 = e2.warmup_stats
    assert s2["cache"]["hits"] == 4 and s2["cache"]["misses"] == 0

    rng = np.random.default_rng(3)
    cat = rng.integers(0, 2, (5, 9)).astype(np.int32)
    num = rng.normal(size=(5, 14)).astype(np.float32)
    assert e1.predict_arrays(cat, num) == e2.predict_arrays(cat, num)

    requests = [[_record()], [_record(), _record()]]
    assert e1.predict_group(requests) == e2.predict_group(requests)


def _record():
    from mlops_tpu.schema import LoanApplicant

    return LoanApplicant().model_dump()


def test_engine_without_cache_unchanged(cc_pipeline):
    """No cache configured: warmup still AOT-compiles (in parallel) and
    serves; responses match a cached engine's (the one-definition
    invariant across dispatch paths)."""
    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.serve.engine import InferenceEngine

    _, result = cc_pipeline
    bundle = load_bundle(result.bundle_dir)
    engine = InferenceEngine(bundle, buckets=(1,), enable_grouping=False)
    engine.warmup()
    assert engine.ready
    assert engine.warmup_stats["cache"] is None
    out = engine.predict_arrays(
        np.zeros((1, 9), np.int32), np.zeros((1, 14), np.float32)
    )
    assert len(out["predictions"]) == 1


# ---------------------------------------------------------------- bulk path
def test_bulk_chunk_cache_hit_bit_identical(tmp_path, cc_pipeline):
    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.parallel.bulk import make_chunk_scorer

    _, result = cc_pipeline
    bundle = load_bundle(result.bundle_dir)
    chunk = 128
    rng = np.random.default_rng(0)
    cat = rng.integers(0, 2, (chunk, 9)).astype(np.int8)
    num = rng.normal(size=(chunk, 14)).astype(np.float32)
    mask = np.arange(chunk) < 100

    c1 = CompileCache(tmp_path)
    s1 = make_chunk_scorer(
        bundle, mesh=None, exact=True, compile_cache=c1, chunk_rows=chunk
    )
    p1, f1 = s1(cat, num, mask)
    assert c1.stats()["misses"] >= 1

    c2 = CompileCache(tmp_path)
    s2 = make_chunk_scorer(
        bundle, mesh=None, exact=True, compile_cache=c2, chunk_rows=chunk
    )
    p2, f2 = s2(cat, num, mask)
    assert c2.stats()["hits"] >= 1 and c2.stats()["misses"] == 0
    assert np.array_equal(np.asarray(p1), np.asarray(p2))
    assert np.array_equal(np.asarray(f1), np.asarray(f2))

    # Novel shapes fall back to the jitted program instead of the cached
    # executable (which is shape-exact).
    small = 32
    p3, _ = s2(cat[:small], num[:small], np.ones(small, bool))
    assert np.asarray(p3).shape == (small,)


# ------------------------------------------------- warmup CLI + never-disagree
def test_warm_entry_points_then_engine_all_hits(tmp_path, cc_pipeline):
    """The ``warmup`` CLI body and the serving engine build keys through
    the SAME job builders: a cache pre-populated from the bundle makes a
    fresh engine warm with zero compiles."""
    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.compilecache.warmup import warm_entry_points
    from mlops_tpu.serve.engine import InferenceEngine

    config, result = cc_pipeline
    bundle = load_bundle(result.bundle_dir)
    config.serve.warmup_batch_sizes = (1, 8)
    config.serve.batch_window_ms = 0.0  # skip the group grid (speed)
    config.score.chunk_rows = 128
    config.train.steps = 4
    config.train.eval_every = 4
    config.data.rows = 256

    cache = CompileCache(tmp_path)
    report = warm_entry_points(config, cache, bundle)
    assert set(report["entries"]) == set(CACHE_ENTRY_IDS)
    assert report["cache"]["hits"] == 0

    engine = InferenceEngine(
        bundle,
        buckets=(1, 8),
        enable_grouping=False,
        compile_cache=CompileCache(tmp_path),
    )
    engine.warmup()
    s = engine.warmup_stats["cache"]
    assert s["misses"] == 0 and s["hits"] == 2, (s, report["cache"])


def test_fit_with_cache_hits_on_second_run(tmp_path, encoded_small):
    """The dense train window rides the cache: a repeat run of the same
    config deserializes its scan instead of recompiling, and trains to
    bit-identical metrics."""
    from mlops_tpu.config import ModelConfig, TrainConfig
    from mlops_tpu.models import build_model
    from mlops_tpu.train.loop import fit
    from mlops_tpu.train.pipeline import split_dataset

    _, ds = encoded_small
    train_ds, valid_ds = split_dataset(ds, 0.2)
    mcfg = ModelConfig(family="mlp", hidden_dims=(8,), embed_dim=4)
    tcfg = TrainConfig(steps=6, eval_every=6, batch_size=64)

    c1 = CompileCache(tmp_path)
    r1 = fit(build_model(mcfg), train_ds, valid_ds, tcfg, compile_cache=c1)
    assert c1.stats()["misses"] == 1

    c2 = CompileCache(tmp_path)
    r2 = fit(build_model(mcfg), train_ds, valid_ds, tcfg, compile_cache=c2)
    assert c2.stats()["hits"] == 1 and c2.stats()["misses"] == 0
    assert r1.metrics == r2.metrics


def test_warmup_cli_config_mode(tmp_path, capsys):
    """`mlops-tpu warmup --cache-dir D <tiny overrides>` — no bundle
    anywhere — warms every entry point abstractly and reports JSON."""
    from mlops_tpu.cli import main

    rc = main(
        [
            "warmup",
            "--cache-dir",
            str(tmp_path),
            "model.hidden_dims=8",
            "model.embed_dim=4",
            "serve.warmup_batch_sizes=1",
            "serve.batch_window_ms=0",
            "score.chunk_rows=128",
            "train.steps=4",
            "train.eval_every=4",
            "data.rows=128",
        ]
    )
    assert rc == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["mode"] == "config"
    assert set(report["entries"]) == set(CACHE_ENTRY_IDS)
    assert report["programs"] >= 3
    assert report["cache"]["misses"] == report["programs"]

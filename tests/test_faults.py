"""faultline (mlops_tpu/faults): determinism, modes, arming, and the
armed-off parity pin.

The subsystem's contract (ISSUE 9):

- seeded schedules are DETERMINISTIC — same seed + scenario -> the
  identical injection trace, on any process;
- disarmed (the product state) it is invisible: bit-identical serving
  responses and zero new lock-order findings;
- mid-write kill faults prove the tmp+rename persistence paths: a
  SIGKILL between write and rename never leaves a torn target file.
"""

import contextlib
import json
import os
import signal
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from mlops_tpu import faults

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    faults.disarm()


# ------------------------------------------------------------ determinism
def test_seeded_schedule_is_deterministic():
    """Same seed + same hit sequence -> the IDENTICAL injection trace."""
    rules = [
        {"point": "serve.*", "mode": "raise", "probability": 0.3, "seed": 7},
        {"point": "cache.read", "mode": "corrupt", "seed": 7},
    ]
    traces = []
    for _ in range(2):
        plan = faults.FaultPlan.from_rules(rules, seed=7)
        faults.arm(plan)
        for i in range(100):
            with contextlib.suppress(faults.FaultInjected):
                faults.fire("serve.engine.dispatch")
        faults.corrupt("cache.read", b"payload-bytes")
        faults.disarm()
        traces.append(plan.trace())
    assert traces[0] == traces[1]
    assert any(point == "cache.read" for point, *_ in traces[0])
    fired = [t for t in traces[0] if t[0] == "serve.engine.dispatch"]
    # Bernoulli(0.3) over 100 hits: some fire, most don't — the schedule
    # is a real subset, not all-or-nothing.
    assert 5 < len(fired) < 70


def test_different_seed_changes_the_schedule():
    def trace_for(seed):
        plan = faults.FaultPlan.from_rules(
            [{"point": "p", "mode": "delay", "probability": 0.5,
              "delay_s": 0.0, "seed": seed}]
        )
        faults.arm(plan)
        for _ in range(64):
            faults.fire("p")
        faults.disarm()
        return [hit for _, hit, _, _ in plan.trace()]

    assert trace_for(1) != trace_for(2)


def test_corruption_is_deterministic_and_bounded():
    data = bytes(range(256)) * 4
    outs = []
    for _ in range(2):
        faults.arm(faults.FaultPlan.from_rules(
            [{"point": "r", "mode": "corrupt", "flip_bits": 4, "seed": 9}]
        ))
        outs.append(faults.corrupt("r", data))
        faults.disarm()
    assert outs[0] == outs[1]
    assert outs[0] != data
    flipped = sum(a != b for a, b in zip(outs[0], data))
    assert 1 <= flipped <= 4  # <=: two flips may land in one byte


def test_after_and_max_fires_windows():
    plan = faults.FaultPlan.from_rules(
        [{"point": "w", "mode": "raise", "after": 3, "max_fires": 2}]
    )
    faults.arm(plan)
    outcomes = []
    for _ in range(10):
        try:
            faults.fire("w")
            outcomes.append("ok")
        except faults.FaultInjected:
            outcomes.append("boom")
    assert outcomes == ["ok"] * 3 + ["boom"] * 2 + ["ok"] * 5


def test_plan_rejects_bad_rules():
    with pytest.raises(ValueError, match="mode"):
        faults.FaultRule(point="p", mode="explode")
    with pytest.raises(ValueError, match="probability"):
        faults.FaultRule(point="p", mode="raise", probability=2.0)
    with pytest.raises(ValueError, match="exc"):
        faults.FaultRule(point="p", mode="raise", exc="SystemExit")


def test_toml_plan_and_env_arming(tmp_path):
    """The chaos-smoke arming path: a TOML plan file named by
    MLOPS_TPU_FAULTS arms every process that imports the package."""
    plan_path = tmp_path / "chaos.toml"
    plan_path.write_text(
        'seed = 11\n'
        '[[fault]]\npoint = "x.y"\nmode = "raise"\nexc = "OSError"\n'
        'message = "injected-io"\n'
    )
    plan = faults.load_plan(plan_path)
    assert plan.seed == 11 and plan.rules[0].exc == "OSError"
    probe = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            from mlops_tpu import faults
            assert faults.armed(), "env plan did not arm at import"
            try:
                faults.fire("x.y")
                raise SystemExit("fault did not fire")
            except OSError as err:
                assert "injected-io" in str(err)
            print("ENV-ARMED-OK")
        """)],
        env={**os.environ, "MLOPS_TPU_FAULTS": str(plan_path),
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert "ENV-ARMED-OK" in probe.stdout, probe.stderr[-2000:]


def test_every_documented_point_is_compiled_in():
    """faults.POINTS is the operator contract: every documented injection
    point must appear as a fire()/corrupt() call site in the package."""
    import mlops_tpu

    root = Path(mlops_tpu.__file__).parent
    source = "\n".join(
        p.read_text()
        for p in root.rglob("*.py")
        if "__pycache__" not in p.parts
    )
    for point in faults.POINTS:
        assert f'"{point}"' in source, f"{point} has no call site"


# -------------------------------------------------------- armed-off parity
def test_armed_off_is_invisible_to_serving(warm_engine, sample_request):
    """The parity pin: responses are bit-identical across (never armed),
    (armed with a zero-match plan), and (armed then disarmed) — the
    subsystem's disarmed hot path cannot perturb serving."""
    records = sample_request * 3
    baseline = warm_engine.predict_records(records)
    faults.arm(faults.FaultPlan.from_rules(
        [{"point": "no.such.point", "mode": "raise"}]
    ))
    armed_noop = warm_engine.predict_records(records)
    faults.disarm()
    disarmed = warm_engine.predict_records(records)
    assert armed_noop == baseline
    assert disarmed == baseline


def test_faults_module_adds_no_concurrency_findings():
    """Zero new lock-order findings with the subsystem in the tree: the
    injection points introduce no locks into serving paths (the plan's
    one leaf lock is declared and clean)."""
    from mlops_tpu.analysis import analyze_concurrency_paths

    findings = analyze_concurrency_paths(
        [REPO / "mlops_tpu" / "faults", REPO / "mlops_tpu" / "serve"]
    )
    assert findings == [], [f.format() for f in findings]


# ------------------------------------------------------- mid-write kills
_RESERVOIR_KILL = """
import numpy as np
from mlops_tpu import faults
from mlops_tpu.lifecycle.retrain import SampleReservoir
from mlops_tpu.schema import SCHEMA

faults.arm(faults.FaultPlan.from_rules(
    [{"point": "lifecycle.reservoir.midwrite", "mode": "kill"}]
))
res = SampleReservoir(16, r"%s")
res.add_batch(
    np.ones((4, SCHEMA.num_categorical), np.int32),
    np.ones((4, SCHEMA.num_numeric), np.float32),
)
res.save()  # killed between write and rename
raise SystemExit("unreachable: the kill fault did not fire")
"""


def test_reservoir_midwrite_kill_never_leaves_a_torn_snapshot(tmp_path):
    """SIGKILL between the reservoir's tmp write and its rename: the
    snapshot path must simply not exist (first save) — and a restart
    must load cleanly from nothing."""
    state = tmp_path / "state"
    proc = subprocess.run(
        [sys.executable, "-c", _RESERVOIR_KILL % state],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]
    from mlops_tpu.lifecycle.retrain import SampleReservoir

    assert not (state / "reservoir.npz").exists()
    fresh = SampleReservoir(16, state)
    assert fresh.load() is False  # a torn tmp is never trusted
    assert fresh.rows == 0


_ATOMIC_KILL = """
from mlops_tpu import faults
from mlops_tpu.utils.io import atomic_write

target = r"%s"
atomic_write(target, b"GOOD" * 1024)  # intact prior generation
faults.arm(faults.FaultPlan.from_rules(
    [{"point": "io.atomic_write.midwrite", "mode": "kill"}]
))
atomic_write(target, b"TORN" * 4096)  # killed before the rename
raise SystemExit("unreachable: the kill fault did not fire")
"""


def test_atomic_write_midwrite_kill_keeps_the_prior_generation(tmp_path):
    """SIGKILL between atomic_write's write and rename (the checkpoint /
    registry discipline): the target keeps the PREVIOUS intact payload —
    never a torn mix, never the partial new one."""
    target = tmp_path / "ckpt.msgpack"
    proc = subprocess.run(
        [sys.executable, "-c", _ATOMIC_KILL % target],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]
    assert target.read_bytes() == b"GOOD" * 1024


def test_cache_corrupt_on_read_discards_and_recompiles(tmp_path):
    """Bit-corrupt-on-read at compilecache.read: the checksum gate turns
    seeded corruption into a counted discard + recompile — never a
    served garbled program, and the store self-heals (the recompile
    persists a fresh artifact)."""
    import jax
    import jax.numpy as jnp

    from mlops_tpu.compilecache.cache import CacheJob, CompileCache

    def f(x):
        return x * 2.0 + 1.0

    job = CacheJob(
        entry_id="faults-test",
        jitted=jax.jit(f),
        abstract_args=(jax.ShapeDtypeStruct((8,), jnp.float32),),
    )
    cache = CompileCache(tmp_path)
    cache.load_or_compile(job)  # miss -> compile -> persist
    assert cache.stats()["misses"] == 1

    faults.arm(faults.FaultPlan.from_rules(
        [{"point": "compilecache.read", "mode": "corrupt", "flip_bits": 8}]
    ))
    try:
        cache2 = CompileCache(tmp_path)
        fn = cache2.load_or_compile(job)
    finally:
        faults.disarm()
    stats = cache2.stats()
    assert stats["discards"] == 1 and stats["misses"] == 1
    np.testing.assert_allclose(
        np.asarray(fn(jnp.arange(8, dtype=jnp.float32))),
        np.arange(8, dtype=np.float32) * 2.0 + 1.0,
    )
    # Self-healed: a third process (no corruption) hits clean.
    cache3 = CompileCache(tmp_path)
    cache3.load_or_compile(job)
    assert cache3.stats()["hits"] == 1


@pytest.mark.slow
def test_cache_persist_midwrite_kill_never_leaves_a_partial_artifact(
    tmp_path,
):
    """SIGKILL between the cache artifact's tmp write and its rename: no
    artifact lands, and the NEXT process compiles + persists cleanly —
    the tmp+rename discipline proven, not trusted."""
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp, sys
        from mlops_tpu import faults
        from mlops_tpu.compilecache.cache import CacheJob, CompileCache
        faults.arm(faults.FaultPlan.from_rules(
            [{"point": "compilecache.persist.midwrite", "mode": "kill"}]
        ))
        cache = CompileCache(sys.argv[1])
        cache.load_or_compile(CacheJob(
            entry_id="kill-test",
            jitted=jax.jit(lambda x: x + 1.0),
            abstract_args=(jax.ShapeDtypeStruct((4,), jnp.float32),),
        ))
        raise SystemExit("unreachable: the kill fault did not fire")
    """)
    proc = subprocess.run(
        [sys.executable, "-c", script, str(tmp_path)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]
    assert list(tmp_path.rglob("*.jaxexe")) == []  # nothing torn landed

    import jax
    import jax.numpy as jnp

    from mlops_tpu.compilecache.cache import CacheJob, CompileCache

    cache = CompileCache(tmp_path)
    cache.load_or_compile(CacheJob(
        entry_id="kill-test",
        jitted=jax.jit(lambda x: x + 1.0),
        abstract_args=(jax.ShapeDtypeStruct((4,), jnp.float32),),
    ))
    stats = cache.stats()
    assert stats["misses"] == 1 and stats["discards"] == 0


# ------------------------------------------------ ring-plane dead work
def test_ring_expired_descriptor_completes_without_dispatch():
    """The engine side of deadline budgets on the shm ring: a descriptor
    whose slot deadline already passed is completed RESP_EXPIRED without
    the engine dispatching it, and the engine-side expiry counter
    moves."""
    import time

    from mlops_tpu.schema import SCHEMA
    from mlops_tpu.serve.ipc import RequestRing, RingClient, RingService
    from mlops_tpu.serve.metrics import ROB_EXPIRED_ENGINE
    from mlops_tpu.serve.wire import RESP_EXPIRED

    class NeverDispatch:
        supports_grouping = True
        monitor_accumulating = False

        def dispatch_arrays(self, cat, num):
            raise AssertionError("expired descriptor must not dispatch")

        dispatch_group_arrays = dispatch_arrays

    async def scenario():
        import asyncio

        ring = RequestRing(workers=1, slots_small=2, slots_large=1,
                           large_rows=8)
        service = RingService(NeverDispatch(), ring, monitor_fetch_every_s=0)
        try:
            client = RingClient(ring, 0)
            loop = asyncio.get_running_loop()
            loop.add_reader(
                ring.worker_doorbells[0].fileno(), client.on_doorbell
            )
            slot = client.claim(1)
            cat = np.zeros((1, SCHEMA.num_categorical), np.int32)
            num = np.zeros((1, SCHEMA.num_numeric), np.float32)
            future = client.submit(
                slot, cat, num, deadline=time.monotonic() - 0.5
            )
            service.start()
            status = await asyncio.wait_for(future, timeout=10)
            assert status == RESP_EXPIRED
            assert int(ring.rob_vals[0, ROB_EXPIRED_ENGINE]) == 1
            client.release(slot)
            loop.remove_reader(ring.worker_doorbells[0].fileno())
        finally:
            service.stop()
            ring.close()

    import asyncio

    asyncio.run(scenario())


def test_multiple_rules_on_one_point_compose():
    """A declined first rule (max_fires exhausted) must not shadow a
    later rule on the same point — 'stall N times, then escalate' plans
    compose, with each rule scheduling on its own counters."""
    plan = faults.FaultPlan.from_rules([
        {"point": "p", "mode": "raise", "exc": "ValueError",
         "max_fires": 2},
        {"point": "p", "mode": "raise", "exc": "OSError"},
    ])
    faults.arm(plan)
    kinds = []
    for _ in range(5):
        try:
            faults.fire("p")
            kinds.append("ok")
        except ValueError:
            kinds.append("first")
        except OSError:
            kinds.append("second")
    faults.disarm()
    assert kinds == ["first", "first", "second", "second", "second"]


def test_mode_mismatch_neither_fires_nor_burns_budget():
    """A raise-mode rule on a corrupt() point (and vice versa) is a plan
    misconfiguration that must test NOTHING rather than lie: no action,
    no trace entry, no max_fires burned."""
    plan = faults.FaultPlan.from_rules(
        [{"point": "read", "mode": "raise", "max_fires": 1}]
    )
    faults.arm(plan)
    out = faults.corrupt("read", b"payload")
    faults.disarm()
    assert out == b"payload"
    assert plan.fires() == 0 and plan.trace() == []

    plan2 = faults.FaultPlan.from_rules(
        [{"point": "p", "mode": "corrupt"}]
    )
    faults.arm(plan2)
    faults.fire("p")  # must not raise/delay/kill and must not count
    faults.disarm()
    assert plan2.fires() == 0 and plan2.trace() == []

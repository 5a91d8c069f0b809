"""Engine replica set tests (mlops_tpu/replicaset/ + the ipc replica axis).

The correctness bar for ISSUE 13:

- `ReplicaRouter` units: least-loaded with a DETERMINISTIC tie-break,
  small-class affinity that holds inside the slack and re-picks beyond
  it, and routing AROUND a dead replica;
- E-replica fan-out parity: responses bit-identical to the single-engine
  plane (same programs, same slabs, same formatter — the router only
  chooses WHERE, never WHAT);
- per-replica re-attach: replica k's respawn replays exactly the busy
  slots tagged k, never a sibling's in-flight work;
- the render fix: every per-replica series is emitted for ALL configured
  replicas on every scrape — a never-dispatched replica exports zeros,
  because "no series" is indistinguishable from "dead replica";
- lock discipline: the PR 5 runtime sanitizer over an E-replica plane
  (per-replica queue locks wrapped explicitly — subscripted lock lists
  are invisible to the attribute-based instrumenter) across seeded
  schedule perturbations;
- partition-rule sharding: a large family (moe) served through
  SHARDED-not-replicated params with a bit-identical parity pin.
"""

import asyncio
import json

import numpy as np
import pytest

from mlops_tpu.replicaset import ReplicaRouter
from mlops_tpu.serve.ipc import LARGE, SMALL, RequestRing, RingClient, RingService


@pytest.fixture(scope="module")
def engine(warm_engine):
    return warm_engine  # session-shared warmed engine (conftest)


# ----------------------------------------------------------- router units
def _bare_ring(replicas: int, workers: int = 2) -> RequestRing:
    return RequestRing(
        workers=workers, slots_small=4, slots_large=1, large_rows=8,
        replicas=replicas,
    )


def test_router_least_loaded_tie_break_is_deterministic():
    ring = _bare_ring(3)
    try:
        ring.set_ready(True)
        router = ReplicaRouter(ring)
        # All depths equal: the tie breaks to the LOWEST index, every
        # time (two workers observing the same gauges agree).
        assert [router.route(0, LARGE) for _ in range(5)] == [0] * 5
        ring.rep_inflight[0, 0] = 3
        assert router.route(0, LARGE) == 1
        ring.rep_inflight[1, 1] = 3
        assert router.route(0, LARGE) == 2
        # Depth sums ACROSS workers: worker 0 and 1 each holding one on
        # replica 2 outweighs a single-slot replica.
        ring.rep_inflight[0, 2] = 2
        ring.rep_inflight[1, 2] = 2
        ring.rep_inflight[0, 0] = 1
        ring.rep_inflight[1, 1] = 0
        assert router.route(0, LARGE) == 1
    finally:
        ring.close()


def test_router_small_class_affinity_under_skewed_mix():
    ring = _bare_ring(2)
    try:
        ring.set_ready(True)
        router = ReplicaRouter(ring, affinity_slack=4)
        first = router.route(7, SMALL)
        assert first == 0
        # Inside the slack the sticky replica keeps winning even while
        # it is strictly deeper — that is the coalescing-company bet.
        ring.rep_inflight[0, 0] = 4
        assert router.route(7, SMALL) == 0
        # Beyond the slack the router re-picks least-loaded and the
        # stickiness moves with it.
        ring.rep_inflight[0, 0] = 5
        assert router.route(7, SMALL) == 1
        ring.rep_inflight[0, 1] = 2  # deeper, but inside the slack again
        assert router.route(7, SMALL) == 1
        # A DIFFERENT tenant's small traffic sticks independently.
        assert router.route(8, SMALL) == 1  # least-loaded now: 1? no —
        # depths: r0=5, r1=2 -> least is 1; tenant 8 sticks there.
        # The LARGE class never consults affinity: pure least-loaded.
        ring.rep_inflight[0, 1] = 9
        assert router.route(7, LARGE) == 0
    finally:
        ring.close()


def test_router_routes_around_dead_replica():
    ring = _bare_ring(3)
    try:
        ring.set_ready(True)
        router = ReplicaRouter(ring)
        sticky = router.route(0, SMALL)
        assert sticky == 0
        # Replica 0 dies: the supervisor clears its ready word — both
        # classes must route around the hole, sticky or not.
        ring.set_ready(False, 0)
        assert router.route(0, SMALL) != 0
        assert router.route(0, LARGE) != 0
        # Full outage: nothing ready. The router still names a concrete
        # replica (admissions PARK on its queue; the first replacement
        # to attach replays them) instead of refusing.
        ring.set_ready(False)
        assert router.route(0, LARGE) in (0, 1, 2)
    finally:
        ring.close()


def test_serveconfig_rejects_replicas_without_ring_plane():
    from mlops_tpu.config import ServeConfig, ServeConfigError

    with pytest.raises(ServeConfigError, match="engine_replicas"):
        ServeConfig(workers=0, engine_replicas=2).validate()
    assert ServeConfig(workers=2, engine_replicas=2).validate()


# ------------------------------------------------- render fix (satellite)
def test_render_emits_every_replica_series_on_every_scrape():
    """A never-dispatched replica must still export ALL its per-replica
    series (zeros): on a dashboard, an absent series is indistinguishable
    from a dead replica — the same always-emit contract PR 6 pinned for
    the per-worker depth/shed series."""
    from mlops_tpu.serve.metrics import render_ring_metrics

    ring = _bare_ring(3)
    try:
        ring.set_ready(True, 0)  # replicas 1 and 2 never served anything
        text = render_ring_metrics(ring)
        for r in range(3):
            for series, value in (
                ("mlops_tpu_replica_ready", 1 if r == 0 else 0),
                ("mlops_tpu_replica_ring_depth", 0),
                ("mlops_tpu_replica_incarnation", 0),
                ("mlops_tpu_replica_respawn_total", 0),
                ("mlops_tpu_replica_replayed_slots_total", 0),
                ("mlops_tpu_replica_rows_scored_total", 0),
            ):
                line = f'{series}{{replica="{r}"}} {value}'
                assert line in text, line
    finally:
        ring.close()


# ------------------------------------------------ per-replica re-attach
def test_reattach_replays_only_own_replica_slots(engine, sample_request):
    """Replica 0's respawn must replay exactly the busy slots tagged
    replica 0 — a sibling's in-flight slot is the sibling's live work
    (or its own successor's replay) and double-answering it would serve
    one slab twice."""
    from mlops_tpu.schema import records_to_columns
    from mlops_tpu.serve.wire import RESP_OK

    async def scenario():
        ring = RequestRing(
            workers=1, slots_small=4, slots_large=1, large_rows=8,
            replicas=2,
        )
        try:
            client = RingClient(ring, 0)
            ds = engine.bundle.preprocessor.encode(
                records_to_columns(sample_request)
            )
            slot0 = client.claim(len(sample_request))
            fut0 = client.submit(slot0, ds.cat_ids, ds.numeric, replica=0)
            slot1 = client.claim(len(sample_request))
            fut1 = client.submit(slot1, ds.cat_ids, ds.numeric, replica=1)
            # Both replicas' dead incarnations popped their descriptors
            # and died mid-batch.
            assert [s for s, _ in ring.pop_submissions(replica=0)] == [slot0]
            assert [s for s, _ in ring.pop_submissions(replica=1)] == [slot1]
            service0 = RingService(
                engine, ring, max_inflight=2, threads=2, replica=0
            )
            try:
                stats = service0.reattach()
            finally:
                service0.stop()
            assert stats["replayed_slots"] == 1
            client.on_doorbell(0)
            client.on_doorbell(1)
            assert fut0.done() and int(fut0.result()) == RESP_OK
            assert not fut1.done(), "a sibling's slot was double-served"
            # Replica 1's own successor answers its slot.
            service1 = RingService(
                engine, ring, max_inflight=2, threads=2, replica=1
            )
            try:
                stats1 = service1.reattach()
            finally:
                service1.stop()
            assert stats1["replayed_slots"] == 1
            client.on_doorbell(1)
            assert fut1.done() and int(fut1.result()) == RESP_OK
            client.release(slot0)
            client.release(slot1)
            assert int(ring.rep_inflight.sum()) == 0
        finally:
            ring.close()

    asyncio.run(scenario())


# ------------------------------------------------------- fan-out parity
def test_two_replica_fanout_responses_bit_identical(engine, sample_request):
    """Distinct payloads fanned out across two replica services must come
    back byte-identical to solo predicts — the router chooses WHERE, the
    shared programs and the one formatter decide WHAT."""
    from mlops_tpu.schema import records_to_columns
    from mlops_tpu.serve.wire import RESP_OK, format_response

    base = dict(sample_request[0])
    variants = []
    for i in range(8):
        record = dict(base)
        record["credit_limit"] = 1000.0 + 500.0 * i
        variants.append(record)
    expected = [
        json.loads(json.dumps(engine.predict_records([r])))
        for r in variants
    ]

    async def scenario():
        ring = RequestRing(
            workers=1, slots_small=16, slots_large=2, large_rows=8,
            replicas=2,
        )
        services = [
            RingService(engine, ring, max_inflight=2, threads=4, replica=r)
            for r in range(2)
        ]
        try:
            for r, service in enumerate(services):
                service.reattach()
                service.start()
                ring.set_ready(True, r)
            loop = asyncio.get_running_loop()
            client = RingClient(ring, 0)
            for r in range(2):
                loop.add_reader(
                    ring.worker_doorbell(0, r).fileno(),
                    client.on_doorbell,
                    r,
                )

            async def one(i: int) -> dict:
                ds = engine.bundle.preprocessor.encode(
                    records_to_columns([variants[i]])
                )
                slot = client.claim(1)
                assert slot is not None
                # Force the spread: even -> replica 0, odd -> replica 1,
                # so BOTH replicas provably serve (the router's own
                # spread is covered by its units).
                future = client.submit(
                    slot, ds.cat_ids, ds.numeric, replica=i % 2
                )
                status = await asyncio.wait_for(future, 30)
                assert status == RESP_OK
                pred, out, drift = client.response_arrays(slot)
                got = format_response(
                    np.array(pred), np.array(out), np.array(drift)
                )
                client.release(slot)
                return got

            results = await asyncio.gather(
                *(one(i) for i in range(len(variants)))
            )
            for r in range(2):
                loop.remove_reader(ring.worker_doorbell(0, r).fileno())
            for i, got in enumerate(results):
                assert json.loads(json.dumps(got)) == expected[i], f"req {i}"
            # Both replicas actually dispatched (each row's dispatch
            # telemetry is written by that replica's pool threads only).
            from mlops_tpu.serve.metrics import ENG_ROWS_DISPATCHED

            served = [
                int(ring.eng_vals[r, ENG_ROWS_DISPATCHED]) for r in range(2)
            ]
            assert all(s > 0 for s in served), served
        finally:
            for service in services:
                service.stop()
            ring.close()

    asyncio.run(scenario())


# --------------------------------------------------------- lock hygiene
@pytest.mark.parametrize(
    "seed",
    [0, pytest.param(1, marks=pytest.mark.slow),
     pytest.param(2, marks=pytest.mark.slow)],
)
def test_replica_plane_lock_discipline_under_perturbed_schedules(seed):
    """The PR 5 runtime sanitizer over router + E-replica RingService:
    the per-replica queue-lock LISTS are wrapped explicitly (the
    attribute instrumenter only sees scalar lock attrs) under the names
    the ipc manifest declares; zero order violations across seeded
    schedules, and every simulated response stays correct."""
    from mlops_tpu.analysis.lockcheck import LockSanitizer, instrument_locks
    from mlops_tpu.replicaset.sim import build_sim_plane, drive_grouped_load

    plane = build_sim_plane(
        replicas=2, device_ms=1.0, slots_small=32, max_group=8,
        max_inflight=2,
    )
    ring = plane.ring
    ring_san = LockSanitizer(
        order=("_submit_locks", "_complete_locks", "_profile_lock"),
        perturb_seed=seed,
    )
    saved_submit = ring._submit_locks
    saved_complete = ring._complete_locks
    ring._submit_locks = [
        ring_san.wrap(lock, "_submit_locks") for lock in saved_submit
    ]
    ring._complete_locks = [
        ring_san.wrap(lock, "_complete_locks") for lock in saved_complete
    ]
    try:
        with instrument_locks(
            plane.services[0], perturb_seed=seed
        ) as san0, instrument_locks(
            plane.services[1], perturb_seed=seed
        ) as san1:
            out = asyncio.run(
                drive_grouped_load(plane, duration_s=1.0, concurrency=24)
            )
        assert out["wrong"] == 0
        assert out["served"] > 0
        for sanitizer in (ring_san, san0, san1):
            assert not sanitizer.violations, [
                str(v) for v in sanitizer.violations
            ]
        assert ring_san.acquired.get("_submit_locks"), (
            "per-replica submit locks never exercised"
        )
        assert ring_san.acquired.get("_complete_locks")
    finally:
        ring._submit_locks = saved_submit
        ring._complete_locks = saved_complete
        plane.stop()


# ------------------------------------------------ partition-rule sharding
def test_mlp_engine_serves_through_sharded_params(tiny_pipeline, sample_request):
    """Fast tier-1 pin: serve.model_shards=2 lays the mlp trunk out over
    a ('model',) mesh (column/row cuts from PARAM_RULES) and responses
    stay bit-identical to the unsharded engine — same masked packed
    programs, layouts differ, XLA inserts the psums."""
    import jax

    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.serve.engine import InferenceEngine

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 (simulated) devices")
    _, result = tiny_pipeline
    baseline = InferenceEngine(
        load_bundle(result.bundle_dir), buckets=(1, 8), enable_grouping=False
    )
    baseline.warmup()
    expected = baseline.predict_records(sample_request)
    sharded = InferenceEngine(
        load_bundle(result.bundle_dir),
        buckets=(1, 8),
        enable_grouping=False,
        model_shards=2,
    )
    sharded.warmup()
    leaves = jax.tree_util.tree_leaves(sharded._variables)
    assert any(not leaf.sharding.is_fully_replicated for leaf in leaves), (
        "no leaf actually sharded — the rules matched nothing"
    )
    got = sharded.predict_records(sample_request)
    assert json.loads(json.dumps(got)) == json.loads(json.dumps(expected))


# Heaviest path (tiny moe train ~45 s serial): CI's parallel job runs it.
@pytest.mark.slow
def test_moe_large_family_served_sharded_not_replicated(tmp_path):
    """ISSUE 13 acceptance parity pin: a LARGE family (moe) trains,
    bundles, and serves through EXPERT-SHARDED params (stacked [E, ...]
    expert weights split over the model axis) with
    responses bit-identical to the unsharded engine."""
    import jax

    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.config import Config, ModelConfig, TrainConfig
    from mlops_tpu.schema import LoanApplicant
    from mlops_tpu.serve.engine import InferenceEngine
    from mlops_tpu.train.pipeline import run_training

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 (simulated) devices")
    config = Config()
    config.data.rows = 2000
    config.model = ModelConfig(
        family="moe", token_dim=16, depth=1, heads=2, num_experts=2
    )
    config.train = TrainConfig(steps=30, eval_every=30, batch_size=256)
    config.registry.root = str(tmp_path / "registry")
    config.registry.run_root = str(tmp_path / "runs")
    result = run_training(config, register=False)
    record = [LoanApplicant().model_dump()]
    baseline = InferenceEngine(
        load_bundle(result.bundle_dir), buckets=(1, 8), enable_grouping=False
    )
    baseline.warmup()
    expected = baseline.predict_records(record)
    sharded = InferenceEngine(
        load_bundle(result.bundle_dir),
        buckets=(1, 8),
        enable_grouping=False,
        model_shards=2,
    )
    sharded.warmup()
    # The EXPERT axis is what shards — stacked [E, D, F] weights split
    # across the model mesh instead of replicating per device.
    from jax.tree_util import tree_leaves_with_path

    expert_leaves = [
        (path, leaf)
        for path, leaf in tree_leaves_with_path(sharded._variables)
        if "experts_" in str(path)
    ]
    assert expert_leaves
    assert any(
        not leaf.sharding.is_fully_replicated for _, leaf in expert_leaves
    ), "expert weights replicated — partition rules missed the family"
    got = sharded.predict_records(record)
    assert json.loads(json.dumps(got)) == json.loads(json.dumps(expected))

"""Per-request SLO tier routing (ISSUE 19, serve/tierroute.py): class
resolution, the brownout governor's hysteresis, the engine's class->tier
ladder, and the bit-identity contract for demoted traffic (a demoted
response must be EXACTLY what serving the cheaper tier directly returns
— demotion changes which program answers, never what that program
says)."""

import numpy as np
import pytest

from mlops_tpu.config import ServeConfig
from mlops_tpu.serve.httpcore import HttpProtocol
from mlops_tpu.serve.tierroute import (
    SLO_ACCURATE,
    SLO_CHEAP,
    SLO_DEFAULT,
    TIERS,
    BrownoutGovernor,
    parse_slo_class,
    resolve_slo_class,
    tier_for_class,
)


# ------------------------------------------------------ class resolution
def test_parse_slo_class_closed_set():
    assert parse_slo_class("default") == SLO_DEFAULT
    assert parse_slo_class("cheap") == SLO_CHEAP
    assert parse_slo_class("ACCURATE ") == SLO_ACCURATE
    assert parse_slo_class("fast") is None
    assert parse_slo_class("") is None


def test_resolve_explicit_header_wins_over_deadline():
    # A generous deadline with an explicit cheap header still routes
    # cheap; a tight deadline with an explicit accurate header is pinned.
    assert resolve_slo_class("cheap", 5000.0, 50.0) == SLO_CHEAP
    assert resolve_slo_class("accurate", 10.0, 50.0) == SLO_ACCURATE


def test_resolve_tight_deadline_routes_cheap():
    assert resolve_slo_class("", 20.0, 50.0) == SLO_CHEAP
    assert resolve_slo_class("", 50.0, 50.0) == SLO_CHEAP  # inclusive
    assert resolve_slo_class("", 51.0, 50.0) == SLO_DEFAULT
    assert resolve_slo_class("", None, 50.0) == SLO_DEFAULT
    # cheap_deadline_ms <= 0 disables deadline routing entirely
    assert resolve_slo_class("", 1.0, 0.0) == SLO_DEFAULT


def test_resolve_malformed_header_falls_through_to_deadline():
    assert resolve_slo_class("turbo", 20.0, 50.0) == SLO_CHEAP
    assert resolve_slo_class("turbo", None, 50.0) == SLO_DEFAULT


def test_tier_for_class_ladder_semantics():
    ladder = ("quant", "exact")
    assert tier_for_class(ladder, "exact", SLO_CHEAP) == "quant"
    assert tier_for_class(ladder, "exact", SLO_ACCURATE) == "exact"
    assert tier_for_class(ladder, "exact", SLO_DEFAULT) == "exact"
    assert tier_for_class(ladder, "quant", SLO_DEFAULT) == "quant"
    # one-tier engine: every class collapses onto the only program
    assert tier_for_class(("gbm",), "gbm", SLO_CHEAP) == "gbm"
    assert tier_for_class(("gbm",), "gbm", SLO_ACCURATE) == "gbm"


# ----------------------------------------------- admission header parsing
def _protocol(**cfg_kwargs) -> HttpProtocol:
    return HttpProtocol(ServeConfig(**cfg_kwargs))


def test_request_slo_disarmed_by_default():
    proto = _protocol()
    assert not proto.slo_routing
    assert proto._request_slo({"x-slo-class": "cheap"}) == SLO_DEFAULT


def test_request_slo_header_and_deadline_routing():
    proto = _protocol(tier_routing=True, slo_cheap_deadline_ms=50.0)
    assert proto.slo_routing
    assert proto._request_slo({}) == SLO_DEFAULT
    assert proto._request_slo({"x-slo-class": "cheap"}) == SLO_CHEAP
    assert proto._request_slo({"x-slo-class": "accurate"}) == SLO_ACCURATE
    assert proto._request_slo({"x-slo-class": "warp9"}) == SLO_DEFAULT
    # deadline-budget routing: tight budgets choose the cheap tier
    assert (
        proto._request_slo({"x-request-deadline-ms": "20"}) == SLO_CHEAP
    )
    assert (
        proto._request_slo({"x-request-deadline-ms": "500"}) == SLO_DEFAULT
    )
    # malformed deadline hints are ignored, never 4xx material
    assert (
        proto._request_slo({"x-request-deadline-ms": "-5"}) == SLO_DEFAULT
    )
    assert (
        proto._request_slo({"x-request-deadline-ms": "soon"}) == SLO_DEFAULT
    )


# -------------------------------------------------------------- governor
def test_brownout_governor_hysteresis_and_flap_counters():
    gov = BrownoutGovernor(demote_depth=0.75, restore_depth=0.5)
    assert not gov.observe(0.5)
    assert not gov.observe(0.74)
    assert gov.observe(0.75)  # enters at the demote threshold
    assert gov.entered == 1
    # stays active anywhere above the restore threshold (no flapping)
    assert gov.observe(0.6)
    assert gov.observe(0.51)
    assert not gov.observe(0.5)  # restores at the restore threshold
    assert gov.exited == 1
    assert not gov.observe(0.74)  # needs a fresh crossing to re-enter
    assert gov.observe(0.9)
    assert gov.entered == 2


def test_brownout_routes_default_only():
    gov = BrownoutGovernor()
    # inactive: every class passes through untouched
    assert gov.route(SLO_DEFAULT) == (SLO_DEFAULT, False)
    gov.observe(1.0)
    assert gov.route(SLO_DEFAULT) == (SLO_CHEAP, True)
    # cheap is already at the floor; accurate is the pinned escape hatch
    assert gov.route(SLO_CHEAP) == (SLO_CHEAP, False)
    assert gov.route(SLO_ACCURATE) == (SLO_ACCURATE, False)
    assert gov.demotions == 1
    assert gov.brownout_demotions == 1


def test_governor_rejects_inverted_thresholds():
    with pytest.raises(ValueError):
        BrownoutGovernor(demote_depth=0.5, restore_depth=0.5)
    with pytest.raises(ValueError):
        BrownoutGovernor(demote_depth=0.0)


def test_serve_config_validates_brownout_depths():
    from mlops_tpu.config import ServeConfigError

    cfg = ServeConfig(
        brownout_demote_depth=0.4, brownout_restore_depth=0.6
    )
    with pytest.raises(ServeConfigError, match="brownout"):
        cfg.validate()


# ------------------------------------------- multi-tier engine contract
@pytest.fixture(scope="module")
def quant_pipeline(tmp_path_factory):
    """A flax training run with the quant student opted in — the bundle
    that gates TWO serving tiers (quant + exact)."""
    from mlops_tpu.config import Config, ModelConfig, TrainConfig
    from mlops_tpu.train.pipeline import run_training

    root = tmp_path_factory.mktemp("tierroute")
    config = Config()
    config.data.rows = 3000
    config.model = ModelConfig(
        family="mlp", hidden_dims=(32, 32), embed_dim=4
    )
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
def routed_engine(quant_bundle):
    """Exact-default engine with the whole gated ladder committed."""
    from mlops_tpu.serve.engine import InferenceEngine

    assert quant_bundle.has_quant and quant_bundle.quant_gates_passed
    return InferenceEngine(
        quant_bundle, buckets=(1, 8), tier_routing=True
    )


def test_multi_tier_ladder_and_routing(routed_engine):
    assert routed_engine.default_tier == "exact"
    assert routed_engine.available_tiers == ("quant", "exact")
    for tier in routed_engine.available_tiers:
        assert tier in TIERS
    # default/accurate classes keep the default program (None = the
    # plain un-suffixed exec keys, bit-for-bit the historical dispatch)
    assert routed_engine.route_tier(SLO_DEFAULT) is None
    assert routed_engine.route_tier(SLO_ACCURATE) is None
    # cheap routes the gated student
    assert routed_engine.route_tier(SLO_CHEAP) == "quant"


def test_quant_default_engine_keeps_exact_escape_hatch(quant_bundle):
    from mlops_tpu.serve.engine import InferenceEngine

    engine = InferenceEngine(
        quant_bundle, buckets=(1,), serve_tier="quant", tier_routing=True
    )
    assert engine.default_tier == "quant"
    assert engine.available_tiers == ("quant", "exact")
    assert engine.route_tier(SLO_CHEAP) is None
    assert engine.route_tier(SLO_ACCURATE) == "exact"


def test_demoted_response_bit_identical_to_cheap_tier(
    quant_bundle, routed_engine
):
    """A brownout-demoted request (exact-default engine, tier='quant')
    returns byte-for-byte what an engine CONFIGURED for the quant tier
    serves — demotion swaps programs, never bits."""
    from mlops_tpu.serve.engine import InferenceEngine

    records = [
        {"age": 30.0, "credit_limit": 2000.0},
        {"age": 61.0, "bill_amount_1": 700.0},
    ]
    quant_native = InferenceEngine(
        quant_bundle, buckets=(1, 8), serve_tier="quant"
    )
    demoted = routed_engine.predict_records(records, tier="quant")
    native = quant_native.predict_records(records)
    assert demoted["predictions"] == native["predictions"]
    assert demoted["outliers"] == native["outliers"]
    assert (
        demoted["feature_drift_batch"] == native["feature_drift_batch"]
    )
    # ...and the default-tier path stays bit-identical to a plain
    # single-tier engine (routing must not perturb un-routed traffic).
    exact_native = InferenceEngine(quant_bundle, buckets=(1, 8))
    assert (
        routed_engine.predict_records(records)["predictions"]
        == exact_native.predict_records(records)["predictions"]
    )


def test_grouped_demotion_bit_identical(quant_bundle, routed_engine):
    from mlops_tpu.serve.engine import InferenceEngine

    requests = [
        [{"age": 25.0}],
        [{"age": 44.0, "credit_limit": 5000.0}, {"age": 31.0}],
    ]
    quant_native = InferenceEngine(
        quant_bundle, buckets=(1, 8), serve_tier="quant"
    )
    demoted = routed_engine.predict_group(requests, tier="quant")
    native = quant_native.predict_group(requests)
    for d, n in zip(demoted, native):
        assert d["predictions"] == n["predictions"]
        assert d["outliers"] == n["outliers"]


def test_ring_replay_resolves_the_same_tier_from_shm(routed_engine):
    """The engine-side tier resolver reads the CLASS back out of the shm
    slot header — a respawned engine's replay therefore re-derives the
    identical tier (the crash-survivability half of the routing
    contract)."""

    class _Ring:
        slot_slo = np.array([SLO_CHEAP, SLO_DEFAULT], np.uint32)

    class _Svc:
        ring = _Ring()
        engines = [routed_engine]

    from mlops_tpu.serve.ipc import RingService

    assert RingService._slot_tier(_Svc(), 0, 0) == "quant"
    assert RingService._slot_tier(_Svc(), 1, 0) is None

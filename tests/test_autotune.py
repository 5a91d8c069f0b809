"""gridtuner (mlops_tpu/autotune/): cost model, grid search, hot regrid.

Three layers, cheapest first: jax-free units over the cost model and the
exact DP search (including the plan-coverage PROPERTY — every plan warms
a bucket for 100% of the observed shape histogram, so a regrid can never
introduce a hot-path compile), controller tick semantics on a stub
engine, then the real-engine hot-regrid path (warm -> twin -> swap ->
rollback) on the shared tiny pipeline bundle.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from mlops_tpu.autotune import (
    AutotuneController,
    CostModel,
    GridPlan,
    apply_plan,
    demand_from_shapes,
    fit_cost_model,
    ledger_rows_from_snapshot,
    search_plan,
    warm_plan,
)
from mlops_tpu.autotune.costmodel import (
    MEASURED_OVERHEAD_FRACTION,
    demand_from_spans,
)
from mlops_tpu.autotune.search import score_grid
from mlops_tpu.config import AutotuneConfig, AutotuneConfigError
from mlops_tpu.trace.shapes import ShapeStats


def _rows(points):
    """(size, mean_dispatch_s, dispatches) -> ledger report rows."""
    return [
        {
            "entry": f"bucket_{size}",
            "device_s": cost * weight,
            "dispatches": weight,
            "rows": size * weight,
            "padded_rows": size * weight,
        }
        for size, cost, weight in points
    ]


# ------------------------------------------------------------ cost model
def test_fit_recovers_affine_coefficients():
    # Exact affine data: a=2ms overhead, b=10us/padded-row.
    a, b = 2e-3, 1e-5
    model = fit_cost_model(
        _rows([(s, a + b * s, 100.0) for s in (1, 8, 64, 256)])
    )
    assert model is not None and model.mode == "affine-fit"
    assert model.a_s == pytest.approx(a, rel=1e-9)
    assert model.b_s == pytest.approx(b, rel=1e-9)
    assert model.dispatch_s(128) == pytest.approx(a + b * 128)


def test_fit_single_point_measured_affine_split():
    model = fit_cost_model(_rows([(64, 4e-3, 50.0)]))
    assert model is not None and model.mode == "measured-affine"
    assert model.points == 1
    assert model.a_s == pytest.approx(4e-3 * MEASURED_OVERHEAD_FRACTION)
    # The split preserves the measured absolute cost at the observed size.
    assert model.dispatch_s(64) == pytest.approx(4e-3)


def test_fit_nonphysical_slope_degrades_to_measured_affine():
    # Bigger buckets measured CHEAPER (noise): optimizing that slope
    # would reward maximal padding — the fit must refuse.
    model = fit_cost_model(_rows([(1, 5e-3, 10.0), (256, 1e-3, 10.0)]))
    assert model is not None and model.mode == "measured-affine"
    assert model.b_s > 0 and model.a_s >= 0


def test_fit_holds_without_solo_observations():
    assert fit_cost_model([]) is None
    assert fit_cost_model(
        [{"entry": "group_8x8", "device_s": 1.0, "dispatches": 10.0,
          "rows": 100.0, "padded_rows": 640.0}]
    ) is None


def test_ledger_snapshot_folds_model_tags():
    rows = ledger_rows_from_snapshot(
        {
            "bucket_8@abc123": [1.0, 10.0, 60.0, 80.0],
            "bucket_8@def456": [3.0, 30.0, 180.0, 240.0],
            "group_8x8": [1.0, 1.0, 8.0, 64.0],
        }
    )
    by_entry = {r["entry"]: r for r in rows}
    assert by_entry["bucket_8"]["dispatches"] == 40.0
    assert by_entry["bucket_8"]["device_s"] == 4.0
    assert by_entry["group_8x8"]["rows"] == 8.0


# ---------------------------------------------------------------- demand
def test_demand_from_shapes_mass_matches_requested_counters():
    stats = ShapeStats()
    rng = np.random.default_rng(3)
    total_requested = total_dispatches = 0
    for _ in range(500):
        n = int(rng.integers(1, 65))
        padded = 8 if n <= 8 else 64
        stats.observe(f"bucket_{padded}", n, padded)
        total_requested += n
        total_dispatches += 1
    demand = demand_from_shapes(stats.snapshot())
    assert sum(w for _, w in demand) == pytest.approx(total_dispatches)
    # The histogram bounds granularity; the rescale pins the mass to the
    # exact requested counter (per-point integer rounding is the only
    # slack left).
    mass = sum(r * w for r, w in demand)
    assert mass == pytest.approx(total_requested, rel=0.02)
    # Group entries never contribute (fixed geometry).
    stats.observe("group_8x8", 5, 64)
    assert demand_from_shapes(stats.snapshot()) == demand


def test_demand_from_spans_exact_rows():
    spans = [
        {"entry": "bucket_8", "rows": 3},
        {"entry": "bucket_8", "rows": 3},
        {"entry": "bucket_64", "rows": 40},
        {"entry": "group_8x8", "rows": 5},  # grouped: excluded
        {"entry": "bucket_8", "rows": 0},  # malformed: excluded
    ]
    assert demand_from_spans(spans) == [(3, 2.0), (40, 1.0)]


# ---------------------------------------------------------------- search
MODEL = CostModel(a_s=2e-3, b_s=1e-5, points=4, mode="affine-fit")


def test_search_beats_hand_picked_grid_on_skewed_trace():
    # The acceptance trace: heavily skewed small-batch demand on a
    # hand-picked (1, 8, 64, 256) grid — almost everything dispatches at
    # 8 or 64 rows while asking for 3 or 12.
    demand = [(3, 900.0), (12, 80.0), (200, 15.0), (256, 5.0)]
    # Padding-dominated economics (per-row cost well above overhead at
    # the observed sizes) — the regime where grid choice actually pays.
    model = CostModel(a_s=1e-3, b_s=1e-4, points=4, mode="affine-fit")
    plan = search_plan(demand, model, (1, 8, 64, 256), max_entries=16)
    assert plan.predicted_rows_per_s > plan.baseline_rows_per_s
    assert plan.predicted_gain_pct > 5.0
    assert plan.predicted_waste_pct < plan.baseline_waste_pct
    # The searched buckets sit ON the demand sizes (the DP's optimality
    # argument) and keep the live ceiling.
    assert set(plan.buckets) <= {3, 12, 200, 256}
    assert plan.buckets[-1] == 256


@pytest.mark.parametrize("seed", range(8))
def test_plan_covers_every_observed_shape(seed):
    """THE coverage property: every demand size (clamped to the live
    ceiling, which the plan must keep) has a bucket >= it — so warming
    exactly the plan's entries leaves NO observed shape to compile on
    the hot path after the swap."""
    rng = np.random.default_rng(seed)
    stats = ShapeStats()
    ceiling = int(rng.choice([64, 256, 1024]))
    for _ in range(int(rng.integers(50, 400))):
        n = int(
            min(np.exp(rng.uniform(0, np.log(ceiling))), ceiling)
        )
        padded = min(
            next(b for b in (1, 8, 64, 256, 1024) if b >= n), ceiling
        )
        stats.observe(f"bucket_{padded}", n, padded)
    demand = demand_from_shapes(stats.snapshot())
    max_entries = int(rng.integers(2, 17))
    plan = search_plan(demand, MODEL, (1, 8, ceiling), max_entries)
    assert len(plan.buckets) <= max_entries
    assert plan.buckets[-1] == ceiling  # the ceiling never shrinks
    for rows, _ in demand:
        clamped = min(rows, ceiling)
        assert any(b >= clamped for b in plan.buckets), (
            f"demand size {clamped} uncovered by {plan.buckets}"
        )
    # The live grid is inside the searched space, so the optimum never
    # loses to it.
    assert plan.predicted_gain_pct >= -1e-9


def test_score_grid_accounting():
    rate, waste = score_grid((8,), [(2, 10.0)], MODEL)
    # 10 dispatches of 2 useful rows padded to 8.
    assert rate == pytest.approx(20.0 / (10 * MODEL.dispatch_s(8)))
    assert waste == pytest.approx(100.0 * (80 - 20) / 80)


def test_plan_dict_round_trip():
    plan = search_plan([(3, 10.0)], MODEL, (1, 8), 4)
    doc = json.loads(json.dumps(plan.as_dict()))
    assert GridPlan.from_dict(doc) == plan
    assert doc["format"] == 1


# ---------------------------------------------------------------- config
def test_autotune_config_validates():
    AutotuneConfig().validate()
    with pytest.raises(AutotuneConfigError, match="interval_s"):
        AutotuneConfig(interval_s=0).validate()
    with pytest.raises(AutotuneConfigError, match="max_entries"):
        AutotuneConfig(max_entries=1).validate()
    with pytest.raises(AutotuneConfigError, match="plan_dir"):
        AutotuneConfig(enabled=True, plan_dir="").validate()


# ------------------------------------------------------------ controller
class _StubLedger:
    def __init__(self):
        self.entries = {}

    def snapshot(self):
        return {k: list(v) for k, v in self.entries.items()}


class _StubEngine:
    monitor_accumulating = True

    def __init__(self, buckets=(1, 8, 64, 256)):
        self.buckets = tuple(buckets)
        self.grid_generation = 0
        self.bundle_generation = 0
        self.shape_stats = ShapeStats()
        self.cost_ledger = _StubLedger()
        self.rolled_back = 0

    def rollback(self):
        self.rolled_back += 1
        self.grid_generation += 1

    def feed(self, demand, model=MODEL, ledger=True):
        for rows, weight in demand:
            padded = next(
                (b for b in self.buckets if b >= rows), self.buckets[-1]
            )
            for _ in range(int(weight)):
                self.shape_stats.observe(f"bucket_{padded}", rows, padded)
        if ledger:
            self.seed_ledger(model)

    def seed_ledger(self, model=MODEL):
        for b in self.buckets:
            self.cost_ledger.entries.setdefault(
                f"bucket_{b}",
                [model.dispatch_s(b) * 100, 100.0, b * 100.0, b * 100.0],
            )


def _config(tmp_path, **kw):
    kw.setdefault("plan_dir", str(tmp_path / "autotune"))
    kw.setdefault("min_dispatches", 10)
    return AutotuneConfig(enabled=True, **kw).validate()


def test_controller_holds_then_plans_dry_run(tmp_path):
    engine = _StubEngine()
    controller = AutotuneController(
        engine, _config(tmp_path, apply=False, min_gain_pct=1.0)
    )
    assert controller.run_once(now=0.0) == "held: 0 dispatches < min"
    engine.feed([(3, 900.0), (200, 20.0)])
    status = controller.run_once(now=1.0)
    assert status.startswith("planned (dry-run)")
    doc = json.loads((tmp_path / "autotune" / "plan.json").read_text())
    assert doc["applied"] is False and doc["buckets"][-1] == 256
    snap = controller.metrics_snapshot()
    assert snap["plans"]["planned"] == 1
    assert snap["predicted_gain_pct"] > 1.0
    assert snap["grid_generation"] == 0


def test_controller_disarmed_without_telemetry(tmp_path):
    engine = _StubEngine()
    engine.shape_stats = None
    controller = AutotuneController(engine, _config(tmp_path))
    assert controller.run_once(now=0.0) == "disarmed"


def test_controller_rejects_subthreshold_gains(tmp_path):
    engine = _StubEngine()
    engine.feed([(3, 900.0), (200, 20.0)])
    controller = AutotuneController(
        engine, _config(tmp_path, min_gain_pct=1e6)
    )
    status = controller.run_once(now=0.0)
    assert status.startswith("rejected: gain")
    assert controller.metrics_snapshot()["plans"]["rejected"] == 1


def test_controller_applies_then_cools_down(tmp_path, monkeypatch):
    engine = _StubEngine()
    engine.feed([(3, 900.0), (200, 20.0)])
    applied = []

    def fake_apply(eng, buckets, workers=0):
        applied.append(tuple(buckets))
        eng.buckets = tuple(buckets)
        eng.grid_generation += 1
        return eng.grid_generation

    monkeypatch.setattr("mlops_tpu.autotune.apply.apply_plan", fake_apply)
    controller = AutotuneController(
        engine, _config(tmp_path, min_gain_pct=1.0, cooldown_s=100.0)
    )
    status = controller.run_once(now=0.0)
    assert status == "applied: grid_generation=1"
    assert applied and applied[0][-1] == 256
    # Cooldown: the audit window must observe the new grid first.
    assert controller.run_once(now=50.0) == "cooling"
    assert controller.run_once(now=200.0) != "cooling"
    doc = json.loads((tmp_path / "autotune" / "plan.json").read_text())
    assert doc["applied"] is True and doc["grid_generation"] == 1


def test_sibling_adopts_leads_applied_plan(tmp_path, monkeypatch):
    lead_engine = _StubEngine()
    lead_engine.feed([(3, 900.0), (200, 20.0)])

    def fake_apply(eng, buckets, workers=0):
        eng.buckets = tuple(buckets)
        eng.grid_generation += 1
        return eng.grid_generation

    monkeypatch.setattr("mlops_tpu.autotune.apply.apply_plan", fake_apply)
    config = _config(tmp_path, min_gain_pct=1.0)
    lead = AutotuneController(lead_engine, config)
    assert lead.run_once(now=0.0).startswith("applied")

    sibling_engine = _StubEngine()
    sibling = AutotuneController(
        sibling_engine, config, adopt=True, replica=1
    )
    status = sibling.run_once(now=0.0)
    assert status == "adopted: grid_generation=1"
    assert sibling_engine.buckets == lead_engine.buckets
    # Idempotent: the same plan generation never re-applies.
    assert sibling.run_once(now=1.0) == "adopt: current"


def test_adopt_without_plan_is_a_noop(tmp_path):
    sibling = AutotuneController(
        _StubEngine(), _config(tmp_path), adopt=True, replica=1
    )
    assert sibling.run_once(now=0.0) == "adopt: no plan"


def test_controller_rollback_counts_and_restores(tmp_path):
    engine = _StubEngine()
    controller = AutotuneController(engine, _config(tmp_path))
    status = controller.rollback()
    assert status == "rolled_back: grid_generation=1"
    assert engine.rolled_back == 1
    assert controller.metrics_snapshot()["plans"]["rolled_back"] == 1


def test_measured_gain_audit_from_ledger_deltas(tmp_path, monkeypatch):
    engine = _StubEngine()

    def fake_apply(eng, buckets, workers=0):
        eng.buckets = tuple(buckets)
        eng.grid_generation += 1
        return eng.grid_generation

    monkeypatch.setattr("mlops_tpu.autotune.apply.apply_plan", fake_apply)
    controller = AutotuneController(
        engine, _config(tmp_path, min_gain_pct=1.0, cooldown_s=0.0)
    )
    # Tick 0 (held: no demand yet) captures the ledger totals; the next
    # window's delta is then exactly the rows/seconds added below.
    engine.seed_ledger()
    controller.run_once(now=0.0)
    engine.feed([(3, 900.0), (200, 20.0)], ledger=False)
    ledger = engine.cost_ledger.entries
    ledger["bucket_8"][0] += 1.0  # +1 device-second
    ledger["bucket_8"][2] += 500.0  # +500 useful rows
    assert controller.run_once(now=1.0).startswith("applied")
    # Post-apply window at double the rate; tick 3 is rejected (already
    # on the plan grid) so it measures WITHOUT resetting the audit.
    ledger["bucket_8"][0] += 1.0
    ledger["bucket_8"][2] += 1000.0
    assert controller.run_once(now=2.0).startswith("rejected")
    snap = controller.metrics_snapshot()
    assert snap["measured_gain_pct"] == pytest.approx(100.0, rel=0.01)


def test_warm_plan_refuses_non_accumulating_engine():
    class _Sklearn:
        monitor_accumulating = False

    with pytest.raises(ValueError, match="flax"):
        warm_plan(_Sklearn(), (1, 8))


# ------------------------------------------------------- real-engine path
@pytest.fixture(scope="module")
def regrid_engine(tiny_pipeline):
    """A private engine the regrid tests MAY mutate (warm_engine is the
    shared read-only one)."""
    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.serve.engine import InferenceEngine

    _, result = tiny_pipeline
    engine = InferenceEngine(
        load_bundle(result.bundle_dir), buckets=(1, 8), enable_grouping=False
    )
    engine.warmup()
    return engine


def test_hot_regrid_swap_and_rollback(regrid_engine, sample_request):
    engine = regrid_engine
    request = sample_request * 2  # 2 rows: pads to 8 now, to 2 after
    before = engine.predict_records(request)
    gen0 = engine.grid_generation
    new_gen = apply_plan(engine, (1, 2, 8))
    assert new_gen == gen0 + 1
    assert tuple(engine.buckets) == (1, 2, 8)
    with engine._compile_lock:
        assert ("bucket", 2) in engine._exec
    # Bit-stable across the regrid: same request, same floats, even
    # though it now dispatches through the new bucket_2 entry.
    after = engine.predict_records(request)
    assert after["predictions"] == pytest.approx(
        before["predictions"], abs=1e-6
    )
    engine.rollback()
    assert tuple(engine.buckets) == (1, 8)
    assert engine.grid_generation == gen0 + 2
    restored = engine.predict_records(request)
    assert restored["predictions"] == pytest.approx(
        before["predictions"], abs=1e-6
    )


def test_regrid_never_shrinks_the_ceiling(regrid_engine):
    with pytest.raises(ValueError, match="max_bucket"):
        apply_plan(regrid_engine, (1, 4))


def test_regrid_aborts_when_promotion_races_warm(
    regrid_engine, monkeypatch
):
    from mlops_tpu.autotune.apply import RegridAborted

    def racing_warm(engine, buckets, workers=0):
        engine.bundle_generation += 1  # a promotion landed mid-warm
        return 0

    monkeypatch.setattr("mlops_tpu.autotune.apply.warm_plan", racing_warm)
    generation = regrid_engine.grid_generation
    with pytest.raises(RegridAborted):
        apply_plan(regrid_engine, (1, 2, 8))
    assert regrid_engine.grid_generation == generation  # no swap happened

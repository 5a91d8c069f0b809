"""Inference engine: bundle -> warmed, bucketed, fused predict.

TPU serving mechanics (SURVEY.md SS7 "hard parts" — batch-1 latency):

- ONE compiled program per batch bucket (1, 8, 64, 256 by default): requests
  are padded up to the nearest bucket with a validity mask, so XLA never
  recompiles in steady state and drift/outlier statistics ignore padding.
- warmup compiles every bucket at startup (readiness gate — the reference
  has no readiness probe at all, `kubernetes/manifest.yml:1-54`).
- host work is minimal: string->id lookups and one float array build per
  request; everything else (classifier + monitors) is a single device
  dispatch.
- the device->host surface is ONE packed f32 buffer per request
  (predictions ‖ outlier flags ‖ drift — `ops/predict.py
  make_packed_predict_base`), its host copy started asynchronously at
  dispatch time, and the running monitor aggregate stays ON DEVICE
  (`monitor/state.py MonitorAccumulator`), read off the request path by
  `monitor_snapshot`.
"""

from __future__ import annotations

import bisect
import json
import logging
import threading
import time
from typing import Any

import jax
import numpy as np

from mlops_tpu import faults
from mlops_tpu.bundle.bundle import Bundle
from mlops_tpu.ops.gbm_tensor import (
    extract_gbm,
    make_gbm_grouped_base,
    make_gbm_packed_base,
    supports_gbm_tensorization,
    trace_context,
    x64_context,
)
from mlops_tpu.ops.predict import (
    ACC_DONATION,
    make_hybrid_predict_fn,
    make_packed_grouped_base,
    make_packed_predict_base,
    packed_layout,
)
from mlops_tpu.schema import SCHEMA, records_to_columns
from mlops_tpu.serve.tierroute import TIERS, tier_for_class

# Declared lock order, OUTERMOST FIRST — the single source of truth for
# both halves of tpulint Layer 3: the static analyzer
# (analysis/concurrency.py TPU401) checks every lexically nested
# acquisition against it, and the runtime sanitizer
# (analysis/lockcheck.py) asserts it on live thread schedules in the
# stress tests. ``_compile_lock`` may be held while the others are taken,
# never the reverse — the lifecycle hot swap (`swap_bundle`/`rollback`)
# nests ``_acc_lock`` under ``_compile_lock`` in exactly that order, and
# its critical section is pure ref assignment. ``_acc_lock`` and
# ``_totals_lock`` stay leaves below that: a blocking XLA compile or
# device fetch nested under the accumulator lock is exactly the PR 4
# stall this manifest exists to prevent.
TPULINT_LOCK_ORDER = {
    "InferenceEngine": ("_compile_lock", "_acc_lock", "_totals_lock")
}

logger = logging.getLogger("mlops_tpu.serve")


def _start_copy(tree: Any) -> None:
    """Begin the device->host copy of every array in ``tree`` WITHOUT
    blocking (``copy_to_host_async`` where the backend provides it): by
    the time the response path blocks in ``np.asarray`` the bytes are
    already moving — the transfer overlaps the host-side Python between
    dispatch and fetch."""

    def one(x):
        try:
            x.copy_to_host_async()
        except AttributeError:
            pass

    jax.tree_util.tree_map(one, tree)


def _pad_rows(
    cat: np.ndarray, num: np.ndarray, n: int, rows: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad ``n`` encoded rows up to ``rows`` with a validity mask — the
    one padding rule the target-bucket and degraded-bucket dispatches
    share (identical masking = identical statistics either way)."""
    pad = rows - n
    if pad:
        cat = np.pad(cat, ((0, pad), (0, 0)))
        num = np.pad(num, ((0, pad), (0, 0)))
    return cat, num, np.arange(rows) < n


def _key_tier(key: tuple) -> str | None:
    """Tier suffix of an exec-table key, None for the default tier:
    ``("bucket", rows[, tier])`` / ``("group", slots, rows[, tier])`` —
    the degraded-mode scans filter on it so a fallback never crosses
    tiers (a demoted request must pay padding, never different bits)."""
    n = 3 if key[0] == "group" else 2
    return key[n] if len(key) > n else None


def _entry_name(base: str, tier: str | None) -> str:
    """Telemetry entry label for a dispatch: the geometry, suffixed with
    the tier for NON-default tiers only — default-tier labels stay
    byte-identical to every earlier release's series."""
    return base if tier is None else f"{base}@{tier}"


class _ArraysHandle:
    """In-flight padded dispatch: the device output plus everything the
    fetch side needs to slice the packed buffer back into the response."""

    __slots__ = ("out", "n", "rows", "packed", "t0", "tier")

    def __init__(
        self, out: Any, n: int, rows: int, packed: bool, t0: float = 0.0,
        tier: str | None = None,
    ):
        self.out = out
        self.n = n
        self.rows = rows  # padded row count (bucket, or n at exact shape)
        self.packed = packed
        # Cost-ledger dispatch stamp (slo/ledger.py): perf_counter at
        # device enqueue, 0.0 when the ledger is disarmed — the fetch
        # side differences it into the entry's device-path seconds.
        self.t0 = t0
        self.tier = tier  # non-default serving tier, None = default

    def start_copy(self) -> None:
        _start_copy(self.out)


class _GroupHandle:
    """In-flight grouped dispatch (or the degenerate solo-path result)."""

    __slots__ = ("out", "sizes", "rows", "responses", "slots", "entry",
                 "t0", "tier")

    def __init__(self, out=None, sizes=None, rows=0, responses=None,
                 slots=0, t0=0.0, tier=None):
        self.out = out
        self.sizes = sizes
        self.rows = rows
        self.responses = responses  # set = degenerate path, already done
        self.slots = slots  # slot-bucket geometry actually dispatched
        self.t0 = t0  # cost-ledger dispatch stamp (see _ArraysHandle)
        self.tier = tier  # non-default serving tier, None = default
        # tracewire compiled-entry key, derived ONCE from the ints the
        # engine chose (degraded fallback included) — consumers carry the
        # ints (serve/ipc.py) or this string (the batcher's span entry),
        # never re-parse it.
        self.entry = (
            _entry_name(f"group_{slots}x{rows}", tier) if slots else None
        )

    def start_copy(self) -> None:
        if self.out is not None:
            _start_copy(self.out)

# Group geometry + response formatting live in the jax-free wire-contract
# module (serve/wire.py) so front-end processes can share them without
# this module's jax import; re-exported here because the batcher, the
# tests, and the compile-cache warmers have always imported them from the
# engine.
from mlops_tpu.serve.wire import (  # noqa: E402, F401  (re-exports)
    EMPTY_RESPONSE_BYTES,
    GROUP_ROW_BUCKET,
    GROUP_ROW_BUCKETS,
    GROUP_SLOT_BUCKETS,
    empty_response,
    encode_response,
    format_response,
)


class InferenceEngine:
    def __init__(
        self,
        bundle: Bundle,
        buckets: tuple[int, ...] = (1, 8, 64, 256),
        service_name: str = "credit-default-api",
        enable_grouping: bool = True,
        compile_cache=None,
        warmup_workers: int = 0,
        model_shards: int = 1,
        device_index: int | None = None,
        serve_tier: str = "exact",
        tier_routing: bool = False,
    ):
        self.bundle = bundle
        # Bundle turnover (mlops_tpu/lifecycle/): the generation counts
        # hot swaps (swap_bundle / rollback), starting at 1 for the
        # construction-time bundle; `_retired` is the one-deep history a
        # rollback restores. `_tee` is the lifecycle observation hook
        # (set_lifecycle_tee): called with each request's PRE-PADDING
        # encoded arrays on the dispatch path, feeding the sample
        # reservoir and the shadow mirror — it must never block and never
        # raise (the tee guards itself; a mirror bug must not 500 live
        # traffic).
        self.bundle_generation = 1
        # Grid turnover (mlops_tpu/autotune/): counts hot REGRIDS — swaps
        # (or rollbacks) whose candidate carried a different bucket set.
        # A plain promotion (same grid, new params) leaves it untouched,
        # so `mlops_tpu_grid_generation` moves only when the autotuner
        # (or an operator) actually re-gridded the plane.
        self.grid_generation = 1
        self._retired: tuple | None = None
        self._tee = None
        # tracewire shape telemetry (mlops_tpu/trace/shapes.py), armed by
        # `set_shape_stats` when trace.enabled: every dispatch records
        # (compiled entry, requested rows, padded rows). Disarmed = None =
        # one branch on the hot path (the faultline overhead discipline).
        self.shape_stats = None
        # Device-time cost ledger (mlops_tpu/slo/ledger.py), armed by
        # `set_cost_ledger`: per-entry dispatch->fetch seconds keyed by
        # entry + model fingerprint. Disarmed = None = one branch on the
        # dispatch path, one on the fetch path.
        self.cost_ledger = None
        self._cost_tag = ""
        if bundle.flavor == "doc":
            raise ValueError(
                "doc bundles score record HISTORIES, not single records — "
                "the HTTP predict contract does not apply; score offline "
                "via `predict-file data.train_path=<history csv>`"
            )
        self.buckets = sorted(buckets)
        self.max_bucket = self.buckets[-1]
        self.service_name = service_name
        # Persistent AOT executable cache (compilecache/): warmup probes it
        # before compiling, so a second process on the same box (deploy,
        # rollout, autoscale replica) deserializes in seconds instead of
        # recompiling for a minute. None = compile-only warmup.
        self.compile_cache = compile_cache
        self.warmup_workers = warmup_workers
        self.warmup_stats: dict[str, Any] = {}
        # AOT dispatch table: ("bucket", b) / ("group", slots, rows) ->
        # compiled executable for exactly that shape (filled by warmup).
        # Misses fall back to the bound jitted programs below, which
        # compile on demand — exactly the pre-cache behavior.
        self._exec: dict[tuple, Any] = {}
        temperature = bundle.temperature  # calibration (train/calibrate.py)
        # Defaults shared by every flavor (the flax branch below builds
        # the real mesh when model_shards > 1; sklearn has no device
        # params to shard and ignores the knobs). ``device_index`` is
        # the engine replica set's in-process placement (ISSUE 13):
        # when one engine process's visibility spans the whole fleet's
        # devices (a dev box, the forced-host-device sim), replica r
        # pins its state to ITS device slice instead of everyone
        # sharing device 0 — production multi-chip deployments scope
        # visibility per process instead (each replica's device 0 IS
        # its chip) and leave this None.
        self.model_shards = max(1, int(model_shards))
        self.device_index = device_index
        self._mesh = None
        self._replicated = None
        self._placement = None
        # Serving tier (ISSUE 17): the quantized student
        # (`ops/quant_kernel.py` — int8/bf16 params, Pallas-fused on TPU)
        # is a different (program, params, temperature) TRIPLE behind the
        # SAME dispatch machinery: the 7-arg packed signature, the AOT
        # table, the accumulator chain, degraded mode, and the lifecycle
        # locks are all tier-blind. "quant" demands the tier (raises when
        # the bundle lacks a GATED one — an explicit ask must never be
        # silently downgraded); "auto" takes it when admissible and logs
        # the fallback otherwise. Single-device by contract: the quant
        # params are a flat dict the partition rules don't cover.
        self.serve_tier = self._resolve_tier(serve_tier, bundle)
        # Per-request tier routing (ISSUE 19, serve/tierroute.py): the
        # DEFAULT tier keeps the historical attribute slots
        # (`_variables` / `_temperature` / the base jits / plain exec
        # keys); every OTHER gated tier this engine holds lives in
        # ``_tier_extra`` as a (variables, temperature, solo jit, group
        # jit) quadruple and dispatches through the SAME exec table
        # under tier-suffixed keys — one accumulator, one lock
        # discipline, one degraded-mode policy across all tiers.
        self.tier_routing = bool(tier_routing)
        self.default_tier = self.serve_tier
        self._tier_extra: dict[str, tuple] = {}
        self.gbm_geometry = None
        if bundle.flavor == "sklearn" and not supports_gbm_tensorization(
            bundle.estimator
        ):
            # CPU tree-ensemble floor (the rf family — unbinned deep
            # forests don't tensorize): host classifier + device
            # monitors. No grouped path — trees run on host threads
            # anyway (and no AOT table: the classifier is not an XLA
            # program). No device accumulator either: the server keeps
            # the seed's host-side metric fold for this flavor.
            self._predict = make_hybrid_predict_fn(
                bundle.estimator, bundle.monitor, temperature
            )
            self._predict_group = None
            self._accumulate = False
        elif bundle.flavor == "sklearn":
            # gbm-tensor tier (ISSUE 19, ops/gbm_tensor.py): the fitted
            # HistGBM ensemble lowers Hummingbird-style to padded
            # gather/compare tensor programs in the SAME packed 7-arg
            # contract as every flax family — so the sklearn floor rides
            # the AOT table, the device accumulator, grouping, degraded
            # mode, and the compile cache instead of host threads.
            # Single-device by construction (sklearn has no partition
            # rules; model_shards is ignored exactly as before).
            gbm_variables, self.gbm_geometry = extract_gbm(bundle.estimator)
            self.default_tier = "gbm"
            if device_index is not None:
                from jax.sharding import SingleDeviceSharding

                self._placement = SingleDeviceSharding(
                    jax.devices()[device_index]
                )
            with x64_context():
                # The tree tensors are f64 by the bit-parity contract —
                # committed under the x64 context or device_put would
                # silently narrow them (jax 0.4.x semantics).
                self._variables = (
                    jax.device_put(gbm_variables, self._placement)
                    if self._placement is not None
                    else jax.device_put(gbm_variables)
                )
            if self._placement is not None:
                self._monitor = jax.device_put(
                    bundle.monitor, self._placement
                )
            else:
                self._monitor = jax.device_put(bundle.monitor)
            with x64_context():
                # f64 temperature, unlike every other tier's f32: the
                # host hybrid divides logits by the FULL python float
                # (train/calibrate.py apply_temperature), and an f32
                # rounding of T shifts ~1/3 of tempered probabilities by
                # one ulp — bit-parity pins would fail.
                self._temperature = (
                    jax.device_put(np.float64(temperature), self._placement)
                    if self._placement is not None
                    else jax.device_put(np.float64(temperature))
                )
            depth = self.gbm_geometry.depth
            self._predict = jax.jit(  # tpulint: disable=TPU203
                make_gbm_packed_base(depth), donate_argnums=ACC_DONATION
            )
            self._predict_group = (
                jax.jit(  # tpulint: disable=TPU203
                    make_gbm_grouped_base(depth), donate_argnums=ACC_DONATION
                )
                if enable_grouping
                else None
            )
            self._accumulate = True
        else:
            # Partition-rule model sharding (ISSUE 13,
            # parallel/sharding.py): model_shards > 1 lays the params
            # out over a ('model',) mesh via the same regex rules the
            # TP train step uses — large families (moe experts,
            # transformer projections) SHARD instead of replicating,
            # while monitor/accumulator/temperature and the batch
            # inputs replicate. The packed programs are unchanged: jit
            # follows the committed shardings, and warmup bakes them
            # into the AOT artifacts (keyed by mesh shape, so sharded
            # and unsharded executables can never mix).
            quant = self.serve_tier == "quant"
            if quant:
                # The quant triple: int8/bf16 params + the tier's own
                # refit temperature (quantization shifts the logit scale;
                # `train/distill.py distill_quant_student`).
                serve_variables = bundle.quant_params
                temperature = bundle.quant_temperature
            else:
                serve_variables = bundle.variables
            if self.model_shards > 1:
                from mlops_tpu.parallel.sharding import (
                    param_shardings,
                    replicated,
                    serve_mesh,
                )

                self._mesh = serve_mesh(
                    self.model_shards, offset=device_index or 0
                )
                self._replicated = replicated(self._mesh)
                self._variables = jax.device_put(
                    bundle.variables,
                    param_shardings(self._mesh, bundle.variables),
                )
                self._monitor = jax.device_put(
                    bundle.monitor, self._replicated
                )
                self._temperature = jax.device_put(
                    np.float32(temperature), self._replicated
                )
            elif device_index is not None:
                # Unsharded but PINNED: the whole serving state lives on
                # this replica's own device (committed placement — jit
                # and the AOT artifacts follow it).
                from jax.sharding import SingleDeviceSharding

                self._placement = SingleDeviceSharding(
                    jax.devices()[device_index]
                )
                self._variables = jax.device_put(
                    serve_variables, self._placement
                )
                self._monitor = jax.device_put(
                    bundle.monitor, self._placement
                )
                self._temperature = jax.device_put(
                    np.float32(temperature), self._placement
                )
            else:
                # device_put ONCE: params/monitor/temperature are
                # per-call ARGUMENTS of the cached programs — host numpy
                # trees would re-pay the full host->device param
                # transfer on every request; committed device arrays
                # pass by reference.
                self._variables = jax.device_put(serve_variables)
                self._monitor = jax.device_put(bundle.monitor)
                self._temperature = jax.device_put(np.float32(temperature))
            # Base-form packed programs, jitted with the same 7-arg
            # convention as the AOT table entries — `_dispatch_fused`
            # AOT-lowers these for any shape warmup missed.
            # Warmed shapes never touch these jits (warmup fills the AOT
            # table through compilecache); they exist only so
            # `_compile_novel` can AOT-lower a shape warmup missed. The
            # tier picks the program family here, ONCE — every dispatch
            # below is tier-blind.
            if quant:
                from mlops_tpu.ops.quant_kernel import (
                    make_quant_grouped_base,
                    make_quant_packed_base,
                )

                predict_base = make_quant_packed_base()
                grouped_base = make_quant_grouped_base()
            else:
                predict_base = make_packed_predict_base(bundle.model)
                grouped_base = make_packed_grouped_base(bundle.model)
            self._predict = jax.jit(  # tpulint: disable=TPU203
                predict_base, donate_argnums=ACC_DONATION
            )
            self._predict_group = (
                jax.jit(  # tpulint: disable=TPU203
                    grouped_base,
                    donate_argnums=ACC_DONATION,
                )
                if enable_grouping
                else None
            )
            if self.tier_routing:
                # Commit every OTHER gated tier alongside the default
                # one — per-request routing needs them resident before
                # traffic, not behind a first-request device_put.
                self._tier_extra = self._build_extra_tiers(
                    bundle, enable_grouping
                )
            self._accumulate = True
        if self._accumulate:
            # The accumulating flavors' shared serving state (flax
            # families and the gbm-tensor tier). Device-resident monitor
            # aggregate, threaded through every fused dispatch
            # (monitor/state.py MonitorAccumulator): the lock serializes
            # only the dispatch-order/ref-swap — executions chain on
            # device through the data dependency, the host never blocks
            # here.
            from mlops_tpu.monitor.state import init_accumulator

            self._acc = self._place_replicated(init_accumulator())
            self._acc_lock = threading.Lock()
            # Novel-shape compiles serialize here, never on _acc_lock: a
            # synchronous XLA compile under the accumulator lock would
            # stall every in-flight request, not just the novel one.
            self._compile_lock = threading.Lock()
            # Exact host-side running totals, folded from each fetched
            # window by `monitor_snapshot` (fetch-and-reset): left to
            # grow on device, the f32 counters would silently saturate
            # at 2^24 rows (~2 h at the benched request rate) where the
            # seed's Python-int /metrics totals could not.
            d = SCHEMA.num_categorical + SCHEMA.num_numeric
            self._totals: dict[str, Any] = {
                "rows": 0.0,
                "outliers": 0.0,
                "batches": 0.0,
                "drift_sum": np.zeros(d, np.float64),
                "drift_last": np.zeros(d, np.float64),
            }
            self._totals_lock = threading.Lock()
            # Degraded-mode dispatch counter (`_dispatch_padded` /
            # `dispatch_group_arrays`): requests served through a
            # larger-than-target warmed shape after a compile/cache
            # failure — exported as mlops_tpu_degraded_dispatch_total.
            self._degraded = 0
        self.ready = False

    def _build_extra_tiers(
        self, bundle: Bundle, enable_grouping: bool
    ) -> dict[str, tuple]:
        """Commit the non-default gated tiers (tier_routing=True, flax
        flavors): an exact-default engine with a GATED quant student adds
        "quant"; a quant-default engine always retains its "exact"
        teacher (the accurate-class escape hatch). Each extra tier is a
        full (params, temperature, solo jit, group jit) quadruple on the
        same committed placement — `_dispatch_fused` reads it under the
        same lock hold as the default refs, so tier choice never changes
        the consistency story."""
        extra: dict[str, tuple] = {}
        others: list[str] = []
        if self.serve_tier == "exact":
            if (
                bundle.has_quant
                and bundle.quant_gates_passed
                and self.model_shards == 1
            ):
                others.append("quant")
        else:
            others.append("exact")
        for tier in others:
            if tier == "quant":
                from mlops_tpu.ops.quant_kernel import (
                    make_quant_grouped_base,
                    make_quant_packed_base,
                )

                variables = self._place_replicated(bundle.quant_params)
                temperature = self._place_replicated(
                    np.float32(bundle.quant_temperature)
                )
                solo_base = make_quant_packed_base()
                group_base = make_quant_grouped_base()
            else:
                variables = self._place_replicated(bundle.variables)
                temperature = self._place_replicated(
                    np.float32(bundle.temperature)
                )
                solo_base = make_packed_predict_base(bundle.model)
                group_base = make_packed_grouped_base(bundle.model)
            extra[tier] = (
                variables,
                temperature,
                jax.jit(  # tpulint: disable=TPU203
                    solo_base, donate_argnums=ACC_DONATION
                ),
                jax.jit(  # tpulint: disable=TPU203
                    group_base, donate_argnums=ACC_DONATION
                )
                if enable_grouping
                else None,
            )
        return extra

    def _resolve_tier(self, serve_tier: str, bundle: Bundle) -> str:
        """Resolve the requested serving tier against what the bundle can
        admissibly serve. "quant" is a demand (raise rather than silently
        serve different bits than asked for); "auto" is a preference (take
        the quant tier when gated and single-device, log the fallback)."""
        if serve_tier not in ("exact", "quant", "auto"):
            raise ValueError(
                f"serve_tier must be 'exact', 'quant' or 'auto', "
                f"got {serve_tier!r}"
            )
        if serve_tier == "exact":
            return "exact"
        admissible, why = True, ""
        if bundle.flavor == "sklearn":
            admissible, why = False, "sklearn bundles have no quant tier"
        elif not bundle.has_quant:
            admissible, why = False, "bundle carries no quant params"
        elif not bundle.quant_gates_passed:
            admissible, why = False, (
                "quant tier failed (or was never graded by) the promotion "
                "gates — lifecycle/promote.py quant_tier_gates"
            )
        elif self.model_shards > 1:
            admissible, why = False, (
                "quant tier is single-device; model_shards > 1 shards the "
                "exact params only"
            )
        if admissible:
            return "quant"
        if serve_tier == "quant":
            raise ValueError(f"serve_tier='quant' refused: {why}")
        logger.info("serve_tier='auto' falling back to exact tier: %s", why)
        return "exact"

    def _place_replicated(self, tree: Any) -> Any:
        """Device-put a host tree onto the engine's committed placement:
        replicated over the serve mesh when sharding is on, this
        replica's pinned device when one was assigned (every fresh
        accumulator must land on the SAME device set as the committed
        params, or the fused dispatch would mix committed device sets),
        plain default-device placement otherwise."""
        sharding = getattr(self, "_replicated", None) or getattr(
            self, "_placement", None
        )
        if sharding is not None:
            return jax.device_put(tree, sharding)
        return jax.device_put(tree)

    @property
    def supports_grouping(self) -> bool:
        return self._predict_group is not None

    @property
    def available_tiers(self) -> tuple[str, ...]:
        """The gated tiers this engine can dispatch per-request, cheapest
        -> most accurate (`tierroute.TIERS` order restricted to what is
        committed). Single-tier engines return a 1-tuple — routing then
        collapses to the default tier for every class."""
        held = {self.default_tier, *self._tier_extra}
        return tuple(t for t in TIERS if t in held)

    def route_tier(self, slo_class: int) -> str | None:
        """SLO class -> the tier that serves it on THIS engine; None
        means the default tier (plain un-suffixed exec keys — the
        historical dispatch, bit-for-bit). The engine owns this mapping
        so the wire carries only the CLASS: front ends don't know which
        tiers a bundle gates, and the ring's crash replay re-derives the
        identical tier from the class tag in shm."""
        tier = tier_for_class(
            self.available_tiers, self.default_tier, slo_class
        )
        return None if tier == self.default_tier else tier

    @property
    def monitor_accumulating(self) -> bool:
        """True when the fused programs fold the monitor aggregate on
        device (`monitor_snapshot` is then the telemetry read path)."""
        return self._accumulate

    @property
    def degraded_dispatch_total(self) -> int:
        """Requests served through a degraded (larger-than-target warmed)
        shape after a compile/cache failure — the telemetry read for the
        mlops_tpu_degraded_dispatch_total counter."""
        if not self._accumulate:
            return 0
        with self._totals_lock:
            return self._degraded

    def _count_degraded(self) -> None:
        with self._totals_lock:
            self._degraded += 1

    # ------------------------------------------------------------- warmup
    def warmup(self) -> None:
        """Ready every bucket size (and group shape) before traffic.

        Flax flavors warm ahead-of-time through `compilecache/warmup.py`:
        probe the persistent cache -> deserialize hits, compile misses IN
        PARALLEL (XLA compilation releases the GIL; a small thread pool
        over shapes) -> persist -> execute each program once on zeros (pay
        first-dispatch allocation; fail loudly on an artifact that loads
        but cannot run). ``warmup_stats`` records the wall time plus the
        cache's hit/miss/bypass counts and per-program compile vs
        deserialize seconds.
        """
        import time

        t0 = time.perf_counter()
        if not self._accumulate:
            # Host-hybrid floor (rf): no AOT table — execute each bucket
            # once so the jitted monitors compile before traffic.
            for bucket in self.buckets:
                cat = np.zeros((bucket, SCHEMA.num_categorical), np.int32)
                num = np.zeros((bucket, SCHEMA.num_numeric), np.float32)
                mask = np.ones((bucket,), bool)
                jax.block_until_ready(self._predict(cat, num, mask)["outliers"])
            self.ready = True
            self.warmup_stats = {
                "warmup_s": round(time.perf_counter() - t0, 3),
                "programs": len(self.buckets),
                "cache": None,
            }
            return

        from mlops_tpu.compilecache.warmup import (
            default_workers,
            run_jobs,
            serve_gbm_group_jobs,
            serve_gbm_jobs,
            serve_group_jobs,
            serve_predict_jobs,
            serve_quant_group_jobs,
            serve_quant_jobs,
        )

        bundle = self.bundle
        # Replica placement rides into the AOT artifacts: lowered
        # layouts follow the committed shardings, and a pinned/offset
        # device assignment joins the CACHE KEY (device_tag) — an
        # executable compiled for replica 0's device must never be
        # deserialized against params committed to replica 1's.
        device_tag = (
            f"@dev{self.device_index}" if self.device_index is not None
            else ""
        )
        grid = [
            (slots, rows)
            for rows in GROUP_ROW_BUCKETS
            for slots in GROUP_SLOT_BUCKETS
        ]
        if self.default_tier == "gbm":
            # The gbm-tensor tier's own entry family (cache ids
            # serve-predict-gbm-*): the tree tensors are the params tree,
            # and lowering runs inside the x64 context (the job carries
            # an x64-wrapping jitted — compilecache/warmup.py).
            jobs = serve_gbm_jobs(
                self._variables,  # the committed f64 tree tensors
                self._monitor,
                tuple(self.buckets),
                geometry=self.gbm_geometry,
                temperature=bundle.temperature,
                placement=self._placement,
                device_tag=device_tag,
            )
            if self._predict_group is not None:
                jobs += serve_gbm_group_jobs(
                    self._variables,
                    self._monitor,
                    grid,
                    geometry=self.gbm_geometry,
                    temperature=bundle.temperature,
                    placement=self._placement,
                    device_tag=device_tag,
                )
        elif self.serve_tier == "quant":
            # The quant tier's own entry family (distinct cache ids:
            # serve-predict-quant-*): same shapes, same dispatch-table
            # keys, different programs + params tree.
            jobs = serve_quant_jobs(
                self._variables,  # the committed quant tree
                self._monitor,
                tuple(self.buckets),
                temperature=bundle.quant_temperature,
                placement=self._placement,
                device_tag=device_tag,
            )
            if self._predict_group is not None:
                jobs += serve_quant_group_jobs(
                    self._variables,
                    self._monitor,
                    grid,
                    temperature=bundle.quant_temperature,
                    placement=self._placement,
                    device_tag=device_tag,
                )
        else:
            jobs = serve_predict_jobs(
                bundle.model,
                bundle.model_config,
                self._variables,  # device-resident (init): avals identical,
                self._monitor,  # and the execute-once pass skips a transfer
                tuple(self.buckets),
                temperature=bundle.temperature,
                mesh=self._mesh,  # sharded layouts bake into the artifacts
                placement=self._placement,
                device_tag=device_tag,
            )
            if self._predict_group is not None:
                jobs += serve_group_jobs(
                    bundle.model,
                    bundle.model_config,
                    self._variables,
                    self._monitor,
                    grid,
                    temperature=bundle.temperature,
                    mesh=self._mesh,
                    placement=self._placement,
                    device_tag=device_tag,
                )
        # Extra-tier warmup (tier_routing): every non-default gated tier
        # warms its OWN job family into the same table under
        # tier-suffixed keys — per-request routing must never pay a
        # first-request compile for a tier the config promised.
        for tier, (variables, _, _, group_jit) in self._tier_extra.items():
            if tier == "quant":
                extra = serve_quant_jobs(
                    variables, self._monitor, tuple(self.buckets),
                    temperature=bundle.quant_temperature,
                    placement=self._placement, device_tag=device_tag,
                )
                if group_jit is not None:
                    extra += serve_quant_group_jobs(
                        variables, self._monitor, grid,
                        temperature=bundle.quant_temperature,
                        placement=self._placement, device_tag=device_tag,
                    )
            else:
                extra = serve_predict_jobs(
                    bundle.model, bundle.model_config, variables,
                    self._monitor, tuple(self.buckets),
                    temperature=bundle.temperature,
                    placement=self._placement, device_tag=device_tag,
                )
                if group_jit is not None:
                    extra += serve_group_jobs(
                        bundle.model, bundle.model_config, variables,
                        self._monitor, grid,
                        temperature=bundle.temperature,
                        placement=self._placement, device_tag=device_tag,
                    )
            for job in extra:
                job.meta["tier"] = tier
            jobs += extra
        for job, fn in run_jobs(
            jobs, cache=self.compile_cache, workers=self.warmup_workers
        ):
            if "bucket" in job.meta:
                key = ("bucket", job.meta["bucket"])
            else:
                key = ("group", job.meta["slots"], job.meta["rows"])
            if job.meta.get("tier"):
                key = key + (job.meta["tier"],)
            # Under _compile_lock (tpulint TPU402): the server binds its
            # socket FIRST and warms concurrently (serve/server.py _serve),
            # so live requests can race this loop — an unlocked table
            # write could interleave with `_compile_novel` double-compiling
            # the same key it is about to install. Taken per write, never
            # across run_jobs: holding it for the whole warmup would stall
            # a novel-shape request until every program compiled.
            with self._compile_lock:
                self._exec[key] = fn
        self.ready = True
        self.warmup_stats = {
            "warmup_s": round(time.perf_counter() - t0, 3),
            "programs": len(jobs),
            "workers": default_workers(len(jobs), self.warmup_workers),
            "cache": (
                self.compile_cache.stats()
                if self.compile_cache is not None
                else None
            ),
        }

    def _dispatch_fused(self, key: tuple, *batch, tier: str | None = None):
        """Dispatch one fused packed call and thread the monitor
        accumulator through it — the ONE critical section shared by the
        solo and grouped paths.

        Warmed shapes dispatch through the AOT table; a novel shape
        (oversized request, unwarmed group geometry) is AOT-compiled into
        the table FIRST, outside the accumulator lock, so warmed traffic
        keeps flowing while it compiles.

        The lock covers only the (read exec entry + serving refs ->
        dispatch -> swap new acc ref) window, which is an ASYNC enqueue —
        concurrent request threads serialize the accumulator chain's
        ORDER here while the executions overlap on device exactly as
        before (the chain is a data dependency, not a host wait).

        BIT-STABILITY across hot swaps (lifecycle/promote.py): the exec
        entry, params, monitor, and temperature are all read under the
        SAME ``_acc_lock`` hold that `swap_bundle` mutates them under, so
        a request in flight during a promotion computes its whole answer
        from exactly one bundle generation — never new params through an
        old program or vice versa. Returns the packed output array; the
        new accumulator stays device-resident.

        ``tier`` (None = default) selects which committed (params,
        temperature) pair feeds the program — ``key`` already carries the
        matching suffix. All tiers thread the ONE accumulator: the
        monitors are f32 on every tier by contract, so the fold chain is
        tier-blind."""
        while True:
            with self._acc_lock:
                fn = self._exec.get(key)
                if fn is not None:
                    if tier is None:
                        variables = self._variables
                        temperature = self._temperature
                    else:
                        variables, temperature = self._tier_extra[tier][:2]
                    acc = self._acc
                    out, new_acc = fn(
                        variables, self._monitor, acc, temperature, *batch,
                    )
                    self._acc = new_acc
                    return out
            # Miss: compile outside the accumulator lock, then retry the
            # consistent-snapshot dispatch (a swap may have replaced the
            # table meanwhile; the loop re-reads everything together).
            self._compile_novel(key, batch, tier=tier)

    def _compile_novel(self, key: tuple, batch, tier: str | None = None):
        """AOT-compile a shape warmup missed and cache it in the dispatch
        table. Double-checked under ONE shared lock: concurrent first
        requests for the same shape compile once, and warmed traffic
        never waits here — but concurrent DIFFERENT novel shapes do
        serialize on this lock (novel shapes are rare offline/oversized
        traffic; per-key locks aren't worth the bookkeeping). The base
        jitted program is looked up from ``self`` INSIDE the lock so the
        lowering, the params it lowers against, and the table it installs
        into all belong to one bundle generation (`swap_bundle` takes
        this lock first)."""
        from mlops_tpu.monitor.state import abstract_accumulator

        # Injection point (mlops_tpu/faults): a raise here models a
        # runtime compile/cache failure — callers degrade to the next
        # larger warmed shape instead of 500ing (`_dispatch_padded`).
        faults.fire("serve.engine.compile")
        with self._compile_lock:
            fn = self._exec.get(key)
            if fn is None:
                if tier is None:
                    jitted = (
                        self._predict if key[0] == "bucket"
                        else self._predict_group
                    )
                    variables = self._variables
                    temperature = self._temperature
                else:
                    variables, temperature, solo, group = (
                        self._tier_extra[tier]
                    )
                    jitted = solo if key[0] == "bucket" else group
                # The sync XLA compile DOES block this lock — that is the
                # design: _compile_lock exists precisely to serialize novel
                # compiles away from _acc_lock (where the same compile once
                # stalled every in-flight request). Warmed traffic never
                # touches this lock on its hot path. The lowering runs in
                # the serving tier's trace context (x64 for gbm-tensor —
                # thread-local, so concurrent f32 dispatches are untouched).
                with trace_context(tier or self.default_tier):
                    fn = jitted.lower(  # tpulint: disable=TPU403
                        variables,
                        self._monitor,
                        abstract_accumulator(),
                        temperature,
                        *batch,
                    ).compile()
                self._exec[key] = fn
        return fn

    def adopt_executables(self, donor: "InferenceEngine") -> None:
        """Share a WARMED architecture-twin's compiled entries instead of
        warming (mlops_tpu/tenancy/registry.py): the packed serving
        programs take params/monitor/temperature as ARGUMENTS, so one
        executable serves any tenant whose bundle matches the donor's
        abstract signature — this engine keeps its OWN state refs
        (`_dispatch_fused` reads them per dispatch) while the exec table,
        the base jits, and crucially the donor's ``_compile_lock`` are
        adopted BY REFERENCE. Sharing the lock is load-bearing: twin
        tenants' concurrent novel-shape compiles must serialize on the
        one lock guarding the one shared table (separate locks over a
        shared dict would race `_compile_novel`'s double-check). A later
        `swap_bundle` on this tenant re-points only ITS refs at the
        candidate's table — the donor and every other twin keep serving
        the shared entries untouched (per-tenant lifecycle isolation)."""
        if not self._accumulate or not donor._accumulate:
            raise ValueError(
                "executable adoption requires device-accumulating engines "
                "on both sides — the host-hybrid flavor (rf) has no "
                "shareable compiled entries"
            )
        if not donor.ready:
            raise ValueError("donor engine is not warmed")
        # Adoption runs pre-traffic (registry warmup, starting thread),
        # but the refs it swaps are the same ones swap_bundle guards —
        # hold the declared _compile_lock -> _acc_lock order anyway so
        # every write site of these fields shares one discipline. The
        # lock handoff itself happens under the OLD lock (nobody else
        # can hold it before the fleet serves).
        with self._compile_lock:
            with self._acc_lock:
                self._exec = donor._exec
                self._predict = donor._predict
                self._predict_group = donor._predict_group
                self._compile_lock = donor._compile_lock
        self.ready = True
        self.warmup_stats = {
            "warmup_s": 0.0,
            "programs": len(donor._exec),
            "mode": "shared",
            "cache": None,
        }

    def set_shape_stats(self, stats) -> None:
        """Install (or clear, with None) the tracewire shape recorder: a
        `trace/shapes.ShapeStats` fed (entry, requested_rows, padded_rows)
        per dispatch. The recorder owns its cheapness (a leaf-lock counter
        add); the engine calls it bare on the dispatch path."""
        self.shape_stats = stats

    @staticmethod
    def _model_tag(bundle: Bundle) -> str:
        """The cost ledger's model dimension: the same model-config
        fingerprint the compile cache hashes into its keys
        (compilecache/keys.py), shortened for the label/shm-key budget.
        Two engines whose architectures match share compiled programs
        (tenancy adoption) and correctly share ledger entries; a
        promotion to a DIFFERENT architecture lands in fresh entries."""
        from mlops_tpu.compilecache.keys import model_fingerprint

        return model_fingerprint(bundle.model_config)[:8]

    def set_cost_ledger(self, ledger) -> None:
        """Install (or clear, with None) the device-time cost ledger
        (`slo/ledger.CostLedger`): every packed dispatch accounts
        (entry, requested rows, padded rows, dispatch->fetch seconds)
        under ``<entry>@<model-tag>``. Disarmed = None = one branch on
        the dispatch path and one on the fetch path (the faultline
        overhead discipline)."""
        if ledger is not None:
            self._cost_tag = self._model_tag(self.bundle)
        self.cost_ledger = ledger

    # ----------------------------------------------------- bundle turnover
    def set_lifecycle_tee(self, tee) -> None:
        """Install (or clear, with None) the lifecycle observation hook:
        a callable ``tee(cat_ids, numeric)`` invoked with each request's
        pre-padding encoded arrays on the dispatch path. The tee OWNS its
        cheapness and safety (bounded non-blocking enqueue, internal
        try/except) — the engine calls it bare on the hot path."""
        self._tee = tee

    def swap_bundle(self, candidate: "InferenceEngine") -> int:
        """Hot-promote a warmed candidate engine's bundle IN PLACE with
        zero downtime (lifecycle/promote.py): exec table + params +
        monitor + temperature + base jits ref-swap under the existing
        ``_compile_lock`` -> ``_acc_lock`` discipline (the declared
        TPULINT_LOCK_ORDER). Everything swapped is already device-resident
        on the candidate engine, so the critical section is pure ref
        assignment — no transfer, no compile, no fetch ever holds these
        locks (tpulint TPU403 stays clean by construction).

        Requests racing the swap are bit-stable: `_dispatch_fused` reads
        the same refs under the same ``_acc_lock`` hold, so each
        response's COMPUTE (program + params + monitor + temperature) is
        exactly one bundle generation. The host-side encode stage reads
        ``self.bundle.preprocessor`` before dispatch, outside these
        locks — identical across generations in the default lifecycle
        flow (``lifecycle.refit_preprocessor=false``, and forced false on
        the ring plane, where the fork-time preprocessor is the encode
        contract), so the one-generation guarantee is unconditional
        there; with an opted-in refit, a request already past encode when
        the swap lands scores old-stats-encoded rows against the new
        generation for that instant. The outgoing state is retained
        (one-deep) for `rollback`. Returns the new generation."""
        if not self._accumulate or not candidate._accumulate:
            raise ValueError(
                "hot swap requires device-accumulating engines on both "
                "sides — the host-hybrid flavor (rf) redeploys instead"
            )
        if self.supports_grouping and not candidate.supports_grouping:
            raise ValueError(
                "candidate engine lacks the grouped path the live engine "
                "serves — build it with enable_grouping=True"
            )
        if candidate.max_bucket < self.max_bucket:
            # The front ends clamp max_batch against max_bucket at START
            # (server.py / the ring slab geometry) — a swap that shrinks
            # coverage would admit requests no warmed entry can hold.
            # Regrids may re-tile below the ceiling, never lower it.
            raise ValueError(
                f"candidate max_bucket {candidate.max_bucket} < live "
                f"{self.max_bucket}: a swap may never shrink shape "
                "coverage below the admission ceiling"
            )
        with self._compile_lock:
            with self._acc_lock:
                self._retired = (
                    self.bundle, self._variables, self._monitor,
                    self._temperature, self._exec, self._predict,
                    self._predict_group, self.buckets, self.max_bucket,
                    self._tier_extra, self.default_tier, self.gbm_geometry,
                )
                regrid = candidate.buckets != self.buckets
                self.bundle = candidate.bundle
                self._variables = candidate._variables
                self._monitor = candidate._monitor
                self._temperature = candidate._temperature
                self._exec = candidate._exec
                self._predict = candidate._predict
                self._predict_group = candidate._predict_group
                self.buckets = candidate.buckets
                self.max_bucket = candidate.max_bucket
                # Tier routing state swaps with the bundle it describes:
                # the candidate's gated extra tiers (and, for gbm-tensor
                # bundles, the traversal geometry) belong to the NEW
                # generation's params, never the old one's.
                self._tier_extra = candidate._tier_extra
                self.default_tier = candidate.default_tier
                self.gbm_geometry = candidate.gbm_geometry
                self.bundle_generation += 1
                if regrid:
                    self.grid_generation += 1
        if self.cost_ledger is not None:
            # Re-key the ledger to the promoted architecture (outside the
            # locks: hashing a config dict must not extend the swap's
            # critical section; the attr store is atomic, and at most a
            # dispatch already in flight bills the outgoing tag).
            self._cost_tag = self._model_tag(self.bundle)
        return self.bundle_generation

    def rollback(self) -> int:
        """Instantly restore the previous bundle (same ref-swap, same
        locks, same bit-stability). The states EXCHANGE, so a rollback is
        itself rollback-able (roll forward again in one call). Raises if
        no swap ever happened."""
        if self._retired is None:
            raise ValueError("no retired bundle to roll back to")
        with self._compile_lock:
            with self._acc_lock:
                retired = self._retired
                self._retired = (
                    self.bundle, self._variables, self._monitor,
                    self._temperature, self._exec, self._predict,
                    self._predict_group, self.buckets, self.max_bucket,
                    self._tier_extra, self.default_tier, self.gbm_geometry,
                )
                regrid = retired[7] != self.buckets
                (self.bundle, self._variables, self._monitor,
                 self._temperature, self._exec, self._predict,
                 self._predict_group, self.buckets, self.max_bucket,
                 self._tier_extra, self.default_tier,
                 self.gbm_geometry) = retired
                self.bundle_generation += 1
                if regrid:
                    self.grid_generation += 1
        if self.cost_ledger is not None:
            self._cost_tag = self._model_tag(self.bundle)  # see swap_bundle
        return self.bundle_generation

    def seed_monitor_totals(
        self,
        rows: float,
        outliers: float,
        batches: float,
        drift_sum,
        drift_last,
    ) -> None:
        """Install absolute monitor totals from a previous engine
        incarnation (ISSUE 11 — the shm mon block survives an engine
        ``kill -9``; the respawned process seeds its exact host-side f64
        totals from it so `monitor_snapshot` — and therefore every
        exported counter — stays MONOTONE across the respawn instead of
        restarting from zero). The accumulator window the dead process
        never fetched is gone (bounded by the telemetry cadence) and is
        counted by the caller in ``monitor_rows_lost_total``, never
        silently absorbed."""
        if not self._accumulate:
            return
        # Materialize the host copies OUTSIDE the lock (TPU403: the
        # critical section is ref assignment only, like monitor_snapshot).
        seeded_sum = np.array(drift_sum, dtype=np.float64)
        seeded_last = np.array(drift_last, dtype=np.float64)
        with self._totals_lock:
            t = self._totals
            t["rows"] = float(rows)
            t["outliers"] = float(outliers)
            t["batches"] = float(batches)
            t["drift_sum"] = seeded_sum
            t["drift_last"] = seeded_last

    def monitor_snapshot(self) -> dict[str, Any]:
        """ONE device->host fetch of the monitor aggregate — the telemetry
        read path (`serve/server.py` calls it every K requests / T
        seconds, and on /metrics scrapes), OFF the request path.

        Fetch-and-RESET: a fresh zero accumulator is swapped in under the
        lock and the fetched window is folded into exact host-side f64
        totals. Left to grow on device, the f32 counters would silently
        stop incrementing at 2^24 rows; windows stay orders of magnitude
        below that (the server fetches every <=512 requests / 2 s) and the
        f64 totals are exact to 2^53. The swap also makes the fetched
        buffers donation-safe — once replaced, no later dispatch can
        donate them — so no defensive on-device copy is needed."""
        if not self._accumulate:
            return {}
        from mlops_tpu.monitor.state import init_accumulator, merge_accumulators

        with self._acc_lock:
            window = self._acc
            self._acc = self._place_replicated(init_accumulator())
        try:
            host = jax.device_get(window)  # blocks OUTSIDE the dispatch lock
        except Exception:
            # Transient fetch failure (a device or transport error): the window
            # was already swapped out, so fold it BACK into the live
            # accumulator — the counts must be delayed, never dropped.
            # (merge is an eager device enqueue; reads window + the current
            # acc under the lock, so no dispatch can donate either mid-merge.)
            with self._acc_lock:
                self._acc = merge_accumulators(window, self._acc)
            raise
        # Host numpy work (dtype casts, rounding, dict building) stays
        # OUTSIDE the totals lock (tpulint TPU403): the critical section
        # is only the counter updates plus alias grabs. Aliasing out is
        # safe because the drift arrays are REPLACED under the lock, never
        # mutated in place — a snapshot read here can't be half-updated by
        # a concurrent fold.
        window_batches = float(host.batches)
        window_drift_sum = np.asarray(host.drift_sum, dtype=np.float64)
        window_drift_last = np.asarray(host.drift_last, dtype=np.float64)
        with self._totals_lock:
            t = self._totals
            t["rows"] += float(host.rows)
            t["outliers"] += float(host.outliers)
            t["batches"] += window_batches
            t["drift_sum"] = t["drift_sum"] + window_drift_sum
            if window_batches:
                t["drift_last"] = window_drift_last
            rows, outliers, batches = t["rows"], t["outliers"], t["batches"]
            drift_sum, drift_last = t["drift_sum"], t["drift_last"]
        drift_mean = drift_sum / max(batches, 1.0)
        return {
            "rows": rows,
            "outliers": outliers,
            "batches": batches,
            "drift_last": dict(
                zip(SCHEMA.feature_names, drift_last.round(6).tolist())
            ),
            "drift_mean": dict(
                zip(SCHEMA.feature_names, drift_mean.round(6).tolist())
            ),
            # UNROUNDED cumulative per-feature sums, schema order — the
            # lifecycle trigger policy differences consecutive snapshots
            # into windows, and reconstructing the sum from the rounded
            # means above would accumulate up to 5e-7 * batches of error
            # (unbounded over a long-lived server). The gauges keep their
            # rounded display values; windowing reads this.
            "drift_sum": drift_sum.tolist(),
        }

    # -------------------------------------------------------------- predict
    def _normalize_tier(self, tier: str | None) -> str | None:
        """Dispatch-entry tier normalization: None and the default tier
        both mean the plain un-suffixed dispatch; anything else must be a
        committed extra tier (routing never invents a tier — a typo'd
        demand fails loudly, exactly like serve_tier='quant' at init)."""
        if tier is None or tier == self.default_tier:
            return None
        if not self._accumulate or tier not in self._tier_extra:
            raise ValueError(
                f"tier {tier!r} is not committed on this engine "
                f"(available: {self.available_tiers})"
            )
        return tier

    def predict_records(
        self, records: list[dict[str, Any]], span=None,
        tier: str | None = None,
    ) -> dict[str, Any]:
        """Validated records -> reference response dict (`app/model.py:64-70`).
        ``span`` (tracewire, `trace/span.Span`) gets the engine-side stage
        stamps — encode / dispatch / device_fetch — when tracing is armed;
        None (the default) costs two branches."""
        columns = records_to_columns(records)
        ds = self.bundle.preprocessor.encode(columns)
        if span is not None:
            span.stamp("encode")
        return self.predict_arrays(ds.cat_ids, ds.numeric, span=span, tier=tier)

    def predict_records_wire(
        self, records: list[dict[str, Any]], span=None,
        tier: str | None = None,
    ) -> bytes:
        """`predict_records` straight to wire bytes: the whole
        encode→dispatch→fetch→json pipeline stays in the executor thread,
        so the event loop only ever writes pre-encoded bytes."""
        columns = records_to_columns(records)
        ds = self.bundle.preprocessor.encode(columns)
        if span is not None:
            span.stamp("encode")
        handle = self.dispatch_arrays(ds.cat_ids, ds.numeric, tier=tier)
        if handle is None:
            return EMPTY_RESPONSE_BYTES
        if span is not None:
            span.stamp("dispatch")
            span.entry = _entry_name(f"bucket_{handle.rows}", handle.tier)
        handle.start_copy()
        response = self.fetch_arrays_wire(handle)
        if span is not None:
            span.stamp("device_fetch")
        return response

    def predict_arrays(
        self, cat_ids: np.ndarray, numeric: np.ndarray, span=None,
        tier: str | None = None,
    ) -> dict[str, Any]:
        handle = self.dispatch_arrays(cat_ids, numeric, tier=tier)
        if handle is None:
            # Empty request: nothing to score, no drift signal (an empty
            # batch must not poison the drift gauges with statistic=1).
            return empty_response()
        if span is not None:
            span.stamp("dispatch")
            span.entry = _entry_name(f"bucket_{handle.rows}", handle.tier)
        handle.start_copy()
        response = self.fetch_arrays(handle)
        if span is not None:
            span.stamp("device_fetch")
        return response

    def dispatch_arrays(
        self, cat_ids: np.ndarray, numeric: np.ndarray,
        tier: str | None = None,
    ) -> _ArraysHandle | None:
        """Pad to the bucket and fire the device dispatch WITHOUT waiting
        for (or fetching) the result: returns a handle whose ``start_copy``
        begins the async D2H and whose ``fetch_arrays`` blocks. None for
        the empty request (no device work at all). ``tier`` selects a
        committed non-default serving tier (per-request SLO routing)."""
        tier = self._normalize_tier(tier)
        n = cat_ids.shape[0]
        if n == 0:
            return None
        tee = self._tee
        if tee is not None:
            # Lifecycle observation (reservoir feed + shadow mirror):
            # pre-padding arrays, bounded non-blocking enqueue inside the
            # tee — never a hot-path stall.
            tee(cat_ids, numeric)
        # Injection point (mlops_tpu/faults): raise = device error (the
        # caller's 500 contract); delay = engine stall (the deadline 504
        # contract). Fired pre-padding, outside every lock.
        faults.fire("serve.engine.dispatch")
        bucket = self._bucket_for(n)
        rows = bucket if bucket is not None else n
        if not self._accumulate:
            # sklearn hybrid: host classifier + device monitors, the seed's
            # dict output (no packed program exists for a non-XLA model).
            cat_ids, numeric, mask = _pad_rows(cat_ids, numeric, n, rows)
            out = self._predict(cat_ids, numeric, mask)
            stats = self.shape_stats
            if stats is not None:
                stats.observe(f"bucket_{rows}", n, rows)
            return _ArraysHandle(out, n, rows, packed=False)
        t0 = time.perf_counter() if self.cost_ledger is not None else 0.0
        out, rows = self._dispatch_padded(cat_ids, numeric, n, rows, tier)
        stats = self.shape_stats
        if stats is not None:
            # rows is the shape that actually SERVED (the degraded
            # fallback bucket when the target failed) — the histogram must
            # describe the compute paid, not the compute intended.
            stats.observe(_entry_name(f"bucket_{rows}", tier), n, rows)
        return _ArraysHandle(out, n, rows, packed=True, t0=t0, tier=tier)

    def _dispatch_padded(
        self, cat_ids, numeric, n: int, rows: int, tier: str | None = None
    ):
        """Pad to ``rows`` and dispatch the fused packed program, keyed by
        the padded row count (equal to the bucket for bucketed requests,
        the exact size for oversized ones — so a repeated oversized shape
        reuses its table entry instead of recompiling).

        DEGRADED MODE: a failure for an unwarmed target shape (compile
        error, corrupt-cache load — the `serve.engine.compile` fault
        class) retries through the NEXT LARGER warmed bucket instead of
        500ing: padding is masked out of every statistic, so the degraded
        response is bit-identical to the target-bucket response — the
        request pays extra padded compute, never an outage. Counted in
        ``degraded_dispatch_total``; with no larger warmed bucket the
        original failure propagates (the caller's 500 contract). Returns
        ``(packed_out, rows_used)``. Degraded fallbacks stay WITHIN the
        request's tier: padding is bit-neutral, a tier change is not."""
        key = ("bucket", rows) if tier is None else ("bucket", rows, tier)
        try:
            cat, num, mask = _pad_rows(cat_ids, numeric, n, rows)
            return self._dispatch_fused(key, cat, num, mask, tier=tier), rows
        except Exception:
            fallback = self._degraded_rows(rows, tier)
            if fallback is None:
                raise
            logger.warning(
                "dispatch at %d rows failed; degrading to warmed bucket %d",
                rows, fallback, exc_info=True,
            )
            cat, num, mask = _pad_rows(cat_ids, numeric, n, fallback)
            fkey = (
                ("bucket", fallback) if tier is None
                else ("bucket", fallback, tier)
            )
            out = self._dispatch_fused(fkey, cat, num, mask, tier=tier)
            self._count_degraded()
            return out, fallback

    def _degraded_rows(
        self, rows: int, tier: str | None = None
    ) -> int | None:
        """Smallest WARMED same-tier bucket strictly larger than ``rows``
        (the degraded-dispatch target), or None when nothing larger is
        warmed for that tier."""
        with self._compile_lock:
            larger = [
                key[1]
                for key in self._exec
                if key[0] == "bucket" and key[1] > rows
                and _key_tier(key) == tier
            ]
        return min(larger, default=None)

    def fetch_arrays(self, handle: _ArraysHandle) -> dict[str, Any]:
        """Block on the host copy and slice the packed buffer into the
        reference response. ONE contiguous f32 buffer per request: the
        seed's 3-leaf tree fetch paid a device->host transfer per leaf,
        the packed buffer pays exactly one."""
        return format_response(*self.fetch_arrays_raw(handle))

    def fetch_arrays_wire(self, handle: _ArraysHandle) -> bytes:
        """`fetch_arrays` straight to wire bytes (serve/wire.py
        `encode_response` — byte-identical to the dict path's json). The
        batcher runs this in the executor thread, so the event loop never
        pays the per-response encode again."""
        return encode_response(*self.fetch_arrays_raw(handle))

    def fetch_arrays_raw(
        self, handle: _ArraysHandle
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The fetch minus the dict/list formatting: ``(predictions f64[n],
        outliers f64[n], drift f64[D] rounded)`` — exactly what
        `format_response` turns into the wire dict. The shared-memory ring
        service (serve/ipc.py) writes these arrays straight into response
        slabs so the front-end processes format the identical floats."""
        n, rows = handle.n, handle.rows
        if handle.packed:
            arr = np.asarray(handle.out)
            p, o, d = packed_layout(rows)
            predictions = arr[p][:n]
            outliers = arr[o][:n]
            drift = arr[d]
        else:
            out = jax.device_get(handle.out)
            predictions = np.asarray(out["predictions"])[:n]
            outliers = np.asarray(out["outliers"])[:n]
            drift = np.asarray(out["feature_drift_batch"])
        ledger = self.cost_ledger
        if ledger is not None and handle.t0:
            # Device-path seconds: dispatch enqueue -> host copy landed
            # (transfer included — exactly the cost a regrid would
            # re-shape). The np.asarray
            # above is the blocking wait, so the buffer is in hand here.
            ledger.observe(
                _entry_name(f"bucket_{rows}", handle.tier),
                self._cost_tag, n, rows,
                time.perf_counter() - handle.t0,
            )
        return (
            predictions.astype(float),
            outliers.astype(float),
            drift.astype(float).round(6),
        )

    # ----------------------------------------------------- grouped predict
    def predict_group(
        self, requests: list[list[dict[str, Any]]],
        tier: str | None = None,
    ) -> list[dict[str, Any]]:
        """Score several concurrent requests in ONE device dispatch.

        Every request must have 1..GROUP_ROW_BUCKET records (the batcher
        enforces this); responses are exactly what each request would get
        from ``predict_records`` alone — per-request drift included.
        """
        return self.fetch_group(self.dispatch_group(requests, tier=tier))

    def dispatch_group(
        self, requests: list[list[dict[str, Any]]],
        tier: str | None = None,
    ) -> _GroupHandle:
        """Encode + fire the grouped device dispatch and start the packed
        output's async host copy, WITHOUT blocking on the result — the
        micro-batcher claims and dispatches the next group while this one's
        fetch completes (`serve/batcher.py`'s fetch ring)."""
        if (
            self._predict_group is None
            or len(requests) == 1
            or len(requests) > GROUP_SLOT_BUCKETS[-1]
        ):
            return _GroupHandle(
                responses=[
                    self.predict_records(r, tier=tier) for r in requests
                ]
            )
        sizes = [len(r) for r in requests]
        if not all(1 <= n <= GROUP_ROW_BUCKET for n in sizes):
            raise ValueError(
                f"grouped requests must have 1..{GROUP_ROW_BUCKET} records, "
                f"got sizes {sizes}"
            )
        # ONE encode pass over the whole group, split back into per-request
        # views: encoding is row-wise (vocab lookup + standardization), so
        # the flat encode is bit-identical to per-request encodes while
        # doing the Python/dict work once instead of per request — this
        # host work is serial (GIL) and sits on the grouped hot path.
        flat = [record for records in requests for record in records]
        ds = self.bundle.preprocessor.encode(records_to_columns(flat))
        parts, offset = [], 0
        for n in sizes:
            parts.append(
                (ds.cat_ids[offset : offset + n], ds.numeric[offset : offset + n])
            )
            offset += n
        return self.dispatch_group_arrays(parts, tier=tier)

    def dispatch_group_arrays(
        self, parts: list[tuple[np.ndarray, np.ndarray]],
        tier: str | None = None,
    ) -> _GroupHandle:
        """Grouped dispatch from PRE-ENCODED per-request arrays — the entry
        the shared-memory ring service uses (serve/ipc.py): front-end
        processes encode before enqueue (the native encoder releases the
        GIL there), so the engine process scatters rows straight into the
        group buffers without touching records or the preprocessor.
        Requires 2..GROUP_SLOT_BUCKETS[-1] requests of 1..GROUP_ROW_BUCKET
        rows each (the callers' coalescing policy guarantees it). The
        whole group serves ONE tier (per-(tier, tenant) coalescing is the
        callers' contract — one grouped dispatch is one program)."""
        tier = self._normalize_tier(tier)
        sizes = [cat.shape[0] for cat, _ in parts]
        tee = self._tee
        if tee is not None:
            for part_cat, part_num in parts:
                tee(part_cat, part_num)
        if not 2 <= len(parts) <= GROUP_SLOT_BUCKETS[-1]:
            raise ValueError(
                f"grouped dispatch takes 2..{GROUP_SLOT_BUCKETS[-1]} "
                f"requests, got {len(parts)}"
            )
        if not all(1 <= n <= GROUP_ROW_BUCKET for n in sizes):
            raise ValueError(
                f"grouped requests must have 1..{GROUP_ROW_BUCKET} records, "
                f"got sizes {sizes}"
            )
        # Injection point (mlops_tpu/faults): the grouped twin of
        # serve.engine.dispatch — covers the micro-batcher and the shm
        # ring plane's coalesced jobs.
        faults.fire("serve.engine.dispatch_group")
        t0 = time.perf_counter() if self.cost_ledger is not None else 0.0
        slots = GROUP_SLOT_BUCKETS[
            bisect.bisect_left(GROUP_SLOT_BUCKETS, len(parts))
        ]
        # Batch-1-only groups (the dominant serving traffic) take the
        # [slots, 1] shape family — no row padding, ~8x less compute per
        # dispatch on serial backends.
        rows = GROUP_ROW_BUCKETS[0] if max(sizes) == 1 else GROUP_ROW_BUCKET
        try:
            out = self._dispatch_group_at(parts, sizes, slots, rows, tier)
        except Exception:
            # DEGRADED MODE, grouped flavor: a compile/cache failure for
            # this group geometry retries through the smallest warmed
            # geometry that FITS (slot padding is masked out of every
            # statistic, so responses stay bit-identical) instead of
            # failing the whole coalesced job.
            fallback = self._degraded_group_shape(
                len(parts), max(sizes), (slots, rows), tier
            )
            if fallback is None:
                raise
            logger.warning(
                "grouped dispatch at (%d, %d) failed; degrading to warmed "
                "geometry (%d, %d)", slots, rows, *fallback, exc_info=True,
            )
            out = self._dispatch_group_at(parts, sizes, *fallback, tier)
            self._count_degraded()
            slots, rows = fallback
        stats = self.shape_stats
        if stats is not None:
            # Geometry occupancy: requested = the rows clients asked for,
            # padded = the full slots x rows grid the program computed
            # (slot padding AND row padding both count as waste).
            stats.observe(
                _entry_name(f"group_{slots}x{rows}", tier),
                sum(sizes), slots * rows,
            )
        handle = _GroupHandle(
            out=out, sizes=sizes, rows=rows, slots=slots, t0=t0, tier=tier
        )
        handle.start_copy()
        return handle

    def _dispatch_group_at(
        self,
        parts: list[tuple[np.ndarray, np.ndarray]],
        sizes: list[int],
        slots: int,
        rows: int,
        tier: str | None = None,
    ):
        """Scatter the pre-encoded parts into one [slots, rows, ...] stack
        and fire the fused grouped dispatch — shared by the target-shape
        and degraded-shape paths (one scatter rule = identical masking)."""
        cat = np.zeros((slots, rows, SCHEMA.num_categorical), np.int32)
        num = np.zeros((slots, rows, SCHEMA.num_numeric), np.float32)
        mask = np.zeros((slots, rows), bool)
        for i, (part_cat, part_num) in enumerate(parts):
            n = sizes[i]
            cat[i, :n] = part_cat
            num[i, :n] = part_num
            mask[i, :n] = True
        key = (
            ("group", slots, rows) if tier is None
            else ("group", slots, rows, tier)
        )
        return self._dispatch_fused(key, cat, num, mask, tier=tier)

    def _degraded_group_shape(
        self, n_parts: int, max_rows: int, failed: tuple[int, int],
        tier: str | None = None,
    ) -> tuple[int, int] | None:
        """Smallest-area WARMED same-tier group geometry that fits
        ``n_parts`` requests of up to ``max_rows`` rows, excluding the
        shape that just failed; None when nothing warmed fits."""
        with self._compile_lock:
            fits = [
                (key[1], key[2])
                for key in self._exec
                if key[0] == "group"
                and key[1] >= n_parts
                and key[2] >= max_rows
                and (key[1], key[2]) != failed
                and _key_tier(key) == tier
            ]
        return min(fits, key=lambda sr: sr[0] * sr[1], default=None)

    def fetch_group(self, handle: _GroupHandle) -> list[dict[str, Any]]:
        """Block on the packed group buffer (ONE D2H transfer for the whole
        group) and slice it back into per-request responses."""
        if handle.responses is not None:
            return handle.responses
        sizes, preds, outs, drifts = self.fetch_group_raw(handle)
        return [
            format_response(preds[i, :n], outs[i, :n], drifts[i])
            for i, n in enumerate(sizes)
        ]

    def fetch_group_wire(self, handle: _GroupHandle) -> list[bytes]:
        """`fetch_group` straight to per-request wire bytes (executor-side
        encode; see `fetch_arrays_wire`). Degenerate handles carry already
        formatted dicts from the solo fallback — encode those here too so
        the caller always gets bytes."""
        if handle.responses is not None:
            return [
                json.dumps(r, separators=(",", ":")).encode()
                for r in handle.responses
            ]
        sizes, preds, outs, drifts = self.fetch_group_raw(handle)
        return [
            encode_response(preds[i, :n], outs[i, :n], drifts[i])
            for i, n in enumerate(sizes)
        ]

    def fetch_group_raw(
        self, handle: _GroupHandle
    ) -> tuple[list[int], np.ndarray, np.ndarray, np.ndarray]:
        """The grouped fetch minus the per-request dict building:
        ``(sizes, predictions f64[slots, rows], outliers f64[slots, rows],
        drift f64[slots, D] rounded)``. Degenerate handles (solo fallback
        responses) never reach here — the ring service only groups through
        `dispatch_group_arrays`."""
        if handle.responses is not None:
            raise ValueError("degenerate group handle carries formatted "
                             "responses; fetch_group owns that path")
        rows = handle.rows
        arr = np.asarray(handle.out)  # [slots, 2*rows + D]
        ledger = self.cost_ledger
        if ledger is not None and handle.t0:
            # Grouped twin of the solo fetch's ledger hook: the whole
            # group rode one device dispatch, so the group's seconds
            # land on its geometry entry (requested = the rows clients
            # asked for; padded = the full slots x rows grid).
            ledger.observe(
                _entry_name(f"group_{handle.slots}x{rows}", handle.tier),
                self._cost_tag,
                sum(handle.sizes), handle.slots * rows,
                time.perf_counter() - handle.t0,
            )
        # Response assembly is serial host Python on the grouped hot path:
        # do the dtype casts/rounding ONCE over the stacked arrays, then
        # slice per slot (per-slot .astype/.round cost ~3x more).
        p, o, d = packed_layout(rows)
        return (
            handle.sizes,
            arr[:, p].astype(float),
            arr[:, o].astype(float),
            arr[:, d].astype(float).round(6),
        )

    def _bucket_for(self, n: int) -> int | None:
        i = bisect.bisect_left(self.buckets, n)
        return self.buckets[i] if i < len(self.buckets) else None

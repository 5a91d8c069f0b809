"""Multi-tenant model multiplexing tests (mlops_tpu/tenancy/, ISSUE 12).

The correctness bar for serving N portfolios from one plane:

- per-tenant responses BIT-IDENTICAL to each tenant's solo engine on
  BOTH planes (>=3 tenants, mixed architectures), with the `x-tenant`
  header routing and untagged traffic landing on the declared default;
- architecture-identical tenants PROVABLY share compiled executables
  (`shared_exec_count`, shared exec table + compile lock identity);
- admission is weighted max-min fair: a hot tenant past its share sheds
  503 against ITS OWN quota while a cold tenant's floor stays claimable
  (the starvation guarantee, deterministic at the governor and live on
  the ring plane);
- an engine kill -9 replay lands each busy slot under the CORRECT
  tenant's bundle with per-tenant monitor counters staying monotone;
- the ring/engine lock discipline holds under the runtime sanitizer
  with multi-tenant traffic, and the tenancy modules' declared-lock-free
  manifests (TPULINT_LOCK_ORDER) match reality;
- the fleet config rejects broken tenants.toml shapes with every
  problem named, and the single-tenant config degrades to the
  pre-tenancy plane.
"""

import contextlib
import dataclasses
import json
import socket
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from mlops_tpu.config import ServeConfig
from mlops_tpu.serve.frontend import reuseport_socket, start_frontends
from mlops_tpu.serve.ipc import RequestRing, RingService
from mlops_tpu.tenancy import (
    QuotaGovernor,
    TenancyConfig,
    TenancyConfigError,
    TenantRouter,
    TenantSpec,
    UNKNOWN_TENANT_LABEL,
    load_tenants_toml,
    single_tenant_config,
)

# ------------------------------------------------------------ unit: config
def _spec(name, bundle_dir="b", weight=1.0):
    return TenantSpec(name=name, bundle_dir=bundle_dir, weight=weight)


def test_tenancy_config_validate_names_every_problem():
    with pytest.raises(TenancyConfigError, match="at least one"):
        TenancyConfig().validate(check_bundles=False)
    with pytest.raises(TenancyConfigError, match="duplicate tenant name"):
        TenancyConfig(
            tenants=(_spec("emea"), _spec("emea"))
        ).validate(check_bundles=False)
    with pytest.raises(TenancyConfigError, match="weight=0.0"):
        TenancyConfig(
            tenants=(_spec("emea", weight=0.0),)
        ).validate(check_bundles=False)
    with pytest.raises(TenancyConfigError, match="no bundle_dir"):
        TenancyConfig(
            tenants=(_spec("emea", bundle_dir=""),)
        ).validate(check_bundles=False)
    with pytest.raises(TenancyConfigError, match="is not a directory"):
        TenancyConfig(
            tenants=(_spec("emea", bundle_dir="/definitely/not/here"),)
        ).validate(check_bundles=True)
    with pytest.raises(TenancyConfigError, match="Prometheus label"):
        TenancyConfig(
            tenants=(_spec('bad"name{}'),)
        ).validate(check_bundles=False)
    with pytest.raises(TenancyConfigError, match="names no"):
        TenancyConfig(
            tenants=(_spec("emea"),), default_tenant="apac"
        ).validate(check_bundles=False)
    # every problem in ONE error, not just the first
    with pytest.raises(TenancyConfigError) as err:
        TenancyConfig(
            tenants=(_spec("a", weight=-1.0), _spec("a")),
            default_tenant="zz",
        ).validate(check_bundles=False)
    text = str(err.value)
    assert "weight=-1.0" in text
    assert "duplicate" in text
    assert "names no" in text


def test_tenants_toml_round_trip_and_shape_errors(tmp_path):
    path = tmp_path / "tenants.toml"
    path.write_text(
        'default_tenant = "apac"\n'
        "[[tenant]]\n"
        'name = "emea"\n'
        'bundle_dir = "reg/emea/3"\n'
        "weight = 2.0\n"
        "[[tenant]]\n"
        'name = "apac"\n'
        'bundle_dir = "reg/apac/1"\n'
    )
    fleet = load_tenants_toml(path)
    assert fleet.names == ("emea", "apac")
    assert fleet.weights == (2.0, 1.0)
    assert fleet.default_tenant == "apac"
    assert fleet.default_index == 1
    fleet.validate(check_bundles=False)

    path.write_text("[[tenant]]\nname = 'x'\nbundel_dir = 'typo'\n")
    with pytest.raises(TenancyConfigError, match="unknown keys"):
        load_tenants_toml(path)
    # A misspelled TOP-LEVEL key is named too: `default-tenant` would
    # otherwise parse cleanly, fall back to the first tenant, and
    # silently misroute all untagged traffic.
    path.write_text(
        '"default-tenant" = "apac"\n[[tenant]]\nname = "x"\n'
        'bundle_dir = "reg/x/1"\n'
    )
    with pytest.raises(TenancyConfigError, match="unknown top-level keys"):
        load_tenants_toml(path)
    path.write_text("tenant = 3\n")
    with pytest.raises(TenancyConfigError, match="array of tables"):
        load_tenants_toml(path)
    path.write_text("not [valid toml\n")
    with pytest.raises(TenancyConfigError, match="not valid TOML"):
        load_tenants_toml(path)
    with pytest.raises(TenancyConfigError, match="cannot read"):
        load_tenants_toml(tmp_path / "missing.toml")


def test_single_tenant_config_is_the_default_fleet(tmp_path):
    fleet = single_tenant_config(str(tmp_path))
    fleet.validate(check_bundles=True)
    assert fleet.names == ("default",)
    assert fleet.default_index == 0
    assert fleet.weights == (1.0,)


# ------------------------------------------------------------- unit: quota
def test_quota_floors_are_fractional_and_sum_to_capacity():
    gov = QuotaGovernor(10, (1.0, 3.0))
    assert gov.floors == (2.5, 7.5)
    assert sum(gov.floors) == pytest.approx(10.0)
    with pytest.raises(ValueError, match="capacity"):
        QuotaGovernor(0, (1.0,))
    with pytest.raises(ValueError, match="weights"):
        QuotaGovernor(4, (1.0, 0.0))


def test_quota_hot_tenant_sheds_against_its_own_share():
    """Weighted max-min with reserved floors: a flood from one tenant
    occupies at most C - sum(other floors), every rejection past that is
    the 'quota' verdict (counted per tenant), and the cold tenant's
    floor admits its whole reservation afterwards."""
    gov = QuotaGovernor(10, (1.0, 1.0))
    verdicts = [gov.try_acquire(0) for _ in range(10)]
    # floor admits 5 (used < 5.0 for used in 0..4); the borrow path is
    # blocked by the cold tenant's fully-unmet 5.0 reservation.
    assert verdicts.count("ok") == 5
    assert verdicts.count("quota") == 5
    # The starvation guarantee: the cold tenant's first request (and its
    # whole floor) always succeeds while the hot tenant floods.
    cold = [gov.try_acquire(1) for _ in range(5)]
    assert cold == ["ok"] * 5
    # Now the pool is physically exhausted: NOT a quota event.
    assert gov.try_acquire(1) == "full"
    assert gov.try_acquire(0) == "full"


def test_quota_reservations_rearm_on_release():
    gov = QuotaGovernor(8, (1.0, 3.0))  # floors 2.0 / 6.0
    # The light tenant is capped at its floor while the heavy tenant's
    # 6.0 reservation is unmet.
    assert [gov.try_acquire(0) for _ in range(3)] == ["ok", "ok", "quota"]
    # The heavy tenant's whole floor admits.
    assert [gov.try_acquire(1) for _ in range(6)] == ["ok"] * 6
    assert gov.try_acquire(0) == "full"
    # A release that drops the heavy tenant below its floor RE-ARMS its
    # reservation: the light tenant still cannot take that capacity (the
    # guarantee is stateless per admission — a cold tenant's floor is
    # reachable at every instant, not only before its first burst).
    gov.release(1)
    assert gov.try_acquire(0) == "quota"
    assert gov.try_acquire(1) == "ok"  # the floor's owner reclaims it
    assert gov.used == [2, 6]


def test_quota_release_clamps_at_zero():
    gov = QuotaGovernor(4, (1.0,))
    gov.release(0)  # release bug: must clamp, never go negative
    assert gov.used == [0]
    assert gov.try_acquire(0) == "ok"
    gov.release(0)
    gov.release(0)
    assert gov.used == [0]


def test_quota_fractional_floors_cannot_be_flooded_away():
    """capacity=8, five equal tenants -> fractional floors 1.6, integer
    reservations 1. Four flooders must NOT be able to fill the pool by
    each overshooting to 2 via a floor fast-path: every admission holds
    back every other tenant's unmet integer floor, so the cold fifth
    tenant's slot is claimable at every instant of the flood."""
    gov = QuotaGovernor(8, (1.0,) * 5)
    for flooder in range(4):
        while gov.try_acquire(flooder) == "ok":
            pass
    # The flood saturated everything EXCEPT the cold tenant's integer
    # reservation.
    assert gov.total_used == 7
    assert gov.try_acquire(4) == "ok"  # the cold tenant's held-back slot
    # Tiny pools never deadlock: one slab, two tenants (integer floors
    # 0) — the first comer takes it, the other waits on "full", and a
    # release hands it over.
    one = QuotaGovernor(1, (1.0, 1.0))
    assert one.try_acquire(0) == "ok"
    assert one.try_acquire(1) == "full"
    one.release(0)
    assert one.try_acquire(1) == "ok"


def test_claim_overflow_gated_on_multi_tenant_planes():
    """The per-class governors admit against the class the ROW COUNT
    names, so a multi-tenant claim may not cross classes: a small
    request overflowing into a large slab would hold capacity the
    large-class governor never accounted (hot tenant starves cold large
    floors with no quota signal). The 1-tenant plane keeps the
    opportunistic overflow (allow_overflow default)."""
    from mlops_tpu.serve.ipc import RequestRing, RingClient

    ring = RequestRing(
        workers=1, slots_small=1, slots_large=1, large_rows=8,
        tenant_names=("emea", "apac"),
    )
    try:
        client = RingClient(ring, 0)
        first = client.claim(1, tenant=0, allow_overflow=False)
        assert first is not None
        assert ring.slot_class(first) == 0  # the small slab
        # Small class exhausted: a governed claim must NOT take the
        # large slab...
        assert client.claim(1, tenant=0, allow_overflow=False) is None
        # ...while the 1-tenant overflow still may, and a large request
        # can always reach the slab a governed small request left free.
        overflow = client.claim(1, tenant=0)
        assert overflow is not None
        assert ring.slot_class(overflow) == 1  # the large slab
    finally:
        ring.close()


# ------------------------------------------------------------ unit: router
def test_router_resolves_default_known_and_unknown():
    router = TenantRouter(("emea", "apac"), default_index=1)
    assert router.resolve("") == 1  # untagged -> declared default
    assert router.resolve("emea") == 0
    assert router.resolve("apac") == 1
    assert router.resolve("latam") is None  # unknown -> caller 404s
    assert router.label("") == "apac"
    assert router.label("emea") == "emea"
    # Arbitrary header text never becomes a label value (bounded set).
    assert router.label('inject",x="y') == UNKNOWN_TENANT_LABEL
    empty = TenantRouter(())
    assert empty.names == ("default",)
    assert empty.resolve("") == 0


def test_tenancy_modules_declare_lock_free_manifests():
    """The ISSUE's concurrency contract: router/registry/quota are
    single-owner or immutable state with NO locks — declared, so the
    static layer and the runtime sanitizer both check the claim."""
    from mlops_tpu.tenancy import quota, registry, router

    assert quota.TPULINT_LOCK_ORDER == {"QuotaGovernor": ()}
    assert router.TPULINT_LOCK_ORDER == {"TenantRouter": ()}
    assert registry.TPULINT_LOCK_ORDER == {"TenantRegistry": ()}


# ------------------------------------------------------------ fleet fixture
@pytest.fixture(scope="module")
def fleet(tiny_pipeline, tmp_path_factory):
    """Three tenant bundles, two distinct architectures:

    - ``emea``: the shared tiny_pipeline bundle (mlp 32x32);
    - ``apac``: a param-perturbed COPY of emea's bundle — identical
      architecture (the executable-sharing twin), different params, so
      its responses must differ from emea's;
    - ``latam``: a freshly trained mlp 16 — a different architecture
      that must get its own compiled entries.
    """
    import jax
    import jax.numpy as jnp

    from mlops_tpu.bundle import load_bundle, save_bundle
    from mlops_tpu.config import Config, ModelConfig, TrainConfig
    from mlops_tpu.train.pipeline import run_training

    _, result = tiny_pipeline
    root = tmp_path_factory.mktemp("tenants")

    base = load_bundle(result.bundle_dir)
    # save_bundle serializes the INNER "params" subtree (the same
    # contract run_training uses); load_bundle rewraps it.
    perturbed = jax.tree_util.tree_map(
        lambda x: (
            x * 1.01
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
            else x
        ),
        base.variables["params"],
    )
    apac_dir = save_bundle(
        root / "apac",
        base.model_config,
        perturbed,
        base.preprocessor,
        base.monitor,
        calibration=dict(base.manifest.get("calibration", {})),
    )

    config = Config()
    config.data.rows = 2500
    config.model = ModelConfig(family="mlp", hidden_dims=(16,), embed_dim=4)
    config.train = TrainConfig(steps=60, eval_every=60, batch_size=256)
    config.registry.root = str(root / "latam-registry")
    config.registry.run_root = str(root / "latam-runs")
    latam = run_training(config)

    return TenancyConfig(
        tenants=(
            TenantSpec("emea", str(result.bundle_dir), weight=2.0),
            TenantSpec("apac", str(apac_dir), weight=1.0),
            TenantSpec("latam", str(latam.bundle_dir), weight=1.0),
        ),
        default_tenant="emea",
    )


@pytest.fixture(scope="module")
def registry(fleet):
    from mlops_tpu.tenancy import TenantRegistry

    reg = TenantRegistry(fleet, buckets=(1, 8, 64))
    reg.warmup()
    return reg


@pytest.fixture(scope="module")
def prep_paths(fleet):
    paths = [
        str(Path(spec.bundle_dir) / "preprocess.npz")
        for spec in fleet.tenants
    ]
    for path in paths:
        assert Path(path).is_file(), path
    return paths


# --------------------------------------------------------------- harnesses
@contextlib.contextmanager
def multi_tenant_plane(
    engines,
    prep_paths,
    tenancy,
    workers=2,
    slots_small=8,
    slots_large=2,
    service_kwargs=None,
    **cfg_kwargs,
):
    """The production multi-tenant topology with the engine half hosted in
    this process (what `serve_multi_worker` builds from a tenants.toml,
    minus the bundle loads): forked SO_REUSEPORT front ends with the
    tenant router + per-worker quota governors, a tenant-dimensioned
    ring, and one RingService dispatching against N engines."""
    import os
    import signal

    cfg_kwargs.setdefault("max_batch", 64)
    cfg = ServeConfig(
        host="127.0.0.1",
        port=0,
        workers=workers,
        ring_slots_small=slots_small,
        ring_slots_large=slots_large,
        **cfg_kwargs,
    ).validate()
    ring = RequestRing(
        workers=workers,
        slots_small=slots_small,
        slots_large=slots_large,
        large_rows=cfg.max_batch,
        tenant_names=tenancy.names,
    )
    placeholder = reuseport_socket(cfg.host, cfg.port)
    child_cfg = dataclasses.replace(cfg, port=placeholder.getsockname()[1])
    procs = start_frontends(child_cfg, ring, list(prep_paths), None, tenancy)
    service = RingService(
        engines[0],
        ring,
        max_group=cfg.max_group,
        max_inflight=cfg.max_inflight,
        threads=cfg.max_workers,
        engines=list(engines),
        **(service_kwargs or {}),
    )
    service.start()
    ring.set_ready(True)
    _wait_accepting(child_cfg.port)
    try:
        yield child_cfg.port, ring, procs, service
    finally:
        ring.set_draining()
        ring.set_ready(False)
        for proc in procs:
            if proc.is_alive() and proc.pid:
                with contextlib.suppress(ProcessLookupError):
                    os.kill(proc.pid, signal.SIGTERM)
        for proc in procs:
            proc.join(timeout=15)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=5)
        service.stop()
        placeholder.close()
        ring.close()


@contextlib.contextmanager
def registry_server(registry, **cfg_kwargs):
    """The single-process plane over a tenant fleet: HttpServer with the
    registry installed (what `_serve` builds from serve.tenants_path)."""
    import asyncio

    from mlops_tpu.serve.server import HttpServer

    cfg_kwargs.setdefault("max_batch", 64)
    holder: dict = {}
    started = threading.Event()

    async def main():
        server = HttpServer(
            registry.default_engine,
            ServeConfig(host="127.0.0.1", port=0, **cfg_kwargs),
            registry=registry,
        )
        srv = await server.start()
        holder["port"] = srv.sockets[0].getsockname()[1]
        holder["stop"] = asyncio.Event()
        holder["loop"] = asyncio.get_running_loop()
        started.set()
        await holder["stop"].wait()
        srv.close()
        server.stop_telemetry()
        await srv.wait_closed()

    thread = threading.Thread(target=lambda: asyncio.run(main()), daemon=True)
    thread.start()
    assert started.wait(15), "registry server did not start"
    try:
        yield holder["port"]
    finally:
        holder["loop"].call_soon_threadsafe(holder["stop"].set)
        thread.join(timeout=10)


def _wait_accepting(port, timeout=15.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=1):
                return
        except OSError:
            time.sleep(0.05)
    raise TimeoutError(f"no front end accepting on :{port}")


def _recv_response(sock_file):
    status_line = sock_file.readline()
    if not status_line:
        return None
    status = int(status_line.split(b" ")[1])
    headers = {}
    while True:
        line = sock_file.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        name, _, value = line.decode("latin1").partition(":")
        headers[name.strip().lower()] = value.strip()
    body = sock_file.read(int(headers.get("content-length", 0)))
    return status, headers, body


def http_exchange(port, method, path, body=None, headers=None):
    data = b"" if body is None else json.dumps(body).encode()
    head = [f"{method} {path} HTTP/1.1", "host: t",
            f"content-length: {len(data)}"]
    for name, value in (headers or {}).items():
        head.append(f"{name}: {value}")
    head.append("connection: close")
    raw = ("\r\n".join(head) + "\r\n\r\n").encode() + data
    with socket.create_connection(("127.0.0.1", port), timeout=60) as sock:
        sock.sendall(raw)
        with sock.makefile("rb") as f:
            return _recv_response(f)


def predict(port, records, tenant=None):
    headers = {"x-tenant": tenant} if tenant else None
    status, resp_headers, body = http_exchange(
        port, "POST", "/predict", records, headers
    )
    return status, resp_headers, (json.loads(body) if body else None)


# ------------------------------------------------------- executable sharing
def test_registry_shares_executables_across_architecture_twins(registry):
    """emea/apac (identical architecture, different params) must share
    ONE exec table + compile lock; latam (different architecture) must
    not. Params-as-args is what makes the sharing sound — proven by the
    parity tests below, where the twins' responses differ."""
    emea, apac, latam = registry.engines
    assert registry.shared_exec_count == 1
    assert apac._exec is emea._exec
    assert apac._compile_lock is emea._compile_lock
    assert apac.warmup_stats["mode"] == "shared"
    assert latam._exec is not emea._exec
    assert latam._compile_lock is not emea._compile_lock
    assert registry.ready
    assert len(registry) == 3
    assert registry.names == ("emea", "apac", "latam")
    # The twins serve DIFFERENT portfolios through the shared programs.
    import jax

    assert not np.allclose(
        np.asarray(jax.tree_util.tree_leaves(emea._variables)[0]),
        np.asarray(jax.tree_util.tree_leaves(apac._variables)[0]),
    )


def test_adopt_executables_rejects_unwarmed_donor(registry):
    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.serve.engine import InferenceEngine

    bundle = load_bundle(registry.tenancy.tenants[0].bundle_dir)
    cold_donor = InferenceEngine(bundle, buckets=(1,))
    adopter = InferenceEngine(bundle, buckets=(1,))
    with pytest.raises(ValueError, match="not warmed"):
        adopter.adopt_executables(cold_donor)


# ----------------------------------------------------------- parity: planes
def test_per_tenant_parity_single_process_plane(
    registry, fleet, sample_request
):
    """Every tenant's plane response is byte-identical to ITS engine's
    solo answer; untagged traffic rides the declared default; an unknown
    tenant answers 404 before any scoring work."""
    sizes = [1, 8, 20]
    with registry_server(registry) as port:
        for name, engine in zip(registry.names, registry.engines):
            for n in sizes:
                records = sample_request * n
                status, _, got = predict(port, records, tenant=name)
                assert status == 200, got
                solo = engine.predict_records(records)
                assert got == json.loads(json.dumps(solo)), (name, n)
        # Untagged -> default tenant (emea).
        status, _, untagged = predict(port, sample_request)
        assert status == 200
        assert untagged == json.loads(
            json.dumps(registry.default_engine.predict_records(sample_request))
        )
        # The twins are genuinely different portfolios.
        emea = predict(port, sample_request, tenant="emea")[2]
        apac = predict(port, sample_request, tenant="apac")[2]
        assert emea["predictions"] != apac["predictions"]
        # Unknown tenant: 404 before any scoring work — never the
        # default tenant's quota or monitors.
        status, _, payload = predict(port, sample_request, tenant="nosuch")
        assert status == 404
        assert "unknown tenant" in payload["detail"]
        # /metrics: header text never becomes a label. The stranger's
        # 404 REQUEST COUNT bills the default tenant's row on BOTH
        # planes (the ring's shm counters have one fixed row per
        # declared tenant, and the series must stay bit-compatible
        # across planes); spans keep the distinct `<unknown>` marker.
        status, _, body = http_exchange(port, "GET", "/metrics")
        text = body.decode()
        assert status == 200
        for name in registry.names:
            assert (
                f'mlops_tpu_requests_total{{route="/predict",status="200",'
                f'tenant="{name}"}}' in text
            )
        assert (
            'mlops_tpu_requests_total{route="/predict",status="404",'
            'tenant="emea"}' in text
        )
        assert f'tenant="{UNKNOWN_TENANT_LABEL}"' not in text
        assert 'tenant="nosuch"' not in text


def test_per_tenant_parity_ring_plane(
    registry, fleet, prep_paths, sample_request
):
    """The multi-worker plane: 3 tenants on 2 forked workers, per-tenant
    bit-identity vs solo, tenant-labeled ring metrics, 404 contract."""
    with multi_tenant_plane(
        registry.engines, prep_paths, fleet, workers=2, slots_small=16
    ) as (port, ring, _, _svc):
        for name, engine in zip(registry.names, registry.engines):
            for n in (1, 8):
                records = sample_request * n
                status, _, got = predict(port, records, tenant=name)
                assert status == 200, got
                solo = engine.predict_records(records)
                assert got == json.loads(json.dumps(solo)), (name, n)
        status, _, untagged = predict(port, sample_request)
        assert status == 200
        assert untagged == json.loads(
            json.dumps(registry.default_engine.predict_records(sample_request))
        )
        status, _, payload = predict(port, sample_request, tenant="nosuch")
        assert status == 404
        assert "unknown tenant" in payload["detail"]
        status, _, body = http_exchange(port, "GET", "/metrics")
        text = body.decode()
        assert status == 200
        for name in registry.names:
            assert f'tenant="{name}"' in text
            assert (
                f'mlops_tpu_tenant_quota_shed_total{{worker="0",'
                f'tenant="{name}"}}' in text
            )
        for worker in (0, 1):
            assert (
                f'mlops_tpu_ring_depth{{worker="{worker}",class="small",'
                'tenant="emea"}' in text
            )


# ---------------------------------------------------- quota contract (ring)
class _SlowStubEngine:
    """Engine-API stub with controllable latency and a per-stub constant
    prediction — jax-free, deterministic: the constant proves WHICH
    tenant's engine served a slot, the latency holds slots in flight."""

    ready = True
    max_bucket = 64
    supports_grouping = False
    monitor_accumulating = False

    class _Handle:
        def __init__(self, n):
            self.n = n

        def start_copy(self):
            pass

    def __init__(self, delay_s: float, value: float):
        self.delay_s = delay_s
        self.value = value

    def dispatch_arrays(self, cat, num):
        return self._Handle(cat.shape[0])

    def fetch_arrays_raw(self, handle):
        time.sleep(self.delay_s)
        n = handle.n
        return (
            np.full(n, self.value, float),
            np.zeros(n, float),
            np.zeros(23, float),
        )


def test_quota_shed_503_contract_per_tenant(prep_paths):
    """Hot tenant floods the SMALL class (4 slots, weights 1:1, floor
    2.0 — the governor is per slot class, so the lone large slab's
    capacity never pads the small-class floors): exactly 2 admitted,
    the rest shed 503 naming the tenant's own quota with Retry-After —
    while the COLD tenant's floor admits its request to the right
    engine. The fairness observable lands per tenant in
    mlops_tpu_tenant_quota_shed_total, and quota sheds do NOT count
    into the physical mlops_tpu_shed_total."""
    fleet = TenancyConfig(
        tenants=(_spec("hot", "x"), _spec("cold", "x")),
        default_tenant="hot",
    )
    hot_stub = _SlowStubEngine(delay_s=1.0, value=0.25)
    cold_stub = _SlowStubEngine(delay_s=0.1, value=0.75)
    with multi_tenant_plane(
        [hot_stub, cold_stub],
        [prep_paths[0], prep_paths[0]],
        fleet,
        workers=1,
        slots_small=4,
        slots_large=1,
    ) as (port, ring, _, _svc):
        results = []
        lock = threading.Lock()

        def hot_call():
            r = predict(port, [{}], tenant="hot")
            with lock:
                results.append(r)

        threads = [threading.Thread(target=hot_call) for _ in range(8)]
        for t in threads:
            t.start()
        time.sleep(0.4)  # hot admissions in flight (1.0s dispatch)
        # The cold tenant's floor is reachable DURING the flood, and its
        # answer comes from the cold engine (value pins the tenant).
        status, _, cold_payload = predict(port, [{}], tenant="cold")
        assert status == 200, cold_payload
        assert cold_payload["predictions"] == [0.75]
        for t in threads:
            t.join(timeout=30)
        statuses = [s for s, _, _ in results]
        assert statuses.count(200) == 2, statuses
        sheds = [r for r in results if r[0] == 503]
        assert len(sheds) == 6, statuses
        for status, headers, payload in sheds:
            assert headers.get("retry-after") == "1"
            assert "'hot' over quota" in payload["detail"]
        for _, _, payload in results:
            if isinstance(payload, dict) and payload.get("predictions"):
                assert payload["predictions"] == [0.25]
        assert int(ring.quota_shed[0, 0]) == 6
        assert int(ring.quota_shed[0, 1]) == 0
        # Quota rejections are NOT physical sheds: the slot-exhaustion
        # counter stays untouched by the whole flood (the counters are
        # disjoint so operators can difference them).
        assert int(ring.shed.sum()) == 0
        status, _, body = http_exchange(port, "GET", "/metrics")
        text = body.decode()
        assert (
            'mlops_tpu_tenant_quota_shed_total{worker="0",tenant="hot"} 6'
            in text
        )
        assert (
            'mlops_tpu_tenant_quota_shed_total{worker="0",tenant="cold"} 0'
            in text
        )


@pytest.mark.slow  # 10x-load timing measurement: CI's parallel job runs it
def test_hot_tenant_at_10x_cannot_starve_cold_tenant(
    registry, prep_paths, sample_request
):
    """The ISSUE acceptance: hot tenant at 10x load, the cold tenant's
    p99 stays within 2x its solo p99 AND it never sheds (its weighted
    max-min floor keeps slots reachable through the flood)."""
    fleet = TenancyConfig(
        tenants=(
            _spec("hot", registry.tenancy.tenants[0].bundle_dir),
            _spec("cold", registry.tenancy.tenants[1].bundle_dir),
        ),
        default_tenant="hot",
    )
    engines = [registry.engines[0], registry.engines[1]]

    def cold_pass(port, n=80):
        lat = []
        for _ in range(n):
            t0 = time.perf_counter()
            status, _, payload = predict(port, sample_request, tenant="cold")
            lat.append(time.perf_counter() - t0)
            assert status == 200, payload
        return float(np.percentile(np.asarray(lat), 99))

    with multi_tenant_plane(
        engines, prep_paths[:2], fleet, workers=1, slots_small=8,
        slots_large=2,
    ) as (port, ring, _, _svc):
        solo_p99 = cold_pass(port)
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                with contextlib.suppress(OSError):
                    predict(port, sample_request, tenant="hot")

        hammers = [threading.Thread(target=hammer) for _ in range(10)]
        for t in hammers:
            t.start()
        try:
            time.sleep(0.5)  # the flood is established
            hot_p99 = cold_pass(port)
        finally:
            stop.set()
            for t in hammers:
                t.join(timeout=30)
        assert int(ring.quota_shed[0, 1]) == 0, "cold tenant was quota-shed"
        assert hot_p99 <= max(2.0 * solo_p99, solo_p99 + 0.025), (
            f"cold p99 {hot_p99 * 1e3:.1f}ms vs solo "
            f"{solo_p99 * 1e3:.1f}ms under 10x hot load"
        )


# ------------------------------------------------------- kill -9 per tenant
def test_engine_kill9_replay_lands_under_correct_tenant(
    registry, sample_request
):
    """A busy slot a dead engine popped-but-never-answered must be
    replayed UNDER ITS SHM-TAGGED TENANT: the replayed answer is the
    tagged tenant's engine's bit-identical solo answer (the twins'
    params differ, so a wrong-tenant replay would produce different
    bytes), and each tenant's seeded monitor totals stay monotone."""
    import asyncio

    from mlops_tpu.schema import records_to_columns
    from mlops_tpu.serve.ipc import RingClient
    from mlops_tpu.serve.wire import RESP_OK, format_response

    emea, apac = registry.engines[0], registry.engines[1]
    expected_apac = apac.predict_records(sample_request)
    expected_emea = emea.predict_records(sample_request)
    assert expected_apac != expected_emea  # the tenant tag is decisive

    async def scenario():
        ring = RequestRing(
            workers=1, slots_small=2, slots_large=1, large_rows=8,
            tenant_names=("emea", "apac"),
        )
        try:
            client = RingClient(ring, 0)
            ds = emea.bundle.preprocessor.encode(
                records_to_columns(sample_request)
            )
            # The dead incarnation's per-tenant telemetry snapshot: the
            # respawn must seed EACH tenant's totals from its own row.
            snap_emea = dict(emea.monitor_snapshot())
            snap_apac = dict(apac.monitor_snapshot())
            ring.write_monitor(snap_emea, 0)
            ring.write_monitor(snap_apac, 1)
            slot = client.claim(len(sample_request), tenant=1)
            assert int(ring.slot_tenant[slot]) == 1
            future = client.submit(slot, ds.cat_ids, ds.numeric)
            popped = ring.pop_submissions()
            assert [s for s, _ in popped] == [slot]
            service = RingService(
                emea, ring, max_inflight=2, threads=2,
                engines=[emea, apac],
            )
            try:
                stats = service.reattach()
            finally:
                service.stop()
            assert stats["replayed_slots"] == 1
            client.on_doorbell()
            assert future.done() and int(future.result()) == RESP_OK
            pred, out, drift = client.response_arrays(slot)
            got = format_response(
                np.array(pred), np.array(out), np.array(drift)
            )
            client.release(slot)
            # Replay landed on APAC's bundle, bit-identically.
            assert got == json.loads(json.dumps(expected_apac))
            assert got != json.loads(json.dumps(expected_emea))
            # Per-tenant monitor totals are monotone across the respawn:
            # each engine's totals continue from its own seeded row (the
            # replayed request re-folded into apac's accumulator only).
            after_emea = emea.monitor_snapshot()
            after_apac = apac.monitor_snapshot()
            assert after_emea["rows"] == snap_emea["rows"]
            assert (
                after_apac["rows"]
                == snap_apac["rows"] + len(sample_request)
            )
        finally:
            ring.close()

    asyncio.run(scenario())


# ----------------------------------------------------------- lock sanitizer
@pytest.mark.parametrize("seed", [0, 1])
def test_multi_tenant_lock_discipline_under_perturbed_schedules(
    registry, fleet, prep_paths, sample_request, seed
):
    """The runtime lock sanitizer over the ring service + a SHARED-exec
    tenant pair with seeded schedule perturbation: zero order violations
    and per-tenant responses stay bit-identical under concurrency (the
    shared compile lock + per-tenant state refs hold up)."""
    from mlops_tpu.analysis.lockcheck import instrument_locks

    expected = {
        name: engine.predict_records(sample_request)
        for name, engine in zip(registry.names, registry.engines)
    }
    with multi_tenant_plane(
        registry.engines, prep_paths, fleet, workers=2, slots_small=16
    ) as (port, ring, _, service):
        with instrument_locks(service, perturb_seed=seed) as san_service, \
                instrument_locks(ring) as san_ring, \
                instrument_locks(
                    registry.engines[0], perturb_seed=seed
                ) as san_emea, \
                instrument_locks(registry.engines[2]) as san_latam:
            results = []
            lock = threading.Lock()

            def call(name):
                r = predict(port, sample_request, tenant=name)
                with lock:
                    results.append((name, r))

            threads = [
                threading.Thread(
                    target=call, args=(registry.names[i % 3],)
                )
                for i in range(12)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        for sanitizer in (san_service, san_ring, san_emea, san_latam):
            assert not sanitizer.violations, [
                str(v) for v in sanitizer.violations
            ]
        assert san_service.acquired, "service locks never exercised"
    assert len(results) == 12
    for name, (status, _, payload) in results:
        assert status == 200
        assert payload == json.loads(json.dumps(expected[name])), name


# ------------------------------------------------------ trace-report filter
def test_trace_report_tenant_filter(tmp_path, capsys):
    from mlops_tpu.commands import _trace_report
    from mlops_tpu.config import Config
    from mlops_tpu.trace import Span, TraceRecorder

    recorder = TraceRecorder(tmp_path / "spans.jsonl")
    for i in range(6):
        span = Span(f"r{i}", tenant="emea" if i % 3 else "apac")
        span.stamp("admission")
        span.stamp("respond")
        recorder.record(span.finish(200))
    recorder.close()
    config = Config()
    config.trace.dir = str(tmp_path)
    assert _trace_report(config) == 0
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])[
        "spans"
    ] == 6
    config.trace.tenant = "apac"
    assert _trace_report(config) == 0
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])[
        "spans"
    ] == 2
    # Tenant with no spans: the empty-report exit (2), still parseable.
    config.trace.tenant = "latam"
    assert _trace_report(config) == 2


def test_serve_cli_tenants_flag_maps_to_config():
    from mlops_tpu.cli import build_parser

    args = build_parser().parse_args(["serve", "--tenants", "t.toml"])
    assert args.tenants == "t.toml"
    args = build_parser().parse_args(
        ["trace-report", "--tenant", "emea"]
    )
    assert args.tenant == "emea"

"""Tenant bundle registry: N bundles -> N engines, executables deduped.

The multiplexer's load half (ROADMAP item 5): every tenant gets its OWN
`InferenceEngine` — its own params/monitor-accumulator/temperature refs,
its own exact host-side totals, its own lifecycle tee — but
architecture-identical tenants SHARE one set of compiled executables.
The mechanism is the one `lifecycle/shadow.py` already exploits: since
PR 3 the packed serving programs take params/monitor/temperature as
ARGUMENTS (never closures), so a compiled entry is keyed purely by the
abstract signature the compile cache hashes (model config + state
avals + shape — `compilecache/keys.py`); two tenants whose bundles agree
on that key can run the SAME executable with different params passed per
dispatch. Warmup therefore compiles (or deserializes) each distinct
architecture ONCE and every architecture-twin adopts the donor's exec
table by reference (`InferenceEngine.adopt_executables`) — N tenants at
K distinct architectures pay K warmups, and ``shared_exec_count`` is the
provable sharing the tests pin.

Concurrency (tpulint Layer 3): the registry itself holds NO locks — the
tenant list is immutable after construction and ``warmup`` runs once,
before traffic, on the starting thread. All serving-time synchronization
lives in the engines (whose ``_compile_lock`` is SHARED across an
adoption group, so concurrent novel-shape compiles from twin tenants
serialize on one lock and install into one table).
"""

from __future__ import annotations

import dataclasses
import json
import logging
from typing import Any

from mlops_tpu.config import Config
from mlops_tpu.tenancy.config import TenancyConfig

logger = logging.getLogger("mlops_tpu.tenancy")

# Declared lock-free (tpulint Layer 3 + lockcheck): the tenant list is
# immutable after construction and warmup runs once, pre-traffic, on the
# starting thread. Serving-time synchronization lives in the ENGINES
# (whose _compile_lock is shared across an adoption group).
TPULINT_LOCK_ORDER: dict[str, tuple[str, ...]] = {"TenantRegistry": ()}


def _arch_key(engine: Any) -> str:
    """The executable-sharing identity: model config + the abstract
    signature of the bound serving state (param/monitor avals). This is
    exactly the bundle-dependent material `compilecache/keys.py` hashes
    into the persistent cache key — equal here implies equal cache keys
    for every warmed entry, so adopting the donor's table hands the twin
    the artifacts its own warmup would have produced."""
    import jax

    shapes = jax.tree_util.tree_map(
        lambda x: [list(getattr(x, "shape", ())), str(getattr(x, "dtype", ""))],
        (engine._variables, engine._monitor),
    )
    return json.dumps(
        {
            "model_config": dataclasses.asdict(engine.bundle.model_config),
            "state": jax.tree_util.tree_leaves(shapes),
            "treedef": str(jax.tree_util.tree_structure(shapes)),
        },
        sort_keys=True,
    )


class TenantRegistry:
    """Load every tenant's bundle, build one engine per tenant, and warm
    the fleet with architecture-level executable dedupe. Tenant INDEX is
    the position in ``tenancy.tenants`` — the same index the shm slot
    tag, the quota governor, and the per-tenant telemetry blocks use."""

    def __init__(
        self,
        tenancy: TenancyConfig,
        buckets: tuple[int, ...],
        service_name: str = "credit-default-api",
        enable_grouping: bool = True,
        compile_cache: Any = None,
        warmup_workers: int = 0,
        model_shards: int = 1,
        device_index: int | None = None,
        serve_tier: str = "exact",
        tier_routing: bool = False,
    ) -> None:
        from mlops_tpu.bundle import load_bundle
        from mlops_tpu.serve.engine import InferenceEngine

        self.tenancy = tenancy.validate()
        self.names: tuple[str, ...] = self.tenancy.names
        self.default_index = self.tenancy.default_index
        self.bundles = [
            load_bundle(spec.bundle_dir) for spec in self.tenancy.tenants
        ]
        # ``model_shards`` is fleet-global (ISSUE 13): every tenant's
        # params lay out over the same ('model',) serve mesh, so
        # architecture twins still share executables — the mesh shape is
        # part of the cache key, identical across the fleet, and N
        # tenants × E replicas at K architectures still pay K warmups
        # per replica process (each against the same persistent cache:
        # one replica compiles, the rest deserialize).
        self.engines = [
            InferenceEngine(
                bundle,
                buckets=buckets,
                service_name=service_name,
                enable_grouping=enable_grouping,
                compile_cache=compile_cache,
                warmup_workers=warmup_workers,
                model_shards=model_shards,
                device_index=device_index,
                # Fleet-global like model_shards: per-tenant tier mixing
                # would break architecture-twin executable sharing (the
                # tiers are different program families).
                serve_tier=serve_tier,
                # Fleet-global for the same reason (ISSUE 19): the tier
                # ladder is extra program families, and every tenant of
                # one architecture must warm the same families to keep
                # the executable-dedupe contract.
                tier_routing=tier_routing,
            )
            for bundle in self.bundles
        ]
        # Tenants served through another tenant's compiled entries.
        self.shared_exec_count = 0

    def __len__(self) -> int:
        return len(self.engines)

    @property
    def default_engine(self) -> Any:
        return self.engines[self.default_index]

    @property
    def ready(self) -> bool:
        return all(engine.ready for engine in self.engines)

    def index(self, name: str) -> int:
        return self.names.index(name)

    def warmup(self) -> dict[str, Any]:
        """Warm each DISTINCT architecture once; twins adopt the donor's
        exec table by reference. Returns a per-tenant warmup report."""
        donors: dict[str, tuple[str, Any]] = {}
        report: dict[str, Any] = {}
        for name, engine in zip(self.names, self.engines):
            if not engine.monitor_accumulating:
                # sklearn flavor: the "executable" is a host estimator —
                # nothing to share; each tenant warms its own.
                engine.warmup()
                report[name] = {"mode": "warmed", **engine.warmup_stats}
                continue
            key = _arch_key(engine)
            donor = donors.get(key)
            if donor is None:
                engine.warmup()
                donors[key] = (name, engine)
                report[name] = {"mode": "warmed", **engine.warmup_stats}
            else:
                donor_name, donor_engine = donor
                engine.adopt_executables(donor_engine)
                self.shared_exec_count += 1
                report[name] = dict(engine.warmup_stats)
                logger.info(
                    "tenant %s shares compiled entries with %s "
                    "(identical architecture)", name, donor_name,
                )
        report["shared_exec_count"] = self.shared_exec_count
        return report


def tenant_scoped_config(config: Config, tenant: str) -> Config:
    """A per-tenant view of the global config for the per-tenant
    lifecycle controllers: the SAME knobs, with the controller state root
    namespaced per tenant (``lifecycle.dir/<tenant>``) so reservoirs,
    candidate bundles, and retrain checkpoints can never cross tenants.
    Shallow-replaces only the lifecycle section — every other section is
    shared by reference (read-only at serving time)."""
    from pathlib import Path

    return dataclasses.replace(
        config,
        lifecycle=dataclasses.replace(
            config.lifecycle, dir=str(Path(config.lifecycle.dir) / tenant)
        ),
    )

"""In-process E-replica plane over simulated devices (tests).

The replica set's scaling claim is about DEVICE-TIME-bound serving: on
the TPU path every dispatch pays a flat device round trip (its size on
the chip: not measured), and data-parallel replicas hide exactly that
wait behind each other. A CPU CI box cannot demonstrate it with real compute — one core
runs one matmul at a time no matter how many processes ask — so the
unit tests drive the REAL ring, router,
and E REAL `RingService` consumers over engines whose device time is a
simulated constant-latency round trip. Host-side work (descriptor
queues, coalescing, scatter, slab writes, doorbells) is all real and
all measured; only the XLA execution is replaced by the latency it
models. ``XLA_FLAGS=--xla_force_host_platform_device_count=E`` is the
companion knob for runs that want E visible jax devices too; this
module itself is jax-free.

Everything here is test harness, not serving code — the
production fleet is `serve_multi_worker` with ``serve.engine_replicas``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np

from mlops_tpu.schema import SCHEMA

# Harness-only module: the engines below hold no locks (per-handle state
# only) and the plane builder wires the production classes, whose own
# manifests govern them.
TPULINT_LOCK_ORDER: dict[str, tuple[str, ...]] = {
    "SimulatedDeviceEngine": ()
}


class _Handle:
    __slots__ = ("parts", "sizes", "n")

    def __init__(self, parts=None, sizes=None, n=0):
        self.parts = parts
        self.sizes = sizes
        self.n = n

    def start_copy(self) -> None:
        pass


class SimulatedDeviceEngine:
    """Engine-API stand-in whose device time is a constant-latency sleep.

    Deterministic, input-dependent outputs (predictions are the numeric
    row sums) so routing/parity tests can detect a cross-wired slab; the
    sleep sits in the FETCH — exactly where the real engine blocks on
    the device — so E RingService pool threads overlap E simulated
    round trips the way E replicas overlap E real ones."""

    ready = True
    max_bucket = 64
    supports_grouping = True
    monitor_accumulating = False

    def __init__(self, device_ms: float = 5.0, replica: int = 0) -> None:
        self.device_ms = float(device_ms)
        self.replica = int(replica)
        self._d = SCHEMA.num_categorical + SCHEMA.num_numeric

    # ------------------------------------------------------------- solo
    def dispatch_arrays(self, cat: np.ndarray, num: np.ndarray) -> _Handle:
        return _Handle(parts=[(cat, num)], sizes=[cat.shape[0]],
                       n=cat.shape[0])

    def fetch_arrays_raw(self, handle: _Handle):
        time.sleep(self.device_ms / 1e3)
        cat, num = handle.parts[0]
        pred = num.sum(axis=1).astype(float)
        return pred, np.zeros(handle.n, float), np.zeros(self._d, float)

    # ---------------------------------------------------------- grouped
    def dispatch_group_arrays(
        self, parts: list[tuple[np.ndarray, np.ndarray]]
    ) -> _Handle:
        return _Handle(parts=parts, sizes=[cat.shape[0] for cat, _ in parts])

    def fetch_group_raw(self, handle: _Handle):
        # ONE simulated round trip for the whole coalesced group — the
        # grouping economics the real plane has (requests-per-dispatch
        # is what amortizes the flat transport cost).
        time.sleep(self.device_ms / 1e3)
        rows = max(handle.sizes)
        preds = np.zeros((len(handle.parts), rows), float)
        outs = np.zeros_like(preds)
        drifts = np.zeros((len(handle.parts), self._d), float)
        for i, (cat, num) in enumerate(handle.parts):
            preds[i, : num.shape[0]] = num.sum(axis=1)
        return handle.sizes, preds, outs, drifts


@dataclasses.dataclass
class SimPlane:
    ring: Any
    services: list[Any]
    engines: list[SimulatedDeviceEngine]

    def stop(self) -> None:
        for service in self.services:
            service.stop()
        self.ring.close()


def build_sim_plane(
    replicas: int,
    workers: int = 1,
    slots_small: int = 64,
    slots_large: int = 2,
    device_ms: float = 5.0,
    max_group: int = 16,
    max_inflight: int = 2,
    threads: int = 4,
    start: bool = True,
) -> SimPlane:
    """The production ring + E production `RingService` consumers over
    simulated-device engines, all in this process (no forks: what it
    exercises is fan-out mechanics and device-time overlap, not HTTP)."""
    from mlops_tpu.serve.ipc import RequestRing, RingService

    ring = RequestRing(
        workers=workers,
        slots_small=slots_small,
        slots_large=slots_large,
        large_rows=64,
        replicas=replicas,
    )
    engines = [
        SimulatedDeviceEngine(device_ms=device_ms, replica=r)
        for r in range(replicas)
    ]
    services = [
        RingService(
            engines[r],
            ring,
            max_group=max_group,
            max_inflight=max_inflight,
            threads=threads,
            monitor_fetch_every_s=0,
            replica=r,
        )
        for r in range(replicas)
    ]
    if start:
        for r, service in enumerate(services):
            service.reattach()
            service.start()
            ring.set_ready(True, r)
    return SimPlane(ring=ring, services=services, engines=engines)


async def drive_grouped_load(
    plane: SimPlane,
    duration_s: float,
    concurrency: int = 64,
    worker: int = 0,
) -> dict[str, Any]:
    """Hammer batch-1 submissions through one worker's RingClient for
    ``duration_s`` and return grouped-path throughput plus the
    per-replica served split. Call inside a fresh event loop (the client
    is loop-confined); doorbell readers are registered per replica, the
    production topology."""
    import asyncio

    from mlops_tpu.serve.ipc import RingClient
    from mlops_tpu.serve.wire import RESP_OK

    ring = plane.ring
    loop = asyncio.get_running_loop()
    client = RingClient(ring, worker)
    for r in range(ring.replicas):
        loop.add_reader(
            ring.worker_doorbell(worker, r).fileno(),
            client.on_doorbell,
            r,
        )
    cat = np.zeros((1, SCHEMA.num_categorical), np.int32)
    num = np.random.default_rng(7).random(
        (1, SCHEMA.num_numeric)
    ).astype(np.float32)
    expected = float(num.sum())
    served = [0]
    wrong = [0]
    deadline = loop.time() + duration_s
    peak_depth = [0] * ring.replicas
    from mlops_tpu.serve.metrics import MON_ROWS

    # Call-local goodput split: mon rows are cumulative across calls on
    # one plane (a warm pass would otherwise inflate the measured
    # window's per-replica split), so snapshot and difference.
    rows_base = [
        int(ring.mon_vals[r, :, MON_ROWS].sum())
        for r in range(ring.replicas)
    ]

    async def sample_depths() -> None:
        # Mid-run router-observable sample: peak live depth per replica
        # (end-of-run depths are trivially zero).
        while loop.time() < deadline:
            for r in range(ring.replicas):
                depth = int(ring.rep_inflight[:, r].sum())
                if depth > peak_depth[r]:
                    peak_depth[r] = depth
            await asyncio.sleep(0.01)

    async def one_lane() -> None:
        while loop.time() < deadline:
            slot = client.claim(1)
            if slot is None:
                await asyncio.sleep(0)  # shed pressure: yield and retry
                continue
            future = client.submit(slot, cat, num)
            status = await future
            if status == RESP_OK:
                pred, _, _ = client.response_arrays(slot)
                if abs(float(pred[0]) - expected) > 1e-5:
                    wrong[0] += 1
                else:
                    served[0] += 1
            client.release(slot)

    t0 = time.perf_counter()
    await asyncio.gather(
        sample_depths(), *(one_lane() for _ in range(concurrency))
    )
    wall = time.perf_counter() - t0
    for r in range(ring.replicas):
        loop.remove_reader(ring.worker_doorbell(worker, r).fileno())
    per_replica_rows = [
        int(ring.mon_vals[r, :, MON_ROWS].sum()) - rows_base[r]
        for r in range(ring.replicas)
    ]
    return {
        "req_per_s": round(served[0] / wall, 1),
        "served": served[0],
        "wrong": wrong[0],
        "wall_s": round(wall, 3),
        "per_replica_rows": per_replica_rows,
        "per_replica_peak_depth": peak_depth,
    }

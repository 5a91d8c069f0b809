"""CI chaos smoke: the live serve plane under seeded fault injection.

The deployment-path proof for ISSUE 9 (faultline): train a tiny bundle,
launch the REAL `mlops-tpu serve --workers 2` plane with a seeded fault
plan armed through `MLOPS_TPU_FAULTS` (every process — supervisor,
engine, front ends — arms at import), and drive the failure scenarios
end to end:

1. engine stall  — a seeded delay fault on `serve.engine.dispatch*`:
   requests carrying `x-request-deadline-ms` answer the documented 504
   inside their budget; nothing hangs.
2. slow client   — a byte-dribbling request must not wedge concurrent
   traffic (and completes 200 itself).
3. overload      — a connection burst against a deliberately tiny ring:
   every response is in the contract set (sheds answer 503+Retry-After).
4. worker kill   — SIGKILL a front end mid-traffic: the supervisor
   respawns it and the plane keeps serving (slot quarantine drains).
5. ENGINE kill (ISSUE 11) — SIGKILL the engine process under live
   budgeted traffic: the supervisor forks a replacement that warm-starts
   from the AOT cache, re-attaches under a new incarnation, and replays
   the busy slots. Asserts: zero statuses outside {200, 503, 504} during
   the outage, every 504 inside its deadline budget, identical 200
   bodies across the respawn (replay bit-identity), recovery, and
   `engine_respawn_total >= 1` with MONOTONE counters across the respawn.
6. mid-write kills (subprocesses) — SIGKILL between tmp-write and rename
   on the compile-cache persist, the reservoir snapshot, and
   `utils.io.atomic_write`: no torn file ever lands.
7. cache corruption — seeded bit flips at `compilecache.read`: counted
   discard + recompile, correct outputs, self-healed store.
8. mid-regrid kill -9 (ISSUE 18) — SIGKILL a hot regrid between its
   warm phase and its swap, under a live serving hammer: the crash must
   leave nothing wedged — a fresh process over the same bundle + cache
   serves bit-identically, completes the regrid cleanly, and rolls back.

Global assertions: every /predict status is in {200, 413, 422, 503, 504},
at least one 504 was produced by the stall scenario, no request hangs
(every client call is deadline-bounded), /metrics counters are MONOTONE
across scrapes, and SIGTERM drains the plane cleanly (exit 0, no leaked
tasks) under the chaos-tuned drain knobs.

Run from the repo root: `python scripts/chaos_smoke.py` (CI pins
JAX_PLATFORMS=cpu).
"""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import textwrap
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RECORD = {"credit_limit": 12000, "age": 34}
ALLOWED_STATUSES = {200, 413, 422, 503, 504}

CHAOS_PLAN = """\
seed = 42

# Engine stall: seeded delays on the engine dispatch points. Probability
# is per-hit Bernoulli on a deterministic hash, so a fixed request count
# replays a fixed stall schedule.
[[fault]]
point = "serve.engine.dispatch*"
mode = "delay"
delay_s = 1.2
probability = 0.15
"""


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def get(url: str, timeout: float = 15.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read()


def raw_predict(port, body: bytes, headers=None, timeout=20.0):
    """One blocking /predict exchange, deadline-bounded (a hang fails the
    smoke via the socket timeout, never via CI's job timeout)."""
    head = [
        "POST /predict HTTP/1.1", "host: chaos",
        "content-type: application/json",
        f"content-length: {len(body)}",
    ]
    for name, value in (headers or {}).items():
        head.append(f"{name}: {value}")
    head.append("connection: close")
    payload = ("\r\n".join(head) + "\r\n\r\n").encode() + body
    with socket.create_connection(("127.0.0.1", port), timeout=timeout) as s:
        s.settimeout(timeout)
        s.sendall(payload)
        data = b""
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            data += chunk
    head_bytes, _, body_bytes = data.partition(b"\r\n\r\n")
    return int(head_bytes.split(b" ")[1]), head_bytes, body_bytes


def parse_counters(text: str) -> dict[str, float]:
    """Every `*_total` counter sample keyed by its full series name+labels
    — the monotonicity contract is per series."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if line.startswith("#") or "_total" not in line:
            continue
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            continue
    return out


def run_subprocess_scenario(name: str, script: str, env=None, expect_kill=False):
    proc = subprocess.run(
        [sys.executable, "-c", script],
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
        capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    if expect_kill:
        assert proc.returncode == -signal.SIGKILL, (
            f"{name}: expected SIGKILL, got {proc.returncode}\n"
            f"{proc.stdout[-1000:]}\n{proc.stderr[-1000:]}"
        )
    else:
        assert proc.returncode == 0, (
            f"{name}: exit {proc.returncode}\n"
            f"{proc.stdout[-1000:]}\n{proc.stderr[-2000:]}"
        )
    print(f"# chaos-smoke: scenario OK — {name}", flush=True)
    return proc


# ------------------------------------------------- mid-write kill scripts
_RESERVOIR_KILL = """
import numpy as np
from mlops_tpu import faults
from mlops_tpu.lifecycle.retrain import SampleReservoir
from mlops_tpu.schema import SCHEMA
faults.arm(faults.FaultPlan.from_rules(
    [{"point": "lifecycle.reservoir.midwrite", "mode": "kill"}]))
res = SampleReservoir(16, {state!r})
res.add_batch(np.ones((4, SCHEMA.num_categorical), np.int32),
              np.ones((4, SCHEMA.num_numeric), np.float32))
res.save()
raise SystemExit("kill fault did not fire")
"""

_ATOMIC_KILL = """
from mlops_tpu import faults
from mlops_tpu.utils.io import atomic_write
atomic_write({target!r}, b"GOOD" * 1024)
faults.arm(faults.FaultPlan.from_rules(
    [{"point": "io.atomic_write.midwrite", "mode": "kill"}]))
atomic_write({target!r}, b"TORN" * 4096)
raise SystemExit("kill fault did not fire")
"""

_CACHE_KILL = """
import jax, jax.numpy as jnp
from mlops_tpu import faults
from mlops_tpu.compilecache.cache import CacheJob, CompileCache
faults.arm(faults.FaultPlan.from_rules(
    [{"point": "compilecache.persist.midwrite", "mode": "kill"}]))
CompileCache({cache!r}).load_or_compile(CacheJob(
    entry_id="chaos", jitted=jax.jit(lambda x: x + 1.0),
    abstract_args=(jax.ShapeDtypeStruct((4,), jnp.float32),)))
raise SystemExit("kill fault did not fire")
"""

_CACHE_CORRUPT = """
import numpy as np, jax, jax.numpy as jnp
from mlops_tpu import faults
from mlops_tpu.compilecache.cache import CacheJob, CompileCache
job = CacheJob(entry_id="chaos", jitted=jax.jit(lambda x: x * 3.0),
               abstract_args=(jax.ShapeDtypeStruct((4,), jnp.float32),))
CompileCache({cache!r}).load_or_compile(job)  # persist a good artifact
faults.arm(faults.FaultPlan.from_rules(
    [{"point": "compilecache.read", "mode": "corrupt", "flip_bits": 8}]))
cache = CompileCache({cache!r})
fn = cache.load_or_compile(job)  # corrupt read -> discard -> recompile
faults.disarm()
stats = cache.stats()
assert stats["discards"] == 1 and stats["misses"] == 1, stats
np.testing.assert_allclose(
    np.asarray(fn(jnp.arange(4, dtype=jnp.float32))),
    np.arange(4, dtype=np.float32) * 3.0)
healed = CompileCache({cache!r})
healed.load_or_compile(job)
assert healed.stats()["hits"] == 1, healed.stats()  # store self-healed
print("CORRUPTION-HANDLED")
"""


# --------------------------------------------------- mid-regrid kill -9
# Phase 1: a hot regrid (ISSUE 18 gridtuner) is SIGKILLed between its
# warm phase and its swap — the most in-flight state a regrid ever
# holds. A serving hammer runs throughout, so the kill lands on a plane
# that is actively dispatching.
_REGRID_KILL = """
import threading, time
from mlops_tpu import faults
from mlops_tpu.autotune import apply_plan
from mlops_tpu.bundle import load_bundle
from mlops_tpu.compilecache.cache import CompileCache
from mlops_tpu.serve.engine import InferenceEngine

engine = InferenceEngine(
    load_bundle({bundle!r}), buckets=(1, 8),
    compile_cache=CompileCache({cache!r}), enable_grouping=False)
engine.warmup()
record = [{record!r}]
ref = engine.predict_records(record)["predictions"]
stop = threading.Event()
def hammer():
    while not stop.is_set():
        assert engine.predict_records(record)["predictions"] == ref
        time.sleep(0.005)
t = threading.Thread(target=hammer, daemon=True); t.start()
time.sleep(0.1)
faults.arm(faults.FaultPlan.from_rules(
    [{"point": "autotune.regrid.midswap", "mode": "kill"}]))
apply_plan(engine, (1, 2, 8))
raise SystemExit("kill fault did not fire")
"""

# Phase 2: a fresh process over the SAME bundle + compile cache must
# serve bit-identically (the crash left nothing durable mid-mutation),
# complete the interrupted regrid cleanly, keep responses bit-stable
# across the swap, and roll back in one call.
_REGRID_RECOVER = """
from mlops_tpu.autotune import apply_plan
from mlops_tpu.bundle import load_bundle
from mlops_tpu.compilecache.cache import CompileCache
from mlops_tpu.serve.engine import InferenceEngine

engine = InferenceEngine(
    load_bundle({bundle!r}), buckets=(1, 8),
    compile_cache=CompileCache({cache!r}), enable_grouping=False)
engine.warmup()
record = [{record!r}]
before = engine.predict_records(record)
gen0 = engine.grid_generation
gen = apply_plan(engine, (1, 2, 8))  # the crashed regrid, re-run clean
assert gen == gen0 + 1 and tuple(engine.buckets) == (1, 2, 8)
assert engine.predict_records(record) == before, "regrid changed bytes"
engine.rollback()
assert tuple(engine.buckets) == (1, 8)
assert engine.predict_records(record) == before, "rollback changed bytes"
print("REGRID-RECOVERED")
"""


def regrid_kill_scenario(tmp: str, bundle: str) -> None:
    cache_dir = os.path.join(tmp, "regrid-cache")
    script = (
        _REGRID_KILL
        .replace("{bundle!r}", repr(bundle))
        .replace("{cache!r}", repr(cache_dir))
        .replace("{record!r}", repr(RECORD))
    )
    run_subprocess_scenario("mid-regrid kill -9", script, expect_kill=True)
    recover = run_subprocess_scenario(
        "post-crash regrid recovery",
        _REGRID_RECOVER
        .replace("{bundle!r}", repr(bundle))
        .replace("{cache!r}", repr(cache_dir))
        .replace("{record!r}", repr(RECORD)),
    )
    assert "REGRID-RECOVERED" in recover.stdout


def midwrite_and_corruption_scenarios(tmp: str) -> None:
    state = os.path.join(tmp, "reservoir-state")
    run_subprocess_scenario(
        "reservoir mid-write kill",
        _RESERVOIR_KILL.replace("{state!r}", repr(state)),
        expect_kill=True,
    )
    assert not os.path.exists(os.path.join(state, "reservoir.npz")), (
        "torn reservoir snapshot landed at the target path"
    )

    target = os.path.join(tmp, "ckpt.bin")
    run_subprocess_scenario(
        "atomic_write mid-write kill",
        _ATOMIC_KILL.replace("{target!r}", repr(target)),
        expect_kill=True,
    )
    with open(target, "rb") as f:
        assert f.read() == b"GOOD" * 1024, "torn atomic_write payload"

    cache_dir = os.path.join(tmp, "chaos-cache")
    proc = subprocess.run(
        [sys.executable, "-c",
         _CACHE_KILL.replace("{cache!r}", repr(cache_dir))],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert proc.returncode == -signal.SIGKILL, (
        proc.returncode, proc.stderr[-1000:]
    )
    leftovers = [
        os.path.join(dirpath, f)
        for dirpath, _, files in os.walk(cache_dir)
        for f in files if f.endswith(".jaxexe")
    ]
    assert leftovers == [], f"torn cache artifact landed: {leftovers}"
    print("# chaos-smoke: scenario OK — cache persist mid-write kill",
          flush=True)

    corrupt = run_subprocess_scenario(
        "cache corruption on read",
        _CACHE_CORRUPT.replace("{cache!r}", repr(cache_dir)),
    )
    assert "CORRUPTION-HANDLED" in corrupt.stdout


# ------------------------------------------------------ live-plane chaos
def live_plane_scenarios(tmp: str, bundle: str) -> None:
    plan_path = os.path.join(tmp, "chaos.toml")
    with open(plan_path, "w") as f:
        f.write(CHAOS_PLAN)
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["MLOPS_TPU_FAULTS"] = plan_path

    port = free_port()
    server = subprocess.Popen(
        [
            sys.executable, "-m", "mlops_tpu", "serve", "--workers", "2",
            "serve.host=127.0.0.1", f"serve.port={port}",
            f"serve.model_directory={bundle}",
            "serve.warmup_batch_sizes=1,8", "serve.max_batch=8",
            # Tiny admission so the overload burst actually sheds, and the
            # chaos-tuned drain knobs (the ex-hard-coded 30/35/50) so the
            # drain assertion exercises their wiring.
            "serve.ring_slots_small=4", "serve.ring_slots_large=1",
            "serve.request_timeout_s=6",
            # Tier routing + brownout (ISSUE 19), DRILL-TUNED: a demote
            # depth of 0.2 on the 5-slot per-worker partition means ONE
            # busy slot activates the governor, so the brownout scenario
            # below can prove demotions precede the first shed without
            # needing a seeded stall. (The tiny bundle has no gated
            # quant tier: the ladder collapses to the default program —
            # demotion counters must rise anyway, bits must not change.)
            "serve.tier_routing=true",
            "serve.brownout_demote_depth=0.2",
            "serve.brownout_restore_depth=0.1",
            "serve.drain_deadline_s=8", "serve.zygote_join_deadline_s=10",
            "serve.engine_zygote_join_s=16",
            # AOT cache: the first boot compiles + persists; the engine
            # RESPAWN in the kill scenario warm-starts by deserializing,
            # which is what keeps the brownout window tight.
            f"cache.dir={os.path.join(tmp, 'chaos-serve-cache')}",
            # sloscope (ISSUE 14), DRILL-TUNED: seconds-scale burn
            # windows, a 0.5 s tick, and a burn threshold of 1.0 so the
            # stall scenario's seeded 504s provably cross it — the
            # acceptance is alert_active flipping within two ticks and
            # a flight-recorder dump whose timeline carries the
            # offending spans (tracewire armed for exactly that).
            "slo.enabled=true", "slo.tick_s=0.5",
            "slo.fast_burn_threshold=1.0", "slo.slow_burn_threshold=1.0",
            "slo.fast_short_s=10", "slo.fast_long_s=30",
            "slo.slow_short_s=45", "slo.slow_long_s=90",
            "slo.flightrec_cooldown_s=2",
            f"slo.flightrec_dir={os.path.join(tmp, 'flightrec')}",
            "trace.enabled=true",
            f"trace.dir={os.path.join(tmp, 'chaos-traces')}",
        ],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    log_lines: list[str] = []
    pump = threading.Thread(
        target=lambda: log_lines.extend(iter(server.stdout.readline, "")),
        daemon=True,
    )
    pump.start()
    statuses: list[int] = []
    statuses_lock = threading.Lock()
    body = json.dumps([RECORD]).encode()

    def record_status(status: int) -> None:
        with statuses_lock:
            statuses.append(status)

    try:
        print("# chaos-smoke: waiting for readiness (faults armed)",
              flush=True)
        deadline = time.time() + 600
        ready = False
        while time.time() < deadline and not ready:
            if server.poll() is not None:
                print("\n".join(log_lines[-60:]))
                raise SystemExit("server died before readiness")
            try:
                status, _ = get(f"http://127.0.0.1:{port}/healthz/ready", 5)
                ready = status == 200
            except (urllib.error.URLError, OSError, urllib.error.HTTPError):
                pass
            if not ready:
                time.sleep(1.0)
        assert ready, "server never became ready under the armed plan"
        assert any("fault injection ARMED" in line for line in log_lines), (
            "the env plan did not arm in the serve processes"
        )

        # ---- scenario: engine stall -> deadline 504s, no hangs --------
        def budgeted_client(n: int) -> None:
            for _ in range(n):
                status, _, _ = raw_predict(
                    port, body, headers={"x-request-deadline-ms": "400"},
                )
                record_status(status)

        threads = [
            threading.Thread(target=budgeted_client, args=(20,))
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not any(t.is_alive() for t in threads), "stalled client hung"
        with statuses_lock:
            got_504 = statuses.count(504)
        assert got_504 >= 1, (
            f"seeded stalls produced no 504 in {len(statuses)} requests"
        )
        print(f"# chaos-smoke: engine stall OK ({got_504} deadline 504s "
              f"in {len(statuses)} budgeted requests)", flush=True)

        # ---- scenario: the 504 storm burns the error budget ----------
        # (ISSUE 14 acceptance) The stall's 504s must flip
        # mlops_tpu_alert_active within two evaluation ticks
        # (tick_s=0.5 -> allow 2 ticks + one watchdog pass of margin for
        # the scrape itself), and a front end watching the shm alert
        # flags must drop a flight-recorder dump whose timeline carries
        # the offending 504 evidence (spans included — tracewire armed).
        alert_deadline = time.time() + 10.0
        burn_alert_on = False
        while time.time() < alert_deadline and not burn_alert_on:
            status, text = get(f"http://127.0.0.1:{port}/metrics", 15)
            assert status == 200
            burn_alert_on = any(
                line.startswith(
                    'mlops_tpu_alert_active{alert="availability_fast_burn"'
                ) and line.endswith(" 1")
                for line in text.decode().splitlines()
            )
            if not burn_alert_on:
                time.sleep(0.5)
        assert burn_alert_on, (
            "availability_fast_burn never flipped after the 504 storm"
        )
        status, text = get(f"http://127.0.0.1:{port}/healthz", 15)
        verdict = json.loads(text)
        assert status == 200 and verdict["verdict"] == "degraded", verdict
        dump_deadline = time.time() + 15.0
        flightrec_dir = os.path.join(tmp, "flightrec")
        offending = None
        while time.time() < dump_deadline and offending is None:
            names = (
                sorted(os.listdir(flightrec_dir))
                if os.path.isdir(flightrec_dir) else []
            )
            for name in names:
                path = os.path.join(flightrec_dir, name)
                try:
                    dump = json.loads(open(path).read())
                except (OSError, ValueError):
                    continue  # a dump mid-rename; the next pass reads it
                has_504 = any(
                    e.get("status") == 504
                    for e in dump.get("events", [])
                    if e.get("kind") in ("request", "span")
                )
                has_span = any(
                    e.get("kind") == "span" and e.get("status") == 504
                    for e in dump.get("events", [])
                )
                if has_504 and has_span:
                    offending = path
                    break
            if offending is None:
                time.sleep(0.5)
        assert offending is not None, (
            "no flight-recorder dump carrying the offending 504 spans"
        )
        # The CLI renders it (timeline includes the 504 evidence).
        render = subprocess.run(
            [sys.executable, "-m", "mlops_tpu", "flightrec", offending],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
        )
        assert render.returncode == 0, render.stderr[-1000:]
        assert "504" in render.stderr
        print(f"# chaos-smoke: burn alert + flight dump OK ({offending})",
              flush=True)

        # ---- wire-contract probes ------------------------------------
        status, _, _ = raw_predict(port, json.dumps([RECORD] * 9).encode())
        record_status(status)
        assert status == 413, status
        status, _, _ = raw_predict(port, json.dumps([{"age": "x"}]).encode())
        record_status(status)
        assert status == 422, status

        # ---- scenario: slow client does not wedge the plane ----------
        slow_done: dict = {}

        def slow_client() -> None:
            payload = (
                f"POST /predict HTTP/1.1\r\nhost: slow\r\n"
                f"content-type: application/json\r\n"
                f"content-length: {len(body)}\r\n"
                f"connection: close\r\n\r\n"
            ).encode() + body
            with socket.create_connection(
                ("127.0.0.1", port), timeout=30
            ) as s:
                s.settimeout(30)
                for i in range(0, len(payload), 40):
                    s.sendall(payload[i : i + 40])
                    time.sleep(0.05)
                data = b""
                while True:
                    chunk = s.recv(65536)
                    if not chunk:
                        break
                    data += chunk
            slow_done["status"] = int(data.split(b" ")[1])

        dribbler = threading.Thread(target=slow_client)
        dribbler.start()
        fast_during_slow = []
        for _ in range(6):
            status, _, _ = raw_predict(port, body)
            record_status(status)
            fast_during_slow.append(status)
        dribbler.join(timeout=60)
        assert not dribbler.is_alive(), "slow client hung the smoke"
        record_status(slow_done["status"])
        assert slow_done["status"] in ALLOWED_STATUSES
        assert any(s == 200 for s in fast_during_slow), (
            "no fast request served while the slow client dribbled"
        )
        print("# chaos-smoke: slow client OK (plane served "
              f"{fast_during_slow.count(200)}/6 during the dribble)",
              flush=True)

        # ---- metrics scrape #1 (monotonicity baseline) ---------------
        status, text = get(f"http://127.0.0.1:{port}/metrics", 30)
        assert status == 200
        first = parse_counters(text.decode())
        assert any("mlops_tpu_deadline_expired_total" in k for k in first)
        assert any("mlops_tpu_degraded_dispatch_total" in k for k in first)

        # ---- scenario: brownout demotes BEFORE the overload shed -----
        # (ISSUE 19) Phase 1 offers sustained concurrency UNDER the
        # per-worker partition (4 loops vs 5 slots — a shed is
        # impossible by construction): the armed governor's demotion
        # counters must rise while every shed counter stays flat.
        # Phase 2 is the 10x-partition overload burst: 503s become
        # legal, statuses stay inside the contract set, and the
        # demotion counters from phase 1 prove the plane spent fidelity
        # before it ever spent availability.
        def counter_sum(counters: dict, prefix: str) -> float:
            return sum(
                v for k, v in counters.items() if k.startswith(prefix)
            )

        def shed_sum(counters: dict) -> float:
            return counter_sum(
                counters, "mlops_tpu_shed_total"
            ) + counter_sum(counters, "mlops_tpu_tenant_quota_shed_total")

        status, text = get(f"http://127.0.0.1:{port}/metrics", 30)
        assert status == 200
        base = parse_counters(text.decode())
        base_demote = counter_sum(base, "mlops_tpu_tier_demotions_total")
        base_shed = shed_sum(base)

        def brownout_client() -> None:
            for _ in range(30):
                status, _, _ = raw_predict(port, body, timeout=30)
                record_status(status)

        browners = [
            threading.Thread(target=brownout_client) for _ in range(4)
        ]
        for t in browners:
            t.start()
        for t in browners:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in browners), (
            "brownout client hung"
        )
        status, text = get(f"http://127.0.0.1:{port}/metrics", 30)
        assert status == 200
        mid = parse_counters(text.decode())
        mid_demote = counter_sum(mid, "mlops_tpu_tier_demotions_total")
        assert mid_demote > base_demote, (
            "governor never demoted under sub-partition pressure "
            f"(demote counter {base_demote} -> {mid_demote})"
        )
        assert shed_sum(mid) == base_shed, (
            "a shed fired while offered load was under the partition — "
            "brownout must come first"
        )
        print(
            "# chaos-smoke: brownout phase OK "
            f"(+{mid_demote - base_demote:.0f} demotions, zero sheds)",
            flush=True,
        )

        def burst_client() -> None:
            try:
                status, _, _ = raw_predict(port, body, timeout=30)
                record_status(status)
            except OSError:
                pass  # connection refused under burst = backpressure, fine

        burst = [threading.Thread(target=burst_client) for _ in range(50)]
        for t in burst:
            t.start()
        for t in burst:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in burst), "burst client hung"
        print("# chaos-smoke: overload burst OK", flush=True)

        # ---- scenario: worker kill -> supervisor respawn -------------
        spawn_line = next(line for line in log_lines if "spawned" in line)
        pids = [
            int(p) for p in
            re.findall(r"\d+", spawn_line.split("(pids", 1)[1])
        ]
        os.kill(pids[0], signal.SIGKILL)
        deadline = time.time() + 30
        while time.time() < deadline and not any(
            "respawning" in line for line in log_lines
        ):
            time.sleep(0.2)
        assert any("respawning" in line for line in log_lines), (
            "supervisor never respawned the SIGKILLed front end"
        )
        deadline = time.time() + 30
        served = False
        while time.time() < deadline and not served:
            try:
                status, _, _ = raw_predict(port, body)
                record_status(status)
                served = status == 200
            except OSError:
                time.sleep(0.2)
        assert served, "plane stopped serving after the worker kill"
        print("# chaos-smoke: worker kill OK (respawned, still serving)",
              flush=True)

        # ---- scenario: ENGINE kill -> respawn + replay (ISSUE 11) ----
        # Budgeted hammer traffic across a SIGKILL of the engine process:
        # requests in flight at kill time park and are replayed by the
        # respawned incarnation; 504 is legal ONLY on true budget expiry
        # (budget = the 5 s header here, tighter than request_timeout_s);
        # every 200 body must be identical to the pre-kill body (replay
        # bit-identity: same AOT artifacts, same slab input, pure packed
        # predict).
        engine_line = next(line for line in log_lines if "engine pid" in line)
        engine_pid = int(re.search(r"engine pid (\d+)", engine_line).group(1))
        status, _, ref_body = raw_predict(port, body)
        assert status == 200, "no reference response before the engine kill"
        kill_results: list[tuple[int, float, bytes]] = []
        kill_lock = threading.Lock()
        hammer_stop = threading.Event()

        def kill_hammer() -> None:
            while not hammer_stop.is_set():
                t0 = time.perf_counter()
                try:
                    s_, _, b_ = raw_predict(
                        port, body,
                        headers={"x-request-deadline-ms": "5000"},
                        timeout=30,
                    )
                except OSError:
                    continue  # accept-queue churn during the brownout
                with kill_lock:
                    kill_results.append(
                        (s_, time.perf_counter() - t0, b_)
                    )

        hammers = [threading.Thread(target=kill_hammer) for _ in range(3)]
        for t in hammers:
            t.start()
        time.sleep(1.0)  # traffic flowing; some requests in flight
        os.kill(engine_pid, signal.SIGKILL)
        deadline = time.time() + 60
        while time.time() < deadline and not any(
            "engine replica" in line and "respawning" in line
            for line in log_lines
        ):
            time.sleep(0.2)
        assert any(
            "engine replica" in line and "respawning" in line
            for line in log_lines
        ), "supervisor never respawned the SIGKILLed engine"
        # Keep hammering until the respawned engine serves again.
        deadline = time.time() + 180
        recovered = False
        while time.time() < deadline and not recovered:
            with kill_lock:
                n_before = len(kill_results)
            time.sleep(0.5)
            with kill_lock:
                recovered = any(
                    s_ == 200 for s_, _, _ in kill_results[n_before:]
                )
        hammer_stop.set()
        for t in hammers:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in hammers), "kill hammer hung"
        assert recovered, "plane never recovered after the engine kill"
        with kill_lock:
            kill_statuses = [s_ for s_, _, _ in kill_results]
            for s_, elapsed, _ in kill_results:
                record_status(s_)
                assert s_ in {200, 503, 504}, (
                    f"status {s_} during the engine-kill window"
                )
                if s_ == 504:
                    assert elapsed <= 6.5, (
                        f"504 took {elapsed:.2f}s — outside its 5 s budget"
                    )
            for s_, _, b_ in kill_results:
                if s_ == 200:
                    assert b_ == ref_body, (
                        "a 200 body across the respawn differs from the "
                        "pre-kill reference (replay bit-identity broken)"
                    )
        tally_kill = {
            s_: kill_statuses.count(s_) for s_ in sorted(set(kill_statuses))
        }
        print(
            "# chaos-smoke: engine kill OK (respawned + replayed; "
            f"window tally {tally_kill})", flush=True,
        )

        # ---- metrics scrape #2: counters are monotone ----------------
        status, text = get(f"http://127.0.0.1:{port}/metrics", 30)
        assert status == 200
        second = parse_counters(text.decode())
        regressions = {
            k: (first[k], second[k])
            for k in first
            if k in second and second[k] < first[k]
        }
        assert not regressions, f"non-monotone counters: {regressions}"
        assert second.get("mlops_tpu_engine_respawn_total", 0) >= 1, (
            "engine_respawn_total missing or zero after the engine kill"
        )

        # ---- the global status contract ------------------------------
        with statuses_lock:
            illegal = sorted({s for s in statuses if s not in ALLOWED_STATUSES})
            tally = {s: statuses.count(s) for s in sorted(set(statuses))}
        assert not illegal, f"statuses outside the contract set: {illegal}"
        print(f"# chaos-smoke: status tally {tally}", flush=True)

        # ---- clean drain under the chaos-tuned knobs -----------------
        server.send_signal(signal.SIGTERM)
        rc = server.wait(timeout=60)
        pump.join(timeout=10)
        log = "\n".join(log_lines)
        assert rc == 0, f"server exited {rc}\n{log[-2000:]}"
        assert "drained" in log, log[-2000:]
        assert "Task was destroyed" not in log, log[-2000:]
        print("# chaos-smoke: drain OK (exit 0 under chaos drain knobs)",
              flush=True)
    finally:
        if server.poll() is None:
            server.kill()
            server.wait(timeout=10)


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="chaos-smoke-")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")

    print("# chaos-smoke: mid-write kill + corruption scenarios", flush=True)
    midwrite_and_corruption_scenarios(tmp)

    print("# chaos-smoke: training tiny bundle", flush=True)
    train = subprocess.run(
        [
            sys.executable, "-m", "mlops_tpu", "train",
            "data.rows=3000",
            "model.hidden_dims=32,32", "model.embed_dim=4",
            "train.steps=100", "train.eval_every=100",
            "train.batch_size=256",
            f"registry.root={tmp}/registry", f"registry.run_root={tmp}/runs",
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    if train.returncode != 0:
        print(train.stdout[-2000:], train.stderr[-2000:], sep="\n")
        raise SystemExit("train failed")
    bundle = json.loads(train.stdout.strip().splitlines()[-1])["bundle"]
    print(f"# chaos-smoke: bundle at {bundle}", flush=True)

    print("# chaos-smoke: mid-regrid kill scenario", flush=True)
    regrid_kill_scenario(tmp, bundle)

    live_plane_scenarios(tmp, bundle)
    print("# chaos-smoke: OK (all seeded scenarios green)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

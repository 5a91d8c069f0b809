"""faultline: deterministic, config-driven fault injection.

Production serving code is full of recovery paths — compile-cache
corruption discards, deadline 504s, degraded bucket fallback, tmp+rename
persistence, circuit breakers — that ordinary traffic never exercises.
This module makes every one of them drivable ON DEMAND: code declares
NAMED INJECTION POINTS (``faults.fire("serve.engine.dispatch")``,
``raw = faults.corrupt("compilecache.read", raw)``) and a seeded
``FaultPlan`` decides, deterministically, which hits of which points do
what:

- ``raise``   — raise a named exception (device error, OSError, ...)
- ``delay``   — sleep ``delay_s`` (an engine stall / slow device)
- ``kill``    — SIGKILL this process mid-operation (torn-write proofs)
- ``corrupt`` — flip seeded bits in the bytes passing a read point

Determinism is the contract: a plan is (rules, seed), every point keeps
a per-process hit counter, and each decision hashes
``(seed, rule, point, hit_index)`` — so the same seed + scenario +
request order produces the IDENTICAL injection trace (recorded, and
pinned by tests/test_faults.py). No global RNG is touched.

Arming:

- programmatic: ``faults.arm(FaultPlan.from_rules([...], seed=...))``
  (tests), ``faults.disarm()`` to restore the no-op state;
- config: ``faults.arm(load_plan("chaos.toml"))``;
- environment: ``MLOPS_TPU_FAULTS=/path/to/chaos.toml`` arms at import
  time in EVERY process that imports this module — the chaos smoke
  arms a whole forked serve plane (engine + front ends) with one env
  var, no code changes.

Zero overhead disarmed: the module-level plan is ``None`` and both
entry points return after one global load + identity check. The module
imports no jax and starts no threads.

TOML plan format (``[[fault]]`` tables, see docs/operations.md):

    seed = 42                      # optional top-level plan seed
    [[fault]]
    point = "serve.engine.dispatch"   # exact name or fnmatch glob
    mode = "delay"                    # raise | delay | kill | corrupt
    delay_s = 1.5
    probability = 0.05                # seeded per-hit Bernoulli
    after = 10                        # skip the first N hits
    max_fires = 3                     # then go quiet (omit = forever)
    exc = "FaultInjected"             # raise mode: exception class
    flip_bits = 4                     # corrupt mode: bits flipped
"""

from __future__ import annotations

import dataclasses
import fnmatch
import logging
import os
import signal
import threading
import time
from hashlib import blake2b
from pathlib import Path
from typing import Any

try:
    import tomllib
except ModuleNotFoundError:  # Python < 3.11 (config.py's fallback)
    import tomli as tomllib  # type: ignore[no-redef]

logger = logging.getLogger("mlops_tpu.faults")

# tpulint Layer-3 manifest: one leaf lock guarding the hit counters and
# the trace list; decisions and actions (sleep, raise, kill) all happen
# OUTSIDE it (TPU403 discipline) — the lock covers dict/list updates only.
TPULINT_LOCK_ORDER = {"FaultPlan": ("_lock",)}

FAULT_MODES = ("raise", "delay", "kill", "corrupt")

ENV_VAR = "MLOPS_TPU_FAULTS"


class FaultInjected(RuntimeError):
    """The default exception a ``raise``-mode rule throws — named so
    tests and log greps can tell an injected failure from a real one."""


# raise-mode exception classes a plan may name. A closed set: the plan is
# config/env-controlled, so arbitrary class resolution would be an
# import-from-string gadget.
_RAISABLE: dict[str, type[BaseException]] = {
    "FaultInjected": FaultInjected,
    "RuntimeError": RuntimeError,
    "OSError": OSError,
    "IOError": OSError,
    "ValueError": ValueError,
    "TimeoutError": TimeoutError,
    "MemoryError": MemoryError,
}


@dataclasses.dataclass(frozen=True)
class FaultRule:
    """One scheduled fault: WHERE (point pattern), WHAT (mode), WHEN
    (after / max_fires / probability — all evaluated against the seeded
    per-point hit counter, never a wall clock or global RNG)."""

    point: str  # injection-point name or fnmatch glob
    mode: str  # raise | delay | kill | corrupt
    probability: float = 1.0  # seeded per-hit Bernoulli
    after: int = 0  # skip the first `after` matching hits
    max_fires: int | None = None  # stop after this many fires
    delay_s: float = 0.0  # delay mode
    exc: str = "FaultInjected"  # raise mode
    message: str = ""  # raise mode: exception text override
    flip_bits: int = 1  # corrupt mode: bit flips per payload
    seed: int = 0  # folded into every decision hash

    def __post_init__(self) -> None:
        if self.mode not in FAULT_MODES:
            raise ValueError(
                f"fault mode {self.mode!r} not in {FAULT_MODES}"
            )
        if not 0.0 <= self.probability <= 1.0:
            raise ValueError(
                f"fault probability {self.probability} outside [0, 1]"
            )
        if self.mode == "raise" and self.exc not in _RAISABLE:
            raise ValueError(
                f"fault exc {self.exc!r} not in {sorted(_RAISABLE)}"
            )
        if self.after < 0:
            raise ValueError(f"fault after={self.after} must be >= 0")
        if self.max_fires is not None and self.max_fires < 1:
            raise ValueError(
                f"fault max_fires={self.max_fires} must be >= 1"
            )
        if self.delay_s < 0:
            raise ValueError(f"fault delay_s={self.delay_s} must be >= 0")
        if self.flip_bits < 1:
            raise ValueError(
                f"fault flip_bits={self.flip_bits} must be >= 1"
            )

    def matches(self, point: str) -> bool:
        return self.point == point or fnmatch.fnmatchcase(point, self.point)


def _decision_hash(seed: int, rule_point: str, point: str, hit: int) -> int:
    """Stable 64-bit decision value for one (rule, point, hit) — the
    whole schedule derives from these, so identical plans replay
    identical traces on any host/process."""
    digest = blake2b(
        f"{seed}:{rule_point}:{point}:{hit}".encode(), digest_size=8
    ).digest()
    return int.from_bytes(digest, "little")


class FaultPlan:
    """A set of rules plus the per-point hit counters and the recorded
    injection trace. Thread-safe: counter/trace updates sit under one
    leaf lock; the ACTIONS (sleep, raise, kill, corruption arithmetic)
    run outside it."""

    def __init__(self, rules: list[FaultRule], seed: int = 0):
        self.rules = list(rules)
        self.seed = int(seed)
        self._lock = threading.Lock()
        self._hits: dict[tuple[str, str], int] = {}  # (rule.point, point)
        self._fires: dict[tuple[str, str], int] = {}
        self._trace: list[tuple[str, int, str, str]] = []

    # ------------------------------------------------------- construction
    @classmethod
    def from_rules(
        cls, rules: list[dict[str, Any] | FaultRule], seed: int = 0
    ) -> "FaultPlan":
        built = [
            r if isinstance(r, FaultRule) else FaultRule(**r) for r in rules
        ]
        return cls(built, seed=seed)

    @classmethod
    def from_toml(cls, path: str | Path) -> "FaultPlan":
        with open(path, "rb") as f:
            doc = tomllib.load(f)
        seed = int(doc.get("seed", 0))
        rules = []
        for table in doc.get("fault", []):
            fields = dict(table)
            fields.setdefault("seed", seed)
            rules.append(FaultRule(**fields))
        return cls(rules, seed=seed)

    # ------------------------------------------------------------ decide
    def _decide(self, point: str, modes: frozenset[str]) -> FaultRule | None:
        """Counter bookkeeping under the lock; returns the rule to apply
        (already counted as fired) or None.

        ``modes`` restricts which rule kinds this call site can act on
        (fire() cannot flip bits, corrupt() cannot raise/kill): rules of
        other modes are SKIPPED WITHOUT counting — a corrupt-point rule
        misconfigured as ``raise`` must not burn its max_fires budget or
        fabricate trace entries for faults that never happened.

        EVERY matching rule's hit counter advances on every hit — rules
        schedule independently, so a declined first rule (after /
        max_fires / probability) never shadows a second rule on the same
        point ("stall N times, then kill" plans compose). The first rule
        that fires wins the action; later rules still count the hit so
        their schedules stay deterministic regardless of which fired."""
        with self._lock:
            chosen: FaultRule | None = None
            for rule in self.rules:
                if rule.mode not in modes or not rule.matches(point):
                    continue
                key = (rule.point, point)
                hit = self._hits.get(key, 0)
                self._hits[key] = hit + 1
                if chosen is not None:
                    continue
                if hit < rule.after:
                    continue
                fired = self._fires.get(key, 0)
                if rule.max_fires is not None and fired >= rule.max_fires:
                    continue
                if rule.probability < 1.0:
                    draw = _decision_hash(
                        rule.seed, rule.point, point, hit
                    ) / float(1 << 64)
                    if draw >= rule.probability:
                        continue
                self._fires[key] = fired + 1
                self._trace.append((point, hit, rule.point, rule.mode))
                chosen = rule
            return chosen

    # ------------------------------------------------------------ actions
    _FIRE_MODES = frozenset({"raise", "delay", "kill"})
    _CORRUPT_MODES = frozenset({"corrupt"})

    def fire(self, point: str) -> None:
        rule = self._decide(point, self._FIRE_MODES)
        if rule is None:
            return
        if rule.mode == "delay":
            logger.warning(
                "fault injected: delay %.3fs at %s", rule.delay_s, point
            )
            time.sleep(rule.delay_s)
        elif rule.mode == "raise":
            logger.warning(
                "fault injected: raise %s at %s", rule.exc, point
            )
            raise _RAISABLE[rule.exc](
                rule.message or f"injected fault at {point}"
            )
        else:  # kill — the only remaining _FIRE_MODES member
            logger.warning("fault injected: SIGKILL at %s", point)
            os.kill(os.getpid(), signal.SIGKILL)

    def corrupt(self, point: str, data: bytes) -> bytes:
        if not data:
            return data
        rule = self._decide(point, self._CORRUPT_MODES)
        if rule is None:
            return data
        flipped = bytearray(data)
        n = len(flipped)
        for i in range(rule.flip_bits):
            h = _decision_hash(rule.seed, rule.point, f"{point}#bit", i)
            flipped[h % n] ^= 1 << ((h >> 32) % 8)
        logger.warning(
            "fault injected: %d bit flip(s) in %d bytes at %s",
            rule.flip_bits, n, point,
        )
        return bytes(flipped)

    # -------------------------------------------------------------- trace
    def trace(self) -> list[tuple[str, int, str, str]]:
        """(point, hit_index, rule_point, mode) per injected fault, in
        injection order — the determinism pin."""
        with self._lock:
            return list(self._trace)

    def fires(self) -> int:
        with self._lock:
            return len(self._trace)


# ------------------------------------------------------- module-level arm
# The ONE global the hot paths read: None = disarmed (the product state),
# a FaultPlan = armed. `fire`/`corrupt` below are the only call surface —
# one global load + identity check when disarmed.
_plan: FaultPlan | None = None


def arm(plan: FaultPlan) -> FaultPlan:
    global _plan
    _plan = plan
    logger.warning(
        "fault injection ARMED: %d rule(s), seed %d",
        len(plan.rules), plan.seed,
    )
    return plan


def disarm() -> None:
    global _plan
    _plan = None


def armed() -> bool:
    return _plan is not None


def active_plan() -> FaultPlan | None:
    return _plan


def fire(point: str) -> None:
    """Injection point for raise/delay/kill faults. No-op unless armed."""
    plan = _plan
    if plan is None:
        return
    plan.fire(point)


def corrupt(point: str, data: bytes) -> bytes:
    """Injection point for bit-corrupt-on-read faults: returns ``data``
    unchanged unless an armed corrupt rule matches."""
    plan = _plan
    if plan is None:
        return data
    return plan.corrupt(point, data)


def load_plan(path: str | Path) -> FaultPlan:
    return FaultPlan.from_toml(path)


def _arm_from_env() -> None:
    """Import-time env arming (`MLOPS_TPU_FAULTS=<toml>`): how the chaos
    smoke arms every process of a forked serve plane with one variable.
    A broken plan file fails LOUDLY — a chaos run that silently tests
    nothing is worse than one that refuses to start."""
    path = os.environ.get(ENV_VAR, "")
    if path:
        arm(FaultPlan.from_toml(path))


_arm_from_env()

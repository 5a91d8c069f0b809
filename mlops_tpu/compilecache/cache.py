"""Persistent AOT executable cache.

The readiness gate of every process — serve warmup, train windows, bulk
sweeps — otherwise pays XLA compilation from scratch. Compile time is pure
goodput loss (*ML Productivity Goodput*, arxiv 2502.06982), and prediction
serving is exactly the workload where ahead-of-time compiled artifacts pay
off (*A Tensor Compiler for Unified ML Prediction Serving*, arxiv
2010.04804). This module makes the compiled program a first-class,
persistent, integrity-checked artifact:

    lowered  = jitted.lower(*abstract_args)      # trace, no devices touched
    compiled = lowered.compile()                 # XLA compile (releases GIL)
    payload  = serialize_executable.serialize(compiled)   # bytes on disk

keyed by `keys.cache_key` (jax/jaxlib versions, backend + device kind, mesh
shape, donation flags, entry id, abstract signature, config hash). Reads
verify a sha256 checksum and discard-and-recompile on ANY failure; writes
are atomic tmp+rename (the same discipline as `data/stream.py` outputs), so
a crashed process can never leave a half-written artifact that a later one
trusts.

An executable is compiled for a fixed device assignment, and
``deserialize_and_load`` must be told which devices those are (its default
is every device of the backend, which loads a single-device program that
then cannot execute on a multi-device host). The artifact header records
the device ids the program was compiled for; a hit is loaded onto exactly
those. An artifact that fails an integrity check is discarded and
recompiled, and the cause is logged; one that loads and then cannot RUN
(``CacheJob.execute_args``) is an error — logged with its cause, counted
``unrunnable``, removed and recompiled — never a silent discard.

Artifacts are trusted local state (same trust level as JAX's own persistent
compilation cache): the checksum guards corruption and truncation, not
adversarial payloads — do not point ``cache.dir`` at an untrusted store.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import pickle
import threading
import time
from hashlib import sha256
from pathlib import Path
from typing import Any, Callable

from mlops_tpu import faults
from mlops_tpu.compilecache import keys
from mlops_tpu.utils.timing import StageClock

_HEADER_MAGIC = "mlops-tpu-exe"

logger = logging.getLogger(__name__)

# tpulint Layer-3 manifest: one stats mutex, declared so the analyzer (and
# the runtime sanitizer) flag any future nesting under it. Compiles,
# deserializes, and disk I/O all happen OUTSIDE `_lock` by design — it
# guards only the counters/program-stats dicts (see _record/stats).
TPULINT_LOCK_ORDER = {"CompileCache": ("_lock",)}


@dataclasses.dataclass
class CacheJob:
    """One program to warm: a jitted callable plus the abstract call
    signature to lower it at, and the key components the signature cannot
    express. ``execute_args`` (concrete) optionally runs the program once
    after load — the engine uses it to pay first-dispatch allocation at
    warmup and to fail loudly on an executable that loads but cannot run."""

    entry_id: str
    jitted: Callable
    abstract_args: tuple
    config_hash: str = ""
    mesh_shape: tuple[int, ...] | None = None
    donated: bool = False
    label: str = ""
    meta: dict = dataclasses.field(default_factory=dict)
    execute_args: tuple | None = None


def execute_once(job: CacheJob, fn: Callable) -> None:
    """Run ``fn`` on the job's ``execute_args``, where it carries any, and
    wait for the result."""
    if job.execute_args is not None:
        import jax

        jax.block_until_ready(fn(*job.execute_args))


class CompileCache:
    """Directory-backed executable cache; thread-safe (warmup pools call
    ``load_or_compile`` concurrently — XLA compilation releases the GIL, so
    misses genuinely overlap)."""

    def __init__(self, directory: str | Path):
        self.directory = Path(directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        from jax.experimental import serialize_executable

        self._se = serialize_executable
        self._lock = threading.Lock()
        self._clock = StageClock()
        self._counts = {
            "hits": 0,
            "misses": 0,
            "discards": 0,
            "unrunnable": 0,
            "unserializable": 0,
        }
        self._programs: dict[str, dict[str, Any]] = {}

    # -------------------------------------------------------------- paths
    def _artifact_path(self, entry_id: str, digest: str) -> Path:
        return self.directory / entry_id / f"{digest}.jaxexe"

    # ------------------------------------------------------------ loading
    def load_or_compile(self, job: CacheJob) -> Callable:
        """Probe -> deserialize hit / compile miss (persisting the result).

        Returns a callable executable for EXACTLY ``job.abstract_args``'s
        shapes/dtypes; it raises on mismatched inputs rather than
        recompiling (callers keep their jitted fallback for novel shapes).
        """
        label = job.label or job.entry_id
        components, digest = keys.cache_key(
            job.entry_id,
            job.abstract_args,
            config_hash=job.config_hash,
            mesh_shape=job.mesh_shape,
            donated=job.donated,
        )
        path = self._artifact_path(job.entry_id, digest)
        if path.is_file():
            fn, seconds = self._try_deserialize(path)
            if fn is not None and self._runs(job, fn, path):
                self._record(label, digest, "deserialized", seconds)
                return fn
            # Corrupt/truncated/incompatible/unrunnable artifact: already
            # unlinked, logged and counted; fall through to a fresh compile.

        fn, seconds = self._compile(job)
        self._persist(path, components, fn, job)
        self._record(label, digest, "compiled", seconds)
        execute_once(job, fn)
        return fn

    def _runs(self, job: CacheJob, fn: Callable, path: Path) -> bool:
        """Execute a deserialized program once (where the job carries
        ``execute_args``). A program that loaded and cannot run is an
        ERROR with its cause in the log — the artifact goes, the caller
        recompiles, and ``unrunnable`` in stats keeps the event visible."""
        try:
            execute_once(job, fn)
            return True
        # Whatever the runtime raises for an executable it loaded and
        # cannot run (wrong device assignment, runtime refusing the
        # program) is the event this method exists to report.
        except Exception:  # tpulint: disable=TPU201
            logger.exception(
                "compile cache: artifact %s loaded but cannot run; "
                "removing it and recompiling", path,
            )
            path.unlink(missing_ok=True)
            with self._lock:
                self._counts["unrunnable"] += 1
            return False

    def _compile(self, job: CacheJob) -> tuple[Callable, float]:
        start = time.perf_counter()
        with self._clock.stage("compile"):
            compiled = job.jitted.lower(*job.abstract_args).compile()
        return compiled, time.perf_counter() - start

    def _load(self, payload, in_tree, out_tree, device_ids: list[int]):
        """Deserialize onto the devices the program was compiled for."""
        import jax

        by_id = {d.id: d for d in jax.devices()}
        return self._se.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in device_ids],
        )

    def _try_deserialize(self, path: Path) -> tuple[Callable | None, float]:
        """Checksum-verified read; a failure discards the artifact, logs
        the cause and reports None (the caller recompiles) — corruption
        can cost a compile, never a crash and never a stale/garbled
        program."""
        start = time.perf_counter()
        try:
            with self._clock.stage("deserialize"):
                raw = path.read_bytes()
                # Injection point (mlops_tpu/faults): corrupt-on-read —
                # seeded bit flips here must land in the discard+recompile
                # path below, never in a served program.
                raw = faults.corrupt("compilecache.read", raw)
                header_line, _, blob = raw.partition(b"\n")
                header = json.loads(header_line)
                if header.get("magic") != _HEADER_MAGIC:
                    raise ValueError("bad artifact magic")
                if header.get("format") != keys.CACHE_FORMAT_VERSION:
                    raise ValueError("artifact format version mismatch")
                if len(blob) != header.get("payload_bytes"):
                    raise ValueError("artifact truncated")
                if sha256(blob).hexdigest() != header.get("sha256"):
                    raise ValueError("artifact checksum mismatch")
                fn = self._load(*pickle.loads(blob), header["device_ids"])
            return fn, time.perf_counter() - start
        # The breadth is the contract: unreadable pickle, jaxlib refusing
        # the executable, header rot — all become a counted, logged
        # discard plus a recompile, never an exception on the warmup path.
        except Exception as err:  # tpulint: disable=TPU201
            logger.warning(
                "compile cache: discarding artifact %s (%s: %s)",
                path, type(err).__name__, err,
            )
            path.unlink(missing_ok=True)
            with self._lock:
                self._counts["discards"] += 1
            return None, time.perf_counter() - start

    def _persist(
        self, path: Path, components: dict, compiled: Any, job: CacheJob
    ) -> None:
        """Atomic tmp+rename write (stream.py discipline): concurrent
        writers race benignly (same key -> same bytes; os.replace is
        atomic), and a crash never leaves a partial artifact in place.

        The payload is VALIDATED before it touches disk: deserialized onto
        the devices it was compiled for and, where the job carries
        ``execute_args``, executed once. A program that does not survive
        that is logged with its cause, counted ``unserializable`` and
        never persisted, so the store only ever holds executables proven
        to round-trip AND run."""
        try:
            device_ids = [
                d.id for d in compiled.runtime_executable().local_devices()
            ]
            serialized = self._se.serialize(compiled)
            execute_once(job, self._load(*serialized, device_ids))
        # Serving must not die for a cache write; the cause is logged.
        except Exception:  # tpulint: disable=TPU201
            logger.exception(
                "compile cache: %s does not round-trip; not persisted",
                job.label or job.entry_id,
            )
            with self._lock:
                self._counts["unserializable"] += 1
            return

        blob = pickle.dumps(serialized)
        header = {
            "magic": _HEADER_MAGIC,
            "format": keys.CACHE_FORMAT_VERSION,
            "sha256": sha256(blob).hexdigest(),
            "payload_bytes": len(blob),
            "device_ids": device_ids,
            "key": components,
        }
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(
            f".{path.name}.tmp-{os.getpid()}-{threading.get_ident()}"
        )
        try:
            tmp.write_bytes(json.dumps(header).encode() + b"\n" + blob)
            # Injection point (mlops_tpu/faults): a kill here — after the
            # tmp write, before the atomic rename — is the torn-persist
            # proof: the artifact path must either not exist or hold a
            # fully verified prior artifact (chaos smoke asserts it).
            faults.fire("compilecache.persist.midwrite")
            os.replace(tmp, path)
        except OSError:
            tmp.unlink(missing_ok=True)

    # -------------------------------------------------------------- stats
    def _record(
        self,
        label: str,
        digest: str,
        source: str,
        seconds: float,
    ) -> None:
        with self._lock:
            self._counts["hits" if source == "deserialized" else "misses"] += 1
            self._programs[label] = {
                "source": source,
                "seconds": round(seconds, 4),
                "key": digest[:12],
            }

    def stats(self) -> dict[str, Any]:
        """Hit/miss/discard counts plus per-program compile vs deserialize
        wall time (`utils/timing.py StageClock` accumulates the busy
        seconds per stage)."""
        with self._lock:
            clock = {
                name: timing["busy_s"]
                for name, timing in self._clock.report(1.0).items()
            }
            return {
                "dir": str(self.directory),
                **dict(self._counts),
                "compile_s": round(clock.get("compile", 0.0), 4),
                "deserialize_s": round(clock.get("deserialize", 0.0), 4),
                "programs": {k: dict(v) for k, v in self._programs.items()},
            }


def from_config(config: Any) -> CompileCache | None:
    """The one construction rule every subsystem shares: ``cache.dir``
    set -> a CompileCache there; empty (the default) -> caching off."""
    directory = getattr(getattr(config, "cache", None), "dir", "")
    return CompileCache(directory) if directory else None

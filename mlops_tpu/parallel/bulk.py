"""Sharded bulk scoring — BASELINE config 4 (1M rows across a v5e-8 slice).

The reference has no batch-scoring path at all (serving is request-at-a-time
FastAPI, `app/main.py:42-86`; the closest artifact is an 80-row
`databricks/data/inference.csv` for ad-hoc tests). This module is the
TPU-native capability the baseline calls for: score an arbitrarily large
encoded dataset by streaming fixed-size chunks through ONE compiled
data-parallel program.

Mechanics (scaling-book recipe):
- a chunk is padded to a fixed shape and jit'd with `in_shardings` that lay
  rows out over the mesh's 'data' axis; params replicate. XLA inserts the
  (trivially few) collectives; every chunk but the job's last reuses the
  same executable, and the last, padded only to the smallest whole-history
  power of two that holds it (``tail_chunk_rows``), is at most one more
  signature of the same ``jax.jit``.
- the compiled chunk program outlives the job: ``make_bulk_jit`` and
  ``make_bulk_quant_jit`` hand out the SAME ``jax.jit`` object for the
  same program from a small bounded keep (``ChunkProgramKeep``), so a
  process that scores file after file traces, lowers, loads and warms the
  program once per signature, not once per job. Weights stay arguments: a
  new bundle of the same architecture reuses the program.
- classifier probabilities and outlier flags are exact per row.
- batch drift is a *dataset-level* statistic: K-S/chi² over millions of rows
  saturates (any tiny shift -> p≈0), so it is computed once over a bounded
  uniform row sample — same semantics as the serving monitor, bounded cost —
  by one compiled program, dispatched once and fetched once.

Where a job's time goes (always on): at its end a job builds ONE plain
dict, its record (``job_record``), and everything that reports the job
reads it: ``BulkScoreResult`` (``record``; ``phases``, ``compile_events``
and ``pipeline`` are views of it), ``summary()``, the
``mlops:bulk.compile_events`` marker, and the process's bounded log
``job_log()``, which outlives the call, so a caller that drops the result
(a warm-up job, an untraced benchmark window) can still be asked where the
time went. ``phases`` holds the seconds of the four phases;
``compile_events`` what the job traced, lowered, compiled and took from
JAX's persistent cache (`compilecache/events.py`), with
``chunk_program_reused``: 1 where the job found its chunk program compiled
for every signature it runs and so warmed nothing, and
``drift_program_reused``: 1 where its drift sample, ONE program a job
(``drift_scores``), traced and compiled nothing; ``stages`` the
executor's busy seconds and queue waits by side (`utils/timing.py
StageClock`); ``pauses`` the garbage collector's share (`utils/timing.py
PauseCounter`).
In a profiler trace the same phases are ``mlops:bulk.<phase>`` spans
inside one ``mlops:bulk.job``, the pipeline's stage executions are
``mlops:pipe.<stage>`` spans on their own threads, and every one of them
carries the job's ``job`` number, all on the device operations' clock.
With no profiler session open a span is one flag test.

A model with sparse experts (one that names a ``routing_collection``
and gives ``routing_counts(state)``, as `models/kimi_k2.py` does) also
counts, per expert layer, the assignments each expert it holds received.
The chunk program returns the counts as a third output; the scorer keeps
each run's on the device and the job sums them there and fetches the sum
once, with the drift sample: ``BulkScoreResult.routing`` and the marker
``mlops:bulk.routing``. Families without experts return two outputs,
write nothing and pay nothing.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import itertools
import operator
import os
import threading
import time
from collections.abc import Callable, Hashable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from mlops_tpu.bundle.bundle import Bundle
from mlops_tpu.compilecache.events import CompileCounter, compile_counter
from mlops_tpu.compilecache.keys import abstract_signature
from mlops_tpu.data.encode import EncodedDataset
from mlops_tpu.monitor import state as monitor_state
from mlops_tpu.monitor.state import outlier_flags
from mlops_tpu.parallel.sharding import batch_sharding, replicated
from mlops_tpu.schema import SCHEMA
from mlops_tpu.utils.timing import PauseCounter, pause_counter

# Chunks a batched fetch stage may drain in one device_get (and how far
# the compute stage may dispatch ahead of it) — the wave bound that
# amortizes the per-fetch round trip while capping in-flight device
# buffers.
FETCH_WAVE = 32

# A bulk job's phases, in order: scorer + transfer build (the chunk
# program taken from the keep); making sure that program is compiled for
# the job's first signature (`warm_chunk_scorer`: microseconds where it is,
# else trace, lower, compile or cache load and one run on a chunk of
# zeros; where the job's tail size is new, a wave of the body's chunks and
# the tail's first dispatch, which traces, lowers and loads it while the
# device runs them); the pipelined sweep; the drift sample.
PHASES = ("build", "warmup", "sweep", "drift")

# The drift sample's statistics as ONE program, dispatched once a job. The
# monitor arrays and the sample are its arguments, nothing is closed over,
# so every bundle of a sample length shares one executable, which the
# ``jax.jit``'s own cache keeps for the process. It compiles for longer than
# JAX's persistent cache's minimum, so a later process loads it from there.
drift_scores = jax.jit(monitor_state.drift_scores)  # tpulint: disable=TPU203

# tpulint Layer-3 manifest: two leaf locks, never held together: one
# around the keep's table, one around the job log.
TPULINT_LOCK_ORDER = {"ChunkProgramKeep": ("_lock",), "JobLog": ("_lock",)}

# Chunk programs kept compiled from job to job: one per (program body,
# model architecture, mesh) a process scores with. A process serves one
# bundle, or a few tenants' bundles; beyond that the least recently used
# program goes, and a later job of it compiles again.
KEPT_CHUNK_PROGRAMS = 4


@dataclasses.dataclass(eq=False)
class KeptProgram:
    """One chunk program of the keep: its ``jax.jit`` object, whose own
    cache holds the executables, and the signatures it has run to
    completion (`chunk_program_ready` reads them, `chunk_program_ran` adds
    them: single set operations, atomic under the GIL)."""

    jitted: Callable
    compiled_for: set = dataclasses.field(default_factory=set)


class ChunkProgramKeep:
    """The bulk chunk programs this process has built, least recently used
    out. The key is what a builder closes over, never an array: weights,
    monitor and temperature are arguments of the program, so every bundle
    of one architecture shares an entry. Dropping an entry drops the last
    reference the keep holds to the ``jax.jit`` object, and with it (once
    no running job holds it) JAX's executables for it."""

    def __init__(self, capacity: int) -> None:
        self.capacity = capacity
        self._lock = threading.Lock()
        self._programs: collections.OrderedDict[Hashable, KeptProgram] = (
            collections.OrderedDict()
        )

    def get(self, key: Hashable, build: Callable[[], Callable]) -> KeptProgram:
        with self._lock:
            kept = self._programs.get(key)
            if kept is None:
                kept = self._programs[key] = KeptProgram(build())
                while len(self._programs) > self.capacity:
                    self._programs.popitem(last=False)
            self._programs.move_to_end(key)
            return kept

    def holding(self, jitted: Callable) -> KeptProgram | None:
        """The entry whose program ``jitted`` is; ``None`` for a
        ``jax.jit`` the keep never held or has dropped."""
        with self._lock:
            for kept in self._programs.values():
                if kept.jitted is jitted:
                    return kept
        return None

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()


CHUNK_PROGRAMS = ChunkProgramKeep(KEPT_CHUNK_PROGRAMS)

# Job records a process keeps (``job_log``): a benchmark window is 4 to 21
# jobs, a serving host's nightly sweep a few files.
LOGGED_JOBS = 64


class JobLog:
    """The records of the newest jobs of this process, oldest out. A record
    is numbers and strings alone (``job_record``): nothing of a job's
    scorer, bundle or dataset is kept alive by it."""

    def __init__(self, capacity: int) -> None:
        self._lock = threading.Lock()
        self._records: collections.deque[dict] = collections.deque(maxlen=capacity)

    def append(self, record: dict) -> None:
        with self._lock:
            self._records.append(record)

    def records(self) -> list[dict]:
        with self._lock:
            return list(self._records)


JOB_LOG = JobLog(LOGGED_JOBS)


def job_log() -> list[dict]:
    """The records of this process's newest ``LOGGED_JOBS`` bulk jobs,
    oldest first."""
    return JOB_LOG.records()


_JOB_IDS = itertools.count(1)


def next_job_id() -> int:
    """The number of the next bulk job in this process: every span of one
    job carries it, so spans on the pipeline's threads can be tied to
    their job."""
    return next(_JOB_IDS)


@contextlib.contextmanager
def _phase(phases: dict[str, float], name: str, job: int):
    """One phase of a job: its seconds into ``phases`` and, in a profiler
    trace, a ``mlops:bulk.<name>`` span over the same statements."""
    start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(f"mlops:bulk.{name}", job=job):
            yield
    finally:
        phases[name] = time.perf_counter() - start


def mesh_chunk_rows(
    chunk_rows: int, mesh: Mesh | None, history_rows: int = 1
) -> int:
    """THE one chunk-size rounding rule: round UP to whole histories on
    every shard of the mesh's 'data' axis (floor: one history per shard).
    ``history_rows`` is `ModelConfig.history_rows`: 1 for models whose
    rows are independent, the records of one history for a model that
    reads consecutive rows as one sequence (family evabyte), so that no
    chunk and no shard ever cuts a history. score_dataset, the streaming
    scorer (data/stream.py), and the compile-cache warmer
    (compilecache/warmup.py) must all agree, or a pre-warmed
    ``bulk-score-chunk`` artifact's signature never matches the shape the
    run actually dispatches (silent cache miss, full recompile)."""
    unit = history_unit(mesh, history_rows)
    return max(unit, -(-chunk_rows // unit) * unit)


def history_unit(mesh: Mesh | None, history_rows: int = 1) -> int:
    """The rows no chunk may cut: one history on every shard of the mesh's
    'data' axis. Every chunk size of a job is a whole number of them."""
    return history_rows * (1 if mesh is None else int(mesh.shape["data"]))


def tail_chunk_rows(rows: int, chunk: int, unit: int) -> int:
    """THE one tail-size rule: the rows a job's last span is padded to.
    ``chunk`` is the job's run size (``mesh_chunk_rows``), a whole number
    of ``unit`` (``history_unit``). The last span holds ``rows - (ceil(rows
    ÷ chunk) - 1) × chunk`` rows, and runs at the smallest ``unit × 2**k``
    that holds them, never more than ``chunk``: no history and no shard is
    cut, and a process's jobs of many lengths meet few tail shapes (each
    one more signature of the same ``jax.jit``, compiled by the first job
    that runs it). Rows that divide evenly, or a tail that rounds back up
    to ``chunk``, give ``chunk``: the job runs one shape. A job of one
    span runs at this size alone."""
    tail = rows - (-(-rows // chunk) - 1) * chunk
    units = -(-tail // unit)
    return min(chunk, unit << (units - 1).bit_length())


@dataclasses.dataclass
class BulkScoreResult:
    predictions: np.ndarray  # float32 [N]
    outliers: np.ndarray  # float32 [N]
    feature_drift: dict[str, float]  # per-feature 1 - p_val on the sample
    rows: int
    elapsed_s: float  # the pipelined sweep's wall time: no scorer build, no
    # warm-up chunk, no drift sample (``phases`` has those); a job whose
    # tail size (``tail_chunk_rows``) the process had not run adds the
    # seconds in which its warm-up dispatched a wave of the body and the
    # tail, the tail's compile among them
    path: str = "exact"  # "exact" | "distilled" | "quant" — which params scored
    compile_cache: dict[str, Any] | None = None  # hit/miss/bypass counts +
    # per-program compile vs deserialize wall time (compilecache/cache.py)
    # when the sweep ran against a persistent executable cache
    record: dict[str, Any] | None = None  # the job's own account of itself
    # (``job_record``; also the newest entry of ``job_log()``); None for
    # the empty dataset, which runs no job
    routing: dict[str, Any] | None = None  # a model with sparse experts:
    # ``tokens`` the job's chunk runs read (padding included; a layer that
    # computes the read positions alone routes only those),
    # ``assignments_held`` of the (token, slot) choices that fell on the
    # experts held here, ``max_expert_load`` / ``mean_expert_load`` a held
    # expert and layer, ``expert_runs``: the (chunk run, expert) pairs in
    # which a held expert got a token, so had to be read (these scalars
    # are the record's ``routing``), and the two tables ``per_layer``
    # ``[expert layers][experts held]`` and ``expert_runs_per_layer``

    @property
    def rows_per_s(self) -> float:
        return self.rows / max(self.elapsed_s, 1e-9)

    @property
    def phases(self) -> dict[str, float] | None:
        """Seconds of each of PHASES."""
        return self.record and self.record["phases"]

    @property
    def compile_events(self) -> dict[str, Any] | None:
        """What the job traced, lowered, compiled and loaded
        (`compilecache/events.py CompileCounter.delta`), and
        ``chunk_program_reused``: 1 where it found its chunk program
        compiled for every signature it ran, the body's and the tail's
        (`chunk_program_ready`); ``drift_program_reused``: 1 where its
        drift sample traced and compiled nothing (``drift_scores``)."""
        return self.record and self.record["compile_events"]

    @property
    def pipeline(self) -> dict[str, Any] | None:
        """The streaming executor's run (`data/pipeline_exec.py
        PipelineStats`): its depth, the sweep's seconds, the chunks stored
        and per stage busy seconds, occupancy and queue waits."""
        return self.record and {
            "depth": self.record["depth"],
            "wall_s": round(self.elapsed_s, 4),
            "items": self.record["chunks"],
            "stages": self.record["stages"],
        }

    def summary(self) -> dict[str, Any]:
        """What `score-batch` prints: the answers' rates, and the job's
        record whole (its ``stages`` under ``pipeline``)."""
        job = dict(self.record or {"rows": self.rows, "path": self.path})
        if job.pop("stages", None) is not None:
            job["pipeline"] = self.pipeline
        if self.routing is not None:
            job["routing"] = self.routing  # the scalars and the two tables
        return {
            **job,
            "elapsed_s": round(self.elapsed_s, 4),
            "rows_per_s": round(self.rows_per_s, 1),
            "default_rate": (
                round(float((self.predictions >= 0.5).mean()), 6) if self.rows else 0.0
            ),
            "outlier_rate": (
                round(float(self.outliers.mean()), 6) if self.rows else 0.0
            ),
            "feature_drift_batch": {
                k: round(v, 6) for k, v in self.feature_drift.items()
            },
            **(
                {"compile_cache": self.compile_cache}
                if self.compile_cache is not None
                else {}
            ),
        }


class RoutingTally:
    """A job's routing counts: each chunk run's (``fused_counting``'s third
    output) stays on the device as it came, nothing is dispatched or
    fetched for it run by run; ``total`` sums them there, once, at the
    job's end. The scorer's closure holds it, and it holds nothing of the
    scorer: a scorer that referred to itself would be a cycle, and the
    weights a job replicates over a mesh would outlive the job until a
    collection."""

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        self.runs, self.rows = [], 0

    def add(self, counts, rows: int) -> None:
        self.runs.append(counts)
        self.rows += rows

    def total(self) -> np.ndarray | None:
        if not self.runs:
            return None
        # adds of one shape whatever the number of runs: nothing compiles
        # in a job whose file is of another length
        return np.asarray(functools.reduce(operator.add, self.runs))


def use_distilled_bulk(bundle: Bundle, exact: bool | None = None) -> bool:
    """Routing decision for bulk sweeps: the distilled student
    (`train/distill.py`) scores when the bundle carries one and either the
    caller asked for it (``exact=False``) or — the auto default — the
    backend is a CPU, where the K-member ensemble's FLOPs lose to the
    reference's sklearn floor (BASELINE.json config 1). On a TPU the exact
    ensemble is already fast, so auto keeps it."""
    if exact is True or not bundle.has_bulk:
        return False
    if exact is False:
        return True
    return jax.default_backend() == "cpu"


def use_quant_bulk(bundle: Bundle, tier: str = "exact") -> bool:
    """Quant-tier routing for bulk sweeps — the same demand-vs-preference
    semantics as `serve/engine.py _resolve_tier`: ``tier="quant"`` is a
    DEMAND (raises when the bundle has no gate-passed quant tree — an
    explicit ask is never silently downgraded), ``"auto"`` takes quant
    when it is there and gated, ``"exact"`` never routes here. Unlike the
    serve tier there is no shard restriction: bulk quant is data-parallel
    (params replicate over the 'data' axis like every other bulk path)."""
    if tier not in ("exact", "quant", "auto"):
        raise ValueError(f"tier must be exact|quant|auto, got {tier!r}")
    if tier == "exact":
        return False
    eligible = (
        bundle.flavor != "sklearn"
        and bundle.has_quant
        and bundle.quant_gates_passed
    )
    if tier == "quant" and not eligible:
        raise ValueError(
            "tier='quant' refused: bundle carries no gate-passed quant "
            "params (train with train.distill_quant=true)"
        )
    return eligible


def make_chunk_scorer(
    bundle: Bundle,
    mesh: Mesh | None,
    exact: bool | None = None,
    compile_cache=None,
    chunk_rows: int | None = None,
    tier: str = "exact",
):
    """One compiled program: (cat[chunk,C], num[chunk,M], mask[chunk]) ->
    (probs, outlier_flags), fixed-shape per call site (the caller feeds
    equal-sized chunks and at most one smaller tail, so one compile serves
    the body of the sweep and one more its tail).
    Sharded over 'data' when a mesh is given. ``exact`` controls
    distilled-student routing (see ``use_distilled_bulk``); ``tier``
    routes the int8/bf16 quant student (``use_quant_bulk``) and, when it
    routes, takes precedence over the exact/distilled pair.

    The jitted program comes from the process's keep (``make_bulk_jit``,
    ``make_bulk_quant_jit``): a second job of the same architecture and
    mesh, whatever its bundle's weights, dispatches the executable the
    first one compiled. The returned scorer carries what
    ``warm_chunk_scorer`` needs to know whether that has happened:
    ``kept`` (the keep's entry) and ``avals`` (of variables and monitor).

    With ``compile_cache`` + ``chunk_rows``, the chunk program is AOT
    loaded through the persistent executable cache (`compilecache/` entry
    ``bulk-score-chunk``: deserialize on hit, compile+persist on miss)
    in every job, with the kept jit as the job's ``jitted``; chunks at
    any OTHER shape fall back to the jitted program, so the cached
    executable can never be fed a signature it was not built for.
    """
    monitor = bundle.monitor
    temperature = bundle.temperature  # calibration (train/calibrate.py):
    # bulk scores must match what the serving engine would return; the
    # distilled student matched the teacher's LOGITS, so the same
    # temperature applies on either path

    if bundle.flavor == "sklearn":
        estimator = bundle.estimator

        @jax.jit
        def outliers_only(num, mask):
            return outlier_flags(monitor, num, mask)

        from mlops_tpu.train.calibrate import apply_temperature

        def score_chunk(cat, num, mask):
            probs = np.zeros(mask.shape[0], np.float32)
            p = estimator.predict_proba(cat[mask], num[mask])
            probs[mask] = apply_temperature(p, temperature)
            return probs, np.asarray(outliers_only(num, mask))

        return score_chunk

    if use_quant_bulk(bundle, tier):
        path = "quant"
        model, variables = None, bundle.quant_params
        temperature = bundle.quant_temperature  # the quant tier carries
        # its OWN post-distillation refit (train/calibrate.py) — the
        # student's logit scale is not the teacher's
        fn = make_bulk_quant_jit(mesh)
    elif use_distilled_bulk(bundle, exact):
        path = "distilled"
        model, variables = bundle.bulk_model, bundle.bulk_variables
        fn = make_bulk_jit(model, mesh)
    else:
        path = "exact"
        model, variables = bundle.model, bundle.variables
        fn = make_bulk_jit(model, mesh)
    # device_put the per-call program state ONCE (replicated over the mesh
    # when sharded): params/monitor travel as arguments now, and host
    # arrays would re-pay the transfer every chunk.
    rep = replicated(mesh) if mesh is not None else None
    place = (lambda x: jax.device_put(x, rep)) if rep else jax.device_put
    variables = place(variables)
    monitor = place(monitor)
    t = place(np.float32(temperature))
    aot = None
    if compile_cache is not None and chunk_rows:
        if path == "quant":
            from mlops_tpu.compilecache.warmup import bulk_quant_chunk_job

            job = bulk_quant_chunk_job(
                variables, monitor, chunk_rows, mesh, jitted=fn
            )
        else:
            from mlops_tpu.compilecache.warmup import bulk_chunk_job

            job = bulk_chunk_job(
                model,
                bundle.model_config,
                variables,
                monitor,
                chunk_rows,
                mesh,
                path_label=path,
                jitted=fn,
            )
        aot = compile_cache.load_or_compile(job)

    tally = RoutingTally()

    def score_chunk(cat, num, mask):
        run = aot if (aot is not None and cat.shape[0] == chunk_rows) else fn
        probs, flags, *counts = run(variables, monitor, t, cat, num, mask)
        if counts:  # a model with sparse experts
            tally.add(counts[0], cat.shape[0])
        return probs, flags

    score_chunk.tally = tally

    # an AOT executable is loaded anew in every job and runs in the jit's
    # place: nothing of it is kept, so nothing is recorded
    score_chunk.kept = CHUNK_PROGRAMS.holding(fn) if aot is None else None
    score_chunk.avals = abstract_signature((variables, monitor))
    return score_chunk


def warm_chunk_scorer(
    scorer,
    transfer,
    chunk_rows: int,
    host_model: bool = False,
    meanwhile: Callable[[], None] | None = None,
) -> bool:
    """THE one warm-up rule of the bulk callers (``score_dataset``,
    `data/stream.py score_csv_stream`): make sure the chunk program is
    compiled for the job's first chunk size before the timed sweep starts,
    so that no compile holds up its first run. Returns whether it already
    was. (A ``score_dataset`` tail of another size, ``tail_chunk_rows``, is
    not warmed here: the first job that runs it compiles it by its own
    first dispatch, behind the body's runs, and records it with
    ``chunk_program_ran``; every later job finds it ready.)

    A job's signature is its chunk rows and the avals of ``variables`` and
    ``monitor`` (what `compilecache/warmup.py bulk_chunk_job` lists in
    ``abstract_args``; temperature and the chunk's columns follow from
    them). Where the keep's entry has run that signature to completion,
    nothing is made and nothing runs: the job's first device work is its
    first real chunk. Otherwise (the first job of a process, another chunk
    size, a monitor of another reference length, an AOT executable, a
    scorer that says nothing about itself) one chunk of zeros goes through
    ``transfer`` (`make_chunk_transfer`) and the scorer, the way every
    chunk of the sweep will, and the signature is recorded once it has
    come back.
    ``host_model``: the sklearn flavour scores on the host and has nothing
    to compile but the outlier program, so its warm-up scores one row.
    ``meanwhile``: the caller's own work, run once on this thread after
    the chunk of zeros is dispatched and before it is waited for (at once
    where nothing is warmed)."""
    if chunk_program_ready(scorer, chunk_rows):
        if meanwhile is not None:
            meanwhile()
        return True
    cat = np.zeros(
        (chunk_rows, SCHEMA.num_categorical), np.int32 if host_model else np.int8
    )
    num = np.zeros((chunk_rows, SCHEMA.num_numeric), np.float32)
    mask = np.arange(chunk_rows) < (1 if host_model else chunk_rows)
    zeros = scorer(*transfer(cat, num, mask))[0]
    if getattr(scorer, "tally", None) is not None:
        scorer.tally.reset()  # a chunk of zeros is no job's tokens
    if meanwhile is not None:
        meanwhile()
    jax.block_until_ready(zeros)
    chunk_program_ran(scorer, chunk_rows)
    return False


def chunk_program_ready(scorer, chunk_rows: int) -> bool:
    """Whether the keep's entry of ``scorer`` has run the signature of
    ``chunk_rows`` (and the scorer's ``avals``) to completion; ``False`` for
    a scorer that says nothing about itself."""
    kept = getattr(scorer, "kept", None)
    signature = (chunk_rows, getattr(scorer, "avals", None))
    return kept is not None and signature in kept.compiled_for


def chunk_program_ran(scorer, chunk_rows: int) -> None:
    """Record, once a run at ``chunk_rows`` has come back, that the keep's
    entry is compiled for that signature (nothing for a scorer the keep
    does not hold)."""
    kept = getattr(scorer, "kept", None)
    if kept is not None:
        kept.compiled_for.add((chunk_rows, getattr(scorer, "avals", None)))


def make_bulk_jit(model, mesh: Mesh | None):
    """The jitted (and, with a mesh, data-sharded) bulk chunk program —
    the ONE jit site the compile cache warms (`compilecache/warmup.py
    bulk_chunk_job`) and ``make_chunk_scorer`` dispatches. The SAME
    ``jax.jit`` object for the same flax module (a dataclass: two
    ``build_model`` of equal configs are equal and hash alike) and mesh,
    from the process's keep, so `jax.jit`'s own cache serves every later
    job: a call with avals it has seen dispatches with no trace, no
    lowering and no read of the persistent cache. A sharded and an
    unsharded program never share an entry."""

    def build():
        fused = make_bulk_fused(model)
        if mesh is None:
            return jax.jit(fused)
        return jax.jit(fused, **_data_parallel(mesh, counts=_counts_routing(model)))

    return CHUNK_PROGRAMS.get(("fused", model, mesh), build).jitted


def _counts_routing(model) -> bool:
    return getattr(model, "routing_collection", None) is not None


def _data_parallel(mesh: Mesh, counts: bool = False) -> dict[str, Any]:
    """The chunk programs' shardings under a mesh: variables, monitor and
    temperature replicate, the chunk's rows (and both answers) lie over
    'data'; the routing counts of a model with sparse experts, summed
    over the shards, replicate."""
    data_in = batch_sharding(mesh)
    rows = batch_sharding(mesh, ndim=1)
    rep = replicated(mesh)
    return {
        "in_shardings": (rep, rep, rep, data_in, data_in, rows),
        "out_shardings": (rows, rows, rep) if counts else (rows, rows),
    }


def make_bulk_fused(model):
    """The ONE fused bulk program — classifier probabilities + outlier
    flags in a single dispatch — shared by ``make_chunk_scorer``, the
    compile cache, and the tpulint Layer-2 registry
    (`analysis/entrypoints.py bulk-score-chunk`), so the jaxpr the
    analyzer gates is the program production compiles. Params, monitor
    state, and temperature are ARGUMENTS (cacheable form: a closed-over
    array would be baked into the serialized executable — see
    `ops/predict.py make_padded_predict_base`)."""

    def fused(variables, monitor, temperature, cat, num, mask):
        # cat ids travel as int8 (max vocab cardinality is 12; lossless)
        # and widen on device: int8 cuts the categorical block's
        # host->device bytes 4x.
        logits = model.apply(variables, cat.astype(jnp.int32), num, train=False)
        return jax.nn.sigmoid(logits / temperature), outlier_flags(monitor, num, mask)

    if not _counts_routing(model):
        return fused

    def fused_counting(variables, monitor, temperature, cat, num, mask):
        """``fused`` with a third output, int32 ``[2, expert layers,
        experts held]``: the assignments each held expert got in this run,
        and 1 where it got any (summed over runs: the runs it was read in)."""
        logits, state = model.apply(
            variables, cat.astype(jnp.int32), num, train=False,
            mutable=[model.routing_collection],
        )
        counts = model.routing_counts(state)
        return (
            jax.nn.sigmoid(logits / temperature),
            outlier_flags(monitor, num, mask),
            jnp.stack([counts, (counts > 0).astype(counts.dtype)]),
        )

    return fused_counting


def make_bulk_quant_fused():
    """Quant-tier bulk chunk body: the int8/bf16 student
    (`ops/quant.py quant_student_logits` — dequantized in-jit, f32
    compute) in place of the flax ensemble, same ``(probs, flags)``
    contract and the same cacheable argument discipline as
    `make_bulk_fused`. ``variables`` is the quant param DICT; the chunk
    program stays tier-keyed in the compile cache via
    ``path_label="quant"`` plus the quant geometry fingerprint
    (`compilecache/warmup.py bulk_quant_chunk_job`)."""
    from mlops_tpu.ops.quant import quant_student_logits

    def fused(variables, monitor, temperature, cat, num, mask):
        logits = quant_student_logits(variables, cat.astype(jnp.int32), num)
        return jax.nn.sigmoid(logits / temperature), outlier_flags(monitor, num, mask)

    return fused


def make_bulk_quant_jit(mesh: Mesh | None):
    """Quant twin of `make_bulk_jit` — the ONE jit site for the quant bulk
    chunk program (whitelisted in `compilecache/registry.py
    CACHED_JIT_BUILDERS`). Data-parallel like the exact path: rows shard
    over 'data', the quant tree replicates (its int8/bf16 leaves are a few
    KB — replication is free; there is no model axis in this tier). Kept
    from job to job like `make_bulk_jit`'s: the body closes over nothing,
    so the mesh is the whole key."""

    def build():
        fused = make_bulk_quant_fused()
        if mesh is None:
            return jax.jit(fused)
        return jax.jit(fused, **_data_parallel(mesh))

    return CHUNK_PROGRAMS.get(("quant_fused", None, mesh), build).jitted


def make_chunk_transfer(bundle: Bundle, mesh: Mesh | None):
    """Stage-3 device placement for the pipelined executors
    (`data/pipeline_exec.py`): ``jax.device_put`` the NEXT chunk's host
    arrays — with the mesh's data-parallel shardings when given, so the
    jitted scorer consumes them zero-copy — while the current chunk
    computes (double buffering). The sklearn flavor scores on host; its
    transfer is the identity."""
    if bundle.flavor == "sklearn":
        return lambda cat, num, mask: (cat, num, mask)
    if mesh is None:
        def place(cat, num, mask):
            return jax.device_put(cat), jax.device_put(num), jax.device_put(mask)

        return place
    data_in = batch_sharding(mesh)
    mask_in = batch_sharding(mesh, ndim=1)

    def place_sharded(cat, num, mask):
        return (
            jax.device_put(cat, data_in),
            jax.device_put(num, data_in),
            jax.device_put(mask, mask_in),
        )

    return place_sharded


def score_dataset(
    bundle: Bundle,
    ds: EncodedDataset,
    mesh: Mesh | None = None,
    chunk_rows: int = 131_072,
    drift_sample: int = 65_536,
    seed: int = 0,
    exact: bool | None = None,
    pipeline_depth: int = 2,
    compile_cache=None,
    tier: str = "exact",
) -> BulkScoreResult:
    """Stream ``ds`` through the chunk scorer; aggregate monitors.

    The sweep runs on the pipelined streaming executor
    (`data/pipeline_exec.py`): chunk slicing/padding, host->device
    transfer, device dispatch, and batched result fetch each occupy their
    own stage, so chunk N+1 transfers while chunk N computes and chunk
    N-1's results fetch — with bounded queues keeping in-flight buffers
    at a few chunks regardless of dataset size. ``pipeline_depth=1``
    degrades to the strict serial loop (bit-identical results; the
    executor preserves chunk order at any depth).

    ``exact=None`` auto-routes through the distilled bulk student on CPU
    backends when the bundle carries one (``use_distilled_bulk``);
    ``exact=True`` forces the serving-identical ensemble. ``tier``
    ("exact"|"quant"|"auto") routes the int8/bf16 quant student
    (``use_quant_bulk``) ahead of both."""
    from mlops_tpu.data.pipeline_exec import Stage, run_pipeline

    if use_quant_bulk(bundle, tier):
        path = "quant"
    elif use_distilled_bulk(bundle, exact):
        path = "distilled"
    else:
        path = "exact"
    n = ds.n
    if n == 0:
        # Same guard as the serving engine: an empty dataset has no drift
        # signal and must not emit NaN rates into the JSON summary.
        return BulkScoreResult(
            predictions=np.empty(0, np.float32),
            outliers=np.empty(0, np.float32),
            feature_drift=dict.fromkeys(SCHEMA.feature_names, 0.0),
            rows=0,
            elapsed_s=0.0,
        )
    config = bundle.model_config
    chunk = mesh_chunk_rows(chunk_rows, mesh, config.history_rows)
    chunks = -(-n // chunk)
    tail_chunk = tail_chunk_rows(n, chunk, history_unit(mesh, config.history_rows))
    # what the job is, for its span and its record alike
    head = {
        "job": next_job_id(),
        "rows": n,
        "chunk_rows": chunk,
        "chunks": chunks,
        "path": path,
        "histories": -(-n // config.history_rows),
        "tail_chunk_rows": tail_chunk,
        # the rows the chunk program computes, padding included
        "rows_run": (chunks - 1) * chunk + tail_chunk,
    }
    job = head["job"]
    phases: dict[str, float] = {}
    counter, pauses = compile_counter(), pause_counter()
    traced_before, paused_before = counter.snapshot(), pauses.snapshot()
    started = time.perf_counter()
    with jax.profiler.TraceAnnotation(
        "mlops:bulk.job",
        **head,
        pid=os.getpid(),
        # the text the model reads of the job, padding apart (0 for a
        # model that reads no text)
        bytes=n * getattr(bundle.model, "bytes_per_row", 0),
        # the record's clock (``time.perf_counter``) at the span's opening:
        # ties a record of ``job_log()`` to the trace's clock
        started=started,
    ):
        with _phase(phases, "build", job):
            scorer = make_chunk_scorer(
                bundle, mesh, exact, compile_cache=compile_cache,
                chunk_rows=chunk, tier=tier,
            )
            transfer = make_chunk_transfer(bundle, mesh)
        predictions = np.empty(n, np.float32)
        outliers = np.empty(n, np.float32)

        narrow = (
            np.int8 if bundle.flavor != "sklearn" else ds.cat_ids.dtype
        )  # host trees index with the original ids; device path widens in-jit
        base_index = np.arange(chunk)
        full_mask = np.ones(chunk, bool)

        def slice_chunk(span):
            start, stop = span
            size = stop - start
            run = chunk if stop < n else tail_chunk
            cat = ds.cat_ids[start:stop].astype(narrow)
            num = ds.numeric[start:stop]
            if size < run:
                cat = np.pad(cat, ((0, run - size), (0, 0)))
                num = np.pad(num, ((0, run - size), (0, 0)))
                mask = base_index[:run] < size
            else:
                mask = full_mask[:run]
            return start, stop, cat, num, mask

        def transfer_chunk(item):
            start, stop, cat, num, mask = item
            return (start, stop, *transfer(cat, num, mask))

        def compute_chunk(item):
            start, stop, cat, num, mask = item
            return (start, stop, *scorer(cat, num, mask))

        def fetch_chunks(items):
            # Batched fetch: one device_get round trip for everything
            # already dispatched, instead of one per chunk. The executor
            # bounds the gather at the queue depth, so in-flight device
            # buffers stay fixed regardless of dataset size.
            fetched = jax.device_get(
                [(probs, flags) for _, _, probs, flags in items]
            )
            return [
                (start, stop, probs, flags)
                for (start, stop, _, _), (probs, flags) in zip(items, fetched)
            ]

        def store_chunk(item):
            start, stop, probs, flags = item
            size = stop - start
            predictions[start:stop] = probs[:size]
            outliers[start:stop] = flags[:size]

        spans = [(start, min(start + chunk, n)) for start in range(0, n, chunk)]
        first = chunk if chunks > 1 else tail_chunk
        tail_ready = tail_chunk == first or chunk_program_ready(scorer, tail_chunk)
        # A tail size the process has not run gets no chunk of zeros. While
        # the warm-up's chunk runs, this thread dispatches a wave of the
        # body's chunks and then the tail's, so that the device runs them
        # while the tail's program is traced, lowered and loaded (the load
        # takes several times longer on an executor thread than here: 2.0 s
        # against 0.55 on a TPU v5e). The executor takes the rest.
        ahead = [] if tail_ready else [*spans[:-1][:FETCH_WAVE], spans[-1]]
        dispatched, ahead_s = [], 0.0

        def dispatch_ahead():
            nonlocal ahead_s
            since = time.perf_counter()
            dispatched.extend(
                compute_chunk(transfer_chunk(slice_chunk(span))) for span in ahead
            )
            ahead_s = time.perf_counter() - since

        with _phase(phases, "warmup", job):
            reused = warm_chunk_scorer(
                scorer, transfer, first, host_model=bundle.flavor == "sklearn",
                meanwhile=dispatch_ahead,
            )

        with _phase(phases, "sweep", job):
            pipe = run_pipeline(
                spans[len(ahead) - 1 : -1] if ahead else spans,
                [
                    Stage("slice", slice_chunk),
                    Stage("transfer", transfer_chunk),
                    Stage("compute", compute_chunk),
                    # The fetch stage keeps the old wave semantics: its
                    # deep input queue lets the compute stage dispatch up
                    # to FETCH_WAVE chunks ahead (JAX queues the
                    # copies/kernels asynchronously) and one batched
                    # device_get drains them — one transport round trip
                    # per wave instead of per chunk, independent of
                    # pipeline_depth. batch_max >= 2 also keeps fetch in
                    # list-in/list-out mode at depth 1 (the gather is
                    # still at most one item there).
                    Stage(
                        "fetch",
                        fetch_chunks,
                        batch_max=FETCH_WAVE,
                        queue_depth=FETCH_WAVE,
                    ),
                ],
                store_chunk,
                depth=pipeline_depth,
                source_name="span",
                sink_name="store",
                span_attrs={"job": job},
            )
            for item in fetch_chunks(dispatched):
                store_chunk(item)
        if not tail_ready:  # every chunk is back: the tail's run among them
            chunk_program_ran(scorer, tail_chunk)

        with _phase(phases, "drift", job):
            # Dataset-level drift on a bounded uniform sample (see module
            # docstring).
            take = min(n, drift_sample)
            idx = (
                np.random.default_rng(seed).choice(n, take, replace=False)
                if take < n
                else np.arange(n)
            )
            before = counter.snapshot()
            drift = np.asarray(
                drift_scores(
                    bundle.monitor,
                    ds.cat_ids[idx],
                    ds.numeric[idx],
                    np.ones(take, bool),
                )
            )
            drift_compiled = CompileCounter.delta(before, counter.snapshot())
            routing = _routing_summary(scorer, bundle.model, job)
        record = job_record(
            head, started, phases, pipe,
            compile_events={
                **CompileCounter.delta(traced_before, counter.snapshot()),
                "chunk_program_reused": int(reused and tail_ready),
                "drift_program_reused": int(
                    drift_compiled["programs_traced"] == 0
                    and drift_compiled["backend_compile_s"] == 0
                ),
            },
            pauses=PauseCounter.delta(paused_before, pauses.snapshot()),
            routing=routing,
        )
        JOB_LOG.append(record)
        # a marker at the job's end: what the job traced, on the trace's clock
        events = record["compile_events"]
        with jax.profiler.TraceAnnotation(
            "mlops:bulk.compile_events",
            job=job,
            **{**events, "programs": "|".join(events["programs"])},
        ):
            pass
    return BulkScoreResult(
        predictions=predictions,
        outliers=outliers,
        feature_drift=dict(
            zip(SCHEMA.feature_names, drift.astype(float).tolist())
        ),
        rows=n,
        elapsed_s=ahead_s + phases["sweep"],
        path=path,
        compile_cache=(
            compile_cache.stats() if compile_cache is not None else None
        ),
        record=record,
        routing=routing,
    )


def job_record(
    head: dict, started: float, phases: dict[str, float], pipe,
    compile_events: dict, pauses: dict, routing: dict | None,
) -> dict[str, Any]:
    """A job's own account of itself, built once at its end: numbers,
    strings and lists and dicts of them, never an array nor a reference to
    the scorer, the bundle or the dataset (a job's scorer dies with the
    job: `tests/test_bulk_dp4.py`). ``head``: ``job``, ``rows``,
    ``chunk_rows``, ``chunks``, ``path``, ``histories``,
    ``tail_chunk_rows`` (the last run's size, ``tail_chunk_rows()``) and
    ``rows_run`` (the rows the chunk program computed, padding included),
    as on the ``mlops:bulk.job`` span; ``started``: ``time.perf_counter()`` at that
    span's opening, and ``wall_s`` from there to here; ``phases``;
    ``compile_events``; the executor's ``depth`` and ``stages`` (busy
    seconds, occupancy and queue waits by side, `utils/timing.py
    StageClock.report`); ``pauses``; ``routing``'s scalars for a model
    with sparse experts."""
    record = {
        **head,
        "started": round(started, 6),
        "wall_s": round(time.perf_counter() - started, 6),
        "phases": {name: round(seconds, 6) for name, seconds in phases.items()},
        "compile_events": compile_events,
        "depth": pipe.depth,
        "stages": pipe.stages,
        "pauses": pauses,
    }
    if routing is not None:
        record["routing"] = _routing_scalars(routing)
    return record


def _routing_scalars(routing: dict[str, Any]) -> dict[str, Any]:
    """``BulkScoreResult.routing`` without its two tables."""
    return {k: v for k, v in routing.items() if not k.endswith("per_layer")}


def _routing_summary(scorer, model, job: int) -> dict[str, Any] | None:
    """The job's routing counts, summed on the device and fetched once, and
    the marker ``mlops:bulk.routing`` on the trace's clock;
    ``None`` for a scorer that counted nothing."""
    tally = getattr(scorer, "tally", None)
    counts = tally.total() if tally is not None else None
    if counts is None:
        return None
    per_layer, active = counts
    routing = {
        "tokens": int(tally.rows * model.tokens_per_row),
        "assignments_held": int(per_layer.sum()),
        "max_expert_load": int(per_layer.max()),
        "mean_expert_load": float(per_layer.mean()),
        "expert_runs": int(active.sum()),
        "per_layer": per_layer.tolist(),
        "expert_runs_per_layer": active.tolist(),
    }
    with jax.profiler.TraceAnnotation(
        "mlops:bulk.routing",
        job=job,
        **_routing_scalars(routing),
        **{f"layer_{i}": "|".join(map(str, row)) for i, row in enumerate(per_layer)},
    ):
        pass
    return routing

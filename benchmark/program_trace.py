"""The program's own spans and scopes, read from the traced run's profile.

``trace_reduce.py`` times the layers from outside: busy and idle time, gaps
named by the programs around them. Since PR 25 the program says what it is
doing, on the device operations' clock:

- host spans, ``jax.profiler.TraceAnnotation``s named ``mlops:<span>`` with
  attributes: ``mlops:bulk.job`` {job, pid, rows, chunk_rows, chunks, path}
  around one ``score_dataset`` call, ``mlops:bulk.build`` / ``.warmup`` /
  ``.sweep`` / ``.drift`` {job} inside it, ``mlops:bulk.compile_events``
  {job + the job's compile counter} at its end, and ``mlops:pipe.<stage>``
  {job, items} around each stage execution on the pipeline's threads;
- device scopes: XLA's ``op_name`` of each operation, which holds flax's
  module path and the program's ``jax.named_scope``s, e.g.
  ``jit(fused)/BertEncoder/block_3/MultiHeadSelfAttention_0/attend/...``.
  On a TPU v5e trace it is the stat ``tf_op`` (``SCOPE_STAT``) of the
  ``XLA Ops`` event's METADATA, as ``<op_name>:``; the event's own stats
  are three timings (found by listing both on the chip, PR 25). About half
  of the events carry none (copies, buffer allocations).

``facts`` hands a reader only ``trace_reduce``'s reduction, so this module
finds the profile itself: ``run.py`` keeps it under
``<tmp>/bench-trace-*/`` while the readers run, and the newest one there
that holds a ``bench:window`` span and ``mlops:bulk.job`` spans of THIS
process (``pid``) is the run's. A program without the spans (the parent of
PR 25) gives ``None``, and every reader of this module then returns
``None``.

As in ``trace_reduce``, the profile is first flattened to plain data
(``load_profile``) and reduced from that (``reduce_profile``), so that the
reduction is checked on hand-made data and on a flattened v5e profile kept
beside the tests::

    {"planes": [
      {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events":
          [["fusion", start_ns, duration_ns, "jit(fused)/BertEncoder/..."], ...]}]},
      {"name": "/host:CPU", "lines": [{"name": "python", "events":
          [["mlops:bulk.warmup", start_ns, duration_ns, {"job": 3}], ...]}]}]}
"""

from __future__ import annotations

import bisect
import functools
import os
import re
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

from benchmark.trace_reduce import (
    DEVICE_PLANE,
    OPS_LINE,
    SPAN_PREFIX as HARNESS_PREFIX,
    WINDOW,
    op_kind,
    union_intervals,
)

PROGRAM_PREFIX = "mlops:"
SCOPE_STAT = "tf_op"
JOB, EVENTS = "bulk.job", "bulk.compile_events"
PHASES = ("build", "warmup", "sweep", "drift")
NO_SPAN = "(no program span)"
TOP = 12


# ------------------------------------------------------------- flattening
@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The fields of the profiler's ``xplane.proto`` (tsl/profiler/protobuf)
    that this module reads, declared here: `jax.profiler.ProfileData` gives
    an event's own stats but not its METADATA's, and on a TPU plane the
    scope (``tf_op``) is a stat of the event's metadata. Maps are declared
    as what they are on the wire, repeated key/value entries; fields this
    module does not read are left out and skipped by the parser."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    field = descriptor_pb2.FieldDescriptorProto
    kinds = {"int64": field.TYPE_INT64, "uint64": field.TYPE_UINT64,
             "double": field.TYPE_DOUBLE, "string": field.TYPE_STRING,
             "bytes": field.TYPE_BYTES}
    messages = {
        "XSpace": [("planes", 1, "*XPlane")],
        "XPlane": [("name", 2, "string"), ("lines", 3, "*XLine"),
                   ("event_metadata", 4, "*EventMetadataEntry"),
                   ("stat_metadata", 5, "*StatMetadataEntry")],
        "XLine": [("name", 2, "string"), ("timestamp_ns", 3, "int64"),
                  ("events", 4, "*XEvent")],
        "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
                   ("duration_ps", 3, "int64"), ("stats", 4, "*XStat")],
        "XStat": [("metadata_id", 1, "int64"), ("double_value", 2, "double"),
                  ("uint64_value", 3, "uint64"), ("int64_value", 4, "int64"),
                  ("str_value", 5, "string"), ("bytes_value", 6, "bytes"),
                  ("ref_value", 7, "uint64")],
        "XEventMetadata": [("id", 1, "int64"), ("name", 2, "string"),
                           ("stats", 5, "*XStat")],
        "XStatMetadata": [("id", 1, "int64"), ("name", 2, "string")],
        "EventMetadataEntry": [("key", 1, "int64"), ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
    }
    file = descriptor_pb2.FileDescriptorProto(
        name="benchmark_xplane.proto", package="benchmark_xplane", syntax="proto3"
    )
    for name, fields in messages.items():
        message = file.message_type.add(name=name)
        if name == "XStat":
            message.oneof_decl.add(name="value")
        for fname, number, kind in fields:
            repeated, kind = kind.startswith("*"), kind.lstrip("*")
            f = message.field.add(
                name=fname, number=number,
                label=field.LABEL_REPEATED if repeated else field.LABEL_OPTIONAL,
            )
            if fname.endswith("_value"):
                f.oneof_index = 0  # XStat's value: one of the six is set
            if kind in kinds:
                f.type = kinds[kind]
            else:
                f.type, f.type_name = field.TYPE_MESSAGE, f".benchmark_xplane.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchmark_xplane.XSpace")
    )


def _stats(stats, stat_names: dict[int, str]) -> dict:
    """An event's (or its metadata's) stats by name; a ``ref_value`` names
    a string kept once in the plane's stat metadata."""
    out = {}
    for stat in stats:
        which = stat.WhichOneof("value")
        value = getattr(stat, which) if which else 0
        if which == "ref_value":
            value = stat_names.get(value, "")
        out[stat_names.get(stat.metadata_id, str(stat.metadata_id))] = value
    return out


def load_profile(path: str | Path) -> dict:
    """Flatten an ``.xplane.pb``: of the device planes the ``XLA Ops`` line
    (operation kind, start, duration, scope), of the host planes the
    harness's and the program's spans with their attributes. Times in
    nanoseconds on the profile's one clock (a line's ``timestamp_ns`` plus
    the event's offset)."""
    space = _xspace_class()()
    space.ParseFromString(Path(path).read_bytes())
    planes = []
    for plane in space.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        names = {e.key: e.value.name for e in plane.event_metadata}
        scopes = {  # an operation's scope is its metadata's, one per operation
            e.key: str(_stats(e.value.stats, stat_names).get(SCOPE_STAT, ""))
            for e in plane.event_metadata
        } if device else {}
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = []
            for event in line.events:
                name = names.get(event.metadata_id, "")
                start = line.timestamp_ns + event.offset_ps // 1000
                duration = event.duration_ps // 1000
                if device:
                    events.append(
                        [op_kind(name), start, duration, scopes.get(event.metadata_id, "")]
                    )
                elif name.startswith((HARNESS_PREFIX, PROGRAM_PREFIX)):
                    events.append(
                        [name, start, duration, _stats(event.stats, stat_names)]
                    )
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def fold_scope(scope: str) -> str:
    """``jit(fused)/BertEncoder/block_3/MultiHeadSelfAttention_0/attend/dot_general:``
    -> ``jit(fused)/BertEncoder/block_*/MultiHeadSelfAttention_0/attend``:
    without the operation's own name (and the ``:`` XLA ends a ``tf_op``
    with), the twelve blocks under one."""
    path = scope.rsplit(":", 1)[0].rpartition("/")[0]
    return re.sub(r"\bblock_\d+", "block_*", path) or "(no scope)"


# -------------------------------------------------------------- reduction
def _host_spans(flat: dict, prefix: str) -> list[tuple[str, int, int, dict]]:
    spans = []
    for plane in flat["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur, attrs in line["events"]:
                if name.startswith(prefix):
                    spans.append((name[len(prefix):], start, start + dur, attrs))
    return sorted(spans, key=lambda s: s[1])


def busy_inside(merged: list[tuple[int, int]], lo: int, hi: int) -> int:
    """Nanoseconds of the (sorted, disjoint) busy intervals inside lo..hi."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def self_times(ops: list[tuple[str, int, int]]) -> list[tuple[str, int]]:
    """(scope, nanoseconds of its own) per operation. An operation that
    holds others (a ``while`` and its body's) is on the ``XLA Ops`` line
    together with them, so each instant goes to the innermost one and the
    sum is the busy time, not more."""
    ordered = sorted(ops, key=lambda op: (op[1], -op[2]))
    own = [e - s for _, s, e in ordered]
    holding: list[int] = []  # the operations that hold the current one
    for i, (_, s, e) in enumerate(ordered):
        while holding and ordered[holding[-1]][2] <= s:
            holding.pop()
        if holding:
            own[holding[-1]] -= min(e, ordered[holding[-1]][2]) - s
        holding.append(i)
    return [(scope, ns) for (scope, _, _), ns in zip(ordered, own)]


def _innermost_by_segment(spans, lo: int, hi: int):
    """Cut lo..hi at every span edge; for each piece the name of the
    shortest span that covers it (a stage on a pipeline thread is inside
    the sweep though another thread opened it), or ``NO_SPAN``."""
    edges = sorted({lo, hi, *(t for _, s, e, _ in spans for t in (s, e))})
    names = []
    for a, b in zip(edges, edges[1:]):
        covering = [s for s in spans if s[1] <= a and b <= s[2]]
        names.append(
            min(covering, key=lambda s: s[2] - s[1])[0] if covering else NO_SPAN
        )
    return edges, names


def reduce_profile(flat: dict, pid: int | None = None) -> dict | None:
    """``None`` where the profile holds no ``bench:window`` span or no
    ``mlops:bulk.job`` span (of process ``pid``, where given)."""
    windows = [s for s in _host_spans(flat, HARNESS_PREFIX) if s[0] == WINDOW]
    if not windows:
        return None
    _, lo, hi, _ = windows[0]
    spans = [  # clipped to the window
        (name, max(s, lo), min(e, hi), attrs)
        for name, s, e, attrs in _host_spans(flat, PROGRAM_PREFIX)
        if e >= lo and s <= hi
    ]
    job_spans = [
        s for s in spans if s[0] == JOB and (pid is None or s[3].get("pid") == pid)
    ]
    if not job_spans:
        return None

    # ------------------------------------------------------------ device
    devices = []
    for plane in flat["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        ops = [
            (scope, max(s, lo), min(s + d, hi))
            for line in plane["lines"] if line["name"] == OPS_LINE
            for _, s, d, scope in line["events"]
            if min(s + d, hi) > max(s, lo)
        ]
        if ops:
            devices.append((ops, union_intervals([(s, e) for _, s, e in ops])))
    share = 1e9 * max(1, len(devices))  # ns summed over devices -> mean seconds
    busy_ns = sum(e - s for _, merged in devices for s, e in merged)

    by_scope: dict[str, int] = defaultdict(int)
    idle_by_span: dict[str, int] = defaultdict(int)
    edges, names = _innermost_by_segment(spans, lo, hi)
    for ops, merged in devices:
        for scope, ns in self_times(ops):
            by_scope[fold_scope(scope)] += ns
        gap_edges = [lo, *(t for pair in merged for t in pair), hi]
        for a, b in zip(gap_edges[0::2], gap_edges[1::2]):
            i = bisect.bisect_right(edges, a) - 1
            while a < b:  # a gap may run over several spans' edges
                cut = min(b, edges[i + 1])
                idle_by_span[names[i]] += cut - a
                a, i = cut, i + 1

    # -------------------------------------------------------------- jobs
    jobs = []
    for _, j_lo, j_hi, attrs in job_spans:
        mine = {
            name: (s, e, a) for name, s, e, a in spans
            if a.get("job") == attrs.get("job") and name.startswith("bulk.")
        }
        seconds = {"job": (j_hi - j_lo) / 1e9}
        for phase in PHASES:
            if f"bulk.{phase}" in mine:
                s, e, _ = mine[f"bulk.{phase}"]
                seconds[phase] = (e - s) / 1e9
        job = {"attrs": attrs, "seconds": seconds,
               "compile_events": mine.get(EVENTS, (0, 0, None))[2]}
        if devices and "bulk.warmup" in mine:
            s, e, _ = mine["bulk.warmup"]
            job["warmup_busy_s"] = (
                sum(busy_inside(merged, s, e) for _, merged in devices) / share
            )
        jobs.append(job)

    def ranked(table: dict[str, int]) -> list[list]:
        return [[k, v / share] for k, v in sorted(table.items(), key=lambda kv: -kv[1])]

    return {
        "window_s": (hi - lo) / 1e9,
        # the harness's own span around each unit of work, to hold the
        # program's phases against
        "harness_job_s": [
            (min(e, hi) - max(s, lo)) / 1e9
            for name, s, e, _ in _host_spans(flat, HARNESS_PREFIX) if name == "job"
        ],
        "devices": len(devices),
        "busy_s": busy_ns / share if devices else None,
        "jobs": jobs,
        "idle_by_span": ranked(idle_by_span),
        "device_by_scope": ranked(by_scope),
    }


def mean_per_job(program: dict | None, phases: tuple[str, ...]) -> float | None:
    """Mean over the window's jobs of the seconds of ``phases``; ``None``
    where no job has them all."""
    if program is None:
        return None
    sums = [
        sum(job["seconds"][p] for p in phases)
        for job in program["jobs"] if all(p in job["seconds"] for p in phases)
    ]
    return sum(sums) / len(sums) if sums else None


# ------------------------------------------------------ this run's profile
def print_tables(program: dict) -> None:
    """To standard error: each job's seconds and compile counter, idle
    seconds by program span, device seconds by scope (top ``TOP``)."""
    lines = []
    for job in program["jobs"]:
        counted = job["compile_events"] or {}
        parts = [" ".join(f"{k} {v:.3f}" for k, v in job["seconds"].items())]
        if "warmup_busy_s" in job:
            parts.append(f"warm-up device {job['warmup_busy_s']:.3f}")
        parts.append(" ".join(
            f"{k} {v}" for k, v in counted.items() if k not in ("job", "programs")
        ))
        lines.append(f"job {job['attrs'].get('job')}: " + " | ".join(parts))
    lines.append("bench:job " + " ".join(f"{s:.3f}" for s in program["harness_job_s"]))
    busy = program["busy_s"]
    if busy is not None:
        idle = program["window_s"] - busy
        lines.append(f"idle seconds by program span (window {program['window_s']:.3f} s, "
                     f"idle {idle:.3f} s):")
        lines += [
            f"  {name:<28}{seconds:9.4f}  {100 * seconds / max(idle, 1e-12):5.1f}%"
            for name, seconds in program["idle_by_span"][:TOP]
        ]
        lines.append(f"device seconds by scope (busy {busy:.3f} s):")
        lines += [
            f"  {name:<64}{seconds:9.4f}  {100 * seconds / max(busy, 1e-12):5.1f}%"
            for name, seconds in program["device_by_scope"][:TOP]
        ]
    print("\n".join(lines), file=sys.stderr)


@functools.lru_cache(maxsize=4)
def _reduced(path: Path, pid: int) -> dict | None:
    try:
        flat = load_profile(path)
    except (OSError, ValueError, RuntimeError):
        return None  # another run's profile, half written or gone
    program = reduce_profile(flat, pid)
    if program is not None:
        print_tables(program)
    return program


def load(facts: dict) -> dict | None:
    """The reduction of THIS run's profile, or ``None``: where the program
    wrote no ``mlops:`` spans, and where ``trace_reduce`` found no device
    plane (``facts["trace"]`` is ``None``: a rehearsal on the CPU, whose
    host clock gives no number to write under a device trace's name)."""
    if facts["trace"] is None:
        return None
    found = Path(tempfile.gettempdir()).glob("bench-trace-*/plugins/profile/*/*.xplane.pb")
    for path in sorted(found, key=lambda p: p.stat().st_mtime, reverse=True):
        program = _reduced(path, os.getpid())
        if program is not None:
            return program
    return None

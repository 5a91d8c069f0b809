"""From a profiler trace to the device's busy time, its top operations and
its idle gaps: the one reduction every PR's traced run goes through.

A trace is first flattened to plain data (``load_xplane``), so that the
reduction can be checked on a small recorded trace kept as JSON beside the
tests::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [["fusion.3", start_ns, duration_ns], ...]}]}]}

What is read:

- a device is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
  one event for each operation that ran on it, its ``XLA Modules`` line one
  for each program;
- the harness's own spans are ``jax.profiler.TraceAnnotation``s whose names
  start with ``bench:``, on the host plane, on the profiler's clock;
- the traced window is the ``bench:window`` span.

Busy time is the union of the operations' intervals inside the window, the
mean over the devices that ran anything. An idle gap is a stretch of the
window in which no operation ran; it is named by the innermost harness
span it falls in and by the programs that ran before and after it on the
device, e.g. ``job:jit_fused>jit_fused`` (between two runs of the chunk
program) or ``job:jit_fused>end`` (after a job's last program). The
harness's spans and the first device's program runs are handed on too
(``spans``, ``programs``; seconds from the window's start), for a reader
that has to tell one run of a program from another.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX, WINDOW = "bench:", "window"  # the traced window is bench:window
TOP = 10


def load_xplane(path: str | Path) -> dict:
    """Flatten an ``.xplane.pb``: device planes whole, of the host planes
    only the harness's spans (the rest is large and read by nothing)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [
                [e.name, int(e.start_ns), int(e.duration_ns)]
                for e in line.events
                if device or e.name.startswith(SPAN_PREFIX)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def find_xplane(trace_dir: str | Path) -> Path | None:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def _events(plane: dict, line_name: str) -> list[list]:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def union_intervals(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def _clip(events: list[list], lo: int, hi: int) -> list[tuple[str, int, int]]:
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e))
    return out


def op_kind(raw: str) -> str:
    """``%reshape.266 = bf16[4096,48,...] reshape(...)`` -> ``reshape``: the
    operation's own name without the number XLA gives each instance, so that
    the twelve layers' copies of one operation add up under one name."""
    return re.sub(r"\.\d+$", "", raw.split(" = ")[0].lstrip("%").strip())


def _module_name(raw: str) -> str:
    """``jit_fused(1234567)`` -> ``jit_fused``: drop the run's fingerprint."""
    return re.sub(r"\(.*\)$", "", raw).strip()


def harness_spans(trace: dict) -> list[tuple[str, int, int]]:
    spans = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    spans.append((name[len(SPAN_PREFIX):], start, start + dur))
    return spans


def reduce_trace(trace: dict) -> dict | None:
    """``None`` where the trace holds no device operation or no window."""
    spans = harness_spans(trace)
    windows = [s for s in spans if s[0] == WINDOW]
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not windows or not devices:
        return None
    _, lo, hi = windows[0]
    inner = [s for s in spans if s[0] != WINDOW]

    def span_of(t: int) -> str:
        holding = [s for s in inner if s[1] <= t < s[2]]
        if not holding:
            return WINDOW
        return min(holding, key=lambda s: s[2] - s[1])[0]

    busy_each, op_time, gap_time = [], defaultdict(int), defaultdict(int)
    programs: list[list] = []
    for plane in devices:
        ops = _clip(_events(plane, OPS_LINE), lo, hi)
        if not ops:
            continue
        merged = union_intervals([(s, e) for _, s, e in ops])
        busy_each.append(sum(e - s for s, e in merged))
        for name, s, e in ops:
            op_time[op_kind(name)] += e - s
        modules = sorted(
            (s, e, _module_name(name))
            for name, s, e in _clip(_events(plane, MODULES_LINE), lo, hi)
        )
        if len(busy_each) == 1:
            programs = [[name, (s - lo) / 1e9, (e - lo) / 1e9] for s, e, name in modules]
        edges = [lo, *[t for pair in merged for t in pair], hi]
        for start, end in zip(edges[0::2], edges[1::2]):
            if end <= start:
                continue
            before = [m for m in modules if m[0] <= start]
            after = [m for m in modules if m[1] >= end]
            if before and after and before[-1] == after[0]:
                where = f"in>{before[-1][2]}"  # a gap inside one program
            else:
                where = (
                    f"{before[-1][2] if before else 'start'}"
                    f">{after[0][2] if after else 'end'}"
                )
            gap_time[f"{span_of((start + end) // 2)}:{where}"] += end - start
    if not busy_each:
        return None

    def top(table: dict) -> list[list]:
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, ns / 1e9 / len(busy_each)] for name, ns in ranked]

    return {
        "busy_s": sum(busy_each) / len(busy_each) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(busy_each),
        "device_ops": top(op_time),
        "idle_gaps": top(gap_time),
        "spans": [[name, (s - lo) / 1e9, (e - lo) / 1e9] for name, s, e in sorted(inner, key=lambda s: s[1])],
        "programs": programs,
    }

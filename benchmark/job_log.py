"""The records the program's bulk jobs leave in their process, read after
the window: ``mlops_tpu.parallel.bulk.job_log()`` (since PR 35), oldest
first, one plain dict a job (``job``, ``rows``, ``chunks``, ``started``,
``wall_s``, ``phases``, ``compile_events``, ``stages`` with the executor's
queue waits by side, ``pauses``).

The drivers drop the result of set-up's warm-up job, and that job runs
before ``run.py`` opens the profiler session, so its spans are in no
trace: its record is the one account of it. Set-up's jobs are the records
that PRECEDE the window's; the window's are the last
``len(facts["driver"].jobs)``.

``load`` gives ``None``, and every reader of this module then returns
``None``: for a program without ``job_log`` (a parent of before PR 35),
and, like ``program_trace.load``, where ``facts["trace"]`` is ``None`` (a
rehearsal on the CPU: its host's seconds are no chip's)."""

from __future__ import annotations

import json
import sys

_printed: set[tuple[int, float]] = set()  # (job, started) of records shown


def split(records: list[dict], window_jobs: int) -> dict[str, list[dict]]:
    """``records`` (oldest first) as set-up's jobs and the window's."""
    cut = max(0, len(records) - window_jobs)
    return {"setup": records[:cut], "window": records[cut:]}


def _print_once(jobs: dict[str, list[dict]]) -> None:
    """To standard error: set-up's records whole, and of each of the
    window's jobs the waits by stage, each record once a run."""
    lines = []
    for record in jobs["setup"]:
        if (record["job"], record["started"]) not in _printed:
            lines.append("set-up job record: " + json.dumps(record))
    for record in jobs["window"]:
        if (record["job"], record["started"]) in _printed:
            continue
        waits = ", ".join(
            f"{name} in {stage['wait_in_s']:.4f}"
            f" (max {stage['max_wait_in_s']:.4f} at {stage['max_wait_in_at']})"
            f" out {stage['wait_out_s']:.4f}"
            f" (max {stage['max_wait_out_s']:.4f} at {stage['max_wait_out_at']})"
            for name, stage in record["stages"].items()
        )
        lines.append(
            f"job {record['job']} wall {record['wall_s']:.4f} s, pauses "
            f"{record['pauses']}, queue waits by stage: {waits}"
        )
    _printed.update((r["job"], r["started"]) for part in jobs.values() for r in part)
    if lines:
        print("\n".join(lines), file=sys.stderr)


def load(facts: dict) -> dict[str, list[dict]] | None:
    if facts["trace"] is None:
        return None
    from mlops_tpu.parallel import bulk

    read = getattr(bulk, "job_log", None)
    if read is None:
        return None
    jobs = split(read(), len(facts["driver"].jobs))
    _print_once(jobs)
    return jobs


def setup_sum(facts: dict, value) -> float | None:
    """Sum of ``value(record)`` over set-up's jobs; ``None`` where there is
    no record of one."""
    jobs = load(facts)
    if jobs is None or not jobs["setup"]:
        return None
    return sum(value(record) for record in jobs["setup"])

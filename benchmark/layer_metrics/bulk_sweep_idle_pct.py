"""Share of the traced window in which the device sat idle BETWEEN the
chunks of a job's pipelined sweep: a chunk waiting for its input (slice,
host-to-device copy, dispatch). A job's chunk program is the program with
the most device time inside its ``job`` span; the sweep is its LAST
ceil(rows_per_file / score_chunk_rows) runs there, so the in-call warm-up
run before them, and the wait between it and the sweep's first chunk, are
the bulk job's start-up and are not counted here
(``bulk_job_overhead_pct`` has them). Copies between host and device are
not operations on the device's ``XLA Ops`` line, so a sweep that waits for
its inputs shows here."""

from collections import defaultdict


def read(facts):
    trace = facts["trace"]
    if trace is None:
        return None
    rows = int(facts["traffic"]["rows_per_file"])
    chunk = int(facts["config"]["deployment"]["score_chunk_rows"])
    chunks = -(-rows // chunk)
    waited, sweeps = 0.0, 0
    for name, lo, hi in trace["spans"]:
        if name != "job":
            continue
        runs = [p for p in trace["programs"] if lo <= p[1] and p[2] <= hi]
        seconds = defaultdict(float)
        for program, start, end in runs:
            seconds[program] += end - start
        if not seconds:
            continue
        chunk_program = max(seconds, key=seconds.get)
        sweep = [p for p in runs if p[0] == chunk_program][-chunks:]
        if len(sweep) < chunks:
            continue
        waited += sum(max(0.0, b[1] - a[2]) for a, b in zip(sweep, sweep[1:]))
        sweeps += 1
    if not sweeps:
        return None
    return 100.0 * waited / trace["window_s"]

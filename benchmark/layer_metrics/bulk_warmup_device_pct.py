"""Share of the device's busy time that runs inside the jobs'
``mlops:bulk.warmup`` spans: the in-call warm-up run of the chunk program,
whose rows count for nothing (it is why ``bulk_program_mfu_pct`` reads lower
in the cell with fewer chunks a job, though the program is the same). Busy
seconds are the union of the ``XLA Ops`` intervals, as in
``trace_reduce``. ``None`` where the program writes no such span or the
profile holds no device."""

from benchmark import program_trace


def read(facts):
    program = program_trace.load(facts)
    if program is None or not program["busy_s"]:
        return None
    inside = [job["warmup_busy_s"] for job in program["jobs"] if "warmup_busy_s" in job]
    if not inside:
        return None
    return 100.0 * sum(inside) / program["busy_s"]

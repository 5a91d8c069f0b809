"""Share of the window that bulk jobs spend outside their pipelined sweep:
scorer build, executable load or re-trace, the in-call warm-up chunk, the
drift sample, and the time between jobs. 1 - sum of the program's own
``BulkScoreResult.elapsed_s`` over the window's wall time."""


def read(facts):
    jobs = facts["driver"].jobs
    if not jobs:
        return None
    sweep = sum(job["sweep_s"] for job in jobs)
    return 100.0 * (1.0 - sweep / facts["window"]["window_s"])

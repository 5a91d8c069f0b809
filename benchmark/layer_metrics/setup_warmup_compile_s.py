"""Seconds set-up's warm-up job spent tracing, lowering, and compiling or
fetching executables from JAX's persistent cache, by the program's own
compile counter: ``trace_s + lower_s + backend_compile_s`` of the
``compile_events`` in the job's record (``benchmark/job_log.py``), summed
over set-up's jobs. ``cache_retrieval_s`` is not added:
``backend_compile_s`` holds it (``bulk_job_retrace_s`` says why). The
inside of ``setup_compile_s``, which is the harness's clock round the same
job: that job also runs a chunk of zeros, a sweep and a drift sample.
``None`` where the program keeps no job log."""

from benchmark import job_log

PARTS = ("trace_s", "lower_s", "backend_compile_s")


def read(facts):
    return job_log.setup_sum(
        facts, lambda record: sum(float(record["compile_events"][p]) for p in PARTS)
    )

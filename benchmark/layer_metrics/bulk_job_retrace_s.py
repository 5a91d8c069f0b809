"""Seconds a bulk job spends tracing, lowering, and compiling or fetching
executables from JAX's persistent cache, by the program's own compile
counter (``mlops_tpu/compilecache/events.py``, `jax.monitoring` listeners):
``trace_s + lower_s + backend_compile_s`` of the job's
``mlops:bulk.compile_events`` marker, mean over the window's jobs.
``cache_retrieval_s`` is not added: JAX reads its cache inside the interval
it reports as ``backend_compile_duration``, so ``backend_compile_s`` holds
it already (on a hit it is little else). The part of ``bulk_job_start_s``
(and of the drift sample's eager operators) that a chunk program kept from
job to job would not pay. Read from the traced run's profile; ``None``
where the program writes no such marker."""

from benchmark import program_trace

PARTS = ("trace_s", "lower_s", "backend_compile_s")


def read(facts):
    program = program_trace.load(facts)
    if program is None:
        return None
    counted = [job["compile_events"] for job in program["jobs"] if job["compile_events"]]
    if not counted:
        return None
    return sum(float(ev[part]) for ev in counted for part in PARTS) / len(counted)

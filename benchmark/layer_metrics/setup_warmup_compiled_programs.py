"""Programs the backend compiled in set-up's warm-up job though JAX's
persistent cache was asked for them: ``cache_requests - cache_hits`` of the
``compile_events`` in the job's record (``benchmark/job_log.py``), summed
over set-up's jobs. ``cache_requests`` counts JAX's event
``/jax/compilation_cache/compile_requests_use_cache``. ``cache_misses``
would not do: JAX records a miss only where it stores the executable, so a
program under the cache's minimum compile time, compiled anew in every
process and never stored (the drift sample's eager operators), counts as
neither hit nor miss. ``None`` where the program keeps no job log."""

from benchmark import job_log


def read(facts):
    return job_log.setup_sum(
        facts,
        lambda record: int(record["compile_events"]["cache_requests"])
        - int(record["compile_events"]["cache_hits"]),
    )

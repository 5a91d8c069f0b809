"""Seconds a bulk job spends before its sweep's first chunk: the program's
``mlops:bulk.build`` span (a new ``jax.jit``, the weights, monitor and
temperature placed on the device) plus its ``mlops:bulk.warmup`` span (the
in-call warm-up call: trace, lower, executable from JAX's cache or a
compile, one run of the chunk program), mean over the window's jobs. Read
from the traced run's profile (``benchmark/program_trace.py``); ``None``
where the program writes no such spans."""

from benchmark import program_trace


def read(facts):
    return program_trace.mean_per_job(program_trace.load(facts), ("build", "warmup"))

"""Driver ``bulk_ssm_token_histories``: ``bulk_histories`` for a token-level
history scorer with state-space layers and no router (family
``falcon_h1``: 3.9 B parameters in bfloat16, one leaf of 1.3 B elements).

What differs from ``bulk_histories`` (everything else, the whole-history
check, the window, the job records, the comparison and its numbers, is
that driver's and ``bulk_files``', unchanged):

- the weights are filled a GROUP of leaves at a time, by
  ``bulk_token_histories.make_weights_by_group`` (that driver's file,
  loaded by path): a leaf of more than 50 M elements by itself, the other
  leaves of a block together, each group through ``inputs.make_weights``'
  own rules. Nothing of that driver's bias fit is taken: there is no
  router;
- the three per-head leaves of every state-space mixer are then SET, from
  the seed (``state_space_leaves``), as Mamba-2's reference
  initialisation has them and as the issue fixed them: ``a_log/bias =
  log(1..H)``, ``dt_bias/bias`` the inverse softplus of a ``dt`` drawn
  log-uniformly from [0.001, 0.1], ``skip/scale`` (the ``D`` of ``y = H C
  + D x``) 1. Under ``inputs.make_weights``' rule for a leaf named
  ``bias`` (0 +- 0.1) ``dt`` would sit near softplus(0) = 0.69 with ``A``
  near -1: every state would halve each token and what one chunk hands
  on would arrive 2^-128 small. As set, a head's state decays by
  exp(-0.001) to exp(-3.2) a token and the slow heads remember a whole
  history. They are weights like any other, handed to program and
  reference alike (as ``bulk_token_histories`` hands both its fitted
  bias). What that does NOT buy at the published widths in bfloat16:
  under seeded kernels and the published muP multipliers ``B`` and ``C``
  come out near 0.06 and 0.08 a channel, so a head's state is 0.2% (head
  31) to 4.5% (head 0) of the skip's ``x``, and a program whose chunks
  start from a ZERO state reads inside the cell's limits
  (``cells/falcon-h1-34b.bulk-hist.json`` ``blind_spots``); that fault is
  held by the float32 tests at the rehearsal's size.
- after the window the driver prints every job's wall seconds and the
  program's own record of each SLOW job (``window``): one job in some
  hundred takes 1 to 1.5 s longer than its 2.48 s, which puts a set of six
  runs over half the bound, and until a record shows where such a job sat
  the cause is unknown (PERF.md section 7, "the slow process").

Traffic parameters and configuration keys: as ``bulk_histories``.
"""

from __future__ import annotations

import functools
import json
import statistics
import sys
from pathlib import Path
from unittest import mock

import numpy as np

from benchmark import run

DT_RANGE = (0.001, 0.1)  # Mamba-2's dt_min, dt_max
SLOW_JOB = 1.04  # times the window's median wall seconds: jobs read 2.476-2.490 s

_token_histories = run.load_module(Path(__file__).with_name("bulk_token_histories.py"))
_bulk_histories = _token_histories._bulk_histories


def state_space_leaves(weights, seed: int):
    """``weights`` with every block's ``a_log``, ``dt_bias`` and ``skip``
    set as the module's docstring says, each layer's ``dt`` from a stream
    of its own; a block without them is left as it is."""
    import jax.numpy as jnp

    params = dict(weights["params"])
    for index, name in enumerate(sorted(params)):
        block = params[name]
        if "a_log" not in block:
            continue
        was = block["dt_bias"]["bias"]
        heads = was.shape[0]
        rng = np.random.default_rng([seed & 0x7FFFFFFF, 39, index])
        dt = np.exp(rng.uniform(*np.log(DT_RANGE), size=heads))
        params[name] = {
            **block,
            "a_log": {"bias": jnp.asarray(np.log(np.arange(1, heads + 1)), was.dtype)},
            "dt_bias": {"bias": jnp.asarray(dt + np.log(-np.expm1(-dt)), was.dtype)},
            "skip": {"scale": jnp.ones(heads, was.dtype)},
        }
    return {"params": params}


class Driver(_bulk_histories.Driver):
    def setup(self) -> None:
        from benchmark import inputs

        by_group = functools.partial(
            _token_histories.make_weights_by_group, inputs.make_weights
        )
        # ``bulk_files``' set-up under ``bulk_token_histories``' generator
        with mock.patch.object(inputs, "make_weights", by_group):
            _bulk_histories._bulk_files.Driver.setup(self)
        with self.ctx.phase("weights"):
            self.weights = state_space_leaves(self.weights, self.ctx.seed)
            self.bundle.variables = self.weights

    def window(self, seconds: float, max_units: int | None = None) -> dict:
        """The window, then to standard error every job's wall seconds and,
        whole, the program's own record (``parallel/bulk.py job_log``) of
        each job that took over ``SLOW_JOB`` times the window's median: in
        this cell nothing in a job depends on the seed, so such a job is the
        machine's or the process's doing and its record says where it sat."""
        from mlops_tpu.parallel import bulk

        out = super().window(seconds, max_units)
        walls = [job["wall_s"] for job in self.jobs]
        print("wall seconds of the window's jobs: "
              + " ".join(f"{wall:.3f}" for wall in walls), file=sys.stderr)
        for record in bulk.job_log()[-len(walls):]:
            if record["wall_s"] > SLOW_JOB * statistics.median(walls):
                print("slow job record: " + json.dumps(record), file=sys.stderr)
        return out


def build(ctx) -> Driver:
    return Driver(ctx)

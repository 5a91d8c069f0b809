"""Driver ``bulk_histories``: ``bulk_files`` for a model that reads
consecutive records of the file as one account's history (family
``evabyte``: every ``records_per_history`` rows from row 0 are one
history, the last may be shorter, every record gets its own answer).

What differs from ``bulk_files`` (everything else, the window, the job
records, the comparison and its numbers, is that driver's, unchanged):

- the check's sample is made of WHOLE histories, ``check_histories`` of
  them: always the first, the (short) last one and the one that ends the
  first chunk, the rest seeded; ``reference_outputs`` hands their rows,
  history by history, to the plain reference, which takes whole histories
  (an answer depends on every record before it in its history, so a
  sample of single rows could not be recomputed);
- the weights are filled one top-level subtree of the parameter tree at a
  time: ``inputs.make_weights`` draws the whole tree as ONE flat normal
  array and cuts it, which at 1.62 B parameters is 6.5 GB flat beside
  6.5 GB of leaves, most of the chip, and would be what
  ``memory_peak_bytes`` reads. Each subtree gets that function's own
  rules and a stream of its own (the seed's high word moved by the
  subtree's index), the eight blocks one compiled program.

Traffic parameters: ``rows_per_file``, ``data``, ``check_histories``,
``outlier_check_rows``, ``traced_units``. From the configuration:
``records_per_history`` and ``deployment`` as ``bulk_files`` reads it.
"""

from __future__ import annotations

import functools
from pathlib import Path
from unittest import mock

import numpy as np

from benchmark import run


def make_weights_by_subtree(fill, abstract_tree, seed: int):
    """``fill`` (``inputs.make_weights``) over each top-level subtree of
    ``params`` in turn (name order), so that no more than one subtree's
    flat draw is ever resident beside the leaves."""
    return {
        "params": {
            name: fill(subtree, seed + (index << 33))
            for index, (name, subtree) in enumerate(
                sorted(abstract_tree["params"].items())
            )
        }
    }


# the driver this one extends, loaded by path as ``run.py`` loads drivers
# (``drivers/`` is a directory of files, not a package)
_bulk_files = run.load_module(Path(__file__).with_name("bulk_files.py"))


class Driver(_bulk_files.Driver):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.per_history = int(self.spec["records_per_history"])
        if self.chunk % self.per_history:
            raise SystemExit("score_chunk_rows is not whole histories")

    def setup(self) -> None:
        from benchmark import inputs

        by_subtree = functools.partial(make_weights_by_subtree, inputs.make_weights)
        with mock.patch.object(inputs, "make_weights", by_subtree):
            super().setup()

    def _check_sample(self) -> np.ndarray:
        """Row numbers of ``check_histories`` whole histories, ascending,
        so that the short last history comes last."""
        per = self.per_history
        histories = -(-self.rows // per)
        want = min(int(self.traffic["check_histories"]), histories)
        forced = list(dict.fromkeys([0, histories - 1, self.chunk // per - 1]))[:want]
        rng = np.random.default_rng([self.job_seed, 21])
        rest = [h for h in rng.permutation(histories) if h not in forced]
        chosen = sorted(forced + rest[: want - len(forced)])
        return np.concatenate(
            [np.arange(h * per, min((h + 1) * per, self.rows)) for h in chosen]
        ).astype(np.int64)


def build(ctx) -> Driver:
    return Driver(ctx)

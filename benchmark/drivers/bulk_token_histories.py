"""Driver ``bulk_token_histories``: ``bulk_histories`` for a history
scorer with sparse experts whose parameter tree no single draw can hold
(family ``kimi_k2``: 5.5 B parameters in bfloat16, one leaf of 352 M
elements).

What differs from ``bulk_histories`` (everything else, the whole-history
check, the window, the job records, the comparison and its numbers, is
that driver's and ``bulk_files``', unchanged):

- the weights are filled a GROUP of leaves at a time: ``bulk_histories``
  fills a top-level subtree through one flat float32 draw, which for an
  expert layer of 1.2 B parameters would be 4.8 GB flat beside 8.5 GB of
  leaves. A leaf of more than ``ALONE`` elements (the held experts'
  stacked matrices, 352 M each, the dense FFN's, the output projections,
  the embedding) is a group of its own, which the compiler fuses into one
  pass with no flat array at all; the other leaves of a top-level subtree
  go together, 89 M elements at most: a group's flat draw has to stay
  under the chunk program's own scratch (1.2 GB), because
  ``memory_peak_bytes`` adds the largest scratch any program of the
  process reserved to what is resident at the end (a 2 GB draw for the
  dense layer read 13.2 GB where the job holds 12.2). Each group goes through ``inputs.make_weights``' own rules
  (by each leaf's last two names) as a tree of its own, with a stream of
  its own (the seed's high word moved by the group's index); equal groups
  (the four expert layers') are one compiled program, 28 calls in all
  where a leaf at a time was 85 and 68 s of set-up on the chip;
- the router's selection bias is FITTED to the seed's weights and rows
  (``balance_selection_bias``), as the source's training fits it
  (``topk_method`` noaux_tc: the bias is moved until the experts' loads
  are even): with weights and a bias drawn from a seed, a few experts take
  most tokens (up to nine times an even share on 1,228 records of 559
  token types) and the held experts' load, hence the job's time, swings
  with the seed. Layer by layer, on the file's first history, from the
  scores the float32 REFERENCE computes (``reference/kimi_k2.py forward``,
  ``refit``): the bias of expert i becomes minus the (1 - k / E) quantile
  of its scores over the history's tokens, so that each expert would
  clear one common bar on k / E of them; centred. Nothing of the program
  under test enters the fit: the weights are the seed's alone, whatever a
  later change does to the program's rounding. The program and the
  reference are handed the same fitted bias: it is a weight like any
  other;
- each job's record also keeps the program's routing counter
  (``BulkScoreResult.routing``: assignments per held expert and layer,
  and the (run, expert) pairs in which an expert was read), which
  ``layer_metrics/moe_experts_roofline_pct.py`` reads; ``None`` from a
  program that counts nothing.

Traffic parameters and configuration keys: as ``bulk_histories``.
"""

from __future__ import annotations

import functools
import importlib
import sys
from pathlib import Path
from unittest import mock

import jax

from benchmark import run


ALONE = 50_000_000  # elements: a larger leaf is filled by itself


def leaf_groups(abstract_tree) -> list[list[int]]:
    """The leaves' indices (tree order) group by group: each leaf of more
    than ``ALONE`` elements by itself, the other leaves of one top-level
    subtree together."""
    groups: dict[tuple, list[int]] = {}
    for index, (path, leaf) in enumerate(jax.tree_util.tree_leaves_with_path(abstract_tree)):
        top = tuple(getattr(p, "key", str(p)) for p in path[:2])
        groups.setdefault((*top, index) if leaf.size > ALONE else top, []).append(index)
    return sorted(groups.values())


def make_weights_by_group(fill, abstract_tree, seed: int):
    """``fill`` (``inputs.make_weights``) over each group in turn, every
    leaf handed over under its last two names, which are what ``fill``'s
    rules read."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    names = [tuple(getattr(p, "key", str(p)) for p in path[-2:]) for path, _ in leaves]
    filled = [None] * len(leaves)
    for number, group in enumerate(leaf_groups(abstract_tree)):
        tree = {  # keyed in tree order: the flat draw is cut in the keys' order
            f"{index:04d}": {names[index][0]: {names[index][1]: leaves[index][1]}}
            for index in group
        }
        out = fill(tree, seed + (number << 33))
        for index in group:
            filled[index] = out[f"{index:04d}"][names[index][0]][names[index][1]]
    return jax.tree_util.tree_unflatten(treedef, filled)


def balance_selection_bias(reference, weights, cat, num, spec: dict):
    """``weights`` with each expert layer's ``router/bias`` refitted, layer
    by layer (a layer's scores depend on the biases before it), to the
    reference's float32 scores on the rows ``cat``, ``num`` (one history)."""
    import jax.numpy as jnp
    import numpy as np

    params = dict(weights["params"])
    top_k = int(spec["model_config"]["experts_per_token"])

    def refit(name: str, scores):
        scores = np.asarray(scores, np.float64)
        bar = np.quantile(scores, 1.0 - top_k / scores.shape[1], axis=0)
        router = params[name]["router"]
        fitted = jnp.asarray(bar.mean() - bar, router["bias"].dtype)
        params[name] = {**params[name], "router": {**router, "bias": fitted}}
        return fitted

    reference.forward(weights, cat, num, spec, refit=refit)
    return {"params": params}


_bulk_histories = run.load_module(Path(__file__).with_name("bulk_histories.py"))


class Driver(_bulk_histories.Driver):
    def __init__(self, ctx):
        super().__init__(ctx)
        self._routing: list = []  # of every job since the window opened

    def setup(self) -> None:
        from benchmark import inputs

        by_group = functools.partial(make_weights_by_group, inputs.make_weights)
        # ``bulk_files``' set-up under this driver's generator
        # (``bulk_histories.setup`` is that same call under its own, which
        # goes subtree by subtree)
        with mock.patch.object(inputs, "make_weights", by_group):
            _bulk_histories._bulk_files.Driver.setup(self)
        with self.ctx.phase("weights"):
            first = slice(0, min(self.per_history, self.rows))
            family = self.spec["model_config"]["family"]
            self.weights = balance_selection_bias(
                importlib.import_module(f"benchmark.reference.{family}"),
                self.weights, self.cat[first], self.num[first], self.spec,
            )
            self.bundle.variables = self.weights

    def _score(self, dataset):
        result = super()._score(dataset)
        self._routing.append(getattr(result, "routing", None))
        return result

    def window(self, seconds: float, max_units: int | None = None) -> dict:
        self._routing = []
        out = super().window(seconds, max_units)
        for job, routing in zip(self.jobs, self._routing):
            job["routing"] = routing
        if self._routing and self._routing[0]:
            print(f"routing of the window's first job: {self._routing[0]}", file=sys.stderr)
        # where a slow run lost its time (one run in ten loses 1.6 s of a
        # window somewhere: PERF.md section 2)
        walls = " ".join(f"{job['wall_s']:.3f}" for job in self.jobs)
        print(f"wall seconds of the window's jobs: {walls}", file=sys.stderr)
        return out


def build(ctx) -> Driver:
    return Driver(ctx)

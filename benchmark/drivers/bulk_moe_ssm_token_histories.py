"""Driver ``bulk_moe_ssm_token_histories``: ``bulk_histories`` for a
token-level history scorer with state-space layers AND sparse experts
(family ``nemotron_h``: 3.9 B parameters in bfloat16, one leaf of 319 M
elements a stacked expert matrix).

It is the two drivers before it put together, each helper called as that
driver's file has it (loaded by path, unedited):

- the weights are filled a GROUP of leaves at a time
  (``bulk_token_histories.make_weights_by_group``);
- the three per-head leaves of every state-space mixer are then SET from
  the seed as Mamba-2's reference initialisation has them
  (``bulk_ssm_token_histories.state_space_leaves``: ``A_log = log(1..H)``,
  ``dt`` log-uniform in [0.001, 0.1], ``D = 1``);
- then each expert layer's selection bias is FITTED to the seed's weights
  and the file's first history (``bulk_token_histories.
  balance_selection_bias``, from the float32 reference's scores): after the
  mixers' leaves, since they move what reaches every later router;
- each job's record keeps the program's routing counter
  (``bulk_token_histories.Driver``), and after the window every job's wall
  seconds and the program's own record of each slow job go to standard
  error (``bulk_ssm_token_histories.Driver``).

Program and reference are handed the same weights, the fitted bias among
them. Traffic parameters and configuration keys: as ``bulk_histories``.
"""

from __future__ import annotations

import functools
import importlib
from pathlib import Path
from unittest import mock

from benchmark import run

_ssm = run.load_module(Path(__file__).with_name("bulk_ssm_token_histories.py"))
_token = _ssm._token_histories  # the one module both drivers' classes come from


class Driver(_ssm.Driver, _token.Driver):
    def setup(self) -> None:
        from benchmark import inputs

        by_group = functools.partial(_token.make_weights_by_group, inputs.make_weights)
        # ``bulk_files``' set-up under ``bulk_token_histories``' generator
        with mock.patch.object(inputs, "make_weights", by_group):
            _token._bulk_histories._bulk_files.Driver.setup(self)
        with self.ctx.phase("weights"):
            weights = _ssm.state_space_leaves(self.weights, self.ctx.seed)
            first = slice(0, min(self.per_history, self.rows))
            family = self.spec["model_config"]["family"]
            self.weights = _token.balance_selection_bias(
                importlib.import_module(f"benchmark.reference.{family}"),
                weights, self.cat[first], self.num[first], self.spec,
            )
            self.bundle.variables = self.weights


def build(ctx) -> Driver:
    return Driver(ctx)

"""Driver ``bulk_files_mesh``: ``bulk_files`` with the job sharded over
the chips of one host, as the ``score-batch`` command runs it on a host
with more than one (`mlops_tpu/commands.py _score_batch`:
``mesh=make_mesh(device_count)``): a ``('data', 'model')`` mesh with every
chip on 'data', the chunk's rows laid over it, the weights replicated.

What differs from ``bulk_files`` (everything else is that driver's,
unchanged):

- ``score_dataset`` is given ``mesh=make_mesh(mesh_chips)`` and
  ``chunk_rows = mesh_chips x`` the configuration's ``score_chunk_rows``:
  the configuration's chunk is what ONE chip's program was sized for
  (``compile_check.py``), and each chip is handed that many rows a run;
- the check's sample holds both sides of every SHARD boundary (each
  multiple of one chip's rows; the larger chunk's boundaries, which
  ``bulk_files`` forces, are every ``mesh_chips``-th of them): a fault at
  the seam between two chips' rows is always compared.

Traffic parameters: ``bulk_files``' and ``mesh_chips`` (the cell's
``chips``; 1 in a CPU rehearsal, which has one device: the path through
``make_mesh`` and the sharded transfer is the same, over one shard).
"""

from __future__ import annotations

from pathlib import Path

from benchmark import run

_bulk_files = run.load_module(Path(__file__).with_name("bulk_files.py"))


class Driver(_bulk_files.Driver):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.mesh_chips = int(self.traffic["mesh_chips"])
        self.chunk *= self.mesh_chips
        self.mesh = None

    def setup(self) -> None:
        from mlops_tpu.parallel import make_mesh

        super().setup()
        self.mesh = make_mesh(self.mesh_chips)

    def _check_sample(self):
        """``bulk_files``' sample with one chip's rows as its chunk."""
        whole, self.chunk = self.chunk, self.chunk // self.mesh_chips
        try:
            return super()._check_sample()
        finally:
            self.chunk = whole

    def _score(self, dataset):
        from mlops_tpu.parallel.bulk import score_dataset

        return score_dataset(
            self.bundle,
            dataset,
            mesh=self.mesh,
            chunk_rows=self.chunk,
            drift_sample=int(self.deploy["score_drift_sample"]),
            seed=self.job_seed,
            exact=True,
            pipeline_depth=int(self.deploy["score_pipeline_depth"]),
            compile_cache=None,
            tier=self.deploy["score_tier"],
        )


def build(ctx) -> Driver:
    return Driver(ctx)

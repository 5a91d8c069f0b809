"""Driver ``bulk_files``: back-to-back bulk scoring jobs through the
program's ``parallel/bulk.py score_dataset``, each over one seeded file.

A new job starts while less than ``--seconds`` have passed; the window ends
when the job in flight completes. The rate is every row of every job over
the whole window: scorer build, the in-call warm-up chunk, the sweep, the
drift sample and any stall included.

Traffic parameters (``benchmark/traffic/<mix>.json``):

- ``rows_per_file``: rows in a job's file;
- ``data``: the generator's parameters (``benchmark/inputs.py``);
- ``check_rows``: how many rows' predictions the reference recomputes (a
  seeded sample that always holds the first and last row and both sides of
  every chunk boundary);
- ``outlier_check_rows``: the outlier flags of that many rows at the end of
  the file (the padded tail chunk among them) are recomputed;
- ``traced_jobs``: jobs a ``--trace 1`` run makes under the profiler.

From the configuration's ``deployment``: ``score_chunk_rows``,
``score_drift_sample``, ``score_pipeline_depth``, ``score_tier``.
"""

from __future__ import annotations

import importlib
import time

import numpy as np


class Driver:
    end_to_end = "bulk_rows_per_s"

    def __init__(self, ctx):
        self.ctx = ctx
        self.spec = ctx.config
        self.traffic = ctx.traffic
        self.deploy = ctx.config["deployment"]
        self.rows = int(self.traffic["rows_per_file"])
        self.chunk = int(self.deploy["score_chunk_rows"])
        self.job_seed = ctx.seed & 0x7FFFFFFF
        self.jobs: list[dict] = []

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from benchmark import inputs
        from mlops_tpu.bundle.bundle import Bundle
        from mlops_tpu.config import ModelConfig
        from mlops_tpu.data.encode import EncodedDataset, Preprocessor
        from mlops_tpu.models import abstract_variables, build_model
        from mlops_tpu.monitor.state import MonitorState
        from mlops_tpu.schema import SCHEMA

        ctx, spec = self.ctx, self.spec
        schema = spec["schema"]
        if list(SCHEMA.cards) != schema["cards"] or SCHEMA.num_numeric != schema["num_numeric"]:
            raise SystemExit("the configuration's schema is not the program's")
        with ctx.phase("data"):
            self.cat, self.num = inputs.make_file(
                ctx.seed, schema, self.traffic["data"], self.rows
            )
            self.monitor_arrays = inputs.fit_monitor_arrays(
                ctx.seed, schema, self.traffic["data"], spec["assumed"]
            )
        with ctx.phase("model"):
            import jax
            import jax.numpy as jnp

            fields = dict(spec["model_config"])
            fields["hidden_dims"] = tuple(fields["hidden_dims"])
            model = build_model(ModelConfig(**fields))
            shapes = abstract_variables(model)
        with ctx.phase("weights"):
            self.weights = inputs.make_weights(shapes, ctx.seed)
            jax.block_until_ready(self.weights)
            ref = self.monitor_arrays["num_ref_sorted"]
            cdf = np.stack(
                [np.searchsorted(row, row, side="right") / row.size for row in ref]
            ).astype(np.float32)
            monitor = MonitorState(
                **{k: jnp.asarray(v) for k, v in self.monitor_arrays.items()},
                num_ref_cdf=jnp.asarray(cdf),
            )
        self.temperature = float(spec["assumed"]["calibration_temperature"])
        zeros = np.zeros(SCHEMA.num_numeric, np.float32)
        self.bundle = Bundle(
            manifest={
                "flavor": "flax",
                "model_config": spec["model_config"],
                "calibration": {"temperature": self.temperature},
            },
            model=model,
            variables=self.weights,
            preprocessor=Preprocessor(zeros, zeros, zeros + 1, SCHEMA.fingerprint()),
            monitor=monitor,
        )
        self.dataset = EncodedDataset(self.cat, self.num)
        self._sample = self._check_sample()

    def _score(self, dataset):
        from mlops_tpu.parallel.bulk import score_dataset

        return score_dataset(
            self.bundle,
            dataset,
            mesh=None,
            chunk_rows=self.chunk,
            drift_sample=int(self.deploy["score_drift_sample"]),
            seed=self.job_seed,
            exact=True,
            pipeline_depth=int(self.deploy["score_pipeline_depth"]),
            compile_cache=None,
            tier=self.deploy["score_tier"],
        )

    def warmup(self) -> None:
        """One job over the fewest rows that use every shape a timed job
        uses: one chunk program, and the drift sample at its own length."""
        from mlops_tpu.data.encode import EncodedDataset

        rows = min(self.rows, max(self.chunk, int(self.deploy["score_drift_sample"])))
        self._score(EncodedDataset(self.cat[:rows], self.num[:rows]))

    # ------------------------------------------------------------ window
    def window(self, seconds: float, max_units: int | None = None) -> dict:
        ctx = self.ctx
        tail = self._outlier_tail()
        start = time.perf_counter()
        while True:
            with ctx.span("job"):
                t0 = time.perf_counter()
                result = self._score(self.dataset)
                wall = time.perf_counter() - t0
            self.jobs.append({
                "wall_s": wall,
                "sweep_s": float(result.elapsed_s),
                "rows": int(result.rows),
                "stages": result.pipeline["stages"],
                "predictions": result.predictions[self._sample].copy(),
                "outliers": result.outliers[tail].copy(),
                "drift": np.asarray(list(result.feature_drift.values()), np.float64),
                "unfinished": int(result.predictions.size != self.rows)
                + int(np.count_nonzero(~np.isfinite(result.predictions))),
            })
            del result
            done = len(self.jobs)
            if max_units is not None and done >= max_units:
                break
            if max_units is None and time.perf_counter() - start >= seconds:
                break
        elapsed = time.perf_counter() - start
        rows = sum(j["rows"] for j in self.jobs)
        return {
            "attempted": len(self.jobs),
            "failed": 0,
            "window_s": elapsed,
            "units": rows,
            "metrics": {self.end_to_end: rows / elapsed},
        }

    def release(self) -> None:
        """Drop what the program holds on the device; the weights stay, they
        are the benchmark's and the reference reads them."""
        self.bundle = None
        self.dataset = None

    # ------------------------------------------------------------- check
    def _check_sample(self) -> np.ndarray:
        rng = np.random.default_rng([self.job_seed, 21])
        want = min(int(self.traffic["check_rows"]), self.rows)
        edges = np.arange(self.chunk, self.rows, self.chunk)
        forced = np.unique(np.concatenate([[0, self.rows - 1], edges - 1, edges]))
        forced = forced[: want]
        rest = np.setdiff1d(
            rng.choice(self.rows, min(self.rows, want), replace=False), forced
        )[: want - forced.size]
        return np.sort(np.concatenate([forced, rest])).astype(np.int64)

    def _outlier_tail(self) -> slice:
        return slice(max(0, self.rows - int(self.traffic["outlier_check_rows"])), self.rows)

    def _drift_rows(self) -> np.ndarray:
        """The rows the job's drift sample holds: a uniform draw without
        replacement under the job's seed, the whole file where it is no
        longer than the sample (the interface ``score_dataset`` documents)."""
        take = min(self.rows, int(self.deploy["score_drift_sample"]))
        if take == self.rows:
            return np.arange(self.rows)
        return np.random.default_rng(self.job_seed).choice(self.rows, take, replace=False)

    def reference_outputs(self, model_precision="f32", monitor_precision="f32") -> dict:
        """What a job has to answer, by the plain reference."""
        from benchmark.reference import monitors

        family = self.spec["model_config"]["family"]
        model_ref = importlib.import_module(f"benchmark.reference.{family}")
        sample = self._sample
        tail = self._outlier_tail()
        idx = self._drift_rows()
        return {
            "predictions": model_ref.predictions(
                self.weights, self.cat[sample], self.num[sample], self.spec,
                self.temperature, precision=model_precision,
            ),
            "outliers": monitors.outlier_flags(
                self.num[tail], self.monitor_arrays, monitor_precision
            )[0],
            # how far, relative to the threshold, each row's exact distance is
            "outlier_margin": np.abs(
                monitors.mahalanobis_sq(self.num[tail], self.monitor_arrays)
                / float(self.monitor_arrays["out_threshold"]) - 1.0
            ),
            "drift": monitors.drift_scores(
                self.cat[idx], self.num[idx], self.monitor_arrays,
                self.spec["schema"]["cards"], monitor_precision,
            ),
            "unfinished": 0,
        }

    def control_outputs(self) -> dict:
        """The control: the reference in the program's place, one precision
        below what the configuration states: 8-bit float operands for the
        bfloat16 products of the model and of the outlier distance,
        bfloat16 values for the float32 inputs of the monitors."""
        return self.reference_outputs(model_precision="fp8", monitor_precision="low")

    @staticmethod
    def altered(expected: dict) -> dict:
        """A sound job with ONE answer altered where it is produced."""
        served = {k: (v.copy() if hasattr(v, "copy") else v) for k, v in expected.items()}
        middle = served["predictions"].size // 2
        p = served["predictions"][middle]
        served["predictions"][middle] = p + 0.25 if p < 0.5 else p - 0.25
        return served

    @staticmethod
    def compare(served: dict, expected: dict) -> dict:
        gap = served["predictions"].astype(np.float64) - expected["predictions"]
        return {
            "pred_rms_gap": float(np.sqrt(np.mean(gap**2))),
            "pred_max_gap": float(np.abs(gap).max()),
            # the farthest from the threshold that a flag still differs: the
            # relative error of the distance, as far as flags can show it
            "outlier_flip_reach": float(
                np.max(
                    expected["outlier_margin"],
                    where=served["outliers"] != expected["outliers"],
                    initial=0.0,
                )
            ),
            "drift_max_gap": float(np.abs(served["drift"] - expected["drift"]).max()),
            "rows_unfinished": float(served["unfinished"]),
        }

    def check(self, expected: dict | None = None) -> dict:
        """The worst reading of each number over every job of the window."""
        expected = expected or self.reference_outputs()
        worst: dict[str, float] = {}
        for job in self.jobs:
            for name, value in self.compare(job, expected).items():
                value = float("inf") if np.isnan(value) else value
                worst[name] = max(worst.get(name, 0.0), value)
        return worst


def build(ctx) -> Driver:
    return Driver(ctx)

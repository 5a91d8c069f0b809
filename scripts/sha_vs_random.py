"""Equal-budget HPO comparison: successive halving vs random search.

The successive-halving evidence: at the SAME total step budget
(trials x steps), SHA should select a better (or equal) validation AUC
than random search, because it reallocates most of the budget to the
candidates that earn it. One JSON line:

    JAX_PLATFORMS=cpu python scripts/sha_vs_random.py

Knobs: SWEEP_TRIALS (default 16), SWEEP_STEPS (default 300), SEEDS
(default 3 comma-separated sweep seeds).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from mlops_tpu.config import HPOConfig, ModelConfig, TrainConfig  # noqa: E402
from mlops_tpu.data import Preprocessor, generate_synthetic  # noqa: E402
from mlops_tpu.train.hpo import run_hpo  # noqa: E402


def main() -> None:
    trials = int(os.environ.get("SWEEP_TRIALS", "16"))
    steps = int(os.environ.get("SWEEP_STEPS", "300"))
    seeds = [
        int(s) for s in os.environ.get("SEEDS", "11,12,13").split(",")
    ]
    columns, labels = generate_synthetic(30_000, seed=5)
    prep = Preprocessor.fit(columns)
    ds = prep.encode(columns, labels)
    idx = np.arange(ds.n)
    train_ds, valid_ds = ds.slice(idx[:24_000]), ds.slice(idx[24_000:])

    model = ModelConfig(family="mlp", hidden_dims=(128, 64), precision="f32")
    tconfig = TrainConfig(batch_size=512)
    rows = {"random": [], "sha": []}
    wall = {"random": 0.0, "sha": 0.0}
    for seed in seeds:
        for strategy in ("random", "sha"):
            hconfig = HPOConfig(
                trials=trials,
                steps=steps,
                seed=seed,
                strategy=strategy,
                eta=2,
                sha_rungs=3,
            )
            t0 = time.perf_counter()
            res = run_hpo(
                model,
                dataclasses.replace(tconfig),
                hconfig,
                train_ds,
                valid_ds,
            )
            wall[strategy] += time.perf_counter() - t0
            rows[strategy].append(
                res.best_metrics["validation_roc_auc_score"]
            )
    budget = trials * steps
    # ACTUAL sha spend, not a re-derivation of run_sha's plan: each trial
    # record carries the steps it had trained when it was eliminated (or
    # finished), so the sum is what the sweep really spent.
    sha_budget = sum(t["steps"] for t in res.trials)
    print(
        json.dumps(
            {
                "metric": "sha_vs_random_auc_delta",
                "value": round(
                    float(np.mean(rows["sha"]) - np.mean(rows["random"])), 5
                ),
                "unit": "auc",
                "budget_steps_random": budget,
                "budget_steps_sha": sha_budget,
                "auc_random": [round(float(v), 5) for v in rows["random"]],
                "auc_sha": [round(float(v), 5) for v in rows["sha"]],
                "wall_s_random": round(wall["random"], 1),
                "wall_s_sha": round(wall["sha"], 1),
                "seeds": seeds,
            }
        )
    )


if __name__ == "__main__":
    main()

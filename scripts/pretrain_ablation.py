"""Does pretraining help? The scripted comparison behind BASELINE config 5.

Same fine-tune budget, same labeled data, same seeds — the ONLY difference
is whether the BERT trunk starts from masked-feature pretraining
(`train/pretrain.py`) or fresh init. Run in the label-scarce regime where
self-supervision earns its keep: plenty of unlabeled rows for the MLM
stage, a small labeled subset for fine-tuning (the reference's setting is
label-rich supervised sklearn, which has no pretrain stage at all —
`01-train-model.ipynb`; this capability is additive).

Reproduce:
    JAX_PLATFORMS=cpu python scripts/pretrain_ablation.py
Prints one JSON line:
    {"auc_scratch": ..., "auc_pretrained": ..., "auc_delta": ...,
     "seeds": N, ...}
with per-seed AUCs; auc_delta > 0 means pretraining helped. The headline
numbers are this script's one JSON line.

Knobs (env): ABLATION_UNLABELED_ROWS (default 40000), ABLATION_LABELED_ROWS
(default 1500), ABLATION_SEEDS (default 3), ABLATION_PRETRAIN_STEPS (600),
ABLATION_FINETUNE_STEPS (300).
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402


def main() -> None:
    import jax

    from mlops_tpu.config import ModelConfig, TrainConfig
    from mlops_tpu.data import Preprocessor, generate_synthetic
    from mlops_tpu.models import build_model, init_params
    from mlops_tpu.train.loop import evaluate, fit
    from mlops_tpu.train.pretrain import fine_tune_params, pretrain_bert

    unlabeled_rows = int(os.environ.get("ABLATION_UNLABELED_ROWS", "40000"))
    labeled_rows = int(os.environ.get("ABLATION_LABELED_ROWS", "1500"))
    seeds = int(os.environ.get("ABLATION_SEEDS", "3"))
    pretrain_steps = int(os.environ.get("ABLATION_PRETRAIN_STEPS", "600"))
    finetune_steps = int(os.environ.get("ABLATION_FINETUNE_STEPS", "300"))

    model_config = ModelConfig(
        family="bert", token_dim=64, depth=2, heads=4, dropout=0.1
    )

    # One shared pool: unlabeled pretraining rows, a labeled fine-tune
    # subset, and a held-out eval split — all from the same generative
    # process. The preprocessor fits on the UNLABELED POOL ONLY (the
    # realistic order: stats exist before labels do, and holdout rows
    # must not leak into the standardization the eval runs under).
    columns, labels = generate_synthetic(unlabeled_rows + 8000, seed=100)
    prep = Preprocessor.fit(
        {k: v[:unlabeled_rows] for k, v in columns.items()}
    )
    ds = prep.encode(columns, labels)
    unlabeled = ds.slice(np.arange(unlabeled_rows))
    holdout = ds.slice(np.arange(unlabeled_rows + 4000, ds.n))

    pretrained = pretrain_bert(
        model_config,
        unlabeled,
        steps=pretrain_steps,
        batch_size=512,
        learning_rate=3e-3,
        seed=7,
    )

    tconfig = TrainConfig(
        batch_size=256,
        steps=finetune_steps,
        eval_every=finetune_steps,
        warmup_steps=finetune_steps // 10,
        learning_rate=1e-3,
    )

    scratch_aucs, pretrained_aucs = [], []
    for seed in range(seeds):
        rng = np.random.default_rng(200 + seed)
        idx = rng.choice(4000, labeled_rows, replace=False) + unlabeled_rows
        labeled = ds.slice(idx)
        run_config = TrainConfig(**{**tconfig.__dict__, "seed": seed})

        model = build_model(model_config)
        for use_pretrain, sink in ((False, scratch_aucs), (True, pretrained_aucs)):
            init_variables = None
            if use_pretrain:
                fresh = init_params(model, jax.random.PRNGKey(seed))
                init_variables = fine_tune_params(pretrained, fresh)
            result = fit(
                model,
                labeled,
                holdout,
                run_config,
                init_variables=init_variables,
            )
            auc = evaluate(model, result.params, holdout)[
                "validation_roc_auc_score"
            ]
            sink.append(float(auc))

    out = {
        "auc_scratch": round(float(np.mean(scratch_aucs)), 4),
        "auc_pretrained": round(float(np.mean(pretrained_aucs)), 4),
        "auc_delta": round(
            float(np.mean(pretrained_aucs) - np.mean(scratch_aucs)), 4
        ),
        "per_seed_scratch": [round(a, 4) for a in scratch_aucs],
        "per_seed_pretrained": [round(a, 4) for a in pretrained_aucs],
        "seeds": seeds,
        "unlabeled_rows": unlabeled_rows,
        "labeled_rows": labeled_rows,
        "pretrain_steps": pretrain_steps,
        "finetune_steps": finetune_steps,
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

"""Bundle save/load: one directory carrying everything serving needs."""

from __future__ import annotations

import dataclasses
import datetime
import json
from pathlib import Path
from typing import Any

import jax

from mlops_tpu.config import ModelConfig
from mlops_tpu.data.encode import Preprocessor
from mlops_tpu.monitor.state import MonitorState
from mlops_tpu.schema.features import SCHEMA
from mlops_tpu.train.checkpoint import restore_tree, tree_bytes
from mlops_tpu.version import __version__

MANIFEST_NAME = "manifest.json"
PARAMS_NAME = "params.msgpack"
BULK_PARAMS_NAME = "bulk_params.msgpack"
QUANT_PARAMS_NAME = "quant_params.npz"
ESTIMATOR_NAME = "estimator.joblib"
PREPROCESS_NAME = "preprocess.npz"
MONITOR_NAME = "monitor.npz"


@dataclasses.dataclass
class Bundle:
    """A loaded bundle: rebuilt model + fitted state, ready to serve.

    Three flavors behind one interface (manifest ``flavor``):
    ``flax`` carries a params pytree for a zoo module; ``sklearn`` carries
    the CPU tree-ensemble floor (BASELINE config 1) — the reference ships
    only the sklearn kind (`02-register-model.ipynb:305-353`); ``doc``
    carries a long-context document model (family bert with
    ``doc_records > 1``: `ModelConfig.reads_documents`,
    `train/long_context.py`) whose inputs are 3-D record HISTORIES
    ``[D, R, C]`` with ONE answer a document — it scores offline via
    ``predict-file``, and `score-batch` and the HTTP endpoint refuse it.
    Family ``evabyte`` also reads histories (``doc_records`` consecutive
    rows) but is a ``flax`` bundle: 2-D rows in, an answer for every
    record, so `score-batch`, ``predict-file`` and `score_dataset` score
    it like any other (chunks rounded to whole histories).
    """

    manifest: dict[str, Any]
    model: Any  # nn.Module (flax flavor) | None
    variables: dict[str, Any]
    preprocessor: Preprocessor
    monitor: MonitorState
    estimator: Any = None  # SklearnBaseline (sklearn flavor) | None
    bulk_model: Any = None  # distilled student (train/distill.py) | None
    bulk_variables: dict[str, Any] | None = None
    quant_params: dict[str, Any] | None = None  # int8/bf16 tier (ops/quant.py)

    @property
    def flavor(self) -> str:
        return self.manifest.get("flavor", "flax")

    @property
    def has_bulk(self) -> bool:
        """True when the bundle carries a distilled bulk student — the
        CPU-backend bulk scorer routes through it (`parallel/bulk.py`);
        serving always uses the exact model."""
        return self.bulk_model is not None

    @property
    def bulk_fidelity(self) -> dict[str, float]:
        return dict(self.manifest.get("bulk", {}).get("fidelity", {}))

    @property
    def has_quant(self) -> bool:
        """True when the bundle carries the int8/bf16 quantized student
        tier (`ops/quant.py`, fitted by `train/distill.py
        distill_quant_student`). Presence alone does NOT make it
        servable — `quant_gates_passed` is the engine's admission check."""
        return self.quant_params is not None

    @property
    def quant_fidelity(self) -> dict[str, float]:
        return dict(self.manifest.get("quant", {}).get("fidelity", {}))

    @property
    def quant_temperature(self) -> float:
        """Post-hoc refit temperature for the quant tier's logits; falls
        back to the exact tier's temperature for old manifests."""
        quant = self.manifest.get("quant", {})
        return float(quant.get("temperature", self.temperature))

    @property
    def quant_gates_passed(self) -> bool:
        """The stamped packaging-time promotion decision
        (`lifecycle/promote.py quant_tier_gates`). Absent block or absent
        decision grades as FAILED — an ungraded tier must not serve."""
        return bool(
            self.manifest.get("quant", {}).get("gates", {}).get("passed", False)
        )

    @property
    def model_config(self) -> ModelConfig:
        return _model_config_from_manifest(self.manifest)

    @property
    def temperature(self) -> float:
        """Fitted calibration temperature (train/calibrate.py); 1.0 when
        the bundle predates calibration or the fit was degenerate."""
        return float(self.manifest.get("calibration", {}).get("temperature", 1.0))


def _model_config_from_manifest(manifest: dict[str, Any]) -> ModelConfig:
    """JSON lists -> tuples so manifests round-trip to equal ModelConfigs."""
    return ModelConfig(**{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in manifest["model_config"].items()
    })


def _environment_pins(flavor: str) -> dict[str, str]:
    """Every runtime package whose version shapes the bundle's behavior —
    the analogue of the reference's conda-env synthesis, which reads
    installed versions via ``importlib.metadata`` and pins them into the
    artifact (`02-register-model.ipynb` cell 11, ~:400-425). A serving
    environment can be reconstructed (or a skew detected) from the
    manifest alone.
    """
    import importlib.metadata
    import platform

    packages = ["jax", "jaxlib", "flax", "optax", "numpy", "pydantic"]
    if flavor == "sklearn":
        packages += ["scikit-learn", "joblib"]
    pins = {"python": platform.python_version()}
    for package in packages:
        try:
            pins[package] = importlib.metadata.version(package)
        except importlib.metadata.PackageNotFoundError:
            pass  # optional dep absent in this env: nothing to pin
    return pins


def save_bundle(
    directory: str | Path,
    model_config: ModelConfig,
    params: Any,
    preprocessor: Preprocessor,
    monitor: MonitorState,
    metrics: dict[str, float] | None = None,
    tags: dict[str, str] | None = None,
    calibration: dict[str, float] | None = None,
    bulk: Any = None,  # DistillResult (train/distill.py) | None
    quant: Any = None,  # QuantDistillResult (train/distill.py) | None
) -> Path:
    """Write a self-contained bundle directory.

    The manifest is the typed replacement for the reference's implicit
    notebook->notebook ``taskValues`` handoff + conda-env synthesis
    (`02-register-model.ipynb` cells 7, 11; SURVEY.md SS3.2).
    """
    from mlops_tpu.models.gbm import SKLEARN_FAMILIES

    directory = Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    if model_config.family in SKLEARN_FAMILIES:
        flavor = "sklearn"
    elif model_config.reads_documents:
        flavor = "doc"
    else:
        flavor = "flax"
    manifest = {
        "format_version": 1,
        "flavor": flavor,
        "framework": {"mlops_tpu": __version__, **_environment_pins(flavor)},
        "created_at": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "schema_fingerprint": SCHEMA.fingerprint(),
        "model_config": dataclasses.asdict(model_config),
        "metrics": metrics or {},
        "tags": tags or {},
        "calibration": calibration or {},
    }
    if flavor == "sklearn":
        params.save(directory / ESTIMATOR_NAME)  # a SklearnBaseline
    else:
        (directory / PARAMS_NAME).write_bytes(tree_bytes(params))
    if bulk is not None:
        # Distilled bulk student (train/distill.py): a second, smaller
        # param tree + its fidelity record, so bulk routing is auditable.
        manifest["bulk"] = {
            "model_config": dataclasses.asdict(bulk.student_config),
            "fidelity": bulk.fidelity,
        }
        (directory / BULK_PARAMS_NAME).write_bytes(
            tree_bytes(bulk.student_params)
        )
    if quant is not None:
        # Quantized student tier (train/distill.py distill_quant_student):
        # flat npz (numpy has no bf16 — ops/quant.py ships the embed as
        # its exact f32 image), with fidelity, refit temperature, AND the
        # stamped gate decision so serving admission needs no labels.
        import numpy as np

        from mlops_tpu.ops.quant import QUANT_FORMAT, quant_params_to_arrays

        manifest["quant"] = {
            "format": QUANT_FORMAT,
            "fidelity": quant.fidelity,
            "temperature": quant.temperature,
            "gates": quant.gates,
        }
        np.savez(
            directory / QUANT_PARAMS_NAME,
            **quant_params_to_arrays(quant.qparams),
        )
    preprocessor.save(directory / PREPROCESS_NAME)
    monitor.save(directory / MONITOR_NAME)
    (directory / MANIFEST_NAME).write_text(json.dumps(manifest, indent=2))
    return directory


def load_bundle(directory: str | Path) -> Bundle:
    """Load + validate a bundle; rebuilds the model from its manifest.

    Schema-fingerprint mismatch is a hard error: serving a bundle trained
    against a different feature contract is the train/serve skew the
    reference is exposed to via its triple-duplicated feature lists
    (SURVEY.md SS2.2 "Feature schema constants").
    """
    from mlops_tpu.models import build_model, init_params

    directory = Path(directory)
    manifest = json.loads((directory / MANIFEST_NAME).read_text())
    if manifest["schema_fingerprint"] != SCHEMA.fingerprint():
        raise ValueError(
            f"bundle {directory} was built for schema "
            f"{manifest['schema_fingerprint']}, runtime schema is "
            f"{SCHEMA.fingerprint()}"
        )
    model_config = _model_config_from_manifest(manifest)
    preprocessor = Preprocessor.load(directory / PREPROCESS_NAME)
    monitor = MonitorState.load(directory / MONITOR_NAME)
    if manifest.get("flavor", "flax") == "sklearn":
        from mlops_tpu.models.gbm import SklearnBaseline

        return Bundle(
            manifest=manifest,
            model=None,
            variables={},
            preprocessor=preprocessor,
            monitor=monitor,
            estimator=SklearnBaseline.load(directory / ESTIMATOR_NAME),
        )
    if manifest.get("flavor") == "doc":
        # Long-context document model: the DENSE BertDocEncoder (the
        # ring is a training-time layout) with a doc-shaped init template.
        import jax.numpy as jnp

        from mlops_tpu.train.long_context import build_doc_model

        model = build_doc_model(
            dataclasses.replace(model_config, seq_parallel=False)
        )
        template = model.init(
            {"params": jax.random.PRNGKey(0)},
            jnp.zeros((2, model_config.doc_records, SCHEMA.num_categorical), jnp.int32),
            jnp.zeros((2, model_config.doc_records, SCHEMA.num_numeric), jnp.float32),
            train=False,
        )
    else:
        model = build_model(model_config)
        template = init_params(model, jax.random.PRNGKey(0))
    try:
        params = restore_tree(
            template["params"], (directory / PARAMS_NAME).read_bytes()
        )
    except ValueError as err:
        raise ValueError(
            f"bundle {directory} holds a param tree that no longer matches "
            f"the {model_config.family!r} module this framework version "
            "builds — re-train/re-register the model with the current "
            "framework"
        ) from err
    quant_params = None
    if "quant" in manifest and (directory / QUANT_PARAMS_NAME).exists():
        import numpy as np

        from mlops_tpu.ops.quant import QUANT_FORMAT, quant_params_from_arrays

        stored = manifest["quant"].get("format")
        if stored != QUANT_FORMAT:
            raise ValueError(
                f"bundle {directory} carries quant params in format "
                f"{stored!r}; this framework serves {QUANT_FORMAT!r} — "
                "re-run packaging to regenerate the quant tier"
            )
        with np.load(directory / QUANT_PARAMS_NAME) as data:
            quant_params = quant_params_from_arrays(
                {k: data[k] for k in data.files}
            )
    bulk_model = None
    bulk_variables = None
    if "bulk" in manifest and (directory / BULK_PARAMS_NAME).exists():
        bulk_config = _model_config_from_manifest(manifest["bulk"])
        bulk_model = build_model(bulk_config)
        bulk_template = init_params(bulk_model, jax.random.PRNGKey(0))
        bulk_variables = {
            "params": restore_tree(
                bulk_template["params"],
                (directory / BULK_PARAMS_NAME).read_bytes(),
            )
        }
    return Bundle(
        manifest=manifest,
        model=model,
        variables={"params": params},
        preprocessor=preprocessor,
        monitor=monitor,
        bulk_model=bulk_model,
        bulk_variables=bulk_variables,
        quant_params=quant_params,
    )

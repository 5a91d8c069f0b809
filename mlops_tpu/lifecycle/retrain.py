"""Off-hot-path incremental retrain: labeled window -> candidate bundle.

Two pieces:

- ``SampleReservoir`` — a bounded on-disk reservoir of encoded serving
  rows (algorithm R: every scored row has equal probability of residing
  in the fixed-size buffer regardless of traffic volume), fed from the
  serve path through the engine's lifecycle tee. It is the controller's
  record of "what recent traffic looked like" — drift forensics and the
  real request shapes the shadow replays — persisted atomically
  (tmp+rename npz) so a pod restart keeps its window.
- ``run_retrain`` — the retrain itself, run on the controller thread,
  never a request thread: read the labeled window
  (``lifecycle.labeled_path`` — serving traffic is unlabeled; realized
  outcomes arrive out of band through this file), optionally re-fit the
  preprocessor over it via the streaming one-pass fit
  (`data/stream.py fit_streaming` — single-process serving only; the
  multi-worker plane's front ends encode with the preprocessor loaded at
  fork, so the ring plane keeps the incumbent's), fine-tune from the
  INCUMBENT's params with a small step budget (`train/loop.fit`, with
  checkpoints — a preempted retrain resumes), re-fit the monitor's
  drift reference + outlier detector on the new window, re-fit
  calibration on the held-out split, and package a candidate bundle
  under ``<lifecycle.dir>/candidates/``. The held-out split is returned
  as the gate-evaluation holdout (lifecycle/promote.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import tempfile
import threading
import time
from pathlib import Path
from typing import Any

import numpy as np

from mlops_tpu import faults
from mlops_tpu.config import Config, TrainConfig
from mlops_tpu.schema import SCHEMA

# tpulint Layer-3 manifest: the reservoir's one lock is a leaf — index
# arithmetic and buffer row assignment only; persistence snapshots copy
# under the lock and write OUTSIDE it (TPU403 discipline).
TPULINT_LOCK_ORDER = {"SampleReservoir": ("_lock",)}


class LifecycleError(RuntimeError):
    """A lifecycle step that cannot proceed (no labeled window, window too
    small, flavor mismatch) — named so the controller can log-and-cool
    instead of crashing the serve process."""


class SampleReservoir:
    """Bounded uniform sample of encoded serving rows (algorithm R).

    Thread-safe: ``add_batch`` is called from the controller's drain of
    the tee queue (one thread in production), but the lock keeps direct
    feeding from test harnesses safe too. The RNG is seeded, so a
    single-threaded feed is deterministic.
    """

    def __init__(self, capacity: int, directory: str | Path, seed: int = 0):
        if capacity < 1:
            raise ValueError(f"reservoir capacity {capacity} must be >= 1")
        self.capacity = capacity
        self.directory = Path(directory)
        self._cat = np.zeros((capacity, SCHEMA.num_categorical), np.int32)
        self._num = np.zeros((capacity, SCHEMA.num_numeric), np.float32)
        self._filled = 0
        self._seen = 0
        self._rng = np.random.default_rng(seed)
        self._lock = threading.Lock()

    @property
    def path(self) -> Path:
        return self.directory / "reservoir.npz"

    # ------------------------------------------------------------- feeding
    def add_batch(self, cat: np.ndarray, num: np.ndarray) -> None:
        """Fold one request's rows into the reservoir (algorithm R row by
        row). The index draws happen OUTSIDE the lock (the RNG has its own
        serialization need, so draws sit under the lock-free fast path
        only when the buffer is still filling); the buffer writes are
        index assignments under the leaf lock."""
        n = int(cat.shape[0])
        if n == 0:
            return
        cat = np.asarray(cat, np.int32)
        num = np.asarray(num, np.float32)
        with self._lock:
            for i in range(n):
                self._seen += 1
                if self._filled < self.capacity:
                    slot = self._filled
                    self._filled += 1
                else:
                    draw = int(self._rng.integers(0, self._seen))
                    if draw >= self.capacity:
                        continue
                    slot = draw
                self._cat[slot] = cat[i]
                self._num[slot] = num[i]

    # -------------------------------------------------------------- reading
    def window(self) -> tuple[np.ndarray, np.ndarray]:
        """(cat int32[k, C], num f32[k, N]) copies of the filled rows."""
        with self._lock:
            k = self._filled
            return self._cat[:k].copy(), self._num[:k].copy()

    @property
    def rows(self) -> int:
        with self._lock:
            return self._filled

    @property
    def rows_seen(self) -> int:
        with self._lock:
            return self._seen

    # ---------------------------------------------------------- persistence
    def save(self) -> Path:
        """Atomic snapshot (tmp+rename): the copy happens under the lock,
        the file I/O outside it."""
        with self._lock:
            payload = {
                "cat": self._cat[: self._filled].copy(),
                "num": self._num[: self._filled].copy(),
                "seen": np.int64(self._seen),
            }
        self.directory.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            dir=str(self.directory), suffix=".reservoir.tmp"
        )
        try:
            with os.fdopen(fd, "wb") as f:
                np.savez(f, **payload)
            # Injection point (mlops_tpu/faults): kill between the tmp
            # write and the rename — a torn reservoir save must leave
            # either no snapshot or the previous intact one, never a
            # half-written npz a restart would trust.
            faults.fire("lifecycle.reservoir.midwrite")
            os.replace(tmp, self.path)
        except BaseException:
            with contextlib.suppress(OSError):
                os.unlink(tmp)
            raise
        return self.path

    def load(self) -> bool:
        """Restore a prior snapshot if one exists; True when restored."""
        if not self.path.is_file():
            return False
        with np.load(self.path) as data:
            cat, num = data["cat"], data["num"]
            seen = int(data["seen"])
        k = min(len(cat), self.capacity)
        with self._lock:
            self._cat[:k] = cat[:k]
            self._num[:k] = num[:k]
            self._filled = k
            self._seen = max(seen, k)
        return True


def _match_monitor_ref(monitor, train_ds, target: int, seed: int):
    """Resize the candidate monitor's K-S reference sample to the
    INCUMBENT's width. ``fit_monitor`` samples min(drift_ref_size, n)
    rows, so a labeled window smaller than the incumbent's training set
    would shrink ``num_ref_sorted``/``num_ref_cdf`` — changing the packed
    programs' abstract signature and defeating both the shared-exec-table
    shadow warm and the zero-compile hot swap. A window smaller than the
    target resamples WITH replacement (the tie-aware right-continuous CDF
    handles the duplicates); shapes stay bit-identical to the incumbent's
    compiled contract."""
    from mlops_tpu.monitor.state import MonitorState, _ref_cdf

    current = int(monitor.num_ref_sorted.shape[1])
    if current == target:
        return monitor
    rng = np.random.default_rng(seed)
    numeric = np.asarray(train_ds.numeric, np.float32)
    n = numeric.shape[0]
    idx = rng.choice(n, size=target, replace=n < target)
    ref = np.sort(numeric[idx], axis=0).T  # [M, target]
    arrays = monitor.to_arrays()
    arrays["num_ref_sorted"] = ref
    arrays["num_ref_cdf"] = _ref_cdf(ref)
    return MonitorState.from_arrays(arrays)


@dataclasses.dataclass
class RetrainResult:
    candidate_dir: Path
    bundle: Any  # the loaded candidate Bundle
    holdout: Any  # EncodedDataset — the held-out split, CANDIDATE encode
    holdout_incumbent: Any  # the SAME held-out rows encoded with the
    # incumbent's preprocessor (identical object when no refit): the
    # gates must score each side in the encode configuration IT serves —
    # scoring the incumbent on candidate-refit normalization stats would
    # systematically collapse its AUC and bias every gate pro-candidate
    metrics: dict[str, float]  # candidate validation metrics (fit)
    labeled_rows: int
    wall_s: float
    refit_preprocessor: bool


def run_retrain(
    incumbent,
    config: Config,
    generation: int,
    seed: int = 0,
    attempt: int = 1,
    reservoir_window: tuple[np.ndarray, np.ndarray] | None = None,
) -> RetrainResult:
    """Labeled window -> fine-tuned candidate bundle + checkpointed run.

    ``incumbent`` is the live Bundle (flax flavor required — the sklearn
    floor redeploys, it does not hot-swap). Raises ``LifecycleError`` on
    a missing/undersized labeled window so the controller can cool down
    instead of crashing the serve process.

    ``attempt`` scopes the checkpoint/candidate directories per trigger:
    a REJECTED attempt's completed checkpoints must never be resumed by
    the next one (``fit`` would restore the final step and return the
    stale params untouched, however fresh the labeled window) — while a
    crash-restarted attempt under the SAME tag still resumes mid-train.

    ``reservoir_window`` — (cat int32[k, C], num f32[k, N]) from the
    serve-path sample reservoir — refits the candidate's drift
    reference/outlier detector on RECENT SERVING TRAFFIC rather than the
    labeled file alone (falls back to the labeled train split when the
    window is thinner than the labeled one).
    """
    from mlops_tpu.bundle import load_bundle, save_bundle
    from mlops_tpu.data import load_table_columns
    from mlops_tpu.data.stream import fit_streaming
    from mlops_tpu.models import build_model
    from mlops_tpu.monitor.state import fit_monitor
    from mlops_tpu.train.loop import fit
    from mlops_tpu.train.pipeline import _fit_calibration, split_dataset

    lc = config.lifecycle.validate()
    if incumbent.flavor != "flax":
        raise LifecycleError(
            f"retrain requires a flax-flavor incumbent, got "
            f"{incumbent.flavor!r} (tree/doc bundles redeploy instead)"
        )
    if not lc.labeled_path:
        raise LifecycleError(
            "lifecycle.labeled_path is empty — no labeled window to "
            "retrain on (serving traffic is unlabeled; deliver realized "
            "outcomes to a CSV/Parquet with the target column)"
        )
    t0 = time.perf_counter()
    columns, labels = load_table_columns(lc.labeled_path)
    if labels is None:
        raise LifecycleError(
            f"{lc.labeled_path} has no target column — the retrain window "
            "must be labeled"
        )
    n_rows = len(labels)
    if n_rows < lc.min_labeled_rows:
        raise LifecycleError(
            f"labeled window has {n_rows} rows < "
            f"lifecycle.min_labeled_rows={lc.min_labeled_rows}"
        )
    if lc.refit_preprocessor:
        # One-pass streaming re-fit of the normalization stats over the
        # recent window (data/stream.py): the candidate encodes the
        # DRIFTED distribution with honest statistics. Single-process
        # serving only — the controller forces this off on the ring plane.
        preprocessor = fit_streaming(lc.labeled_path)
    else:
        preprocessor = incumbent.preprocessor
    ds = preprocessor.encode(columns, labels)
    train_ds, valid_ds = split_dataset(ds, 0.2)
    if lc.refit_preprocessor:
        # Same rows, INCUMBENT encode, for the gate comparison: the
        # split permutation depends only on (n, seed), so the two valid
        # splits select identical rows.
        _, valid_inc = split_dataset(
            incumbent.preprocessor.encode(columns, labels), 0.2
        )
    else:
        valid_inc = valid_ds

    model = build_model(incumbent.model_config)
    steps = lc.retrain_steps
    tcfg = TrainConfig(
        batch_size=min(lc.retrain_batch_size, max(1, train_ds.n)),
        steps=steps,
        eval_every=steps,
        warmup_steps=max(1, steps // 10),
        seed=seed,
        checkpoint_every=max(1, steps // 2),
        keep_best=True,
    )
    state_dir = Path(lc.dir)
    tag = f"gen-{generation}-t{attempt}"
    ckpt_dir = state_dir / "checkpoints" / tag
    # A COMPLETED prior run under this tag must never be resumed: `fit`
    # would restore the final step and return the stale params untouched
    # (attempt tags collide across process restarts — the trigger counter
    # restarts with the process — and the offline CLI reruns with the
    # same tag after a gate rejection). A PARTIAL checkpoint (crash
    # mid-retrain) is exactly what resume is for; only done-state wipes.
    latest = ckpt_dir / "latest.json"
    if latest.is_file():
        try:
            import json as _json

            done_step = int(_json.loads(latest.read_text()).get("step", 0))
        except (OSError, ValueError):
            done_step = 0
        if done_step >= lc.retrain_steps:
            import shutil

            shutil.rmtree(ckpt_dir, ignore_errors=True)
    # Fine-tune FROM the incumbent's params (fit copies them into fresh
    # buffers before the donated scan can consume them).
    result = fit(
        model,
        train_ds,
        valid_ds,
        tcfg,
        init_variables=incumbent.variables,
        metrics_path=ckpt_dir / "metrics.jsonl",
        checkpoint_dir=ckpt_dir,
    )
    # Monitor refit source: the serve-path reservoir when it carries at
    # least as much evidence as the labeled train split (the drift
    # reference should describe what TRAFFIC looks like now), else the
    # labeled window.
    monitor_ds = train_ds
    if reservoir_window is not None and (
        len(reservoir_window[0]) >= min(train_ds.n, 512)
    ):
        from mlops_tpu.data.encode import EncodedDataset

        monitor_ds = EncodedDataset(
            cat_ids=reservoir_window[0],
            numeric=reservoir_window[1],
            labels=None,
        )
    monitor = _match_monitor_ref(
        fit_monitor(monitor_ds, seed=seed), monitor_ds,
        target=int(incumbent.monitor.num_ref_sorted.shape[1]), seed=seed,
    )
    calibration = _fit_calibration(valid_ds, result.params, model)
    candidate_dir = state_dir / "candidates" / tag
    save_bundle(
        candidate_dir,
        incumbent.model_config,
        result.params,
        preprocessor,
        monitor,
        metrics=result.metrics,
        tags={
            "lifecycle": "candidate",
            "parent_generation": str(generation - 1),
            "labeled_rows": str(n_rows),
        },
        calibration=calibration,
    )
    return RetrainResult(
        candidate_dir=candidate_dir,
        bundle=load_bundle(candidate_dir),
        holdout=valid_ds,
        holdout_incumbent=valid_inc,
        metrics=result.metrics,
        labeled_rows=n_rows,
        wall_s=round(time.perf_counter() - t0, 3),
        refit_preprocessor=lc.refit_preprocessor,
    )

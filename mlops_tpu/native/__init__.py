"""Native (C++) data-loading kernel with ctypes bindings.

The reference has zero native code (SURVEY.md §2: "Native components:
NONE") — its bulk data handling lives in managed Spark. This framework's
equivalent obligation is a native host-side path of its own: the CSV
parse + encode hot loop (``encoder.cpp``) that feeds the TPU during bulk
scoring (BASELINE config 4), where the Python csv module would otherwise be
the bottleneck long before the chip is.

Build model: compiled on first use with plain ``g++ -O3 -shared -fPIC``
into ``_build/`` next to the source, keyed by a source hash so edits
rebuild automatically. No pybind11 (not in the image) — a pure C ABI called
through ctypes. If the toolchain is absent or compilation fails, callers
fall back to the pure-Python encoder (``Preprocessor.encode``) with
identical semantics — a parity test pins native == Python output exactly
— and `encoder_status` says which one serves and why, so a result can
name its encoder (chip_smoke.py prints it, and fails when the build
failed on a machine that has ``g++``).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
from pathlib import Path

import numpy as np

from mlops_tpu.data.encode import EncodedDataset, Preprocessor
from mlops_tpu.schema.features import SCHEMA, FeatureSchema

logger = logging.getLogger(__name__)

_SRC = Path(__file__).with_name("encoder.cpp")
_BUILD_DIR = Path(__file__).with_name("_build")

_ERRORS = {
    -1: "required schema column missing from CSV header",
    -2: "row count exceeded the preallocated buffer",
    -3: "target column required but absent",
    -4: "unparseable value in the target column",
}

_lib_cache: ctypes.CDLL | None | bool = None  # False = tried and failed
_python_reason = ""  # why the Python encoder serves, when it does


def _compile() -> Path | None:
    global _python_reason
    source = _SRC.read_bytes()
    tag = hashlib.sha256(source).hexdigest()[:12]
    so_path = _BUILD_DIR / f"encoder_{tag}.so"
    if so_path.exists():
        return so_path
    _BUILD_DIR.mkdir(exist_ok=True)
    # Compile to a private temp name, then rename: an interrupted or
    # concurrent build must never leave a partial .so at the final path
    # (os.replace is atomic within the directory).
    tmp_path = _BUILD_DIR / f".encoder_{tag}.{os.getpid()}.tmp.so"
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC",
        str(_SRC), "-o", str(tmp_path),
    ]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, text=True, timeout=120
        )
        os.replace(tmp_path, so_path)
    except (OSError, subprocess.SubprocessError) as err:
        detail = getattr(err, "stderr", "") or str(err)
        _python_reason = f"build failed: {detail.strip()[:500]}"
        logger.warning("native encoder %s; using Python path", _python_reason)
        tmp_path.unlink(missing_ok=True)
        return None
    # Clean superseded builds (old source hashes).
    for stale in _BUILD_DIR.glob("encoder_*.so"):
        if stale != so_path:
            stale.unlink(missing_ok=True)
    return so_path


def _lib() -> ctypes.CDLL | None:
    global _lib_cache, _python_reason
    if _lib_cache is None:
        if os.environ.get("MLOPS_TPU_NO_NATIVE"):
            _python_reason = "MLOPS_TPU_NO_NATIVE is set"
            _lib_cache = False
        else:
            so_path = _compile()
            if so_path is None:
                _lib_cache = False
            else:
                try:
                    lib = ctypes.CDLL(str(so_path))
                except OSError as err:
                    # Unloadable artifact (e.g. leftover from a crashed
                    # build): drop it and fall back to the Python path —
                    # the module contract is graceful degradation, never
                    # a hard failure.
                    logger.warning(
                        "native encoder %s failed to load (%s); using "
                        "Python path", so_path.name, err,
                    )
                    so_path.unlink(missing_ok=True)
                    _python_reason = f"load failed: {err}"
                    _lib_cache = False
                    return None
                lib.mlops_encode_csv.restype = ctypes.c_long
                lib.mlops_encode_csv.argtypes = [
                    ctypes.c_char_p, ctypes.c_long,      # csv, csv_len
                    ctypes.c_char_p,                     # feature_names
                    ctypes.c_int, ctypes.c_int,          # n_cat, n_num
                    ctypes.c_char_p,                     # vocabs
                    ctypes.POINTER(ctypes.c_float),      # medians
                    ctypes.POINTER(ctypes.c_float),      # means
                    ctypes.POINTER(ctypes.c_float),      # stds
                    ctypes.POINTER(ctypes.c_int32),      # cat_out
                    ctypes.POINTER(ctypes.c_float),      # num_out
                    ctypes.POINTER(ctypes.c_float),      # lab_out
                    ctypes.c_long,                       # max_rows
                    ctypes.c_int,                        # require_label
                    ctypes.POINTER(ctypes.c_int),        # has_label_out
                ]
                _lib_cache = lib
    return _lib_cache or None


def native_available() -> bool:
    return _lib() is not None


def encoder_status() -> dict[str, str]:
    """Which encoder serves in this process — ``{"encoder": "c++"}`` or
    ``{"encoder": "python", "reason": ...}`` — after trying to build and
    load the native one."""
    if native_available():
        return {"encoder": "c++"}
    return {"encoder": "python", "reason": _python_reason}


def encode_csv_native(
    path: str | Path,
    prep: Preprocessor,
    schema: FeatureSchema = SCHEMA,
    require_target: bool = False,
) -> EncodedDataset:
    """Parse + encode a schema CSV file in one native pass.

    Semantics identical to ``load_csv_columns`` + ``Preprocessor.encode``;
    raises ``RuntimeError`` if the native library is unavailable (callers
    use ``encode_csv`` for automatic fallback).
    """
    return encode_csv_bytes(
        Path(path).read_bytes(), prep, schema, require_target, source=str(path)
    )


def encode_csv_bytes(
    data: bytes,
    prep: Preprocessor,
    schema: FeatureSchema = SCHEMA,
    require_target: bool = False,
    source: str = "<bytes>",
) -> EncodedDataset:
    """Parse + encode an in-memory CSV byte buffer (header + rows) with
    the native kernel.

    This is the streaming hot path: the pipelined executor
    (`data/stream.py score_csv_stream`) feeds header-prefixed chunk
    buffers through here on a worker thread, and the ctypes foreign call
    RELEASES the GIL for the whole parse+encode — so chunk N+1 encodes in
    C++ while chunk N computes on the device and the GIL-bound
    reader/writer stages keep running.
    """
    lib = _lib()
    if lib is None:
        raise RuntimeError("native encoder unavailable")

    # Upper bound on data rows; the kernel returns the true count. max()
    # covers every record-terminator convention (LF, CRLF, bare CR).
    max_rows = max(1, data.count(b"\n"), data.count(b"\r")) + 1

    names = "\x1e".join(
        [f.name for f in schema.categorical]
        + [f.name for f in schema.numeric]
        + [schema.target]
    ).encode()
    vocabs = "\x1e".join(
        "\x1f".join(f.vocab) for f in schema.categorical
    ).encode()

    cat = np.empty((max_rows, schema.num_categorical), np.int32)
    num = np.empty((max_rows, schema.num_numeric), np.float32)
    lab = np.empty(max_rows, np.float32)
    has_label = ctypes.c_int(0)

    def fptr(a: np.ndarray):
        return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))

    result_rows = lib.mlops_encode_csv(
        data, len(data), names,
        schema.num_categorical, schema.num_numeric, vocabs,
        fptr(np.ascontiguousarray(prep.numeric_median)),
        fptr(np.ascontiguousarray(prep.numeric_mean)),
        fptr(np.ascontiguousarray(prep.numeric_std)),
        cat.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        fptr(num), fptr(lab),
        max_rows, int(require_target), ctypes.byref(has_label),
    )
    if result_rows < 0:
        raise ValueError(
            f"{source}: native encode failed: "
            f"{_ERRORS.get(result_rows, result_rows)}"
        )
    labels = (
        lab[:result_rows].astype(np.int8) if has_label.value else None
    )
    return EncodedDataset(
        cat_ids=cat[:result_rows].copy(),
        numeric=num[:result_rows].copy(),
        labels=labels,
    )


def encode_csv(
    path: str | Path,
    prep: Preprocessor,
    schema: FeatureSchema = SCHEMA,
    require_target: bool = False,
) -> EncodedDataset:
    """Encode a CSV with the native kernel when available, else pure Python.

    ``gs://`` sources are materialized locally first (`data/ingest.py`
    ``fetch_local``) so the byte-oriented native kernel serves remote
    datasets too.
    """
    from mlops_tpu.data.ingest import fetch_local, load_csv_columns

    path = fetch_local(path)
    if native_available():
        return encode_csv_native(path, prep, schema, require_target)
    columns, labels = load_csv_columns(path, schema, require_target)
    return prep.encode(columns, labels, schema)


__all__ = [
    "encode_csv",
    "encode_csv_bytes",
    "encode_csv_native",
    "encoder_status",
    "native_available",
]

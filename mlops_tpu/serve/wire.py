"""The serving wire contract, jax-free: group geometry + response shape.

Both halves of the multi-worker plane need these without importing the
engine (whose module pulls jax): the HTTP front-end processes
(`serve/frontend.py`) size ring slabs and coalescing classes from the
group geometry and format responses from raw arrays; the engine process
uses the same constants to pick compiled shapes and the same formatter
for its in-process fetch — which is what makes the two planes
bit-identical by construction. `serve/engine.py` re-exports everything
here, so historical imports keep working.
"""

from __future__ import annotations

import json
from typing import Any

import numpy as np

from mlops_tpu.schema import SCHEMA

# Micro-batching shape grid: concurrent requests coalesce into [R, B, ...]
# stacks — R request-slots (padded up to a slot bucket), each padded to B
# rows. Only small requests coalesce; big ones already fill the MXU alone.
# Slot buckets go to 64: every dispatch pays a flat device round trip
# (its size on the chip: not measured), so request throughput scales with
# requests-per-dispatch — 64 batch-1 requests ride one vmapped program.
# Row buckets are (1, 8): batch-1 is the dominant serving shape and
# padding it to 8 rows made every grouped dispatch compute 8x the rows it
# returned — on CPU backends (serial compute) that padding was the
# throughput ceiling. An all-batch-1 group now rides the [R, 1, ...]
# family; mixed small sizes pad to 8 as before.
GROUP_SLOT_BUCKETS = (2, 4, 8, 16, 32, 64)
GROUP_ROW_BUCKETS = (1, 8)
GROUP_ROW_BUCKET = GROUP_ROW_BUCKETS[-1]

# Ring completion statuses (serve/ipc.py resp_status): the engine answers
# every accepted descriptor with exactly one of these. EXPIRED is the
# dead-work-shedding path — a descriptor whose deadline budget ran out
# before dispatch is completed WITHOUT touching the device, and the front
# end answers 504 (docs/operations.md "Failure domains & degraded modes").
RESP_OK, RESP_ERROR, RESP_EXPIRED = 0, 1, 2


class DeadlineExceeded(Exception):
    """A request's deadline budget (``x-request-deadline-ms``, or
    ``serve.request_timeout_s``) ran out before its work dispatched —
    raised engine-side (the micro-batcher's claim-time purge) so the
    handler answers the documented 504 without the device ever seeing
    the dead request. Jax-free by design: both planes' HTTP layers and
    the batcher share it without an engine import."""



def format_response(
    predictions: np.ndarray, outliers: np.ndarray, drift: np.ndarray
) -> dict[str, Any]:
    """Raw response arrays -> the reference response dict.

    THE one formatting rule for every serving path: the in-process fetch
    (`InferenceEngine.fetch_arrays`/`fetch_group`) and the multi-worker
    front ends (which read the same f64 arrays back out of the
    shared-memory ring) both format through here, so the two planes are
    bit-identical by construction — the parity suite pins it
    (tests/test_frontend.py). Inputs are the engine's raw-fetch contract:
    f64 predictions/outliers of the request's row count and the f64 drift
    vector already rounded to 6 places."""
    return {
        "predictions": predictions.tolist(),
        "outliers": outliers.tolist(),
        "feature_drift_batch": dict(zip(SCHEMA.feature_names, drift.tolist())),
    }


def empty_response() -> dict[str, Any]:
    """The zero-row response (no device work, no drift signal) — shared by
    `predict_arrays` and the front ends' local empty-request fast path."""
    return {
        "predictions": [],
        "outliers": [],
        "feature_drift_batch": dict.fromkeys(SCHEMA.feature_names, 0.0),
    }


# Pre-encoded response scaffolding (ISSUE 18 satellite — the encode-bound
# HTTP residue): the response's entire static skeleton — braces, key
# names, the 20+ drift feature keys with their quoting/escaping — is
# identical on every response, yet `json.dumps` of the formatted dict
# rebuilt the dict AND re-serialized the skeleton per request (on the
# single-process plane's event loop — its bottleneck thread at high
# concurrency). `encode_response` serializes ONLY the floats, in one C
# `json.dumps` call over the three flat lists, and splices the baked
# skeleton around them. Because every float goes through the SAME C
# encoder the dict path used, the wire bytes are EXACTLY what
# `json.dumps(format_response(...), separators=(",", ":"))` produced —
# for every input, non-finite included (NaN/Infinity render identically;
# no fallback needed). The parity suite pins it
# (tests/test_wire_encode.py), and the encode runs wherever the caller
# already holds the arrays (the engine's executor thread, the ring front
# end's handler) — cheaper in total CPU than dict-build + dumps, not
# just moved off the loop.
_DRIFT_KEYS = tuple(
    json.dumps(name) + ":" for name in SCHEMA.feature_names
)


def encode_response(
    predictions: np.ndarray, outliers: np.ndarray, drift: np.ndarray
) -> bytes:
    """Raw response arrays -> pre-encoded wire bytes, byte-identical to
    ``json.dumps(format_response(...), separators=(",", ":")).encode()``
    for every input (pinned by tests/test_wire_encode.py)."""
    # One C-encoder pass over all the floats. The "],[" delimiter can
    # never occur inside a rendered float (digits, sign, dot, eE,
    # NaN/Infinity letters only), so the three segments split back out
    # exactly — including the empty-list edges.
    floats = json.dumps(
        [
            np.asarray(predictions).tolist(),
            np.asarray(outliers).tolist(),
            np.asarray(drift).tolist(),
        ],
        separators=(",", ":"),
    )
    preds, outs, drifts = floats[2:-2].split("],[")
    return (
        '{"predictions":['
        + preds
        + '],"outliers":['
        + outs
        + '],"feature_drift_batch":{'
        + ",".join(map(str.__add__, _DRIFT_KEYS, drifts.split(",")))
        + "}}"
    ).encode()


# The zero-row fast path's cached bytes (the dict is static, so the
# encode is too).
EMPTY_RESPONSE_BYTES = json.dumps(
    empty_response(), separators=(",", ":")
).encode()

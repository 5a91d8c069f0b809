"""Out-of-core CSV streaming: chunked ingest, mergeable stats, stream scoring.

The reference delegates bigger-than-memory data to Spark (external table +
``spark.read.table``, `00-create-external-table.ipynb:92-95`); this module is
the framework-native answer: a dataset is consumed as fixed-size row chunks,
preprocessing statistics accumulate in ONE pass with bounded memory, and bulk
scoring streams chunk -> encode -> device -> append-to-output without ever
holding the dataset.

Statistics design (single pass, exact where it matters):

- mean/std: the batch fit standardizes MEDIAN-IMPUTED values. Streaming
  keeps per-feature SHIFTED sums ``(count_finite, sum(x-s), sum((x-s)^2),
  count_missing)`` with ``s`` = the first finite value seen — the shift
  kills the catastrophic cancellation a raw ``E[x^2]-E[x]^2`` suffers on
  large-magnitude features (mean ~1e8, std ~1 would otherwise collapse to
  std=1 silently). Once the median is known the imputed moments close
  exactly in shifted space — no second pass.
- median: exact only with the full sample, so a uniform RESERVOIR (default
  100k values/feature) stands in; for datasets at or under the reservoir
  size the result is exactly the batch fit's.

Chunk semantics share the batch reader's parsing helpers
(`data/ingest.py` ``rows_to_columns``/``parse_labels``, themselves
parity-tested against the native C++ kernel): blank lines skipped, short
rows read missing cells as empty (-> OOV / median). Labels are parsed only
under ``require_target=True`` and fail fast on corrupt values — the
streaming consumers (fit, scoring) are feature-only, and a permissive
per-chunk label parse could not honor the batch reader's
one-bad-value-unlabels-the-FILE contract without lookahead.
"""

from __future__ import annotations

import csv
from pathlib import Path
from typing import Iterator

import numpy as np

from mlops_tpu.data.encode import Preprocessor
from mlops_tpu.data.ingest import fetch_local, parse_labels, rows_to_columns
from mlops_tpu.schema.features import SCHEMA, FeatureSchema


def iter_csv_chunks(
    path: str | Path,
    chunk_rows: int = 65_536,
    schema: FeatureSchema = SCHEMA,
    require_target: bool = False,
) -> Iterator[tuple[dict[str, list], np.ndarray | None]]:
    """Yield ``(columns, labels)`` chunks of at most ``chunk_rows`` rows.

    Labels are parsed (strictly) only when ``require_target=True``;
    otherwise every chunk yields ``labels=None`` — see module docstring.
    Accepts local paths and ``gs://`` URIs (staged through the same cache
    as the batch reader). Memory is bounded by one chunk.
    """
    with fetch_local(path).open(newline="") as f:
        reader = csv.reader(f)
        header = next(reader)
        col_index = _validated_col_index(header, path, schema, require_target)

        def emit(rows: list, base_row: int):
            columns = rows_to_columns(rows, col_index, schema)
            labels = (
                parse_labels(rows, col_index, schema, path, base_row)
                if require_target
                else None
            )
            return columns, labels

        buffer: list = []
        seen = 0
        for row in reader:
            if not row or row == [""]:
                continue
            buffer.append(row)
            if len(buffer) >= chunk_rows:
                yield emit(buffer, seen)
                seen += len(buffer)
                buffer = []
        if buffer:
            yield emit(buffer, seen)


def _validated_col_index(header_fields: list[str], path, schema, require_target):
    col_index = {name: i for i, name in enumerate(header_fields)}
    missing = [n for n in schema.feature_names if n not in col_index]
    if missing:
        raise ValueError(f"{path}: missing required columns {missing}")
    if require_target and schema.target not in col_index:
        raise ValueError(f"{path}: missing target column {schema.target!r}")
    return col_index


_READ_BYTES = 4 << 20  # reader granularity; several chunks per read


def iter_raw_csv_chunks(
    path: str | Path,
    chunk_rows: int = 65_536,
    schema: FeatureSchema = SCHEMA,
) -> Iterator[tuple[str, object]]:
    """Byte-level chunk reader for the native-encode streaming path.

    Yields ``("bytes", header + rows_block)`` items of at most
    ``chunk_rows`` records each, split at newline boundaries that are
    verified record-safe: the fast split is only sound while the bytes
    contain no double quotes (an RFC-4180 quoted field may embed
    newlines) and no bare-CR record terminators. The moment a block trips
    either check, the reader degrades PERMANENTLY to the csv-module
    parser for the rest of the stream, yielding ``("columns", columns)``
    items instead — correctness over speed, decided per run, invisible to
    the consumer because the encode stage accepts both forms.

    Feature-only contract (labels are never parsed): this reader serves
    ``score_csv_stream``, whose consumers ignore the target column.
    """
    with fetch_local(path).open("rb") as f:
        header = f.readline()
        header_fields = next(csv.reader([header.decode()]))
        col_index = _validated_col_index(
            header_fields, path, schema, require_target=False
        )
        # Each read block is scanned ONCE (quote / bare-CR / newline
        # counts); blocks accumulate in a list and are joined only when a
        # chunk's worth of records is present — no quadratic re-scan of
        # the leftover when chunk_rows spans many read blocks.
        pending: list[bytes] = []
        pending_newlines = 0
        hold = b""  # trailing CR held back: may be half of a CRLF split
        # across reads, which would trip the bare-CR check
        while True:
            block = f.read(_READ_BYTES)
            if not block:
                break
            block = hold + block
            hold = b""
            if block.endswith(b"\r"):
                block, hold = block[:-1], block[-1:]
            if b'"' in block or block.count(b"\r") != block.count(b"\r\n"):
                rest = b"".join(pending) + block + hold
                yield from _python_tail_chunks(
                    col_index, rest, f, chunk_rows, schema
                )
                return
            pending.append(block)
            pending_newlines += block.count(b"\n")
            if pending_newlines >= chunk_rows:
                buf = b"".join(pending)
                newlines = np.flatnonzero(
                    np.frombuffer(buf, np.uint8) == 0x0A
                )
                start = 0
                taken = 0
                while newlines.size - taken >= chunk_rows:
                    end = int(newlines[taken + chunk_rows - 1]) + 1
                    yield ("bytes", header + buf[start:end])
                    start = end
                    taken += chunk_rows
                pending = [buf[start:]] if start < len(buf) else []
                pending_newlines = int(newlines.size) - taken
        tail = b"".join(pending) + hold
        if tail.strip(b"\r\n"):
            yield ("bytes", header + tail)


def _python_tail_chunks(
    col_index, buf: bytes, f, chunk_rows, schema
) -> Iterator[tuple[str, object]]:
    """Degraded continuation of ``iter_raw_csv_chunks``: csv-parse the
    remaining stream (already-buffered bytes + the rest of the file),
    preserving line terminators so quoted embedded newlines survive."""

    def byte_lines():
        import itertools

        carry = b""
        blocks = iter(lambda: f.read(_READ_BYTES), b"")
        for block in itertools.chain([buf], blocks):
            carry += block
            lines = carry.splitlines(keepends=True)
            carry = b""
            if lines:
                # The final piece may be a partial line (no terminator) or
                # end in a CR that could be half of a CRLF — carry it.
                if not lines[-1].endswith((b"\n", b"\r")) or lines[-1].endswith(
                    b"\r"
                ):
                    carry = lines.pop()
            yield from lines
        if carry:
            yield carry

    reader = csv.reader(line.decode() for line in byte_lines())
    buffer: list = []
    for row in reader:
        if not row or row == [""]:
            continue
        buffer.append(row)
        if len(buffer) >= chunk_rows:
            yield ("columns", rows_to_columns(buffer, col_index, schema))
            buffer = []
    if buffer:
        yield ("columns", rows_to_columns(buffer, col_index, schema))


def iter_table_chunks(
    path: str | Path,
    chunk_rows: int = 65_536,
    schema: FeatureSchema = SCHEMA,
    require_target: bool = False,
) -> Iterator[tuple[dict[str, list], np.ndarray | None]]:
    """Format-dispatching chunk iterator: Parquet files stream through
    ``parquet.iter_parquet_chunks`` (exact-size re-buffered chunks),
    everything else through ``iter_csv_chunks``. Same yielded contract."""
    from mlops_tpu.data import parquet

    if parquet.is_parquet(path):
        return parquet.iter_parquet_chunks(path, chunk_rows, schema, require_target)
    return iter_csv_chunks(path, chunk_rows, schema, require_target)


class StreamingStats:
    """Mergeable single-pass accumulator for the Preprocessor's fit.

    ``update(columns)`` per chunk, then ``finalize()`` -> Preprocessor.
    """

    def __init__(
        self,
        schema: FeatureSchema = SCHEMA,
        reservoir_size: int = 100_000,
        seed: int = 0,
    ):
        self.schema = schema
        m = schema.num_numeric
        self._count = np.zeros(m, np.int64)  # finite values
        self._missing = np.zeros(m, np.int64)
        self._shift = np.full(m, np.nan)  # first finite value per feature
        self._sum_d = np.zeros(m, np.float64)  # sum of (x - shift)
        self._sumsq_d = np.zeros(m, np.float64)  # sum of (x - shift)^2
        self._reservoirs: list[np.ndarray] = [
            np.empty(0, np.float64) for _ in range(m)
        ]
        self._reservoir_size = reservoir_size
        self._rng = np.random.default_rng(seed)

    def update(self, columns: dict[str, list]) -> None:
        self.update_arrays(
            [
                np.asarray(columns[feat.name], dtype=np.float64)
                for feat in self.schema.numeric
            ]
        )

    def update_arrays(self, raws: list[np.ndarray]) -> None:
        """Fold one chunk given per-numeric-feature float64 arrays (in
        schema order). The list-of-columns conversion is split out so the
        pipelined fit (`fit_streaming`) can run it on a worker thread
        while this fold — which must stay sequential for the reservoir
        RNG — runs on the sink."""
        for j, raw in enumerate(raws):
            finite = raw[np.isfinite(raw)]
            self._missing[j] += raw.size - finite.size
            if finite.size and np.isnan(self._shift[j]):
                self._shift[j] = finite[0]
            if finite.size:
                d = finite - self._shift[j]
                self._sum_d[j] += d.sum()
                self._sumsq_d[j] += np.square(d).sum()
            self._reservoirs[j] = self._fold_reservoir(
                self._reservoirs[j], finite, self._count[j]
            )
            self._count[j] += finite.size

    def _fold_reservoir(
        self, reservoir: np.ndarray, values: np.ndarray, seen: int
    ) -> np.ndarray:
        """Uniform reservoir over the stream: every value seen so far has
        equal probability of residing in the sample (Vitter's Algorithm R,
        vectorized per chunk)."""
        k = self._reservoir_size
        if reservoir.size < k:
            taken = min(k - reservoir.size, values.size)
            reservoir = np.concatenate([reservoir, values[:taken]])
            values = values[taken:]
            seen += taken
        if values.size == 0:
            return reservoir
        # For the i-th remaining value (global index seen+i), replace a
        # random slot with probability k / (seen+i+1).
        idx = seen + 1 + np.arange(values.size, dtype=np.float64)
        accept = self._rng.random(values.size) < (k / idx)
        slots = self._rng.integers(0, k, size=values.size)
        sel_slots = slots[accept]
        if sel_slots.size:
            # Vectorized scatter with explicit last-write-wins on duplicate
            # slots (bit-identical to the per-value loop it replaces):
            # np.unique over the REVERSED slot array returns, per unique
            # slot, the index of its last occurrence in stream order.
            sel_values = values[accept]
            unique_slots, last_in_reversed = np.unique(
                sel_slots[::-1], return_index=True
            )
            reservoir[unique_slots] = sel_values[::-1][last_in_reversed]
        return reservoir

    def finalize(self) -> Preprocessor:
        medians, means, stds = [], [], []
        for j in range(self.schema.num_numeric):
            reservoir = self._reservoirs[j]
            median = float(np.median(reservoir)) if reservoir.size else 0.0
            n = self._count[j] + self._missing[j]
            if n == 0:
                means.append(0.0)
                stds.append(1.0)
                medians.append(median)
                continue
            shift = self._shift[j] if np.isfinite(self._shift[j]) else 0.0
            med_d = median - shift
            mean_d = (self._sum_d[j] + self._missing[j] * med_d) / n
            ex2_d = (self._sumsq_d[j] + self._missing[j] * med_d**2) / n
            mean = shift + mean_d
            var = max(ex2_d - mean_d**2, 0.0)
            std = float(np.sqrt(var))
            medians.append(median)
            means.append(float(mean))
            stds.append(std if std > 1e-12 else 1.0)
        return Preprocessor(
            numeric_median=np.asarray(medians, np.float32),
            numeric_mean=np.asarray(means, np.float32),
            numeric_std=np.asarray(stds, np.float32),
            schema_fingerprint=self.schema.fingerprint(),
        )


def fit_streaming(
    path: str | Path,
    chunk_rows: int = 65_536,
    schema: FeatureSchema = SCHEMA,
    reservoir_size: int = 100_000,
    seed: int = 0,
    pipeline_depth: int = 1,
) -> Preprocessor:
    """One-pass Preprocessor fit over an arbitrarily large CSV/Parquet.

    ``pipeline_depth > 1`` overlaps chunk read+parse and the list->float64
    conversion with the sequential moment/reservoir fold on background
    threads (`data/pipeline_exec.py`); depth 1 is the serial loop. The
    fold order is preserved either way, so the fitted Preprocessor is
    bit-identical at any depth.
    """
    from mlops_tpu.data.pipeline_exec import Stage, run_pipeline

    stats = StreamingStats(schema, reservoir_size=reservoir_size, seed=seed)
    names = [feat.name for feat in schema.numeric]

    def to_float_arrays(item):
        columns, _ = item
        return [np.asarray(columns[name], dtype=np.float64) for name in names]

    run_pipeline(
        iter_table_chunks(path, chunk_rows, schema),
        [Stage("tofloat", to_float_arrays)],
        stats.update_arrays,
        depth=pipeline_depth,
        sink_name="fold",
    )
    return stats.finalize()


def score_csv_stream(
    bundle,
    in_path: str | Path,
    out_path: str | Path | None = None,
    chunk_rows: int = 65_536,
    mesh=None,
    exact: bool | None = None,
    pipeline_depth: int = 2,
    native: bool | None = None,
    compile_cache=None,
) -> dict[str, float]:
    """Stream-score a CSV/Parquet of any size through the bundle's fused
    predict.

    Stage graph (`data/pipeline_exec.py`): read+parse -> vectorized
    encode(+pad) -> device transfer -> ONE device dispatch (classifier +
    outliers) -> batched result fetch -> append ``prediction,outlier``
    rows to ``out_path``. At ``pipeline_depth=1`` the stages run serially
    on the caller thread (the pre-pipeline behavior, bit-identical
    output); at depth D they overlap on bounded queues — chunk N+1
    transfers while chunk N computes and chunk N-1's results fetch — with
    peak memory fixed at a few chunks. With a ``mesh``, each chunk is
    data-parallel over the 'data' axis (chunk size rounds up so the batch
    divides the axis). Returns aggregate stats including per-stage
    busy/occupancy timings and post-warmup ``rows_per_s``.

    Failure safety: output is written to a ``.tmp`` sibling and renamed
    into place only on success, so a mid-stream exception (which drains
    the pipeline and propagates) never leaves a partial file behind
    looking like a finished run.

    In a profiler trace every stage execution is a ``mlops:pipe.<stage>``
    span carrying the job's number (`parallel/bulk.py next_job_id`).
    """
    import contextlib

    from mlops_tpu.data.pipeline_exec import Stage, run_pipeline
    from mlops_tpu.parallel.bulk import (
        FETCH_WAVE,
        make_chunk_scorer,
        make_chunk_transfer,
        mesh_chunk_rows,
        next_job_id,
        use_distilled_bulk,
        warm_chunk_scorer,
    )

    chunk_rows = mesh_chunk_rows(
        chunk_rows, mesh, bundle.model_config.history_rows
    )
    # Same routing contract as score_dataset: ``exact=None`` auto-routes
    # through the distilled bulk student on CPU backends; the returned
    # stats carry ``path`` so the substitution is always visible.
    path_used = "distilled" if use_distilled_bulk(bundle, exact) else "exact"
    score_chunk = make_chunk_scorer(
        bundle,
        mesh=mesh,
        exact=exact,
        compile_cache=compile_cache,
        chunk_rows=chunk_rows,
    )
    transfer = make_chunk_transfer(bundle, mesh)
    # cat ids narrow to int8 on the device path (max vocab cardinality is
    # 12; lossless, a quarter of the host->device bytes) — same
    # convention as score_dataset.
    narrow = None if bundle.flavor == "sklearn" else np.int8

    # Make sure the one chunk program is compiled before the streamed (and
    # timed) run, so ``rows_per_s`` measures streaming, not a one-off
    # compile: score_dataset's rule, a no-op where an earlier call of this
    # process compiled it.
    warm_chunk_scorer(
        score_chunk, transfer, chunk_rows, host_model=bundle.flavor == "sklearn"
    )

    # Source + encode selection: when the native C++ kernel is available
    # and the input is CSV, the reader yields raw byte blocks and the
    # encode stage parses+encodes them in ONE ctypes call that RELEASES
    # the GIL — so encode genuinely overlaps the GIL-bound read/write
    # stages and the device compute (on CPU backends the Python csv parse
    # would otherwise serialize the whole pipeline on the GIL). Output is
    # parity-pinned bit-identical to the Python path (tests/test_native.py).
    # ``native=None`` auto-detects; ``False`` forces the Python csv parse
    # (the pre-executor serial baseline, the reference of
    # tests/test_native.py and tests/test_pipeline_exec.py).
    from mlops_tpu.data import parquet
    from mlops_tpu.native import encode_csv_bytes, native_available

    prep = bundle.preprocessor
    use_native = (
        native is not False
        and native_available()
        and not parquet.is_parquet(in_path)
    )
    if use_native:
        source = iter_raw_csv_chunks(in_path, chunk_rows)
    else:
        source = (
            ("columns", columns)
            for columns, _ in iter_table_chunks(in_path, chunk_rows)
        )

    # Hoisted mask: every full chunk shares ONE all-true mask; only the
    # tail chunk builds a fresh one from the hoisted arange.
    base_index = np.arange(chunk_rows)
    full_mask = np.ones(chunk_rows, bool)

    def encode_chunk(item):
        kind, payload = item
        ds = (
            encode_csv_bytes(payload, prep, source=str(in_path))
            if kind == "bytes"
            else prep.encode(payload)
        )
        n = ds.n
        cat = ds.cat_ids if narrow is None else ds.cat_ids.astype(narrow)
        # Pad to the fixed chunk shape so one compiled program serves
        # every chunk (the tail chunk is the only padded one; byte-split
        # chunks may also run short when blank lines were skipped).
        pad = chunk_rows - n
        if pad:
            cat = np.pad(cat, ((0, pad), (0, 0)))
            num = np.pad(ds.numeric, ((0, pad), (0, 0)))
            mask = base_index < n
        else:
            num = ds.numeric
            mask = full_mask
        return cat, num, mask, n

    def transfer_chunk(item):
        cat, num, mask, n = item
        return (*transfer(cat, num, mask), n)

    def compute_chunk(item):
        cat, num, mask, n = item
        probs, outliers = score_chunk(cat, num, mask)
        return probs, outliers, n

    def fetch_chunks(items):
        import jax

        fetched = jax.device_get([(probs, flags) for probs, flags, _ in items])
        return [
            (np.asarray(probs)[:n], np.asarray(flags)[:n])
            for (probs, flags), (_, _, n) in zip(fetched, items)
        ]

    rows = 0
    outlier_count = 0.0
    prob_sum = 0.0
    writer = None

    def write_chunk(item):
        nonlocal rows, outlier_count, prob_sum
        probs, outliers = item
        rows += probs.size
        outlier_count += float(outliers.sum())
        prob_sum += float(probs.sum())
        if writer is not None:
            writer.writerows(
                zip(np.round(probs, 6).tolist(), outliers.tolist())
            )

    tmp_path = None
    try:
        with contextlib.ExitStack() as stack:
            if out_path is not None:
                out_path = Path(out_path)
                out_path.parent.mkdir(parents=True, exist_ok=True)
                tmp_path = out_path.with_name(out_path.name + ".tmp")
                f = stack.enter_context(tmp_path.open("w", newline=""))
                writer = csv.writer(f)
                writer.writerow(["prediction", "outlier"])
            pipe = run_pipeline(
                source,
                [
                    Stage("encode", encode_chunk),
                    Stage("transfer", transfer_chunk),
                    Stage("compute", compute_chunk),
                    # Deep fetch input queue = the async-dispatch wave:
                    # compute runs ahead and one batched device_get
                    # drains it (see parallel/bulk.py FETCH_WAVE).
                    # batch_max >= 2 keeps fetch list-in/list-out even
                    # at depth 1.
                    Stage(
                        "fetch",
                        fetch_chunks,
                        batch_max=FETCH_WAVE,
                        queue_depth=FETCH_WAVE,
                    ),
                ],
                write_chunk,
                depth=pipeline_depth,
                span_attrs={"job": next_job_id()},
            )
        if tmp_path is not None:
            tmp_path.replace(out_path)
    except BaseException:
        if tmp_path is not None:
            tmp_path.unlink(missing_ok=True)
        raise
    return {
        "rows": rows,
        "path": path_used,
        "mean_prediction": prob_sum / max(rows, 1),
        "outlier_rate": outlier_count / max(rows, 1),
        "pipeline_depth": pipe.depth,
        "elapsed_s": round(pipe.wall_s, 4),
        "rows_per_s": round(rows / max(pipe.wall_s, 1e-9), 1),
        "stages": pipe.stages,
        **(
            {"compile_cache": compile_cache.stats()}
            if compile_cache is not None
            else {}
        ),
    }

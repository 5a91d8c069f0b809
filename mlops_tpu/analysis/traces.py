"""Layer 2: jaxpr-level checks over registered jitted entry points.

Each entry point (`analysis/entrypoints.py`) is abstract-evaluated with
``jax.make_jaxpr`` on schema-derived ``ShapeDtypeStruct`` batches — the
trace runs entirely in Python (no XLA compile, no device execution, works
under ``JAX_PLATFORMS=cpu``) yet sees exactly the program the production
builder would compile, because the entry wrappers call the REAL builders
(`make_train_window`, `make_padded_predict_fn`, `make_sharded_train_step`).

Checks (rule IDs continue the tpulint catalog):

- **TPU301 float64-leak**: any f64 value anywhere in the traced program —
  on TPU that silently demotes per-op or recompiles, and it means an
  unintended ``jax_enable_x64`` dependency.
- **TPU302 weak-type-output**: an output aval with ``weak_type=True`` —
  feeding it back into the entry (train-state loops!) makes the second
  call's signature differ from the first and recompiles.
- **TPU303 convert-element-type-round-trip**: ``convert_element_type``
  directly chained onto another whose output dtype returns to the start —
  a wasted cast pair that usually marks a dtype discipline bug.
- **TPU304 bucket-shape-polymorphism**: the primitive sequence of the
  traced program differs across the declared batch buckets — each bucket
  is then a genuinely different program, not the same program at another
  shape (padding/bucketing assumptions broken).
- **TPU305 sharding-link-mismatch**: a declared producer->consumer link
  (train step emits params, serve predict consumes them) whose shardings
  disagree — the consumer reshards on every handoff.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

from mlops_tpu.analysis.findings import Finding, Severity

TRACE_RULES = {
    "TPU301": ("float64-leak", Severity.ERROR),
    "TPU302": ("weak-type-output", Severity.ERROR),
    "TPU303": ("convert-element-type-round-trip", Severity.WARNING),
    "TPU304": ("bucket-shape-polymorphism", Severity.ERROR),
    "TPU305": ("sharding-link-mismatch", Severity.ERROR),
    "TPU306": ("entry-point-trace-failure", Severity.ERROR),
}


@dataclasses.dataclass
class EntryPoint:
    """One registered jitted entry point.

    ``build()`` returns ``(fn, args_by_bucket)`` where ``args_by_bucket``
    maps a batch-bucket size to the argument pytree (ShapeDtypeStructs) the
    entry is traced with. ``min_devices`` gates mesh-dependent entries;
    they are reported as skipped, never silently dropped.
    """

    name: str
    build: Callable[[], tuple[Callable, dict[int, tuple]]]
    min_devices: int = 1
    # Declared param-sharding contract for TPU305 links: a pytree of
    # PartitionSpec-like leaves (or None = replicated), produced/consumed.
    params_out_spec: Any = None
    params_in_spec: Any = None
    # Declared program families: buckets in the SAME tuple must trace to
    # the identical primitive sequence (TPU304); buckets in different
    # tuples are KNOWN distinct programs (e.g. the serve path's dense
    # small-batch K-S below 64 rows vs the sort-based one above it,
    # monitor/state.py). None = all buckets are one family.
    bucket_families: tuple[tuple[int, ...], ...] | None = None
    # Declared x64 entry (the gbm-tensor tier): the trace runs inside
    # `ops/gbm_tensor.py x64_context()` — exactly how production lowers it
    # (ops/gbm_tensor.py) — and the dtype rules treat f64 as the entry's
    # CONTRACT rather than a leak: TPU301 is skipped, and TPU303 ignores
    # round-trips through an f64 endpoint (the f64->f32->f64 narrowing at
    # the calibration boundary is the bit-parity semantics, not waste).
    x64: bool = False


@dataclasses.dataclass(frozen=True)
class ShardingLink:
    """Producer's packaged params feed the consumer. ``transport`` names
    the declared normalization between them ("as-is", "merge-to-dense")
    purely for the report message."""

    producer: str
    consumer: str
    transport: str = "as-is"


def _flag(rule: str, entry: str, message: str, bucket: int = 0) -> Finding:
    name, severity = TRACE_RULES[rule]
    return Finding(
        rule=rule,
        name=name,
        severity=severity,
        path=f"<trace:{entry}>",
        line=bucket,
        message=message,
    )


def _walk_jaxprs(jaxpr):
    """Yield every (sub)jaxpr: the top-level one plus everything nested in
    eqn params (pjit bodies, scan bodies, cond branches, custom-vjp...)."""
    seen: set[int] = set()
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        yield j
        for eqn in j.eqns:
            for value in eqn.params.values():
                for sub in _as_jaxprs(value):
                    stack.append(sub)


def _as_jaxprs(value) -> list:
    out = []
    values = (
        list(value) if isinstance(value, (tuple, list)) else [value]
    )
    for v in values:
        if hasattr(v, "jaxpr"):  # ClosedJaxpr
            out.append(v.jaxpr)
        elif hasattr(v, "eqns"):  # raw Jaxpr
            out.append(v)
    return out


def _iter_eqns(jaxpr):
    for j in _walk_jaxprs(jaxpr):
        yield from j.eqns


def primitive_signature(jaxpr) -> tuple[str, ...]:
    """The bucket-invariant fingerprint of the program: primitive names in
    traversal order. Shapes are deliberately excluded — shapes SHOULD
    differ across buckets; the op sequence should not."""
    return tuple(eqn.primitive.name for eqn in _iter_eqns(jaxpr))


def check_dtypes(
    entry_name: str, bucket: int, jaxpr, x64_entry: bool = False
) -> list[Finding]:
    """TPU301 (f64 anywhere) + TPU303 (convert round-trips).
    ``x64_entry`` relaxes both for a DECLARED f64 program (see
    `EntryPoint.x64`)."""
    import numpy as np

    findings: list[Finding] = []
    f64_hits = 0
    for eqn in _iter_eqns(jaxpr):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and dtype == np.float64:
                f64_hits += 1
    if f64_hits and not x64_entry:
        findings.append(
            _flag(
                "TPU301",
                entry_name,
                f"{f64_hits} float64 value(s) in the traced program — "
                "an unintended x64 dependency (TPUs demote or recompile); "
                "pin dtypes at the boundary",
                bucket,
            )
        )
    # Round-trip casts: convert(convert(x: A->B): B->A).
    producer_of: dict[Any, Any] = {}
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = eqn.invars[0]
        prev = producer_of.get(src)
        if prev is not None:
            start = getattr(prev.invars[0], "aval", None)
            end = getattr(eqn.outvars[0], "aval", None)
            if (
                start is not None
                and end is not None
                and start.dtype == end.dtype
                and not (x64_entry and start.dtype == np.float64)
            ):
                findings.append(
                    _flag(
                        "TPU303",
                        entry_name,
                        f"convert_element_type round-trip "
                        f"{start.dtype}->{prev.outvars[0].aval.dtype}->"
                        f"{end.dtype} — a wasted cast pair (dtype "
                        "discipline bug or a missing fused op)",
                        bucket,
                    )
                )
        for out in eqn.outvars:
            producer_of[out] = eqn
    return findings


def check_weak_types(entry_name: str, bucket: int, jaxpr) -> list[Finding]:
    """TPU302: outputs whose avals are weakly typed."""
    findings = []
    for i, aval in enumerate(jaxpr.out_avals):
        if getattr(aval, "weak_type", False):
            findings.append(
                _flag(
                    "TPU302",
                    entry_name,
                    f"output {i} is weak-typed ({aval.dtype}) — feeding it "
                    "back in (train-state loop, cached buffer) changes the "
                    "call signature and recompiles; anchor it with an "
                    "explicit jnp dtype",
                    bucket,
                )
            )
    return findings


def check_bucket_stability(
    entry_name: str,
    jaxprs_by_bucket: dict[int, Any],
    families: tuple[tuple[int, ...], ...] | None = None,
) -> list[Finding]:
    """TPU304: the primitive sequence must be identical across the buckets
    of each declared family (all buckets, when no families declared)."""
    if families is None:
        families = (tuple(sorted(jaxprs_by_bucket)),)
    findings = []
    # A traced bucket missing from every declared family would silently
    # dodge the check — the registry declaration must keep up with the
    # bucket list it covers (e.g. serve warmup_batch_sizes).
    declared = {b for family in families for b in family}
    for bucket in sorted(set(jaxprs_by_bucket) - declared):
        findings.append(
            _flag(
                "TPU304",
                entry_name,
                f"bucket {bucket} is traced but belongs to no declared "
                "bucket family — add it to the entry's bucket_families "
                "so shape stability is actually checked for it",
                bucket,
            )
        )
    for family in families:
        present = [b for b in family if b in jaxprs_by_bucket]
        findings.extend(
            _family_stability(entry_name, jaxprs_by_bucket, present)
        )
    return findings


def _family_stability(
    entry_name: str, jaxprs_by_bucket: dict[int, Any], buckets: list[int]
) -> list[Finding]:
    if len(buckets) < 2:
        return []
    reference = primitive_signature(jaxprs_by_bucket[buckets[0]])
    findings = []
    for bucket in buckets[1:]:
        sig = primitive_signature(jaxprs_by_bucket[bucket])
        if sig != reference:
            diff_at = next(
                (
                    i
                    for i, (a, b) in enumerate(zip(reference, sig))
                    if a != b
                ),
                min(len(reference), len(sig)),
            )
            findings.append(
                _flag(
                    "TPU304",
                    entry_name,
                    f"program shape-polymorphic across batch buckets "
                    f"{buckets[0]} vs {bucket}: {len(reference)} vs "
                    f"{len(sig)} primitives, first divergence at op "
                    f"{diff_at} — each bucket compiles a genuinely "
                    "different program, breaking the padded-bucket "
                    "serving contract",
                    bucket,
                )
            )
    return findings


def _spec_leaves(spec_tree: Any) -> list[tuple[str, str]]:
    """Canonicalize a sharding-spec pytree to (path, spec-string) pairs so
    trees built from different libraries compare structurally."""
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(spec_tree)[0]
    out = []
    for path, leaf in leaves:
        spec = getattr(leaf, "spec", leaf)  # NamedSharding -> PartitionSpec
        out.append((jax.tree_util.keystr(path), str(spec)))
    return sorted(out)


def check_sharding_links(
    entries: dict[str, EntryPoint], links: list[ShardingLink]
) -> list[Finding]:
    """TPU305 over the declared producer->consumer links."""
    findings = []
    for link in links:
        producer = entries.get(link.producer)
        consumer = entries.get(link.consumer)
        if producer is None or consumer is None:
            continue  # entry skipped (devices) — reported elsewhere
        out_spec = _spec_leaves(producer.params_out_spec)
        in_spec = _spec_leaves(consumer.params_in_spec)
        if out_spec != in_spec:
            mismatched = [
                f"{po} produces {so!r}, consumer expects {si!r}"
                for (po, so), (pi, si) in zip(out_spec, in_spec)
                if so != si
            ][:3] or [f"{len(out_spec)} vs {len(in_spec)} param leaves"]
            findings.append(
                _flag(
                    "TPU305",
                    f"{link.producer}->{link.consumer}",
                    f"params sharding mismatch over {link.transport!r} "
                    "transport: " + "; ".join(mismatched) + " — the "
                    "consumer reshards (all-gather) on every handoff",
                )
            )
    return findings


def run_trace_checks(
    entries: list[EntryPoint] | None = None,
    links: list[ShardingLink] | None = None,
) -> tuple[list[Finding], list[str]]:
    """Trace every available entry point and run every check.

    Returns ``(findings, notes)`` — notes record skipped entries (not
    enough devices) and per-entry trace stats for the CLI report.
    """
    import jax

    if entries is None or links is None:
        from mlops_tpu.analysis import entrypoints

        registered = entrypoints.registered_entry_points()
        entries = registered if entries is None else entries
        links = entrypoints.LINKS if links is None else links

    findings: list[Finding] = []
    notes: list[str] = []
    traced: dict[str, EntryPoint] = {}
    for entry in entries:
        if jax.device_count() < entry.min_devices:
            notes.append(
                f"skipped {entry.name}: needs >= {entry.min_devices} "
                f"devices, have {jax.device_count()} (run with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8)"
            )
            continue
        try:
            # A declared-x64 entry traces inside the x64 context — the
            # same context production lowers it in (ops/gbm_tensor.py);
            # aval canonicalization would otherwise silently demote its
            # f64 signature to f32 and trace a program nobody compiles.
            if entry.x64:
                from mlops_tpu.ops.gbm_tensor import x64_context

                ctx = x64_context()
            else:
                ctx = contextlib.nullcontext()
            with ctx:
                fn, args_by_bucket = entry.build()
                jaxprs = {
                    bucket: jax.make_jaxpr(fn)(*args)
                    for bucket, args in args_by_bucket.items()
                }
        # Any trace failure IS the finding (TPU306) — nothing is swallowed.
        except Exception as err:  # tpulint: disable=TPU201
            findings.append(
                _flag(
                    "TPU306",
                    entry.name,
                    f"entry point failed to trace abstractly: "
                    f"{type(err).__name__}: {err}",
                )
            )
            continue
        traced[entry.name] = entry
        ops = len(primitive_signature(next(iter(jaxprs.values()))))
        notes.append(
            f"traced {entry.name}: buckets {sorted(jaxprs)} "
            f"({ops} primitives, abstract — no device code executed)"
        )
        for bucket, jaxpr in jaxprs.items():
            findings.extend(
                check_dtypes(entry.name, bucket, jaxpr, x64_entry=entry.x64)
            )
            findings.extend(check_weak_types(entry.name, bucket, jaxpr))
        findings.extend(
            check_bucket_stability(entry.name, jaxprs, entry.bucket_families)
        )
    findings.extend(check_sharding_links(traced, links))
    return findings, notes

"""``mlops-tpu analyze`` — orchestrates both layers and gates the exit code.

Exit codes: 0 clean, 1 findings that gate (errors always; warnings too
under ``--strict``), 2 internal analyzer failure. Layer 1 never imports
JAX; Layer 2 does (skip it with ``--no-trace`` on JAX-less machines).
"""

from __future__ import annotations

import argparse
from pathlib import Path

from mlops_tpu.analysis.astrules import analyze_paths
from mlops_tpu.analysis.findings import Finding, format_findings


def _default_paths() -> list[str]:
    """Lint the installed package when run without paths — works from any
    cwd, matching how CI invokes the gate."""
    return [str(Path(__file__).resolve().parents[1])]


def run_analyze(args: argparse.Namespace) -> int:
    """Exit 2 (usage/analyzer failure) is distinct from 1 (findings):
    scripts keying on the gate must not read a typo'd path or an analyzer
    crash as lint violations."""
    try:
        return _run_analyze(args)
    # The boundary that implements the documented exit-code contract:
    # any analyzer crash becomes a visible 2, never a fake 1.
    except Exception as err:  # tpulint: disable=TPU201
        print(f"tpulint: internal analyzer failure: {type(err).__name__}: {err}")
        return 2


def _run_analyze(args: argparse.Namespace) -> int:
    paths = list(getattr(args, "paths", []) or []) or _default_paths()
    strict = bool(getattr(args, "strict", False))
    missing = [p for p in paths if not Path(p).exists()]
    if missing:
        print(f"tpulint: error: no such path: {', '.join(missing)}")
        return 2

    if getattr(args, "list_suppressions", False):
        # Report mode: the suppression ledger instead of the finding gate.
        from mlops_tpu.analysis.suppressions import (
            audit_paths,
            format_suppressions,
        )

        suppressions = audit_paths(paths)
        print(format_suppressions(suppressions))
        stale = [
            s for s in suppressions if not s.live and not s.skipped_file
        ]
        return 1 if (stale and getattr(args, "fail_stale", False)) else 0

    # Per-layer wall time, reported under --strict: the gate grows a
    # layer per review epoch, and a slow layer should show up in CI
    # output, not in folklore.
    from time import perf_counter

    timings: list[tuple[str, float]] = []

    def timed(label: str, fn):
        t0 = perf_counter()
        result = fn()
        timings.append((label, perf_counter() - t0))
        return result

    findings: list[Finding] = timed("layer1", lambda: analyze_paths(paths))
    if getattr(args, "concurrency", False):
        from mlops_tpu.analysis.concurrency import analyze_concurrency_paths

        findings.extend(
            timed("layer3", lambda: analyze_concurrency_paths(paths))
        )
    if getattr(args, "contracts", False):
        from mlops_tpu.analysis.contracts import analyze_contracts_paths

        findings.extend(
            timed("layer4", lambda: analyze_contracts_paths(paths))
        )
    if getattr(args, "async_rules", False):
        from mlops_tpu.analysis.asyncdiscipline import analyze_async_paths

        findings.extend(
            timed("layer5", lambda: analyze_async_paths(paths))
        )
    if getattr(args, "fail_stale", False):
        from mlops_tpu.analysis.suppressions import stale_findings

        # TPU400 findings are immune to disable comments by construction
        # (suppressions.py): a stale disable can't silence its own report.
        findings.extend(timed("audit", lambda: stale_findings(paths)))

    notes: list[str] = []
    if not getattr(args, "no_trace", False):
        # First jax touch of the command (deferred to here so --no-trace
        # stays importable on JAX-less machines).
        from mlops_tpu.analysis.traces import run_trace_checks

        trace_findings, notes = timed("layer2", run_trace_checks)
        findings.extend(trace_findings)

    if getattr(args, "numeric", False):
        from mlops_tpu.analysis.entrypoints import NumericAuditError, numeric_audit

        try:
            notes.extend(numeric_audit())
        except NumericAuditError as err:
            from mlops_tpu.analysis.findings import Severity

            findings.append(
                Finding(
                    rule="TPU307",
                    name="numeric-audit-failure",
                    severity=Severity.ERROR,
                    path=f"<numeric:{err.entry}>",
                    line=0,
                    message=str(err),
                )
            )

    for note in notes:
        print(f"tpulint: {note}")
    if strict and timings:
        spent = " | ".join(f"{label} {secs:.2f}s" for label, secs in timings)
        print(f"tpulint: layer timings: {spent}")
    if findings:
        print(format_findings(findings))
    gating = [f for f in findings if f.gates(strict)]
    print(
        f"tpulint: {len(findings)} finding(s), {len(gating)} gating"
        f"{' (strict)' if strict else ''} over {len(paths)} path(s)"
    )
    return 1 if gating else 0

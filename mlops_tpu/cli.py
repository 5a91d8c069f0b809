"""Command-line entry point: train | tune | register | serve | predict-file.

Replaces the reference's operational surface (Databricks bundle job runs,
`databricks bundle run train_register_model_job` — `deploy-kubernetes.yml:61`
— and ad-hoc notebook widgets) with one typed CLI.

Subcommands land with their subsystems; this module grows with the framework.
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="mlops-tpu",
        description="TPU-native credit-default MLOps framework",
    )
    parser.add_argument(
        "--config", default=None, help="path to a TOML config file"
    )
    sub = parser.add_subparsers(dest="command")
    for name, help_text in [
        ("synth", "generate a synthetic schema-conforming CSV"),
        ("train", "train a model and write a bundle"),
        ("pretrain", "masked-feature pretraining on unlabeled rows (bert)"),
        ("tune", "hyperparameter search (vmapped + sharded trials)"),
        ("register", "register a bundle in the model registry"),
        ("promote", "move a registered version between stages"),
        ("versions", "list registered versions, stages, tags"),
        ("gc", "prune registry orphans (and old unstaged versions)"),
        ("validate", "schema-check a CSV (OOV / unparseable counts)"),
        ("serve", "serve a bundle over HTTP (lifecycle.enabled=true also "
                  "runs the drift-triggered retrain -> shadow -> gated "
                  "hot-promotion loop in-process)"),
        ("lifecycle", "one-shot offline lifecycle pass: retrain a "
                      "candidate from the labeled window "
                      "(lifecycle.labeled_path), grade it against the "
                      "incumbent through the AUC/calibration gates, and "
                      "register it when it passes"),
        ("predict-file", "batch-score a CSV offline"),
        ("score-batch", "bulk-score 1M-scale rows data-parallel over the mesh"),
        ("warmup", "pre-populate the AOT executable cache (compilecache/) "
                   "for every registered entry point — bake it into the "
                   "serving image so restarts deserialize instead of "
                   "recompiling"),
        ("trace-report", "aggregate a traced server's span JSONL "
                         "(trace.dir): p50/p99 per stage per compiled "
                         "entry — where each request spent its latency"),
        ("autotune", "one-shot offline gridtuner pass: fit the per-entry "
                     "dispatch cost model from the device-time ledger "
                     "(slo.ledger_dir), search bucket grids against the "
                     "observed traffic shape, and print the winning "
                     "warmup plan (exit 3 when the current grid already "
                     "wins)"),
    ]:
        p = sub.add_parser(name, help=help_text)
        p.add_argument(
            "overrides",
            nargs="*",
            help="config overrides, e.g. train.steps=500",
        )
        if name == "warmup":
            p.add_argument(
                "--cache-dir",
                default=None,
                help="cache directory (sugar for cache.dir=<dir>)",
            )
        if name == "serve":
            p.add_argument(
                "--workers",
                type=int,
                default=None,
                help="HTTP front-end processes (sugar for serve.workers=N): "
                "N >= 2 binds one port from N processes via SO_REUSEPORT, "
                "all feeding one engine process over the shared-memory "
                "ring; 0/1 = single-process server",
            )
            p.add_argument(
                "--tenants",
                default=None,
                help="multi-tenant fleet declaration (sugar for "
                "serve.tenants_path=<file>): a tenants.toml naming N "
                "tenants (name, bundle_dir, quota weight, default "
                "tenant) served from ONE engine process — requests "
                "route by the x-tenant header, ring-plane admission "
                "(--workers >= 2) is weighted max-min fair per tenant "
                "per slot class (the single-process plane reserves "
                "each tenant a fixed slice of the dispatch pool "
                "instead), and every per-tenant series and span "
                "carries a tenant label",
            )
        if name == "serve":
            p.add_argument(
                "--replicas",
                type=int,
                default=None,
                help="engine replica set (sugar for "
                "serve.engine_replicas=E): E engine processes behind "
                "the one shared-memory ring — front ends fan "
                "descriptors out least-loaded with small-class "
                "affinity, every replica warms from the same AOT "
                "cache, and a kill -9 of one replica is a brownout of "
                "1/E capacity (needs --workers >= 2)",
            )
        if name == "trace-report":
            p.add_argument(
                "--ledger",
                action="store_true",
                help="report the device-time cost ledger (slo.ledger_dir) "
                "ranked by cost_ms_per_row instead of aggregating span "
                "files — the measured per-entry cost model the "
                "traffic-shape autotuner consumes",
            )
            p.add_argument(
                "--tenant",
                default=None,
                help="only aggregate spans whose tenant label matches "
                "(multi-tenant planes stamp every span with its tenant)",
            )
            p.add_argument(
                "--replica",
                type=int,
                default=None,
                help="only aggregate spans served by this engine "
                "replica (the ring plane stamps every span with the "
                "router's choice; pre-replica spans count as 0)",
            )
    # `flightrec` takes dump paths, not config overrides: rendering a
    # post-mortem must work on any box with just the dump files.
    flightrec = sub.add_parser(
        "flightrec",
        help="render flight-recorder dumps (runs/flightrec-*.json — "
        "written on burn-rate alerts, engine respawns, error spikes, "
        "and incident-time drains) into a human timeline",
    )
    flightrec.add_argument(
        "paths",
        nargs="+",
        help="dump files to render (e.g. runs/flightrec-*.json)",
    )
    # `analyze` takes paths + flags, not config overrides: static analysis
    # must run identically with zero configuration (CI, pre-commit).
    analyze = sub.add_parser(
        "analyze",
        help="tpulint: static TPU-correctness lint (AST rules + jaxpr "
        "trace checks over the registered entry points)",
    )
    analyze.add_argument(
        "--strict",
        action="store_true",
        help="warnings gate the exit code too (the CI mode)",
    )
    analyze.add_argument(
        "--no-trace",
        action="store_true",
        help="skip the jaxpr trace layer (no JAX import; AST rules only)",
    )
    analyze.add_argument(
        "--concurrency",
        action="store_true",
        help="also run the Layer-3 concurrency rules (lock-order graph, "
        "guard inference, blocking-under-lock, semaphore pairing — "
        "TPU401-404; pure AST, no JAX import)",
    )
    analyze.add_argument(
        "--contracts",
        action="store_true",
        help="also run the Layer-4 cross-process contract rules (shm "
        "ownership, metric-series parity + alert/doc references, config "
        "knob liveness, fault-point liveness — TPU501-504; pure AST, "
        "no JAX import)",
    )
    analyze.add_argument(
        "--async",
        action="store_true",
        dest="async_rules",
        help="also run the Layer-5 async/event-loop discipline rules "
        "(blocking call in a loop-confined context, fire-and-forget "
        "tasks, cross-thread writes to loop state, await under a sync "
        "mutex — TPU601-604; pure AST, no JAX import)",
    )
    analyze.add_argument(
        "--list-suppressions",
        action="store_true",
        help="report every `# tpulint: disable` in the tree with file:line,"
        " rule ids, and live/stale status, then exit (no analysis gate)",
    )
    analyze.add_argument(
        "--fail-stale",
        action="store_true",
        help="suppressions that no longer suppress anything become gating "
        "TPU400 findings (the CI mode keeping old disables honest)",
    )
    analyze.add_argument(
        "--numeric",
        action="store_true",
        help="also run the checkify numeric audit on the serve entry "
        "point (executes on the current backend; not part of the "
        "abstract gate)",
    )
    analyze.add_argument(
        "paths",
        nargs="*",
        help="files/directories to lint (default: the mlops_tpu package)",
    )
    return parser


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    if args.command is None:
        build_parser().print_help()
        return 1
    from mlops_tpu import commands

    return commands.run(args)


if __name__ == "__main__":
    sys.exit(main())

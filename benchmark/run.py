"""One run of one benchmark cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, driver, cell or
per-layer metric is a file of its own that this program finds by the name
in ``BENCHMARK.json`` (see ``benchmark/README.md``). One process: it owns
the chip. The last line of standard output is the result; nothing else is
printed there.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
EXIT_NO_PROGRAM, EXIT_NO_CHIP = 3, 4


class Context:
    """What a driver is given: the seed, the cell's data files, and the
    harness's clocks (set-up phases; spans that go into the trace)."""

    def __init__(self, seed: int, cell: dict, config: dict, traffic: dict):
        self.seed = seed
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.tracing = False
        self.phases: dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        start = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = (
                self.phases.get(name, 0.0) + time.perf_counter() - start
            )

    def span(self, name: str):
        """A harness span: in a traced run it is written into the profiler's
        trace as ``bench:<name>``, on the device operations' clock."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench:{name}")


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def find_file(roots: list[Path], *parts: str) -> Path:
    for root in roots:
        candidate = root.joinpath(*parts)
        if candidate.is_file():
            return candidate
    raise FileNotFoundError(f"{'/'.join(parts)} not under {[str(r) for r in roots]}")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The metrics of one kind that this cell reports: those that list it,
    and those that list no cell (for a per-layer metric: where the cell
    reports the end-to-end metric it moves)."""
    e2e = {
        m["name"]
        for m in bench["end_to_end"]
        if cell in m.get("workloads", [cell])
    }
    if kind == "end_to_end":
        return [m for m in bench["end_to_end"] if m["name"] in e2e]
    return [
        m
        for m in bench["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in e2e)
    ]


def device_facts(devices, chips: int) -> dict:
    """The device as JAX names it, and the fullest chip's memory.

    The TPU runtime books a running program's scratch (the compiler's
    temporaries, live from the program's start to its end) under
    ``peak_bytes_reserved`` and NOT under ``peak_bytes_in_use``, which counts
    buffers alone. What is resident now (the weights, which every program of
    the window took as arguments) was resident while that scratch was held,
    so ``memory_peak_bytes`` is the larger of ``peak_bytes_in_use`` and
    ``peak_bytes_reserved + bytes_in_use``. The three raw readings of the
    fullest chip go into the line beside it (PERF.md section 4 has the chip
    run that shows the scratch is held: ``benchmark/memory_probe.py``)."""
    fullest = {"memory_peak_bytes": 0}
    for device in devices[:chips]:
        stats = device.memory_stats() or {}
        print(f"memory_stats {device}: {stats}", file=sys.stderr)
        raw = {
            key: int(stats.get(key, 0))
            for key in ("peak_bytes_in_use", "peak_bytes_reserved", "bytes_in_use")
        }
        held = raw["peak_bytes_reserved"] + raw["bytes_in_use"]
        peak = max(raw["peak_bytes_in_use"], held)
        if peak >= fullest["memory_peak_bytes"]:
            fullest = {"memory_peak_bytes": peak, **raw}
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        **fullest,
    }


def judge(readings: dict, limits: dict) -> tuple[dict, bool]:
    """Each compared number beside its limit, and whether all hold."""
    compared = {
        name: {"value": value, "limit": limits[name]}
        for name, value in readings.items()
    }
    return compared, all(c["value"] <= c["limit"] for c in compared.values())


def load_cell(bench_root: Path, workload: str) -> dict:
    """Everything ``BENCHMARK.json`` and the cell's own files say of one cell."""
    bench = load_json(bench_root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; one of {sorted(cells)}")
    cell = cells[workload]
    roots = [bench_root / p for p in bench["paths"]]
    if HERE not in roots:
        roots.append(HERE)  # drivers and readers a test fixture does not copy
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    traffic = load_json(find_file(roots, "traffic", f"{cell['traffic']}.json"))
    return {
        "bench": bench,
        "cell": cell,
        "roots": roots,
        "config": load_json(bench_root / entry["file"]),
        "traffic": traffic,
        "limits": load_json(find_file(roots, "cells", f"{cell['name']}.json"))["limits"],
        "driver_file": find_file(roots, "drivers", f"{traffic['driver']}.py"),
    }


def run_cell(args, bench_root: Path, require_chip: bool, started: float) -> int:
    loaded = load_cell(bench_root, args.workload)
    bench, cell, roots = loaded["bench"], loaded["cell"], loaded["roots"]
    config, traffic, limits = loaded["config"], loaded["traffic"], loaded["limits"]

    sys.path.insert(0, str(CHECKOUT))
    try:
        import mlops_tpu  # noqa: F401  (the system under test)
    except ImportError as exc:
        print(f"the program is not in this checkout: {exc}", file=sys.stderr)
        return EXIT_NO_PROGRAM

    import jax

    from mlops_tpu.compilecache.location import enable_persistent_cache

    with contextlib.ExitStack() as stack:
        ctx = Context(args.seed, cell, config, traffic)
        with ctx.phase("device"):
            enable_persistent_cache()
            devices = jax.devices()
        if require_chip and (
            devices[0].platform == "cpu" or len(devices) < cell["chips"]
        ):
            print(
                f"cell {cell['name']} needs {cell['chips']} accelerator chip(s); "
                f"JAX found {len(devices)} x {devices[0].platform}",
                file=sys.stderr,
            )
            return EXIT_NO_CHIP
        ctx.phases["imports"] = time.perf_counter() - started - ctx.phases["device"]

        driver = load_module(loaded["driver_file"]).build(ctx)
        driver.setup()
        with ctx.phase("warmup"):
            driver.warmup()
        setup_s = time.perf_counter() - started

        trace_dir = None
        if args.trace:
            trace_dir = stack.enter_context(tempfile.TemporaryDirectory(prefix="bench-trace-"))
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            ctx.tracing = True
        try:
            with ctx.span("window"):
                window = driver.window(
                    args.seconds,
                    max_units=int(traffic["traced_units"]) if args.trace else None,
                )
        finally:
            if args.trace:
                ctx.tracing = False
                jax.profiler.stop_trace()

        device = device_facts(devices, cell["chips"])
        driver.release()

        if args.trace:
            from benchmark import trace_reduce
            from benchmark.peaks import peaks_for

            found = trace_reduce.find_xplane(trace_dir)
            flat = trace_reduce.load_xplane(found) if found else {"planes": []}
            trace = trace_reduce.reduce_trace(flat)
            facts = {
                "cell": cell,
                "config": config,
                "traffic": traffic,
                "window": window,
                "driver": driver,
                "setup": {**ctx.phases, "setup_s": setup_s},
                "trace": trace,
                # a CPU rehearsal has no peak to take a share of
                "peaks": None if device["platform"] == "cpu" else peaks_for(device["kind"]),
            }
            metrics = {}
            for metric in cell_metrics(bench, cell["name"], "per_layer"):
                reader = load_module(
                    find_file(roots, "layer_metrics", f"{metric['name']}.py")
                )
                value = reader.read(facts)
                if value is not None:
                    metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
            if trace is not None:
                device["busy_s"] = trace["busy_s"]
                device["window_s"] = trace["window_s"]
        else:
            values = {**window["metrics"], "setup_s": setup_s}
            metrics = {
                m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in cell_metrics(bench, cell["name"], "end_to_end")
            }

        print(
            "set-up split (s): "
            + ", ".join(f"{k}={v:.2f}" for k, v in ctx.phases.items())
            + f"; window {window['window_s']:.2f} s, {window['attempted']} unit(s)",
            file=sys.stderr,
        )
        check_start = time.perf_counter()
        compared, correct = judge(driver.check(), limits)
        print(f"reference and comparison took {time.perf_counter() - check_start:.2f} s",
              file=sys.stderr)
        for name, c in compared.items():
            print(
                f"compared {name}: value={c['value']:.6g} limit={c['limit']:.6g} "
                f"{'ok' if c['value'] <= c['limit'] else 'NOT CORRECT'}",
                file=sys.stderr,
            )
        result = {
            "correct": bool(correct),
            "attempted": window["attempted"],
            "failed": window["failed"],
            "metrics": metrics,
            "device": device,
        }
        if args.trace and trace is not None:
            result["breakdown"] = {
                "device_ops": trace["device_ops"],
                "idle_gaps": trace["idle_gaps"],
            }
        if args.trace:
            result["traced_end_to_end"] = window["metrics"]
        result["compared"] = compared
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
    return 0


def main(argv=None, bench_root: Path = CHECKOUT, require_chip: bool = True,
         started: float | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    return run_cell(
        args, Path(bench_root), require_chip,
        time.perf_counter() if started is None else started,
    )


if __name__ == "__main__":
    raise SystemExit(main(started=_PROCESS_START))

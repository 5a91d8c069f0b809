"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user would call —
``python -m mlops_tpu train`` -> bundle -> ``python -m mlops_tpu serve`` ->
``POST /predict`` — on ONE TPU chip at the flagship's full width (8-member
ensemble of (256, 256, 128) MLPs over the 23-feature schema, default serve
buckets, drift + outlier fused in), checks what comes out, and prints as
its LAST stdout line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failed phase, or a device that is not a TPU, gives ``"ok": false`` and
a non-zero exit. There is no fallback: nothing here runs on the CPU
except the one child LABELLED as the CPU reference.

One process for each chip: this parent NEVER imports jax. Every phase that
needs the chip is one child process at a time, and the parent learns the
device from what a child reports. All children share one compile cache
root (`mlops_tpu/compilecache/location.py`: ``$JAX_COMPILATION_CACHE_DIR``
or the checkout's ``.jax_cache``), with the AOT executable store at its
fixed sub-path.

Phases (one chip): device, encoder (host: which CSV encoder serves),
train, serve on the ring plane twice (``--workers 2``: jax-free
supervisor, two front ends, one engine child; the second start must
report AOT hits that execute), serve single-process, quant tier (the
fused Pallas kernel compiled, against its jnp composite), kernels (flash
attention forward and grad, compiled, against dense attention at
``highest`` precision).

``--chips 4`` runs ONLY the sharded trainer and what it is compared with:
``train model.family=ft_transformer model.tensor_parallel=2`` on the
(2, 2) ('data', 'model') mesh against the same seed and batches on one
device of that host.

Output beyond the result lines goes under ``chiprun_out/chip_smoke/``
(git-ignored): run roots, server logs, request and reference files.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
OUT = REPO / "chiprun_out" / "chip_smoke"

# ---------------------------------------------------------------- tolerances
# Stated once; every comparison below names one of these. Each is a few
# times the largest difference the chip runs of PR 22 printed (CHANGES.md
# has the readings).
#
# Served probabilities, TPU vs the same bundle on the CPU-pinned reference:
# both compute the MLPs in bf16 with f32 accumulation, in different orders
# (largest reading 1.6e-3).
PROB_ATOL_VS_CPU = 5e-3
# Quant tier: compiled Pallas kernel vs its jnp composite on the same chip
# — `ops/quant_kernel.py KERNEL_COMPOSITE_ATOL`, the one statement of that
# contract (the quant child reports it; this parent never imports jax) —
# for probabilities and drift scores. Outlier flags are 0/1 and must agree
# on all but FLAG_MISMATCH_MAX of the rows: a flag flips where the
# Mahalanobis distance sits on the threshold.
FLAG_MISMATCH_MAX = 0.02
# Flash attention vs dense attention under matmul precision "highest", on
# unit-normal q, k, v. The kernel's dots run at Mosaic's default precision
# (bf16 passes, f32 accumulation) whatever the input dtype, and the
# probabilities are rounded to the value dtype before the second dot; the
# gradient check differentiates sum(out**2), whose gradients reach ~4.
# Largest readings: forward 3.2e-3 (f32) / 1.5e-3 (bf16), gradient 1.3e-2
# (f32) / 6.4e-3 (bf16) — f32 inputs lose more, to the bf16 passes.
FLASH_ATOL = {"float32": 1e-2, "bfloat16": 1e-2}
FLASH_GRAD_ATOL = {"float32": 4e-2, "bfloat16": 4e-2}
# DP x TP trainer vs one device, per-step training loss (bf16 compute,
# reductions split over the 'model' axis; largest reading 1.9e-3).
TP_LOSS_ATOL = 1e-2

FLAGSHIP_TRAIN = (
    "model.family=mlp",
    "model.hidden_dims=256,256,128",
    "model.ensemble_size=8",
    "data.rows=50000",
    "train.steps=300",
    "train.eval_every=100",
    "train.batch_size=1024",
    "train.warmup_steps=30",
    "train.distill_quant=true",
)
TP_TRAIN = (
    "model.family=ft_transformer",
    "model.tensor_parallel=2",
    "data.rows=8000",
    "train.steps=6",
    "train.eval_every=1",
    "train.batch_size=256",
    "train.warmup_steps=2",
)
# The bert family's real attention shapes: (batch, seq, heads, head_dim).
FLASH_SHAPES = ((2, 508, 12, 64), (2, 2048, 12, 64))
BULK_ROWS = 256  # the top default serve bucket


class PhaseFailed(RuntimeError):
    pass


def check(condition: bool, message: str) -> None:
    if not condition:
        raise PhaseFailed(message)


def say(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


# ------------------------------------------------------------------ children
def child_env(**extra: str) -> dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(REPO), env.get("PYTHONPATH", "")) if p
    )
    env.update(extra)
    return env


def run(what: str, argv: list[str], env=None, timeout: float = 900) -> str:
    """One child process to its end -> its stdout; a non-zero exit fails
    the phase with the end of what the child wrote."""
    proc = subprocess.run(
        [sys.executable, *argv], env=env or child_env(), cwd=REPO, text=True,
        timeout=timeout, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    if proc.returncode != 0:
        raise PhaseFailed(
            f"{what} exited {proc.returncode}: "
            f"{(proc.stderr or proc.stdout)[-2000:]}"
        )
    return proc.stdout


def last_json_line(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def child_argv(name: str, *args: str) -> list[str]:
    """A fresh interpreter that runs one of CHILDREN (bottom of this
    file) — the only processes of this script that import jax."""
    code = ("import sys, chip_smoke; "
            "chip_smoke.run_as_child(sys.argv[1], sys.argv[2:])")
    return ["-c", code, name, *args]


def run_child(name: str, *args: str, env=None, timeout: float = 900) -> dict:
    """One of CHILDREN in its own process; its last stdout line is its
    JSON report."""
    return last_json_line(
        run(f"child {name}", child_argv(name, *args), env, timeout)
    )


def run_cli(*args: str, env=None, timeout: float = 900) -> dict:
    """``python -m mlops_tpu ARGS``; its last stdout line is its JSON
    report."""
    argv = ["-m", "mlops_tpu", *args]
    return last_json_line(run(f"mlops_tpu {args[0]}", argv, env, timeout))


# ------------------------------------------------------------ host-side phases
def phase_device(env=None) -> dict:
    return run_child("device", env=env, timeout=300)


def phase_encoder(out: Path) -> dict:
    """Which CSV encoder (C++ or Python) serves on this machine, built
    from ``encoder.cpp`` on first use; the C++ one must agree with the
    Python one, and a failed build on a machine with g++ is a failure."""
    from mlops_tpu import native
    from mlops_tpu.data import Preprocessor, generate_synthetic, write_csv_columns

    status = native.encoder_status()
    has_gxx = shutil.which("g++") is not None
    check(
        status["encoder"] == "c++" or not has_gxx,
        f"g++ is installed but the C++ encoder does not serve: {status}",
    )
    if status["encoder"] == "c++":
        columns, labels = generate_synthetic(64, seed=3)
        path = out / "encoder-probe.csv"
        write_csv_columns(path, columns, labels)
        prep = Preprocessor.fit(columns)
        fast = native.encode_csv_native(path, prep)
        slow = prep.encode(columns, labels)
        check(
            (fast.cat_ids == slow.cat_ids).all()
            and (fast.numeric == slow.numeric).all(),
            "C++ and Python encoders disagree",
        )
    return {**status, "gxx": has_gxx}


def phase_train(out: Path, overrides=FLAGSHIP_TRAIN, env=None) -> dict:
    """``python -m mlops_tpu train`` at the flagship config: a bundle is
    written, and the loss is finite and falls."""
    t0 = time.perf_counter()
    report = run_cli(
        "train", *overrides,
        f"registry.root={out / 'registry'}",
        f"registry.run_root={out / 'runs'}",
        env=env,
    )
    bundle = Path(report["bundle"])
    check((bundle / "manifest.json").is_file(), f"no bundle at {bundle}")
    journal = Path(report["run_dir"]) / "metrics.jsonl"
    rows = [json.loads(line) for line in journal.read_text().splitlines()]
    losses = [row.get("train_loss", row.get("loss")) for row in rows]
    check(len(losses) >= 2, f"need two loss readings, got {losses}")
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return {
        "bundle": str(bundle),
        "run_dir": report["run_dir"],
        "losses": losses,
        "auc": report["metrics"].get("validation_roc_auc_score"),
        "seconds": round(time.perf_counter() - t0, 1),
    }


def request_bodies(out: Path) -> dict[str, Path]:
    """The golden sample request and a seeded ``BULK_ROWS``-row body."""
    from mlops_tpu.data import generate_synthetic

    columns, _ = generate_synthetic(BULK_ROWS, seed=11)
    names = list(columns)
    records = [
        {name: columns[name][i] for name in names} for i in range(BULK_ROWS)
    ]
    bulk = out / f"request-{BULK_ROWS}.json"
    bulk.write_text(json.dumps(records, default=float))
    return {
        "sample": REPO / "tests" / "golden" / "sample-request.json",
        "bulk": bulk,
    }


class CpuReference:
    """The CPU-pinned child that scores the same bundle on the same
    bodies: a labelled reference, never a fallback. It needs no chip, so
    it runs beside the first server start; `result` waits for it."""

    def __init__(self, bundle: str, bodies: dict[str, Path], out: Path):
        self.target = out / "reference-cpu.json"
        self.target.unlink(missing_ok=True)
        self.report: dict | None = None
        self.proc = subprocess.Popen(
            [sys.executable, *child_argv(
                "reference", bundle, str(bodies["sample"]),
                str(bodies["bulk"]), str(self.target),
            )],
            env=self._env(), cwd=REPO,
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
        )

    @staticmethod
    def _env() -> dict[str, str]:
        """Pinned to the CPU and kept OUT of the shared compile cache: the
        reference is not the program under test, and (on a CPU rehearsal,
        where it compiles for the platform the servers use) a program it
        put into JAX's cache first could not be persisted by the AOT store
        afterwards (`compilecache/location.py`)."""
        env = child_env(JAX_PLATFORMS="cpu")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        return env

    def result(self) -> dict:
        if self.report is None:
            _, stderr = self.proc.communicate(timeout=600)
            check(
                self.proc.returncode == 0,
                f"cpu reference failed: {stderr[-2000:]}",
            )
            self.report = json.loads(self.target.read_text())
        return self.report

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


# ------------------------------------------------------------------- serving
def request(port: int, method: str, path: str, body: bytes | None = None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        headers = {"content-type": "application/json"} if body else {}
        conn.request(method, path, body=body, headers=headers)
        response = conn.getresponse()
        return response.status, response.read()
    finally:
        conn.close()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """``python -m mlops_tpu serve ARGS`` as a child, in its own process
    group so that leaving the ``with`` block stops everything it forked."""

    def __init__(self, log: Path, *args: str, env=None, ready_timeout=600):
        self.port = free_port()
        self.log = log
        self.args = args
        self.env = env or child_env()
        self.ready_timeout = ready_timeout

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.log_file = open(self.log, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "mlops_tpu", "serve", *self.args,
             "serve.host=127.0.0.1", f"serve.port={self.port}"],
            env=self.env, cwd=REPO, stdout=self.log_file,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        try:
            self._wait_ready()
        except BaseException:
            self.__exit__(None, None, None)
            raise
        return self

    def _wait_ready(self) -> None:
        deadline = time.perf_counter() + self.ready_timeout
        while time.perf_counter() < deadline:
            if self.proc.poll() is not None:
                raise PhaseFailed(
                    f"server exited {self.proc.returncode} before ready:\n"
                    + self.log.read_text()[-3000:]
                )
            try:
                if request(self.port, "GET", "/healthz/ready")[0] == 200:
                    self.ready_s = round(time.perf_counter() - self.t0, 1)
                    return
            except OSError:
                pass
            time.sleep(0.25)
        raise PhaseFailed(
            f"server not ready in {self.ready_timeout}s:\n"
            + self.log.read_text()[-3000:]
        )

    def __exit__(self, *exc) -> None:
        if self.proc.poll() is None:
            os.killpg(self.proc.pid, signal.SIGTERM)
            try:
                self.proc.wait(timeout=45)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)  # stragglers, if any
        except ProcessLookupError:
            pass
        self.proc.wait()
        self.log_file.close()

    def cache_stats(self) -> dict:
        """Summed AOT cache counters from the ``warmup complete; ready``
        log line(s) (one per engine)."""
        totals: dict[str, int] = {}

        def walk(node):
            if isinstance(node, dict):
                if "hits" in node and "misses" in node:
                    for key in ("hits", "misses", "discards", "unrunnable",
                                "unserializable"):
                        totals[key] = totals.get(key, 0) + int(node.get(key, 0))
                for value in node.values():
                    walk(value)

        for line in self.log.read_text().splitlines():
            _, marker, tail = line.partition("warmup complete; ready ")
            if marker:
                walk(json.loads(tail))
        return totals


def check_response(raw: bytes, rows: int) -> dict:
    response = json.loads(raw)
    for key in ("predictions", "outliers", "feature_drift_batch"):
        check(key in response, f"response lacks {key!r}")
    predictions = response["predictions"]
    check(len(predictions) == rows, f"{len(predictions)} predictions != {rows}")
    check(
        all(math.isfinite(p) and 0.0 <= p <= 1.0 for p in predictions),
        "a prediction is not a probability",
    )
    check(len(response["outliers"]) == rows, "outliers: wrong row count")
    drift = response["feature_drift_batch"]
    check(
        len(drift) > 0 and all(math.isfinite(float(v)) for v in drift.values()),
        "feature_drift_batch empty or not finite",
    )
    return response


def max_abs_diff(a, b) -> float:
    return max(abs(float(x) - float(y)) for x, y in zip(a, b, strict=True))


def exercise(server: Server, bodies: dict[str, Path], reference,
             atol: float) -> dict:
    """The requests every serve phase sends: the golden sample, the
    ``BULK_ROWS``-row body (probabilities against ``reference`` — a dict,
    or a callable that yields it — within ``atol``), a malformed body
    (422), and /metrics."""
    port = server.port
    if callable(reference):
        reference = reference()
    worst = {}
    for name, rows in (("sample", 1), ("bulk", BULK_ROWS)):
        status, raw = request(port, "POST", "/predict", bodies[name].read_bytes())
        check(status == 200, f"POST /predict {name}: HTTP {status}: {raw[:300]!r}")
        response = check_response(raw, rows)
        worst[name] = max_abs_diff(
            response["predictions"], reference[name]["predictions"]
        )
        check(
            worst[name] <= atol,
            f"{name}: probabilities differ from the reference by "
            f"{worst[name]:.3g} > {atol}",
        )
    status, _ = request(port, "POST", "/predict", b'{"not": "a list of records"')
    check(status == 422, f"malformed body: HTTP {status}, expected 422")
    status, raw = request(port, "GET", "/metrics")
    check(status == 200 and b"mlops_tpu_" in raw, f"/metrics: HTTP {status}")
    return {"max_abs_prob_diff": worst, "ready_s": server.ready_s}


def aot_dir() -> str:
    from mlops_tpu.compilecache.location import aot_store_dir

    return str(aot_store_dir())


def phase_serve(bundle: str, bodies, reference, out: Path, *, workers: int,
                expect_all_hits: bool, tag: str, overrides=(), env=None,
                atol: float = PROB_ATOL_VS_CPU) -> dict:
    """One server start on ``bundle`` with the AOT store on. No start may
    report an artifact that was discarded, could not run, or could not be
    persisted; with ``expect_all_hits`` every program must be an AOT hit
    (hits execute at warmup: `compilecache/cache.py _runs`)."""
    args = [f"serve.model_directory={bundle}", f"cache.dir={aot_dir()}",
            *overrides]
    if workers > 1:
        args = ["--workers", str(workers), *args]
    with Server(out / f"serve-{tag}.log", *args, env=env) as server:
        facts = exercise(server, bodies, reference, atol)
        cache = server.cache_stats()
    check(bool(cache), "no AOT cache statistics in the warmup log line")
    bad = {k: cache.get(k, 0) for k in ("discards", "unrunnable", "unserializable")}
    check(not any(bad.values()), f"AOT cache reported {bad}")
    if expect_all_hits:
        check(
            cache["hits"] > 0 and cache["misses"] == 0,
            f"second start must be all AOT hits, got {cache}",
        )
    return {**facts, "cache": cache}


# ----------------------------------------------------------------- the script
def run_one_chip(out: Path) -> None:
    say("encoder", **phase_encoder(out))
    trained = phase_train(out)
    say("train", **trained)
    bundle = trained["bundle"]
    bodies = request_bodies(out)
    cpu = CpuReference(bundle, bodies, out)
    try:
        # The ring plane first, twice: its first start is the first
        # compile of the serving programs anywhere, so what it persists in
        # the AOT store came from real compiles; the second must be all
        # hits, and so must the single-process plane after it.
        for tag, all_hits in (("ring-1", False), ("ring-2", True)):
            say("serve-" + tag, **phase_serve(
                bundle, bodies, cpu.result, out, workers=2,
                expect_all_hits=all_hits, tag=tag,
            ))
        say("serve-single", **phase_serve(
            bundle, bodies, cpu.result, out, workers=1, expect_all_hits=True,
            tag="single",
        ))
    finally:
        cpu.stop()
    say("quant", **phase_quant(bundle, bodies, out))
    say("kernels", **phase_kernels())


def phase_quant(bundle: str, bodies, out: Path, *, interpret: bool = False,
                overrides=(), env=None) -> dict:
    """The quant tier through ``serve.serve_tier=quant`` (the fused kernel
    compiled into the serving programs), then the kernel against its jnp
    composite in one child: the served probabilities must match the
    composite's within KERNEL_COMPOSITE_ATOL."""
    composite = run_child(
        "quant", bundle, str(bodies["sample"]), str(bodies["bulk"]),
        json.dumps(interpret), env=env,
    )
    atol = composite["atol"]
    say("quant-kernel-vs-composite", atol=atol, **composite["worst"])
    for name, worst in composite["worst"].items():
        check(
            worst["probabilities"] <= atol
            and worst["drift"] <= atol
            and worst["flag_mismatch_share"] <= FLAG_MISMATCH_MAX,
            f"quant kernel vs composite, {name} body: {worst} exceeds "
            f"{atol} / flags {FLAG_MISMATCH_MAX}",
        )
    served = phase_serve(
        bundle, bodies, composite["composite"], out, workers=1,
        expect_all_hits=False, tag="quant",
        overrides=("serve.serve_tier=quant", *overrides), env=env,
        atol=atol,
    )
    return {"kernel_vs_composite": composite["worst"], **served}


def phase_kernels(shapes=FLASH_SHAPES, interpret: bool = False) -> dict:
    """Flash attention forward and grad in one child, against dense
    attention at "highest": every difference inside FLASH_ATOL /
    FLASH_GRAD_ATOL for its input dtype."""
    report = run_child("kernels", json.dumps(shapes), json.dumps(interpret))
    say("kernels-measured", **report)
    for case, diffs in report["flash"].items():
        dtype = case.split("-")[1]
        check(
            diffs["fwd_max_abs_diff"] <= FLASH_ATOL[dtype]
            and diffs["grad_max_abs_diff"] <= FLASH_GRAD_ATOL[dtype],
            f"flash attention {case}: {diffs} exceeds "
            f"{FLASH_ATOL[dtype]} / {FLASH_GRAD_ATOL[dtype]}",
        )
    return report


def run_four_chips(out: Path) -> None:
    say("tp", **phase_tp(out))


def phase_tp(out: Path, overrides=TP_TRAIN, env=None) -> dict:
    """The DP x TP trainer through the CLI on every device of the host,
    then — one process at a time — the same seed and batches on ONE
    device, plus the bytes each device holds under the TP layout."""
    roots = (f"registry.root={out / 'registry'}",
             f"registry.run_root={out / 'runs'}")
    report = run_cli("train", *overrides, *roots, env=env)
    journal = Path(report["run_dir"]) / "metrics.jsonl"
    tp_losses = [
        json.loads(line)["loss"] for line in journal.read_text().splitlines()
    ]
    compared = run_child("tp-reference", json.dumps(list(overrides)), env=env)
    one_losses = compared["losses"]
    check(len(tp_losses) == len(one_losses), "step counts differ")
    check(all(math.isfinite(x) for x in tp_losses), f"tp loss: {tp_losses}")
    worst = max_abs_diff(tp_losses, one_losses)
    check(
        worst <= TP_LOSS_ATOL,
        f"DPxTP and one-device losses differ by {worst:.3g} > {TP_LOSS_ATOL}: "
        f"{tp_losses} vs {one_losses}",
    )
    for what in ("param_bytes_per_device", "batch_bytes_per_device"):
        held = compared[what]
        check(
            len(held) == compared["mesh_devices"] and all(held.values()),
            f"{what}: not every device holds a share: {held}",
        )
    return {
        "tp_losses": tp_losses, "one_device_losses": one_losses,
        "max_abs_loss_diff": worst, "mesh": compared["mesh"],
        "param_bytes_per_device": compared["param_bytes_per_device"],
        "batch_bytes_per_device": compared["batch_bytes_per_device"],
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)

    device = None
    try:
        shutil.rmtree(OUT, ignore_errors=True)
        OUT.mkdir(parents=True)
        device = phase_device()
        say("device", **device)
        check(
            device["platform"] == "tpu",
            f"the device is not a TPU: {device}",
        )
        check(
            device["count"] == args.chips,
            f"--chips {args.chips} but JAX reports {device['count']} devices",
        )
        (run_four_chips if args.chips == 4 else run_one_chip)(OUT)
        check("jax" not in sys.modules, "the parent process imported jax")
    except Exception as err:  # every failure ends in the one result line
        print(json.dumps({
            "ok": False, "device": device,
            "error": f"{type(err).__name__}: {err}"[-4000:],
        }), flush=True)
        return 1
    finally:
        # Keep the logs and the request/reference files; the run roots
        # (checkpoints, bundles, registry copies) are tens of MiB.
        for heavy in ("runs", "registry"):
            shutil.rmtree(OUT / heavy, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# ====================================================================== children
# Everything below runs in a child process. Only here is jax imported.
def run_as_child(name: str, args: list[str]) -> None:
    from mlops_tpu.compilecache.location import enable_persistent_cache

    if name != "reference":
        enable_persistent_cache()
    print(json.dumps(CHILDREN[name](*args)), flush=True)


def child_device() -> dict:
    import jax

    device = jax.devices()[0]
    return {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }


def _records(path: str) -> list[dict]:
    return json.loads(Path(path).read_text())


def child_reference(bundle_dir, sample, bulk, target) -> dict:
    """The same bundle, scored by the normal engine on the CPU."""
    import jax

    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.serve.engine import InferenceEngine

    check(jax.devices()[0].platform == "cpu", "the reference is CPU-pinned")
    engine = InferenceEngine(
        load_bundle(bundle_dir), buckets=(1, BULK_ROWS), enable_grouping=False
    )
    engine.warmup()
    report = {
        "platform": "cpu",
        "sample": engine.predict_records(_records(sample)),
        "bulk": engine.predict_records(_records(bulk)),
    }
    Path(target).write_text(json.dumps(report))
    return {"written": target}


def child_quant(bundle_dir, sample, bulk, interpret) -> dict:
    """The fused quant kernel against its jnp composite, both jitted on
    this process's device, at the buckets the two bodies land in. Without
    ``interpret`` the kernel is the production route (`make_quant_packed_
    base()`), which must have lowered to a Mosaic call."""
    import jax
    import numpy as np

    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.monitor.state import init_accumulator
    from mlops_tpu.ops.predict import packed_layout
    from mlops_tpu.ops.quant_kernel import (
        KERNEL_COMPOSITE_ATOL,
        make_quant_packed_base,
    )
    from mlops_tpu.schema import records_to_columns

    interpret = json.loads(interpret)
    bundle = load_bundle(bundle_dir)
    qparams, monitor = bundle.quant_params, bundle.monitor
    temperature = np.float32(bundle.quant_temperature)
    if interpret:
        kernel = jax.jit(make_quant_packed_base(use_kernel=True, interpret=True))
    else:
        kernel = jax.jit(make_quant_packed_base())
    composite = jax.jit(make_quant_packed_base(use_kernel=False))

    out = {"composite": {}, "worst": {}, "atol": KERNEL_COMPOSITE_ATOL}
    for name, path in (("sample", sample), ("bulk", bulk)):
        ds = bundle.preprocessor.encode(records_to_columns(_records(path)))
        rows = ds.n
        args = (qparams, monitor, init_accumulator(), temperature,
                ds.cat_ids, ds.numeric, np.ones(rows, bool))
        if not interpret:
            check(
                "tpu_custom_call" in kernel.lower(*args).compile().as_text(),
                "the quant kernel did not lower to a Mosaic call",
            )
            check(
                "tpu_custom_call"
                not in composite.lower(*args).compile().as_text(),
                "the composite holds a Mosaic call",
            )
        got, _ = kernel(*args)
        want, _ = composite(*args)
        got, want = np.asarray(got), np.asarray(want)
        check(bool(np.isfinite(got).all()), "quant kernel output not finite")
        p, o, d = packed_layout(rows)
        out["worst"][name] = {
            "probabilities": float(np.abs(got[p] - want[p]).max()),
            "drift": float(np.abs(got[d] - want[d]).max()),
            "flag_mismatch_share": float((got[o] != want[o]).mean()),
        }
        out["composite"][name] = {"predictions": want[p].tolist()}
    return out


def child_kernels(shapes, interpret="false") -> dict:
    """Flash attention forward and ``jax.grad`` through it, compiled (the
    lowered text must hold a ``tpu_custom_call``; an interpreted or dense
    run does not count), against dense attention at precision "highest"."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mlops_tpu.ops.attention import (
        attend,
        flash_attention,
        reference_attention,
    )

    interpret = json.loads(interpret)
    if interpret:
        def kernel(q, k, v):
            return flash_attention(q, k, v, interpret=True)
    else:
        kernel = attend  # the product's dispatch: flash on a TPU at S >= 128

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum()

    report = {}
    for shape in json.loads(shapes):
        for dtype in (jnp.float32, jnp.bfloat16):
            keys = jax.random.split(jax.random.PRNGKey(0), 3)
            q, k, v = (jax.random.normal(key, tuple(shape), dtype) for key in keys)
            fwd = jax.jit(kernel)
            grad = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))
            if not interpret:
                for fn in (fwd, grad):
                    check(
                        "tpu_custom_call"
                        in fn.lower(q, k, v).compile().as_text(),
                        "flash attention did not lower to a Mosaic call",
                    )
            with jax.default_matmul_precision("highest"):
                f32 = [x.astype(jnp.float32) for x in (q, k, v)]
                want = reference_attention(*f32)
                want_grad = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(*f32)
            name = jnp.dtype(dtype).name
            out_diff = float(jnp.abs(fwd(q, k, v).astype(jnp.float32) - want).max())
            grad_diff = max(
                float(jnp.abs(g.astype(jnp.float32) - w).max())
                for g, w in zip(grad(q, k, v), want_grad)
            )
            report[f"S{shape[1]}-{name}"] = {
                "fwd_max_abs_diff": out_diff, "grad_max_abs_diff": grad_diff,
            }
    return {"flash": report}


def child_tp_reference(overrides) -> dict:
    """What the CLI's DP x TP run is compared with, in one process that
    sees the same devices: (1) the bytes each device holds under the TP
    layout — params and optimizer state after one step, and one batch
    placed by the step's own data sharding; (2) the per-step losses of the
    same model, seed, batches and dropout keys on ONE device."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from mlops_tpu.config import load_config
    from mlops_tpu.data import Preprocessor
    from mlops_tpu.models import build_model, init_params
    from mlops_tpu.parallel.mesh import make_mesh
    from mlops_tpu.parallel.sharding import batch_sharding
    from mlops_tpu.parallel.steps import make_sharded_train_step
    from mlops_tpu.train.loop import TrainState, make_optimizer
    from mlops_tpu.train.pipeline import (
        _batch_indices,
        load_training_data,
        split_dataset,
    )
    from mlops_tpu.train.tensor_parallel import make_tp_trainer

    config = load_config(None, overrides=json.loads(overrides))
    tcfg = config.train
    columns, labels = load_training_data(config)
    ds = Preprocessor.fit(columns).encode(columns, labels)
    train_ds, _ = split_dataset(ds, config.data.valid_fraction)
    drop_key = jax.random.fold_in(jax.random.PRNGKey(tcfg.seed), 0x7EA50000)

    def batch(step):
        idx = _batch_indices(train_ds.n, tcfg.batch_size, tcfg.seed, step)
        return (
            jnp.asarray(train_ds.cat_ids[idx]),
            jnp.asarray(train_ds.numeric[idx]),
            jnp.asarray(train_ds.labels[idx]),
            jax.random.fold_in(drop_key, step),
        )

    def bytes_per_device(tree) -> dict[str, int]:
        held: dict[str, int] = {}
        for leaf in jax.tree_util.tree_leaves(tree):
            for shard in leaf.addressable_shards:
                key = str(shard.device.id)
                held[key] = held.get(key, 0) + shard.data.nbytes
        return dict(sorted(held.items()))

    # (1) the TP layout, exactly as the CLI builds it.
    trainer = make_tp_trainer(config)
    state, _ = trainer.step_fn(trainer.state, *batch(1))
    placed = jax.device_put(batch(1)[:3], (
        batch_sharding(trainer.mesh), batch_sharding(trainer.mesh),
        batch_sharding(trainer.mesh, ndim=1),
    ))
    report = {
        "mesh": dict(zip(trainer.mesh.axis_names, trainer.mesh.devices.shape)),
        "mesh_devices": int(trainer.mesh.devices.size),
        "param_bytes_per_device": bytes_per_device(
            (state.params, state.opt_state)
        ),
        "batch_bytes_per_device": bytes_per_device(placed),
    }

    # (2) one device: the same step function on a (1, 1) mesh.
    model = build_model(dataclasses.replace(config.model, tensor_parallel=0))
    params = init_params(model, jax.random.PRNGKey(tcfg.seed))["params"]
    optimizer = make_optimizer(tcfg)
    one = make_mesh(1, model_parallel=1, devices=jax.devices()[:1])
    step_fn, _ = make_sharded_train_step(model, optimizer, tcfg, one, params)
    state = TrainState(
        params=params, opt_state=optimizer.init(params),
        step=jnp.asarray(0, jnp.int32), rng=jax.random.PRNGKey(tcfg.seed),
        ema=(jax.tree_util.tree_map(jnp.zeros_like, params)
             if tcfg.ema_decay else None),
    )
    losses = []
    for step in range(1, tcfg.steps + 1):
        state, loss = step_fn(state, *batch(step))
        losses.append(round(float(loss), 6))
    report["losses"] = losses
    return report


CHILDREN = {
    "device": child_device,
    "reference": child_reference,
    "quant": child_quant,
    "kernels": child_kernels,
    "tp-reference": child_tp_reference,
}


if __name__ == "__main__":
    raise SystemExit(main())

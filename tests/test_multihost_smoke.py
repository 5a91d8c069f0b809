"""Live 2-process DCN smoke: jax.distributed over localhost, one psum.

`parallel/distributed.py` claims a real multi-host handshake via the
``MLOPS_TPU_COORDINATOR`` env contract (what the GKE JobSet sets); this
test backs the claim with two actual OS processes on the CPU backend —
coordinator bring-up, Gloo peer connect, a cross-process ``psum`` through
``jax.shard_map``, and coordinator-only artifact gating. The reference
has nothing to test here (its "distributed" layer is HTTPS to managed
services, SURVEY.md §5.8); this is the TPU-native replacement's wire
check.
"""

import socket
import subprocess
import sys
from pathlib import Path

_WORKER = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")

from mlops_tpu.parallel.compat import shard_map
from mlops_tpu.parallel.distributed import initialize, is_coordinator

ran = initialize()
assert ran, "initialize() must run under MLOPS_TPU_COORDINATOR"
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 2, jax.device_count()

import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

mesh = Mesh(jax.devices(), ("data",))
f = jax.jit(
    shard_map(
        lambda x: jax.lax.psum(x, "data"),
        mesh=mesh, in_specs=P("data"), out_specs=P(),
    )
)
rank = int(os.environ["MLOPS_TPU_PROCESS_ID"])
try:
    out = np.asarray(f(jnp.arange(2.0)))
    assert out.item() == 1.0, out
    psum = "ok"
except Exception as err:
    # jaxlib 0.4.x: "Multiprocess computations aren't implemented on the
    # CPU backend" — the DCN handshake above still proves the wire-up;
    # anything OTHER than that capability gap must fail the worker.
    if "Multiprocess computations" not in str(err):
        raise
    psum = "unsupported"
assert is_coordinator() == (rank == 0)
print(f"rank{{rank}} psum {{psum}}")
"""


def test_two_process_psum(tmp_path):
    repo = str(Path(__file__).resolve().parent.parent)
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER.format(repo=repo))

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    procs = []
    for rank in range(2):
        env = {
            "PATH": "/usr/bin:/bin:/usr/local/bin",
            "HOME": str(tmp_path),
            "JAX_PLATFORMS": "cpu",
            "MLOPS_TPU_COORDINATOR": f"127.0.0.1:{port}",
            "MLOPS_TPU_PROCESS_ID": str(rank),
            "MLOPS_TPU_NUM_PROCESSES": "2",
        }
        procs.append(
            subprocess.Popen(
                [sys.executable, str(worker)],
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
                env=env,
            )
        )
    outputs = []
    for rank, proc in enumerate(procs):
        out, _ = proc.communicate(timeout=180)
        outputs.append(out)
        assert proc.returncode == 0, f"rank{rank} failed:\n{out}"
    for rank in range(2):
        assert f"rank{rank} psum ok" in outputs[rank]

"""Ask the TPU compiler, without a chip, what a cell's timed program needs.

Run by hand in the sandbox (it is not part of a benchmark run):

    JAX_PLATFORMS=cpu python benchmark/compile_check.py bert-base 4096 8192

For each ``chunk_rows`` it lowers the program's own fused bulk chunk program
(``parallel/bulk.py make_bulk_fused``) on ``ShapeDtypeStruct``s placed on one
described ``v5e:2x2`` device, compiles it, and prints ``memory_analysis()``
and the operand types of the ``dot_general``s in the lowered text. The sizes
in ``benchmark/configs/*.json`` were chosen from this output by the rule in
PERF.md (largest power of two that needs no more than 75% of what the
compiler allows). A compile that passes here is not a chip run.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time
from collections import Counter
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

HBM_ALLOWED = 15.75 * 2**30  # what the v5e compiler allows one program


def dot_types(text: str) -> dict[str, int]:
    """Operand element types of every dot_general in a lowered module."""
    found = Counter()
    for line in text.splitlines():
        if "dot_general" not in line:
            continue
        sig = line.rsplit(":", 1)[-1]
        types = re.findall(r"tensor<[^>]*?x?([a-z]+[0-9]+)>", sig)
        found["x".join(types[:2]) + "->" + (types[2] if len(types) > 2 else "?")] += 1
    return dict(found)


def main(argv: list[str]) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from mlops_tpu.config import ModelConfig
    from mlops_tpu.models import abstract_variables, build_model
    from mlops_tpu.monitor.state import abstract_monitor_state
    from mlops_tpu.parallel.bulk import make_bulk_fused
    from mlops_tpu.schema import SCHEMA

    name, sizes = argv[0], [int(a) for a in argv[1:]]
    spec = json.loads(
        (Path(__file__).parent / "configs" / f"{name}.json").read_text()
    )
    fields = dict(spec["model_config"])
    fields["hidden_dims"] = tuple(fields["hidden_dims"])
    model = build_model(ModelConfig(**fields))

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree
        )

    variables = place(abstract_variables(model))
    monitor = place(abstract_monitor_state())
    fused = jax.jit(make_bulk_fused(model))
    for rows in sizes:
        args = (
            variables,
            monitor,
            jax.ShapeDtypeStruct((), jnp.float32, sharding=one),
            jax.ShapeDtypeStruct((rows, SCHEMA.num_categorical), jnp.int8, sharding=one),
            jax.ShapeDtypeStruct((rows, SCHEMA.num_numeric), jnp.float32, sharding=one),
            jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one),
        )
        lowered = fused.lower(*args)
        t0 = time.perf_counter()
        try:
            mem = lowered.compile().memory_analysis()
        except Exception as exc:  # the compiler's refusal is the answer
            print(json.dumps({"config": name, "chunk_rows": rows,
                              "refused": str(exc).splitlines()[0][:300]}))
            continue
        total = (
            mem.temp_size_in_bytes
            + mem.argument_size_in_bytes
            + mem.output_size_in_bytes
        )
        print(json.dumps({
            "config": name,
            "chunk_rows": rows,
            "temp_gb": round(mem.temp_size_in_bytes / 1e9, 3),
            "argument_gb": round(mem.argument_size_in_bytes / 1e9, 3),
            "output_gb": round(mem.output_size_in_bytes / 1e9, 3),
            "share_of_allowed": round(total / HBM_ALLOWED, 3),
            "compile_s": round(time.perf_counter() - t0, 1),
            "dots": dot_types(lowered.as_text()),
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))

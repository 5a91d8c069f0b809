"""Ask the TPU's compiler — the ONE file that does.

The compiler for the chip is installed here and compiles for a chip that
is described, not attached (`on-chip-measurement` guide §2, rehearsal 3).
Every Pallas kernel of the main path is compiled at the widths the code
really uses, plus the flagship packed predict program; what Mosaic or XLA
would refuse on the chip, it refuses here, at no chip time. A compile
that passes is NOT a chip run: nothing executes, so these tests say
nothing about results or times (chip_smoke.py does).

Discipline (why this is one file, with a plain module-scoped fixture):
only one process may load the TPU library, and it keeps it until exit.
The topology is therefore described inside a fixture — never at import,
in a ``skipif``, in ``parametrize`` or in conftest — so every xdist worker
collects the same tests and only the worker that runs this file loads the
library. Compiles happen in this process, with JAX's persistent cache off
around them (an entry written for a described chip cannot be read back
without one, and warns).

The kernel-or-XLA decision is made at lowering from the platform lowered
for (`ops/kernel_gate.py`), so the production entry points (`attend`,
`make_quant_packed_base()`) take their TPU branch here with no steering.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

S = jax.ShapeDtypeStruct


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from conftest import persistent_cache_off

    with persistent_cache_off():
        yield


def _on(tree, sharding):
    """Abstract pytree -> the same shapes committed to ``sharding``."""
    return jax.tree_util.tree_map(
        lambda leaf: S(leaf.shape, leaf.dtype, sharding=sharding), tree
    )


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# The bert family's real attention shapes: d = 64 (hidden 768 / 12 heads),
# S = 508 for doc_records=11 (2 + 46*11) and S = 2048; default blocks.
@pytest.mark.parametrize("seq", [508, 2048])
@pytest.mark.parametrize(
    "kernel,argnums",
    [
        ("flash_fwd", None),
        ("flash_bwd_dq", (0,)),  # grad wrt q: XLA drops the dk/dv kernel
        ("flash_bwd_dkv", (1, 2)),  # grad wrt k, v: XLA drops the dq kernel
    ],
)
def test_flash_attention_kernels_compile_for_v5e(
    one_chip, no_persistent_cache, seq, kernel, argnums
):
    from mlops_tpu.ops.attention import attend

    x = S((2, seq, 12, 64), jnp.bfloat16, sharding=one_chip)
    if argnums is None:
        fn = attend
    else:
        fn = jax.grad(
            lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
            argnums=argnums,
        )
    text = _compile(fn, x, x, x)
    assert "tpu_custom_call" in text
    assert kernel in text, f"{kernel} is not in the compiled program"


@pytest.mark.parametrize("rows", [1, 256])
def test_quant_fused_kernel_compiles_for_v5e(
    one_chip, no_persistent_cache, rows
):
    """Through the real entry (`make_quant_packed_base()`, auto route) at
    the student's real widths (QUANT_EMBED_DIM / QUANT_HIDDEN, the
    schema's vocabulary sizes, the monitor's real reference size)."""
    from mlops_tpu.monitor.state import (
        abstract_accumulator,
        abstract_monitor_state,
    )
    from mlops_tpu.ops.quant import abstract_quant_params
    from mlops_tpu.ops.quant_kernel import make_quant_packed_base
    from mlops_tpu.schema import SCHEMA

    args = _on(
        (
            abstract_quant_params(),
            abstract_monitor_state(),
            abstract_accumulator(),
            S((), jnp.float32),
            S((rows, SCHEMA.num_categorical), jnp.int32),
            S((rows, SCHEMA.num_numeric), jnp.float32),
            S((rows,), jnp.bool_),
        ),
        one_chip,
    )
    text = _compile(make_quant_packed_base(), *args)
    assert "tpu_custom_call" in text
    assert "quant_fused" in text


def test_flagship_packed_predict_compiles_for_v5e(
    one_chip, no_persistent_cache
):
    """The flagship serving program — 8-member ensemble of (256, 256, 128)
    MLPs with drift + outlier fused in — at the top serve bucket."""
    from mlops_tpu.config import ModelConfig
    from mlops_tpu.models import abstract_variables, build_model
    from mlops_tpu.monitor.state import (
        abstract_accumulator,
        abstract_monitor_state,
    )
    from mlops_tpu.ops.predict import make_packed_predict_base
    from mlops_tpu.schema import SCHEMA

    model = build_model(ModelConfig(family="mlp", ensemble_size=8))
    rows = 256
    args = _on(
        (
            abstract_variables(model),
            abstract_monitor_state(),
            abstract_accumulator(),
            S((), jnp.float32),
            S((rows, SCHEMA.num_categorical), jnp.int32),
            S((rows, SCHEMA.num_numeric), jnp.float32),
            S((rows,), jnp.bool_),
        ),
        one_chip,
    )
    text = _compile(make_packed_predict_base(model), *args)
    assert "fusion" in text

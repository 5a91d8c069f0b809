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

import math
import os
import re

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


def _entry_instructions(text: str) -> list[tuple[str, list[int], str]]:
    """(op, dims, op_name) of every array-valued instruction of the ENTRY
    computation of an optimised HLO text."""
    found = []
    entry = text[text.index("\nENTRY ") :]
    for line in entry.splitlines()[1:]:
        m = re.match(
            r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(", line
        )
        if not m:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        found.append(
            (
                m.group(2),
                [int(d) for d in m.group(1).split(",") if d],
                name.group(1) if name else "",
            )
        )
    return found


def _layout_changes(text: str, at_least: int) -> list[tuple[str, list[int], str]]:
    """The ENTRY computation's `reshape`, `copy` and `transpose`
    instructions of ``at_least`` elements or more. In optimised HLO a
    reshape that moves nothing is a `bitcast`; what is still called
    `reshape` is a physical relayout."""
    return [
        i
        for i in _entry_instructions(text)
        if i[0] in ("reshape", "copy", "transpose")
        and math.prod(i[1]) >= at_least
    ]


def test_layout_scan_sees_the_relayout_it_guards_against(
    one_chip, no_persistent_cache
):
    """The scan below is only a guard if it reads this compiler's print:
    on the form the module had before PR 26 (projection straight to
    ``[rows, 3, H, D]``, ``[B, S, H, D]`` einsums) it has to find the
    fusions and the physical `reshape` that a v5e trace timed at 3.5 s
    of a 12.6 s job."""
    from mlops_tpu.ops.attention import reference_attention

    rows, seq, dim, heads = 256, 48, 768, 12

    def before(x, wqkv, wout):
        free = (((1,), (0,)), ((), ()))
        qkv = jax.lax.dot_general(x.reshape(rows * seq, dim), wqkv, free)
        qkv = qkv.reshape(rows, seq, 3, heads, dim // heads)
        out = reference_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2])
        return jax.lax.dot_general(
            out.reshape(rows * seq, heads, dim // heads),
            wout,
            (((1, 2), (0, 1)), ((), ())),
        )

    text = _compile(
        before,
        *_on(
            (
                S((rows, seq, dim), jnp.bfloat16),
                S((dim, 3, heads, dim // heads), jnp.bfloat16),
                S((heads, dim // heads, dim), jnp.bfloat16),
            ),
            one_chip,
        ),
    )
    ops = {i[0] for i in _entry_instructions(text)}
    assert {"fusion", "reshape"} <= ops, ops
    moved = _layout_changes(text, at_least=rows * seq)
    assert [m for m in moved if m[0] == "reshape"], moved


# One layout through the attention module (PERF.md section 6, PR 26): at
# the shapes the code runs, the module's activations go from the qkv
# projection through both products to the out projection with no physical
# `reshape`. The parent of PR 26 compiled, at these sizes, to two such
# reshapes a block (the qkv output re-tiled with S on the lanes, and back
# before `out`), 793 kB of temporaries and 7.8 MB touched a row at the
# bert-base widths, 0.74 MB touched a row at ft_transformer's.
@pytest.mark.parametrize(
    "seq,dim,heads,copies,temp_kb_a_row,touched_mb_a_row",
    [
        pytest.param(48, 768, 12, 0, 400, 5.5, id="bert-base-s48"),
        # dim 64 is under the 128 lanes, so the compiler keeps this model's
        # activations ROW-minor outside the module and copies at its edges
        pytest.param(24, 64, 8, 3, 50, 0.65, id="ft-transformer-s24"),
    ],
)
def test_attention_module_keeps_one_layout_on_v5e(
    one_chip,
    no_persistent_cache,
    seq,
    dim,
    heads,
    copies,
    temp_kb_a_row,
    touched_mb_a_row,
):
    from mlops_tpu.models.ft_transformer import TransformerBlock

    rows = 256
    block = TransformerBlock(heads=heads, token_dim=dim, dropout=0.1)
    x = S((rows, seq, dim), jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: block.init(
            jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype), train=False
        )
    )
    compiled = (
        jax.jit(lambda v, x: block.apply(v, x, train=False))
        .lower(*_on((variables, x), one_chip))
        .compile()
    )
    # anything of a token's worth of elements a row is an activation; the
    # 2,304-element bias is reshaped, and that is no relayout to guard
    text = compiled.as_text()
    named = [i for i in _entry_instructions(text) if "MultiHeadSelfAttention" in i[2]]
    assert len(named) >= 2 * heads, len(named)  # the scan reads this print
    moved = _layout_changes(text, at_least=rows * seq)
    assert [m for m in moved if m[0] == "reshape"] == [], moved
    in_module = [m for m in moved if "MultiHeadSelfAttention" in m[2]]
    assert len(in_module) <= copies, in_module
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp / rows <= temp_kb_a_row * 1e3, temp / rows
    touched = compiled.cost_analysis()["bytes accessed"]
    assert touched / rows <= touched_mb_a_row * 1e6, touched / rows


def test_block_under_tensor_parallel_rules_on_v5e(topo, no_persistent_cache):
    """A block on a 4-chip ('model',) mesh, params by `PARAM_RULES`: the
    FFN is Megatron-split (each chip's widening product makes a quarter of
    the features, one all-reduce after the narrowing one), and the
    attention module, which has no heads axis to partition, runs whole on
    every chip with NO collective under its scope. PR 26 measured the
    alternative, its kernels sharded on heads: GSPMD gathers them and
    re-splits every head's slices, 13 all-to-alls a block, and the block
    is slower on four chips than on one (PERF.md section 6)."""
    import numpy as np
    from flax import linen as nn
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mlops_tpu.models.ft_transformer import TransformerBlock
    from mlops_tpu.parallel.sharding import param_shardings

    rows, seq, dim, heads = 256, 48, 768, 12
    mesh = Mesh(np.asarray(topo.devices), ("model",))

    class Encoder(nn.Module):  # the rules key on the models' `block_<i>`
        @nn.compact
        def __call__(self, x):
            return TransformerBlock(
                heads=heads, token_dim=dim, dropout=0.1, name="block_0"
            )(x, train=False)

    block = Encoder()
    x = S((rows, seq, dim), jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: block.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype))
    )
    placed = jax.tree_util.tree_map(
        lambda leaf, sharding: S(leaf.shape, leaf.dtype, sharding=sharding),
        variables,
        param_shardings(mesh, variables),
    )
    replicated = NamedSharding(mesh, P())
    text = (
        jax.jit(
            lambda v, x: block.apply(v, x),
            out_shardings=replicated,
        )
        .lower(placed, S(x.shape, x.dtype, sharding=replicated))
        .compile()
        .as_text()
    )
    collectives = [
        line
        for line in text.splitlines()
        if re.search(
            r" (all-gather|all-reduce|all-to-all|collective-permute|"
            r"reduce-scatter)(-start)?\(",
            line,
        )
    ]
    assert collectives, "nothing is partitioned"
    assert not [c for c in collectives if "MultiHeadSelfAttention" in c]
    # a quarter of the FFN's 4 * dim features a chip
    assert re.search(
        rf"bf16\[{rows * seq},{dim}\]\S* (fusion|convolution)\(.*"
        r"ffn/Dense_0/dot_general",
        text,
    ), "the FFN's widening product is not split four ways"


def test_eva_attention_compiles_for_v5e_at_evabytes_shape(one_chip, no_persistent_cache):
    """`ops/eva_attention.py` at the shape `evabyte-8l.bulk-hist` runs it:
    two 16,384-byte histories, 32 heads of 128, window 2048, chunk 16,
    bfloat16. `eva_attend` is the Mosaic kernel, under its scope; no
    buffer of the program holds a head's scores (one head's joint scores
    were ``f32[2,8,2048,2944]``, 0.4 GB in HBM, and the heads a ``while``);
    what the attention adds to the program's temporaries is the three
    relayouts of q, k, v from ``[B, S, H, D]`` tiles to ``[B, S, H * D]``
    tiles (0.25 GiB each), read off this compile: 1.75 GiB with `rope`'s
    and `eva_prep_kv`'s float32 copies, 3 GiB allowed before the kernel."""
    from mlops_tpu.ops.eva_attention import eva_attend, eva_prep_kv, rope

    def attention(q, k, v, phi, mu):
        q, k = rope(q, 100000.0), rope(k, 100000.0)
        k_sum, v_sum = eva_prep_kv(k, v, phi, mu, 16)
        return eva_attend(q, k, v, k_sum, v_sum, 2048, 16)

    qkv = S((2, 16384, 32, 128), jnp.bfloat16, sharding=one_chip)
    vec = S((32, 128), jnp.float32, sharding=one_chip)
    compiled = jax.jit(attention).lower(qkv, qkv, qkv, vec, vec).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 2 * 2**30, memory.temp_size_in_bytes
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and "eva_attend_fwd" in calls[0], calls
    op_name = re.search(r'op_name="([^"]*)"', calls[0]).group(1)
    assert "eva_attend" in op_name.split("/"), op_name  # the scope a trace reads
    assert "while" not in text  # no loop over heads is left
    for shape in re.findall(r"\b(?:f32|bf16)\[([0-9,]+)\]", text):
        extents = [int(n) for n in shape.split(",")]
        scores = extents.count(2048) >= 2 or (2048 in extents and 2944 in extents)
        assert not scores, f"a buffer of a window's scores: [{shape}]"
    # the longest sequence `wants_eva_kernel` admits (EvaByte's 32,768
    # positions: 16 windows, 2,048 summaries a head in VMEM) compiles too
    long = S((1, 32768, 32, 128), jnp.bfloat16, sharding=one_chip)
    summaries = S((1, 2048, 32, 128), jnp.bfloat16, sharding=one_chip)
    text = _compile(
        lambda *xs: eva_attend(*xs, 2048, 16), long, long, long, summaries, summaries
    )
    assert "eva_attend_fwd" in text


def _assert_the_experts_products_are_the_kernels(text: str, layers: int) -> None:
    """A segment's grouped products are `ops/expert_products.py`'s two
    Mosaic calls a layer (`wants_grouped_kernel` admits both cells'
    shapes), each under the scope ``experts`` that a trace's readers key
    on, and no call of the compiler's own grouped product is left."""
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    for kernel in ("experts_gate_up_fwd", "experts_down_fwd"):
        mine = [line for line in calls if kernel in line.split(" = ")[0]]
        assert len(mine) == layers, (kernel, len(mine))
        for call in mine:
            op_name = re.search(r'op_name="([^"]*)"', call).group(1)
            assert "experts" in op_name.split("/"), op_name
    assert "ragged-dot" not in text


def _assert_the_attention_is_the_kernel(text: str, calls: dict[str, int], seq: int) -> None:
    """The layers that answer every position of a history are
    `ops/gqa_attention.py`'s Mosaic call, ``calls[scope]`` of them under
    each scope a trace's readers key on, and nothing under those scopes is
    a float32 array with an axis of ``seq`` keys (or of a block's share of
    them): the scores stay in VMEM."""
    mine = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "gqa_attend_fwd" in line.split(" = ")[0]
    ]
    found = {scope: 0 for scope in calls}
    for call in mine:
        op_name = re.search(r'op_name="([^"]*)"', call).group(1).split("/")
        (scope,) = [s for s in calls if s in op_name]
        found[scope] += 1
    assert found == calls and len(mine) == sum(calls.values()), (found, len(mine))
    wide = "|".join(str(n) for n in range(seq, seq // 2, -128))
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if name and set(calls) & set(name.group(1).split("/")):
            assert not re.search(rf"= f32\[[\d,]*,({wide})\]", line), line[:300]


def _scoped_instructions(text: str, scope: str, kind: str) -> list[str]:
    """The program's ``kind`` instructions (``scatter``, ``gather``,
    ``sort``) whose ``op_name`` lies under ``scope``, fused or not."""
    found = []
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if name and scope in name.group(1).split("/") and f" {kind}(" in line:
            found.append(line.strip())
    return found


def test_kimi_k2_chunk_program_compiles_for_v5e_at_published_widths(
    one_chip, no_persistent_cache
):
    """The bulk chunk program of `kimi-k2-5l.bulk-hist` as the cell runs it
    (`parallel/bulk.py make_bulk_fused` over `models/kimi_k2.py` at the
    configuration file's widths, one history of 64 records a run,
    bfloat16 parameters): it fits the 75% rule its chunk was sized by
    (`benchmark/compile_check.py`), nothing holds a float32 copy of a held
    expert's stacked weights or of the embedding, the routed experts'
    products are the two grouped kernels (eight calls: gate and up with the
    SwiGLU, then down, in four expert layers), and the counter is the
    program's third output. `mla_attend` is the Mosaic kernel in the four layers that run
    whole sequences (the last layer's ``read`` form stays XLA), under the
    scope the trace's readers key on; no buffer holds a block's scores of
    64 heads or the keys joined over heads, and the program's temporaries
    are no more than they were before the grouped kernels (0.867 GB; 1.611
    with the XLA form of `mla_attend`, PERF.md section 4)."""
    import json
    from pathlib import Path

    from mlops_tpu.config import ModelConfig
    from mlops_tpu.models import abstract_variables, build_model
    from mlops_tpu.monitor.state import abstract_monitor_state
    from mlops_tpu.parallel.bulk import make_bulk_fused
    from mlops_tpu.schema import SCHEMA

    real = json.loads(
        (Path(__file__).resolve().parents[1] / "benchmark/configs/kimi-k2-5l.json").read_text()
    )
    fields = dict(real["model_config"])
    fields["hidden_dims"] = tuple(fields["hidden_dims"])
    model = build_model(ModelConfig(**fields))
    rows = real["deployment"]["score_chunk_rows"]
    compiled = (
        jax.jit(make_bulk_fused(model))
        .lower(
            _on(abstract_variables(model), one_chip),
            _on(abstract_monitor_state(), one_chip),
            S((), jnp.float32, sharding=one_chip),
            S((rows, SCHEMA.num_categorical), jnp.int8, sharding=one_chip),
            S((rows, SCHEMA.num_numeric), jnp.float32, sharding=one_chip),
            S((rows,), jnp.bool_, sharding=one_chip),
        )
        .compile()
    )
    memory = compiled.memory_analysis()
    needed = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    assert 10.9e9 < memory.argument_size_in_bytes < 11.0e9  # 5.46 B parameters, 2 bytes each
    assert needed <= 0.75 * 15.75 * 2**30, needed
    text = compiled.as_text()
    assert not re.search(r"f32\[24,7168,2048\]|f32\[24,2048,7168\]|f32\[20480,7168\]", text)
    _assert_the_experts_products_are_the_kernels(text, layers=4)
    assert re.search(r"s32\[2,4,24\]", text), "the routing counter is not an output"
    kernels = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "mla_attend_fwd" in line.split(" = ")[0]
    ]
    assert len(kernels) == 4, len(kernels)
    for call in kernels:
        op_name = re.search(r'op_name="([^"]*)"', call).group(1)
        assert "mla_attend" in op_name.split("/"), op_name  # the scope a trace reads
    assert not re.search(r"f32\[2,64,(512|256),", text), "a block's scores of 64 heads"
    # keys joined over heads (``[2, 3072, 64, 192]``) are the last layer's
    # alone, whose ``read`` form is XLA's; elsewhere the shape is `q_b`'s
    # output viewed by head
    for line in text.splitlines():
        if re.search(r"= bf16\[2,3072,64,192\]", line) and "op_name=" in line:
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert "/block_4/" in op_name or op_name.endswith("mla_q/reshape"), op_name
    assert memory.temp_size_in_bytes <= 0.867e9, memory.temp_size_in_bytes
    # eight segments under the loop: the combine stays the scatter-add of a
    # segment's rows (`ops/moe_dispatch.py`: a token-side gather would
    # read 49,152 rows to use some 3,072), and no inverse is sorted for it
    scatters = _scoped_instructions(text, "moe_combine", "scatter")
    assert len(scatters) == 4 and all(" = f32[" in line for line in scatters), scatters
    assert len(_scoped_instructions(text, "moe_dispatch", "sort")) == 4  # `plan`'s, one a layer


def test_lfm2_moe_chunk_program_compiles_for_v5e_at_published_widths(
    one_chip, no_persistent_cache
):
    """The bulk chunk program of `lfm2-8b-a1b.bulk-hist` as the cell runs it
    (`parallel/bulk.py make_bulk_fused` over `models/lfm2_moe.py` at the
    configuration file's widths, four histories of 64 records a run,
    bfloat16 parameters, all 32 experts held): it fits the 75% rule its
    chunk was sized by (`benchmark/compile_check.py`), nothing holds a
    float32 copy of the stacked experts, of a convolution's input
    projection or of the embedding, the routed experts' products are the
    two grouped kernels (gate and up with the SwiGLU, then down, in
    fourteen expert layers) and leave no float32 ``[rows, f]`` in HBM, the
    counter is the program's third output, grouped attention holds no
    keys or values repeated to the query heads' count and is ONE
    ``gqa_attend_fwd`` kernel call a layer (`ops/gqa_attention.py` admits
    heads of 64: a pair of key/value heads a lane tile) that leaves no
    float32 scores in HBM, and the combine (one segment holds every
    assignment) is a gather of each token's four rows, slots outermost, and
    a reduction: no scatter under ``moe_combine``."""
    import json
    from pathlib import Path

    from mlops_tpu.config import ModelConfig
    from mlops_tpu.models import abstract_variables, build_model
    from mlops_tpu.monitor.state import abstract_monitor_state
    from mlops_tpu.parallel.bulk import make_bulk_fused
    from mlops_tpu.schema import SCHEMA

    real = json.loads(
        (Path(__file__).resolve().parents[1] / "benchmark/configs/lfm2-8b-a1b.json").read_text()
    )
    fields = dict(real["model_config"])
    fields["hidden_dims"] = tuple(fields["hidden_dims"])
    model = build_model(ModelConfig(**fields))
    rows = real["deployment"]["score_chunk_rows"]
    assert rows == 256
    compiled = (
        jax.jit(make_bulk_fused(model))
        .lower(
            _on(abstract_variables(model), one_chip),
            _on(abstract_monitor_state(), one_chip),
            S((), jnp.float32, sharding=one_chip),
            S((rows, SCHEMA.num_categorical), jnp.int8, sharding=one_chip),
            S((rows, SCHEMA.num_numeric), jnp.float32, sharding=one_chip),
            S((rows,), jnp.bool_, sharding=one_chip),
        )
        .compile()
    )
    memory = compiled.memory_analysis()
    needed = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    assert 10.79e9 < memory.argument_size_in_bytes < 10.81e9  # 5.40 B parameters, 2 bytes each
    assert needed <= 0.75 * 15.75 * 2**30, needed
    text = compiled.as_text()
    assert not re.search(
        r"f32\[32,2048,1792\]|f32\[32,1792,2048\]|f32\[65536,2048\]|f32\[2048,6144\]", text
    )
    _assert_the_experts_products_are_the_kernels(text, layers=14)
    # gate's and up's float32 products of a run stay in VMEM, and the
    # temporaries are no more than the XLA form's were (PERF.md section 4)
    assert "f32[49152,1792]" not in text
    assert memory.temp_size_in_bytes <= 1.145e9, memory.temp_size_in_bytes
    assert re.search(r"s32\[2,14,32\]", text), "the routing counter is not an output"
    _assert_the_attention_is_the_kernel(text, {"gqa_attend": 4}, seq=3072)
    assert not _scoped_instructions(text, "moe_combine", "scatter")
    # `plan`'s sort and the inverse's, a layer
    assert len(_scoped_instructions(text, "moe_dispatch", "sort")) == 28
    gathers = _scoped_instructions(text, "moe_combine", "gather")
    assert len(gathers) == 14, gathers
    assert all(re.search(r" = f32\[4,(12288|256),2048\]", line) for line in gathers), gathers
    # 8 key/value heads stay 8: the only 32-head buffers of a whole run are q's and o's
    for line in text.splitlines():
        if re.search(r"= bf16\[4,3072,32,64\]", line) and "op_name=" in line:
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert "/k/" not in op_name and "/v/" not in op_name and "repeat" not in op_name, op_name


def test_exaone_moe_chunk_program_compiles_for_v5e_at_published_widths(
    one_chip, no_persistent_cache
):
    """The bulk chunk program of `k-exaone-236b-a23b.bulk-hist` as the cell
    runs it (`parallel/bulk.py make_bulk_fused` over `models/exaone_moe.py`
    at the configuration file's widths, eight histories of 64 records a run,
    bfloat16 parameters, 16 of 128 experts held): it fits the 75% rule its
    chunk was sized by (`benchmark/compile_check.py`), nothing holds a
    float32 copy of the stacked experts or of the embedding, the routed
    experts' products of its four sparse layers are the two grouped
    kernels, the counter is the program's third output, and the four
    layers that answer every position (three window layers and the full
    one) are ONE ``gqa_attend_fwd`` kernel call each under their scopes,
    with no float32 scores a history wide left in HBM; the last layer, a
    window layer at the read positions, is the XLA form's 128 keys a
    position."""
    import json
    from pathlib import Path

    from mlops_tpu.config import ModelConfig
    from mlops_tpu.models import abstract_variables, build_model
    from mlops_tpu.monitor.state import abstract_monitor_state
    from mlops_tpu.parallel.bulk import make_bulk_fused
    from mlops_tpu.schema import SCHEMA

    real = json.loads(
        (Path(__file__).resolve().parents[1] / "benchmark/configs/k-exaone-236b-a23b.json")
        .read_text()
    )
    fields = dict(real["model_config"])
    fields["hidden_dims"] = tuple(fields["hidden_dims"])
    model = build_model(ModelConfig(**fields))
    rows = real["deployment"]["score_chunk_rows"]
    assert rows == 512 and model.depth == 5
    compiled = (
        jax.jit(make_bulk_fused(model))
        .lower(
            _on(abstract_variables(model), one_chip),
            _on(abstract_monitor_state(), one_chip),
            S((), jnp.float32, sharding=one_chip),
            S((rows, SCHEMA.num_categorical), jnp.int8, sharding=one_chip),
            S((rows, SCHEMA.num_numeric), jnp.float32, sharding=one_chip),
            S((rows,), jnp.bool_, sharding=one_chip),
        )
        .compile()
    )
    memory = compiled.memory_analysis()
    needed = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    assert 7.18e9 < memory.argument_size_in_bytes < 7.20e9  # 3.59 B parameters, 2 bytes each
    assert needed <= 0.75 * 15.75 * 2**30, needed
    assert memory.temp_size_in_bytes <= 4.6e9, memory.temp_size_in_bytes
    text = compiled.as_text()
    assert not re.search(r"f32\[16,6144,2048\]|f32\[16,2048,6144\]|f32\[19200,6144\]", text)
    _assert_the_experts_products_are_the_kernels(text, layers=4)
    assert re.search(r"s32\[2,4,16\]", text), "the routing counter is not an output"
    _assert_the_attention_is_the_kernel(text, {"swa_attend": 3, "gqa_attend": 1}, seq=3072)
    # the band's tiles of before the kernel: [histories, blocks, groups, 8 x 128 queries, 256 keys]
    assert not re.search(r"f32\[8,24,8,1024,256\]", text)
    # the last layer reads 64 positions a history, each against its window's 128 keys
    assert re.search(r"f32\[8,64,8,8,128\]", text), "the read form's scores"
    # 8 key/value heads stay 8: no key or value repeated to the query heads' count
    for line in text.splitlines():
        if re.search(r"= bf16\[8,3072,64,128\]", line) and "op_name=" in line:
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert "/k/" not in op_name and "/v/" not in op_name and "repeat" not in op_name, op_name


def test_falcon_h1_chunk_program_compiles_for_v5e_at_published_widths(
    one_chip, no_persistent_cache
):
    """The bulk chunk program of `falcon-h1-34b.bulk-hist` as the cell runs
    it (`parallel/bulk.py make_bulk_fused` over `models/falcon_h1.py` at the
    configuration file's widths, eight histories of 64 records a run,
    bfloat16 parameters, six layers and the whole vocabulary): it fits the
    75% rule its chunk was sized by (`benchmark/compile_check.py`), nothing
    holds a float32 copy of a projection or of the embedding, the five
    layers that answer every position run `gqa_attend_fwd` at a group of
    FIVE query heads (a tiling `ops/gqa_attention.py _tiling` had not been
    asked for before this family), the scan forms nothing a history wide
    and hands its states over in ONE float32 array a layer, and the last
    layer answers at the read positions."""
    import json
    from pathlib import Path

    from mlops_tpu.config import ModelConfig
    from mlops_tpu.models import abstract_variables, build_model
    from mlops_tpu.monitor.state import abstract_monitor_state
    from mlops_tpu.parallel.bulk import make_bulk_fused
    from mlops_tpu.schema import SCHEMA

    real = json.loads(
        (Path(__file__).resolve().parents[1] / "benchmark/configs/falcon-h1-34b.json")
        .read_text()
    )
    fields = dict(real["model_config"])
    for name in ("hidden_dims", "ssm_multipliers", "mlp_multipliers"):
        fields[name] = tuple(fields[name])
    model = build_model(ModelConfig(**fields))
    rows = real["deployment"]["score_chunk_rows"]
    assert rows == 512 and model.depth == 6
    compiled = (
        jax.jit(make_bulk_fused(model))
        .lower(
            _on(abstract_variables(model), one_chip),
            _on(abstract_monitor_state(), one_chip),
            S((), jnp.float32, sharding=one_chip),
            S((rows, SCHEMA.num_categorical), jnp.int8, sharding=one_chip),
            S((rows, SCHEMA.num_numeric), jnp.float32, sharding=one_chip),
            S((rows,), jnp.bool_, sharding=one_chip),
        )
        .compile()
    )
    memory = compiled.memory_analysis()
    needed = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    assert 7.83e9 < memory.argument_size_in_bytes < 7.85e9  # 3.92 B parameters, 2 bytes each
    assert needed <= 0.75 * 15.75 * 2**30, needed
    assert memory.temp_size_in_bytes <= 3.7e9, memory.temp_size_in_bytes
    text = compiled.as_text()
    assert not re.search(
        r"f32\[5120,9248\]|f32\[5120,21504\]|f32\[21504,5120\]|f32\[261120,5120\]", text
    )
    kernels = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "gqa_attend_fwd" in line.split(" = ")[0]
    ]
    assert len(kernels) == 5, len(kernels)  # every layer but the last
    for call in kernels:
        assert "gqa_attend" in re.search(r'op_name="([^"]*)"', call).group(1).split("/")
        assert re.search(r"bf16\[8,3072,2560\]", call.split(" = ")[1])  # 20 heads of 128, as projected
    # the last layer: 64 read positions of 5 query heads a key/value head against every key
    assert re.search(r"f32\[8,4,320,3072\]", text), "the read form's scores"
    # the scan: a chunk's decays and scores [histories, chunks, groups, heads a group,
    # 128, 128], the states handed over [chunks, histories, groups, heads a group, 128,
    # 256], and nothing 3,072 x 3,072
    assert re.search(r"f32\[24,8,2,16,128,256\]", text), "the chunk states"
    assert not re.search(r"\[[\d,]*3072,3072\]", text)
    under_scan = [
        line for line in text.splitlines()
        if (name := re.search(r'op_name="([^"]*)"', line)) and "ssm_scan" in name.group(1).split("/")
    ]
    assert under_scan and not any(re.search(r"= f32\[[\d,]*,3072\]", line) for line in under_scan)
    for scope in ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out", "gqa_qkv", "gqa_o", "ffn", "embed"):
        assert re.search(rf'op_name="[^"]*/{scope}[/"]', text), scope


def test_nemotron_h_chunk_program_compiles_for_v5e_at_published_widths(
    one_chip, no_persistent_cache
):
    """The bulk chunk program of `nemotron-labs-twotower-30b-a3b.bulk-hist`
    as the cell runs it (`parallel/bulk.py make_bulk_fused` over
    `models/nemotron_h.py` at the configuration file's widths, eight
    histories of 64 records a run, bfloat16 parameters, layers 0-12 of the
    pattern, 64 of 128 experts held): it fits the 75% rule its chunk was
    sized by; each of the five E layers runs the ReLU² experts as
    `ops/expert_products.py`'s two kernels under ``relu2_experts`` with an
    expert width of 1,856 taken whole (no ``ragged-dot`` left); attention
    layer 5 answers every position by `gqa_attend_fwd` at a group of SIXTEEN
    query heads, and layer 12 (the last) at the read positions; the six
    mixers' scans hand their states over in ONE float32 array a layer and
    form nothing 3,072 wide; and no rotation is applied anywhere."""
    import json
    from pathlib import Path

    from mlops_tpu.config import ModelConfig
    from mlops_tpu.models import abstract_variables, build_model
    from mlops_tpu.monitor.state import abstract_monitor_state
    from mlops_tpu.parallel.bulk import make_bulk_fused
    from mlops_tpu.schema import SCHEMA

    real = json.loads(
        (Path(__file__).resolve().parents[1]
         / "benchmark/configs/nemotron-labs-twotower-30b-a3b.json").read_text()
    )
    fields = dict(real["model_config"])
    for name in ("hidden_dims", "layer_types"):
        fields[name] = tuple(fields[name])
    model = build_model(ModelConfig(**fields))
    rows = real["deployment"]["score_chunk_rows"]
    assert rows == 512 and model.depth == 13
    compiled = (
        jax.jit(make_bulk_fused(model))
        .lower(
            _on(abstract_variables(model), one_chip),
            _on(abstract_monitor_state(), one_chip),
            S((), jnp.float32, sharding=one_chip),
            S((rows, SCHEMA.num_categorical), jnp.int8, sharding=one_chip),
            S((rows, SCHEMA.num_numeric), jnp.float32, sharding=one_chip),
            S((rows,), jnp.bool_, sharding=one_chip),
        )
        .compile()
    )
    memory = compiled.memory_analysis()
    needed = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    assert 7.85e9 < memory.argument_size_in_bytes < 7.86e9  # 3.93 B parameters, 2 bytes each
    assert needed <= 0.75 * 15.75 * 2**30, needed
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    for kernel in ("experts_up_fwd", "experts_down_fwd"):
        mine = [line for line in calls if kernel in line.split(" = ")[0]]
        assert len(mine) == 5, (kernel, len(mine))
        for call in mine:
            op_name = re.search(r'op_name="([^"]*)"', call).group(1).split("/")
            assert "relu2_experts" in op_name and "moe_layer" in op_name, op_name
    assert not [line for line in calls if "experts_gate_up_fwd" in line.split(" = ")[0]]
    assert "ragged-dot" not in text
    # the up kernel's output: a segment's 147,456 rows by the whole 1,856
    assert re.search(r"bf16\[147456,1856\]", text)
    (kernel,) = [line for line in calls if "gqa_attend_fwd" in line.split(" = ")[0]]
    op_name = re.search(r'op_name="([^"]*)"', kernel).group(1).split("/")
    assert {"block_5", "attention_layer", "gqa_attend"} <= set(op_name), op_name
    assert re.search(r"bf16\[8,3072,4096\]", kernel.split(" = ")[1])  # 32 heads of 128
    # the last layer: 64 read positions of 16 query heads a key/value head against every key
    assert re.search(r"f32\[8,2,1024,3072\]", text), "the read form's scores"
    # the scan: states [chunks, histories, groups, heads a group, 64, 128]
    assert re.search(r"f32\[24,8,8,8,64,128\]", text), "the chunk states"
    assert not re.search(r"\[[\d,]*3072,3072\]", text)
    names = [m.group(1).split("/") for m in re.finditer(r'op_name="([^"]*)"', text)]
    assert not [n for n in names if "rope" in n]
    for scope in ("mamba_mixer", "moe_layer", "attention_layer", "ssm_scan", "shared_expert",
                  "router", "moe_combine", "gqa_qkv", "gqa_o", "embed"):
        assert any(scope in n for n in names), scope


def test_mla_attention_compiles_for_v5e_at_the_longest_sequence_its_rule_admits(
    one_chip, no_persistent_cache
):
    """`ops/mla.py mla_attend` at 4,096 positions (`MAX_VISIT_KEYS`): the
    last query block's one visit is 512 x 4,096 float32 scores beside the
    head's keys and values, the most VMEM the rule lets a step ask for.
    One position more than whole blocks takes the XLA form: no kernel."""
    from mlops_tpu.ops.mla import MAX_VISIT_KEYS, mla_attend, softmax_scale

    scale = softmax_scale(192, 64.0)

    def operands(seq):
        return (
            S((1, seq, 2, 128), jnp.bfloat16, sharding=one_chip),
            S((1, seq, 2, 64), jnp.bfloat16, sharding=one_chip),
            S((1, seq, 2 * 256), jnp.bfloat16, sharding=one_chip),
            S((1, seq, 64), jnp.bfloat16, sharding=one_chip),
        )

    attend = lambda *xs: mla_attend(*xs, scale)
    assert "mla_attend_fwd" in _compile(attend, *operands(MAX_VISIT_KEYS))
    assert "tpu_custom_call" not in _compile(attend, *operands(1025))


def test_gqa_attention_compiles_for_v5e_at_the_published_and_the_longest_shapes(
    one_chip, no_persistent_cache
):
    """`ops/gqa_attention.py gqa_attend` as both cells call it
    (`k-exaone-236b-a23b`: 64 heads over 8 of 128, full and window 128;
    `lfm2-8b-a1b`: 32 over 8 of 64) and at the most VMEM and the most code
    its rule lets a kernel ask for: a full layer's last visit of 4,096
    keys, short histories whose steps stack two and four lane tiles of
    heads up to `MAX_STEP_SCORES`, windows whose steps stack eight and
    four, the widest window at all (36 places) and the longest history
    held as one tile's keys and values. A ragged history and the ``read``
    form take the XLA form: no kernel."""
    import numpy as np

    from mlops_tpu.ops.gqa_attention import MAX_KEYS, gqa_attend

    def operands(batch, seq, heads, kv_heads, width, asked=None):
        return tuple(
            S((batch, n, h, width), jnp.bfloat16, sharding=one_chip)
            for n, h in ((asked or seq, heads), (seq, kv_heads), (seq, kv_heads))
        )

    def attend(window=None, read=None):
        return lambda *xs: gqa_attend(*xs, 0.1, read=read, window=window)

    for window, shape in [
        (None, (8, 3072, 64, 8, 128)),
        (128, (8, 3072, 64, 8, 128)),
        (None, (4, 3072, 32, 8, 64)),
        (None, (1, 4096, 8, 1, 128)),
        (None, (1, 2048, 8, 1, 128)),
        (None, (1, 1024, 8, 1, 128)),
        (None, (1, 2048, 8, 2, 64)),
        (1025, (1, 4096, 8, 1, 128)),
        (1921, (1, 4096, 8, 1, 128)),
        (4481, (1, 8192, 8, 1, 128)),
        (128, (1, MAX_KEYS, 8, 1, 128)),
    ]:
        text = _compile(attend(window), *operands(*shape))
        assert "gqa_attend_fwd" in text, (window, shape)
    assert "tpu_custom_call" not in _compile(attend(), *operands(8, 3072 - 48, 64, 8, 128))
    read = np.arange(47, 3072, 48)
    assert "tpu_custom_call" not in _compile(
        attend(128, read), *operands(8, 3072, 64, 8, 128, asked=len(read))
    )

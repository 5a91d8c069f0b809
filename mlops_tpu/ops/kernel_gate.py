"""The one place that decides "compiled Pallas kernel or not".

The decision is made when the computation is LOWERED, from the platform
it is lowered for — the device it is committed to — not from the
process's default backend: a program compiled ahead of time for a TPU
from a CPU host takes the kernel, and a CPU-pinned reference in a process
that also holds a TPU takes the XLA path.

On a TPU the kernel compiles or the compile raises. Nothing here (or at
any call site) sets ``interpret=True`` by itself, and nothing swaps the
XLA path in after a failure. Interpret mode is an explicit argument of
the kernel entry points (`ops/attention.py flash_attention`,
`ops/quant_kernel.py quant_fused`, each ``*_blockwise`` kernel), passed by
CPU tests.

- ``tpu_kernel_or``: the bare choice, for a kernel with its own backward
  (`ops/attention.py flash_attention`) or none (`ops/quant_kernel.py`).
- ``tpu_kernel_forward``: the choice as a forward whose backward is the
  XLA form's, recomputed: the scaffold of every kernel that has no
  backward kernel (`ops/eva_attention.py eva_attend`, `ops/mla.py
  mla_attend`, `ops/moe_dispatch.py segment_products` and
  `segment_relu2`, `ops/gqa_attention.py gqa_attend`). The three attention kernels' joint
  softmax is `ops/lane_softmax.py`.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax


def tpu_kernel_or(kernel: Callable, xla_path: Callable, *args):
    """``kernel(*args)`` where the computation is lowered for a TPU,
    ``xla_path(*args)`` on every other platform. Both must return the
    same shapes and dtypes."""
    return jax.lax.platform_dependent(*args, tpu=kernel, default=xla_path)


def tpu_kernel_forward(
    kernel: Callable, xla_path: Callable, static_argnames: Sequence[str] = ()
) -> Callable:
    """``f(*operands, **static)``: ``kernel(*operands, **static)`` where the
    computation is lowered for a TPU and ``xla_path(*operands, **static)``
    everywhere else (`tpu_kernel_or`), as a ``custom_vjp`` whose backward
    differentiates ``xla_path``, recomputed from the operands (an integer
    operand gets a ``float0`` zero cotangent). ``static_argnames`` are the
    keyword arguments that shape the program (widths, windows, scales);
    they are passed by keyword, the operands by position.

    Call it once, at import: the choice is a ``jax.jit`` named
    ``_kernel_or_xla``, made here and nowhere else, so that a model traces
    and lowers a kernel ONCE for all its layers and XLA inlines the calls,
    each under its own layer's scope (``.../<scope>/jit(_kernel_or_xla)/
    cond/branch_0_fun/<kernel>``, what a device trace's readers key on). A
    kernel's body is some 600 operations of Python tracing: unjitted, the
    eight layers of `evabyte-8l` traced it eight times and added 18 s to a
    process's set-up on a v5e machine's host (PERF.md section 6, the eva kernel)."""

    def _kernel_or_xla(*operands, **static):
        return tpu_kernel_or(
            functools.partial(kernel, **static),
            functools.partial(xla_path, **static),
            *operands,
        )

    _kernel_or_xla = jax.jit(_kernel_or_xla, static_argnames=tuple(static_argnames))

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def gated(static, *operands):
        return _kernel_or_xla(*operands, **dict(static))

    def gated_fwd(static, *operands):
        return _kernel_or_xla(*operands, **dict(static)), operands

    def gated_bwd(static, operands, g):
        """No backward kernel: the XLA form, recomputed, is differentiated."""
        _, pull = jax.vjp(functools.partial(xla_path, **dict(static)), *operands)
        return pull(g)

    gated.defvjp(gated_fwd, gated_bwd)

    def forward(*operands, **static):
        return gated(tuple(static.items()), *operands)

    return forward

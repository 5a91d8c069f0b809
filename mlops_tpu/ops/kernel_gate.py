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
`ops/quant_kernel.py quant_fused`), passed by CPU tests.
"""

from __future__ import annotations

from typing import Callable

import jax


def tpu_kernel_or(kernel: Callable, xla_path: Callable, *args):
    """``kernel(*args)`` where the computation is lowered for a TPU,
    ``xla_path(*args)`` on every other platform. Both must return the
    same shapes and dtypes."""
    return jax.lax.platform_dependent(*args, tpu=kernel, default=xla_path)

"""The device programs' share of the chip's bfloat16 peak WHILE THE DEVICE
IS BUSY: forward matrix-multiply operations per row, from the
configuration's shapes (``benchmark/flops/``), times the rows the traced
jobs completed, over the device's busy seconds in the trace and the peak of
the device kind. Idle time is left out (``device_idle_pct.bulk`` has it),
so a faster chunk program moves this and a cheaper job start does not. The
in-call warm-up chunk and the drift sample are device time whose rows do
not count, so they lower it. Times the busy share of the window, it is the
whole job's share of the peak, which bounds any later kernel's claim."""

from benchmark.flops import forward_flops_per_row


def read(facts):
    trace, peaks = facts["trace"], facts["peaks"]
    if trace is None or peaks is None:
        return None
    flops = forward_flops_per_row(facts["config"]) * facts["window"]["units"]
    chips = facts["cell"]["chips"]
    return 100.0 * flops / trace["busy_s"] / (chips * peaks["bf16_flops_per_s"])

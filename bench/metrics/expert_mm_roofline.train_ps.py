"""The held experts' grouped matrix products' share of the chip's bf16
peak, in percent: the FLOPs of the window's held slots (the program's
counter ``moe.held_slots`` over the window, × 6·M·F per pass, × the passes
the trace runs: forward, remat's recompute and the backward's two) over
those products' device seconds in the trace times the peak.

The products are ``lax.ragged_dot``, which the TPU compiler lowers to its
own kernels named ``ragged-dot-<variant>`` (a ``tpu_custom_call``, beside
a ``ragged-dot-metadata`` call that only lays out the groups and is not
counted); they are found by that name.  Absent where the program keeps no
such counter or the trace holds no such kernel."""

import re

KERNEL = re.compile(r"ragged-dot(?!-metadata)")


def read(ctx):
    red = ctx.get("reduction")
    if red is None or not ctx.get("expert_flops"):
        return None
    seconds = sum(s for name, s in red.op_seconds.items()
                  if KERNEL.search(name))
    if seconds <= 0:
        return None
    return 100.0 * ctx["expert_flops"] / (seconds
                                          * ctx["peaks"]["bf16_flops"])

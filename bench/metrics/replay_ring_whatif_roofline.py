"""The Pallas what-if ring kernel's share of its roofline, in percent:
the bytes its events must move (``counts.whatif_event_bytes``) over the
kernel's device time in the trace times the chip's HBM bandwidth.  The
kernel does about one FLOP per byte, so bandwidth bounds it.  The
kernel's ``pallas_call`` carries no name today: it shows in the trace as
an anonymous ``closed_call`` custom call to ``tpu_custom_call``, the only
Pallas kernel these cells run.  Absent, the metric is left out."""

KERNEL = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    red = ctx.get("reduction")
    if red is None or "event_bytes" not in ctx:
        return None
    kernel_s = red.seconds_matching(KERNEL)
    if kernel_s <= 0:
        return None
    moved = ctx["event_bytes"] * ctx["events"]
    return 100.0 * moved / (kernel_s * ctx["peaks"]["hbm_bytes_per_s"])

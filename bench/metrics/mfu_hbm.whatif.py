"""The whole what-if event step's share of the chip's HBM bandwidth, in
percent: the bytes every event must move (``counts.whatif_event_bytes``)
times the window's events, over the window and the chips' peak.  It
bounds any kernel's roofline share, whichever kernel runs the event."""


def read(ctx):
    if "event_bytes" not in ctx:
        return None
    rate = ctx["event_bytes"] * ctx["events"] / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["hbm_bytes_per_s"])

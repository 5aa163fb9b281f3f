"""Share of the traced what-if window in which no operation ran on the
device, in percent (``tracereduce``: 1 − busy union / window)."""


def read(ctx):
    red = ctx.get("reduction")
    return None if red is None else 100.0 * red.idle_share

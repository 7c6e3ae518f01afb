"""Share of the traced window in which no operation ran on the device,
in percent (``trace_reduce``: 1 - union of op intervals / window)."""


def read(ctx):
    if ctx.trace is None or ctx.trace["idle_share"] is None:
        return None
    return 100.0 * ctx.trace["idle_share"]

"""90th percentile of the whole step's wall time (plan + slot batch +
device step + loss fetch) over every step of the window."""
import numpy as np


def read(ctx):
    steps = ctx.window.get("steps")
    if not steps:
        return None
    return float(np.percentile([s["wall_s"] for s in steps], 90))

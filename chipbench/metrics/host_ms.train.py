"""90th percentile over the window's steps of the host's part of a step,
in ms: the step's wall time less the device step (dispatch to
``block_until_ready``) that ``CodedStep.seconds`` times."""
import numpy as np


def read(ctx):
    steps = ctx.window.get("steps")
    if not steps:
        return None
    return 1e3 * float(np.percentile(
        [s["wall_s"] - s["device_s"] for s in steps], 90))

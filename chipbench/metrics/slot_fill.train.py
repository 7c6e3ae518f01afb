"""Decoded partition tokens over the slot tokens the step computed, in
percent, summed over the window's steps: the share of the coded step's
work that the decode keeps (the rest is redundant coded copies and
zero-weight padding slots).  Both are counted from what the program hands
its step each step: the slot batch's shape and the plan's partitions."""


def read(ctx):
    steps = ctx.window.get("steps")
    if not steps:
        return None
    computed = sum(s["slot_tokens"] for s in steps)
    return 100.0 * sum(s["decoded_tokens"] for s in steps) / computed

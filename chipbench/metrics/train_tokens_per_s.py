"""Partition tokens whose gradient was decoded, over the whole window:
the tokens of the partitions each step's plan holds, for each step with
``decode_ok`` (0 for a no-op step), divided by the host-clock length of
the window."""


def read(ctx):
    w = ctx.window
    steps = w.get("steps")
    if not steps:
        return None
    return sum(s["decoded_tokens"] for s in steps) / (w["t1"] - w["t0"])

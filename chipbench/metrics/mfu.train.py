"""Model FLOP/s utilization of the whole step, in percent: decoded tokens
per second over the window times the forward and backward FLOPs one
token needs (``flops.dense_lm_train_flops_per_token``), over the chip's
published bf16 peak.  Slot redundancy and recomputation do not count."""
from chipbench.flops import peaks


def read(ctx):
    w = ctx.window
    steps = w.get("steps")
    if not steps:
        return None
    rate = sum(s["decoded_tokens"] for s in steps) / (w["t1"] - w["t0"])
    peak = peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * rate * w["flops_per_token"] / peak

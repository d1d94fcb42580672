"""step.mfu: the model FLOPs of one call (forward and backward of every
sample, no recompute) over the call's wall time in the traced window times
the chip's peak bf16 FLOP/s, in %."""
from harness.peaks import lookup


def read(r):
    if not r.n_calls or r.window_s <= 0 or r.busy_s <= 0:
        return None
    call_s = r.window_s / r.n_calls
    peak = lookup(r.device_kind)["bf16_flops_per_s"]
    return 100.0 * r.counts["model_flops_per_call"] / (call_s * peak)

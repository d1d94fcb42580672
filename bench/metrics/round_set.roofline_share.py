"""round_set.roofline_share: the least time the chip could take for the work
an exact round-set needs, over ``round_set.device_ms``, in %.

The work: one read and one write of every agent's parameters,
``2 x K x D x 4`` bytes (the exact Gram recurrence runs any number of rounds
in two passes over D), and the combine's ``2 x K^2 x D`` FLOPs.  D is the
unpadded parameter count of one agent.  The least time is the larger of
bytes over the peak HBM bandwidth and FLOPs over the peak FLOP/s."""
from harness.peaks import lookup


def bound(counts, peaks):
    """``(least seconds, what bounds it)`` of one exact round-set."""
    K, D = counts["K"], counts["D"]
    t_bytes = 2 * K * D * 4 / peaks["hbm_bytes_per_s"]
    t_flops = 2 * K * K * D / peaks["bf16_flops_per_s"]
    return max(t_bytes, t_flops), ("hbm" if t_bytes >= t_flops else "flops")


def read(r):
    ms = r.per_call_ms("round_set")
    if ms is None:
        return None
    least, _ = bound(r.counts, lookup(r.device_kind))
    return 100.0 * least / (ms * 1e-3)

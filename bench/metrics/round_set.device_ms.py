"""round_set.device_ms: device time per call of the ops under the
``round_set`` scope (packing, DRT statistics and weights, the combine, the
Pallas kernels)."""


def read(r):
    return r.per_call_ms("round_set")

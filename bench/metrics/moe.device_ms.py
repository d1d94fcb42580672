"""moe.device_ms: device time per call of the held experts' grouped matrix
products under the ``local_step`` scope: the ops whose instruction is a
``moe_gmm`` call (forward and input gradient) or a ``moe_tgmm`` call
(weight gradient), ``repro.kernels.moe_gmm``."""

KERNELS = ("moe_gmm", "moe_tgmm")


def seconds(r) -> float:
    """Device seconds of the kernels' ops in the traced window."""
    total = 0.0
    for key, s in r.op_s.items():
        scope, _, instr = key.split(":", 1)[-1].partition("/")
        if scope == "local_step" and instr.startswith(KERNELS):
            total += s
    return total


def read(r):
    s = seconds(r)
    return 1e3 * s / r.n_calls if s > 0 and r.n_calls else None

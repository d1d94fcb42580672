"""moe.roofline_share: the least time the chip could take for the held
experts' grouped matrix products of one call, over ``moe.device_ms``, in %.

The work, at balanced routing: ``pairs = K x T x top_k x held / router
width`` token slots per MoE layer per call (6,144 in the kanana cell), each
through the SiLU-gated FFN's three ``d x f`` products forward, their input
gradients and their weight gradients, with no recompute: ``18 x d x f``
FLOPs a pair.  Bytes: every agent's held weights in bf16 read in three
passes (forward, input gradient, weight gradient), plus each pair's rows in
bf16 in those passes (in and out of the gate-up product, in and out of the
down product: ``2d + 3f`` values).  The least time is the larger of bytes
over the peak HBM bandwidth and FLOPs over the peak bf16 FLOP/s.  The
counts come from the one cell's configuration (``Reading.counts`` carries
only K, D and the call's FLOPs)."""
from harness.peaks import lookup
from harness.spec import find_cell, load_module, BENCH

CELL = "kanana2-30b-a3b-5l-k2.every-step"
PASSES = 3


def work(cfg, traffic) -> tuple[float, float]:
    """``(FLOPs, bytes)`` of the held experts' products in one call."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    K, T = cfg["num_agents"], cfg["batch_size"] * cfg["seq_len"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    steps = traffic["local_steps"]
    pairs = K * T * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["router_experts"]
    flops = 18 * d * f * pairs * layers * steps
    weights = K * cfg["n_routed_experts"] * 3 * d * f * 2
    rows = pairs * (2 * d + 3 * f) * 2
    return flops, PASSES * (weights + rows) * layers * steps


def read(r):
    ms = load_module(BENCH / "metrics" / "moe.device_ms.py").read(r)
    if ms is None:
        return None
    cell = find_cell(CELL)
    flops, nbytes = work(cell.config, cell.traffic)
    peaks = lookup(r.device_kind)
    least = max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)

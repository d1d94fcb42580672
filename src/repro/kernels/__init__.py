"""Pallas TPU kernels for the performance hot spots (validated in interpret
mode on CPU; see EXPERIMENTS.md §Perf for the HBM-traffic math per kernel).

  drt_dist        fused DRT distance statistics (eq. 14 inner loop)
  weighted_combine fused neighbour combine (the combination step 3b/11)
  int8_quantize   fused scale + stochastic round for the int8 wire codec
  int8_dequantize q * s -> f32
  dequant_combine fused dequantize + weighted combine over int8 neighbours
  slab_combine    per-layer mixing of a group's region: ONE launch per group
  slab_dequant_combine  whole-slab fused int8 dequant+combine, one launch
  slab_source_combine   whole-slab {self}+neighbour combine (permute engine)
  slab_encode_combine   a WHOLE coded round (encode + Gram + DRT mixing +
                        combine + self term) in ONE launch per round
  slab_edge_combine     a sparse consensus round over a padded edge list
                        (per-edge stats + eq. 12-14 edge factors +
                        gather/scatter combine), one O(|E| D) launch
  slab_edge_encode_combine  the wire-resident sparse round: in-kernel wire
                        decode in both phases + sort-free CSR segment
                        combine — the decoded (K, D) slab never hits HBM
  slab_quant_encode     fused int8 encode: in-kernel counter RNG + scale
                        reconstruction + stochastic round, one launch
  slab_cast_combine     bf16/f16 cast-combine round, wire never in HBM
  selective_scan  chunked Mamba-1 recurrence, VMEM-carried state
  flash_attention online-softmax attention, VMEM score tiles
  moe_gmm         grouped matmuls over the experts a chip holds, with a
                  custom VJP (``repro.kernels.moe_gmm``; used by
                  ``models.moe``)
"""
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import (
    default_interpret,
    dequant_combine,
    drt_dist,
    int8_dequantize,
    int8_quantize,
    selective_scan,
    slab_cast_combine,
    slab_combine,
    slab_dequant_combine,
    slab_edge_combine,
    slab_edge_encode_combine,
    slab_encode_combine,
    slab_quant_encode,
    slab_source_combine,
    weighted_combine,
)

__all__ = [
    "ops",
    "ref",
    "default_interpret",
    "drt_dist",
    "weighted_combine",
    "int8_quantize",
    "int8_dequantize",
    "dequant_combine",
    "slab_combine",
    "slab_dequant_combine",
    "slab_source_combine",
    "slab_edge_combine",
    "slab_edge_encode_combine",
    "slab_encode_combine",
    "slab_quant_encode",
    "slab_cast_combine",
    "selective_scan",
    "flash_attention",
]

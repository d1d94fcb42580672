"""Public jit'd wrappers for the Pallas kernels.

Off a TPU the kernels run in interpret mode (the body executes in
Python/XLA-CPU and is validated against the ref.py oracles); on a TPU they
compile under Mosaic (``repro.kernels.runtime.default_interpret``).
"""
from __future__ import annotations

from repro.kernels import ref
from repro.kernels.combine import weighted_combine as _combine
from repro.kernels.drt_dist import drt_dist as _drt_dist
from repro.kernels.quantize import dequant_combine as _dequant_combine
from repro.kernels.quantize import int8_dequantize as _int8_dequantize
from repro.kernels.quantize import int8_quantize as _int8_quantize
from repro.kernels.selective_scan import selective_scan as _selective_scan
from repro.kernels.slab_codec import slab_cast_combine as _slab_cast_combine
from repro.kernels.slab_codec import slab_encode_combine as _slab_encode_combine
from repro.kernels.slab_codec import slab_quant_encode as _slab_quant_encode
from repro.kernels.slab_combine import slab_combine as _slab_combine
from repro.kernels.slab_segment import slab_edge_combine as _slab_edge_combine
from repro.kernels.slab_segment import (
    slab_edge_encode_combine as _slab_edge_encode_combine,
)
from repro.kernels.slab_combine import slab_dequant_combine as _slab_dequant_combine
from repro.kernels.slab_combine import slab_source_combine as _slab_source_combine

from repro.kernels.runtime import default_interpret  # noqa: E402  (re-export)


def drt_dist(x, y, *, interpret: bool | None = None):
    """Fused [sum((x-y)^2), sum(y^2)] -> (2,) f32."""
    return _drt_dist(x, y, interpret=interpret)


def weighted_combine(a, xs, *, interpret: bool | None = None):
    """out = sum_n a[n] * xs[n] over the leading neighbour axis."""
    return _combine(a, xs, interpret=interpret)


def int8_quantize(x, key, *, interpret: bool | None = None):
    """Fused stochastic-rounding int8 quantization -> (q int8, scale f32)."""
    return _int8_quantize(
        x, key, interpret=interpret
    )


def int8_dequantize(q, scale, *, interpret: bool | None = None):
    """f32 reconstruction q * scale."""
    return _int8_dequantize(
        q, scale, interpret=interpret
    )


def dequant_combine(a, scales, qs, *, interpret: bool | None = None):
    """Fused out = sum_n a[n] * scales[n] * qs[n] over int8 neighbour blocks."""
    return _dequant_combine(
        a, scales, qs, interpret=interpret
    )


def slab_combine(A, region, *, interpret: bool | None = None):
    """Per-layer agent mixing of one group's region in ONE grid launch."""
    return _slab_combine(
        A, region, interpret=interpret
    )


def slab_dequant_combine(A_blocks, scales, col_seg, q_slab, *, interpret: bool | None = None):
    """Fused whole-slab int8 dequantize + combine in ONE grid launch."""
    return _slab_dequant_combine(
        A_blocks, scales, col_seg, q_slab,
        interpret=interpret,
    )


def slab_source_combine(w_blocks, srcs, *, interpret: bool | None = None):
    """Per-layer weighted combine over N source slabs, ONE launch."""
    return _slab_source_combine(
        w_blocks, srcs, interpret=interpret
    )


def slab_encode_combine(block_layer, slab, wire_operands, mix, *, interpret: bool | None = None, **kw):
    """ONE coded consensus round (encode + stats + mixing + combine + self)
    on the packed (K, D) slab in ONE grid launch."""
    return _slab_encode_combine(
        block_layer, slab, wire_operands, mix,
        interpret=interpret, **kw,
    )


def slab_edge_combine(block_layer, self_slab, dec_slab, src, dst, w, *, interpret: bool | None = None, **kw):
    """ONE sparse (edge-list) consensus round — gather-by-edge stats +
    eq. 12-14 edge factors + scatter-combine — in ONE grid launch."""
    return _slab_edge_combine(
        block_layer, self_slab, dec_slab, src, dst, w,
        interpret=interpret, **kw,
    )


def slab_edge_encode_combine(
    block_layer, self_slab, wire_operands, src, dst, w, nbr, pos, valid,
    dst_base=0, *, interpret: bool | None = None, **kw,
):
    """ONE wire-resident sparse round — in-kernel wire decode + per-edge
    stats + eq. 12-14 edge factors + sort-free CSR segment combine — in ONE
    grid launch; the decoded slab never exists in HBM."""
    return _slab_edge_encode_combine(
        block_layer, self_slab, wire_operands, src, dst, w, nbr, pos, valid,
        dst_base, interpret=interpret, **kw,
    )


def slab_quant_encode(scales, col_seg, col_leaf, col_idx, w0, w1, slab, *, interpret: bool | None = None):
    """Fused int8 encode (in-kernel counter RNG + scale reconstruction +
    stochastic round) of a packed (K, D) slab, ONE launch."""
    return _slab_quant_encode(
        scales, col_seg, col_leaf, col_idx, w0, w1, slab,
        interpret=interpret,
    )


def slab_cast_combine(block_layer, slab, mix, *, dtype="bf16", interpret: bool | None = None, **kw):
    """bf16/f16 cast-combine coded round in ONE launch (wire never in HBM)."""
    return _slab_cast_combine(
        block_layer, slab, mix, dtype=dtype,
        interpret=interpret, **kw,
    )


def selective_scan(dt, A, Bm, Cm, x, *, interpret: bool | None = None, chunk: int = 64):
    """Chunked Mamba-1 selective scan -> y (B, S, di) f32."""
    return _selective_scan(
        dt, A, Bm, Cm, x,
        interpret=interpret,
        chunk=chunk,
    )


__all__ = [
    "default_interpret",
    "drt_dist",
    "weighted_combine",
    "selective_scan",
    "int8_quantize",
    "int8_dequantize",
    "dequant_combine",
    "ref",
]

"""Counter-based uniforms for stochastic rounding.

``jax.random.uniform`` runs the full threefry2x32 block cipher per draw.
That is the right tool for statistical work, but the int8 wire codec draws
one uniform per parameter per agent per consensus round (K x D ~ 4M draws a
round on the benchmark model) purely to break rounding ties, and on CPU the
threefry pass alone costs more than the whole exact consensus round-set
(~30 ms vs ~10 ms measured at K=16).  Stochastic rounding needs decorrelated,
unbiased tie-breaks — not a CSPRNG.

``counter_uniform`` is the cheap drop-in: a murmur3-style integer hash
(``fmix32`` double avalanche) of ``(key word 0, key word 1, element index)``.
It is

* **stateless / counter-based** — u[i] depends only on the key and the
  element's linear index, so the slab fast path, the per-leaf tree codec and
  the Pallas kernels can all compute the SAME bits from static index maps
  (wire bit-parity across every path), in any order, with no carried state;
* **~20x cheaper than threefry on CPU** (two 5-op avalanche rounds per draw,
  all vectorizable int32 ALU work, no odd/even lane recombination);
* **computable inside a Pallas kernel** — plain uint32 arithmetic on an iota,
  which is exactly what the fused encode kernels do ("in-kernel RNG").

The derivation contract every caller shares: a leaf's uniforms are
``uniform_from_words(w0, w1, idx)`` where ``(w0, w1)`` are the LAST TWO words
of ``jax.random.key_data`` of the per-leaf key (threefry keys have exactly
two) and ``idx`` is the element's row-major linear index within the leaf.
Key splitting/folding stays ordinary jax.random — only the per-element draw
is replaced.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_PHI = np.uint32(0x9E3779B9)  # 2^32 / golden ratio: index stride constant
_C1 = np.uint32(0x85EBCA6B)  # murmur3 fmix32 multipliers
_C2 = np.uint32(0xC2B2AE35)
_INV24 = np.float32(2.0**-24)


def fmix32(x):
    """murmur3 32-bit finalizer: full avalanche (every input bit flips each
    output bit with p~=0.5).  ``x`` is a uint32 array; ops wrap mod 2^32."""
    x = x ^ (x >> np.uint32(16))
    x = x * _C1
    x = x ^ (x >> np.uint32(13))
    x = x * _C2
    x = x ^ (x >> np.uint32(16))
    return x


def counter_bits(w0, w1, idx):
    """uint32 hash of (key words, element counter); broadcasts like jnp ops.

    Two chained avalanches with the second key word injected between them —
    adjacent counters and adjacent fold_in keys land in unrelated places.
    """
    x = idx.astype(jnp.uint32) * _PHI + w0.astype(jnp.uint32)
    x = fmix32(x) ^ w1.astype(jnp.uint32)
    return fmix32(x)


def bits_to_uniform(bits):
    """uint32 -> f32 U[0, 1): top 24 bits scaled by 2^-24 (every value is an
    exact f32; 1.0 is never produced, so ``floor(x/s + u)`` never rounds a
    representable value past its ceiling).  The 24-bit value goes through
    an int32 bitcast (exact: it is below 2^31) because Mosaic has no
    uint32 -> f32 conversion."""
    top = jax.lax.bitcast_convert_type(bits >> np.uint32(8), jnp.int32)
    return top.astype(jnp.float32) * _INV24


def key_words(key):
    """Last two uint32 words of a typed (or raw uint32) PRNG key.

    Threefry keys have exactly two words; wider impls (rbg) contribute their
    last two — the split/fold_in derivation upstream already mixed the rest.
    """
    data = key if jnp.issubdtype(jnp.asarray(key).dtype, jnp.integer) else jax.random.key_data(key)
    data = jnp.asarray(data, jnp.uint32)
    return data[..., -2], data[..., -1]


def uniform_from_words(w0, w1, idx):
    """The shared per-element rule: f32 U[0,1) from key words + linear index."""
    return bits_to_uniform(counter_bits(w0, w1, idx))


def counter_uniform(key, shape):
    """U[0, 1) f32 draws of ``shape`` from a jax PRNG key — the cheap
    stochastic-rounding replacement for ``jax.random.uniform(key, shape)``.

    Element ``i`` (row-major) gets ``uniform_from_words(w0, w1, i)``; any
    other path (slab regions, Pallas blocks) reproduces the same bits from
    the same linear indices.
    """
    w0, w1 = key_words(key)
    n = math.prod(shape) if shape else 1
    idx = jnp.arange(n, dtype=jnp.uint32)
    return uniform_from_words(w0, w1, idx).reshape(shape)

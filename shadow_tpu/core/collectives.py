"""Cross-shard min / max that also take 64-bit integers.

The TPU compiler lowers only a Sum all-reduce for 64-bit types: a
`lax.pmin` of the int64 window barrier time is refused at compile
time ("Supported lowering only of Sum all reduce"). These reduce a
64-bit operand as two 32-bit words — the high words first, then the
low words among the shards that tie on the high word — which is exact,
and costs one more small collective. Narrower operands take the plain
collective.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_I32_MIN = jnp.iinfo(jnp.int32).min
_I32_MAX = jnp.iinfo(jnp.int32).max


def _words(x):
    """Order-preserving (hi, lo) int32 words of a 64-bit integer."""
    key = lax.bitcast_convert_type(x, jnp.uint64)
    if jnp.issubdtype(x.dtype, jnp.signedinteger):
        key = key ^ jnp.uint64(1 << 63)
    flip = jnp.uint32(1 << 31)
    hi = lax.bitcast_convert_type((key >> 32).astype(jnp.uint32) ^ flip,
                                  jnp.int32)
    lo = lax.bitcast_convert_type(key.astype(jnp.uint32) ^ flip, jnp.int32)
    return hi, lo


def _join(hi, lo, dtype):
    flip = jnp.uint32(1 << 31)
    key = ((lax.bitcast_convert_type(hi, jnp.uint32) ^ flip)
           .astype(jnp.uint64) << 32) | (
        lax.bitcast_convert_type(lo, jnp.uint32) ^ flip).astype(jnp.uint64)
    if jnp.issubdtype(dtype, jnp.signedinteger):
        key = key ^ jnp.uint64(1 << 63)
    return lax.bitcast_convert_type(key, dtype)


def _reduce(x, axis, op, lo_identity):
    x = jnp.asarray(x)
    if x.dtype.itemsize < 8:
        return op(x, axis)
    if not jnp.issubdtype(x.dtype, jnp.integer):
        raise TypeError(f"no 64-bit {op.__name__} for {x.dtype}")
    hi, lo = _words(x)
    hi_r = op(hi, axis)
    lo_r = op(jnp.where(hi == hi_r, lo, lo_identity), axis)
    return _join(hi_r, lo_r, x.dtype)


def pmin(x, axis):
    """lax.pmin over `axis`, 64-bit integers included."""
    return _reduce(x, axis, lax.pmin, _I32_MAX)


def pmax(x, axis):
    """lax.pmax over `axis`, 64-bit integers included."""
    return _reduce(x, axis, lax.pmax, _I32_MIN)

"""Seeded 64-bit keys, made on the device or on the host from ``--seed``.

Key ``(stream, index)`` of seed ``s`` is ``fmix64(((stream << 32) | index)
+ s * PHI64 mod 2**64)``: fmix64 (murmur3's 64-bit finalizer) is a
bijection, so distinct ``(stream, index)`` pairs give distinct keys, and
streams never share a key. The device path works on ``(hi, lo)`` uint32
pairs (a TPU has no 64-bit integer datapath) and returns the filter's
``uint32[n, 2]`` ``(lo, hi)`` layout; the host path is plain numpy uint64.
Both are checked equal by the tests.

The seed enters the device program as data, so one compiled program serves
every seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PHI64 = 0x9E3779B97F4A7C15
FMIX1 = 0xFF51AFD7ED558CCD
FMIX2 = 0xC4CEB9FE1A85EC53
_M64 = (1 << 64) - 1
_U32 = np.uint32
_MASK16 = _U32(0xFFFF)


def seed_offset(seed: int) -> np.ndarray:
    """``seed * PHI64 mod 2**64`` as uint32 ``[hi, lo]`` (any int seed)."""
    off = (int(seed) * PHI64) & _M64
    return np.array([off >> 32, off & 0xFFFFFFFF], np.uint32)


# -- host ---------------------------------------------------------------------

def keys_np(stream, index, seed: int) -> np.ndarray:
    """uint64 keys of ``(stream, index)`` pairs (broadcast), on the host."""
    x = ((np.asarray(stream, np.uint64) << np.uint64(32))
         | np.asarray(index, np.uint64))
    with np.errstate(over="ignore"):
        x = x + np.uint64((int(seed) * PHI64) & _M64)
        x ^= x >> np.uint64(33)
        x *= np.uint64(FMIX1)
        x ^= x >> np.uint64(33)
        x *= np.uint64(FMIX2)
        x ^= x >> np.uint64(33)
    return x


def to_pairs(keys_u64: np.ndarray) -> np.ndarray:
    """uint64[n] -> the filter's uint32[n, 2] ``(lo, hi)`` layout."""
    k = np.asarray(keys_u64, np.uint64)
    return np.stack([(k & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                     (k >> np.uint64(32)).astype(np.uint32)], axis=-1)


def from_pairs(pairs) -> np.ndarray:
    """uint32[n, 2] ``(lo, hi)`` -> uint64[n]."""
    p = np.asarray(pairs, np.uint32)
    return (p[..., 0].astype(np.uint64)
            | (p[..., 1].astype(np.uint64) << np.uint64(32)))


# -- device: 64-bit arithmetic on (hi, lo) uint32 pairs ------------------------

def _add(a, b):
    lo = a[1] + b[1]
    return a[0] + b[0] + (lo < a[1]).astype(jnp.uint32), lo


def _shr33_xor(a):
    """``x ^ (x >> 33)``: the shift moves hi >> 1 into lo and zeros hi."""
    hi, lo = a
    return hi, lo ^ (hi >> 1)


def _mul(a, c: int):
    """Low 64 bits of ``a * c`` for a constant ``c``, with 16-bit limbs."""
    c_hi, c_lo = _U32(c >> 32), _U32(c & 0xFFFFFFFF)
    x = a[1]
    x0, x1 = x & _MASK16, x >> 16
    y0, y1 = c_lo & _MASK16, c_lo >> 16
    p00, p01, p10, p11 = x0 * y0, x0 * y1, x1 * y0, x1 * y1
    mid = p01 + p10
    mid_carry = (mid < p01).astype(jnp.uint32)
    lo = p00 + (mid << 16)
    lo_carry = (lo < p00).astype(jnp.uint32)
    hi = p11 + (mid >> 16) + (mid_carry << 16) + lo_carry
    return hi + a[0] * c_lo + x * c_hi, lo


def device_keys(stream, index, offset) -> jnp.ndarray:
    """uint32[n, 2] ``(lo, hi)`` keys; ``offset`` is :func:`seed_offset`."""
    offset = jnp.asarray(offset, jnp.uint32)
    x = _add((jnp.asarray(stream, jnp.uint32), jnp.asarray(index, jnp.uint32)),
             (offset[0], offset[1]))
    x = _mul(_shr33_xor(x), FMIX1)
    x = _mul(_shr33_xor(x), FMIX2)
    hi, lo = _shr33_xor(x)
    return jnp.stack([lo, hi], axis=-1)


@functools.partial(jax.jit, static_argnums=(0,))
def bench_key_block(width: int, stream, start, offset) -> jnp.ndarray:
    """Keys of indices ``start + [0, width)`` of one stream, on the device."""
    idx = jnp.asarray(start, jnp.uint32) + jnp.arange(width, dtype=jnp.uint32)
    return device_keys(jnp.full((width,), stream, jnp.uint32), idx, offset)

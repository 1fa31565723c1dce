"""The plain reference: a cuckoo filter's answers worked out from its key set.

It imports nothing of the program and reads nothing the program made. It
follows the published design (Cuckoo-GPU, arXiv:2603.15486, Alg. 1-3, with
xxHash64 as the paper configures it):

* ``h = xxHash64(key)`` (8-byte little-endian input, seed 0);
* the fingerprint is the low ``fp_bits`` of the upper 32 bits of ``h``,
  with 0 mapped to 1 (0 marks an empty slot);
* the primary bucket is the lower 32 bits of ``h`` modulo the (power-of-two)
  bucket count, and the alternate bucket is the primary XOR
  ``fmix32(fingerprint)`` (partial-key cuckoo hashing).

A fingerprint stored in bucket ``b`` can only belong to the pair
``{b, b ^ fmix32(fp)}``, so two keys collide in the filter exactly when they
share the *signature* ``(min(i1, i2), fp)``. Whatever slot the program put an
item in, a query hits iff the live multiset holds its signature. The filter's
answers are therefore a function of the live key multiset alone, and this
module computes them from keys, with no table.

All arithmetic is numpy on the host, in chunks spread over a few threads.
"""

from __future__ import annotations

import concurrent.futures as cf
from typing import Callable

import numpy as np

PRIME64_1 = 0x9E3779B185EBCA87
PRIME64_2 = 0xC2B2AE3D4F118CB1
PRIME64_3 = 0x165667B19E3779F9
PRIME64_4 = 0x85EBCA77C2B2AE63
PRIME64_5 = 0x27D4EB2F165667C5
_U64 = np.uint64
CHUNK = 1 << 22
THREADS = 8


def _rotl(x: np.ndarray, r: int) -> np.ndarray:
    return (x << _U64(r)) | (x >> _U64(64 - r))


def xxhash64(keys: np.ndarray, seed: int = 0) -> np.ndarray:
    """xxHash64 of each uint64 key taken as its 8 little-endian bytes."""
    k = np.asarray(keys, _U64)
    with np.errstate(over="ignore"):
        h = np.full(k.shape, (seed + PRIME64_5 + 8) & ((1 << 64) - 1), _U64)
        k1 = _rotl(k * _U64(PRIME64_2), 31) * _U64(PRIME64_1)
        h ^= k1
        h = _rotl(h, 27) * _U64(PRIME64_1) + _U64(PRIME64_4)
        h ^= h >> _U64(33)
        h *= _U64(PRIME64_2)
        h ^= h >> _U64(29)
        h *= _U64(PRIME64_3)
        h ^= h >> _U64(32)
    return h


def fmix32(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finalizer."""
    x = np.asarray(x, np.uint32).copy()
    with np.errstate(over="ignore"):
        x ^= x >> np.uint32(16)
        x *= np.uint32(0x85EBCA6B)
        x ^= x >> np.uint32(13)
        x *= np.uint32(0xC2B2AE35)
        x ^= x >> np.uint32(16)
    return x


def signatures(keys: np.ndarray, num_buckets: int, fp_bits: int) -> np.ndarray:
    """uint64 ``(min(i1, i2) << fp_bits) | fingerprint`` of each key."""
    if num_buckets & (num_buckets - 1):
        raise ValueError("XOR placement needs a power-of-two bucket count")
    h = xxhash64(keys)
    fp = (h >> _U64(32)).astype(np.uint32) & np.uint32((1 << fp_bits) - 1)
    fp[fp == 0] = 1
    mask = np.uint32(num_buckets - 1)
    i1 = h.astype(np.uint32) & mask
    i2 = i1 ^ (fmix32(fp) & mask)
    return (np.minimum(i1, i2).astype(_U64) << _U64(fp_bits)) | fp


def chunked(n: int, fn: Callable[[int, int], np.ndarray],
            dtype=_U64) -> np.ndarray:
    """``concatenate(fn(a, b) for [a, b) in CHUNK-wide pieces of [0, n))``,
    computed on :data:`THREADS` threads (numpy releases the GIL)."""
    out = np.empty((n,), dtype)
    spans = [(a, min(n, a + CHUNK)) for a in range(0, n, CHUNK)]

    def one(span):
        a, b = span
        out[a:b] = fn(a, b)

    with cf.ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(one, spans))
    return out


def contains(sorted_set: np.ndarray, items: np.ndarray) -> np.ndarray:
    """bool per item: is it in ``sorted_set`` (ascending uint64)?"""
    if not sorted_set.size:
        return np.zeros(items.shape, bool)
    pos = np.searchsorted(sorted_set, items)
    return sorted_set[np.minimum(pos, sorted_set.size - 1)] == items

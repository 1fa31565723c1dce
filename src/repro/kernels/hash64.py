"""Pallas TPU kernel: batched xxHash64 (paper §4.3 step 1).

Pure VPU arithmetic — the emulated-u64 xxHash64 runs entirely in 32-bit
lanes (16-bit-limb multiplies). Exists both as a building block and as the
cleanest micro-benchmark of the hashing cost the paper folds into every op.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.hashing import xxhash64_u64


def _hash_kernel(seed: int, keys_lo_ref, keys_hi_ref, out_hi_ref, out_lo_ref):
    hi, lo = xxhash64_u64((keys_hi_ref[...], keys_lo_ref[...]), seed=seed)
    out_hi_ref[...] = hi
    out_lo_ref[...] = lo


def hash64_pallas(keys_lo: jnp.ndarray, keys_hi: jnp.ndarray, *,
                  seed: int = 0, block_keys: int = 2048,
                  interpret: bool):
    """xxHash64 of n packed keys -> (hi, lo) uint32[n]."""
    n = keys_lo.shape[0]
    assert n % block_keys == 0
    kernel = functools.partial(_hash_kernel, seed)
    return pl.pallas_call(
        kernel,
        grid=(n // block_keys,),
        in_specs=[
            pl.BlockSpec((block_keys,), lambda i: (i,)),
            pl.BlockSpec((block_keys,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block_keys,), lambda i: (i,)),
            pl.BlockSpec((block_keys,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.uint32),
            jax.ShapeDtypeStruct((n,), jnp.uint32),
        ],
        interpret=interpret,
        name="xxhash64",
    )(keys_lo, keys_hi)

"""Pallas TPU kernel: rolling k-mer packing (paper §5.5 case study).

Packs 2-bit base codes into 2k-bit k-mer values (k <= 31 fits the 62-bit
budget of a u64 pair): output position i holds bases[i : i+k] packed
big-endian-by-base. The genomic pipeline (data/kmer.py) feeds these straight
into the filter as keys, reproducing the paper's KMC3 -> uint64 path.

Tiling: each grid step computes one tile of positions and needs a (k-1)-base
halo; the input stays in ANY/HBM memory and the kernel pl.load's its
(block + halo) window — the standard overlapping-window pattern.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..core import bits64 as b64

_U32 = np.uint32


def _kmer_kernel(k: int, block: int, bases_ref, out_hi_ref, out_lo_ref):
    i = pl.program_id(0)
    window = bases_ref[pl.ds(i * block, block + k)]   # tile + halo
    acc = (jnp.zeros((block,), jnp.uint32), jnp.zeros((block,), jnp.uint32))
    for j in range(k):  # statically unrolled rolling pack
        nxt = jax.lax.dynamic_slice(window, (j,), (block,))
        acc = b64.shl(acc, 2)
        acc = (acc[0], acc[1] | (nxt & _U32(3)))
    out_hi_ref[...] = acc[0]
    out_lo_ref[...] = acc[1]


def kmer_pack_pallas(bases: jnp.ndarray, k: int = 31, *,
                     block: int = 1024, interpret: bool):
    """bases: uint32[n] 2-bit codes, n a multiple of ``block``.

    Returns (hi, lo) uint32[n]; positions > n-k are computed from zero
    padding and should be sliced off by the caller.
    """
    n = bases.shape[0]
    assert n % block == 0, (n, block)
    padded = jnp.concatenate([bases, jnp.zeros((k,), jnp.uint32)])
    kernel = functools.partial(_kmer_kernel, k, block)
    return pl.pallas_call(
        kernel,
        grid=(n // block,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.uint32),
            jax.ShapeDtypeStruct((n,), jnp.uint32),
        ],
        interpret=interpret,
        name="kmer_pack",
    )(padded)

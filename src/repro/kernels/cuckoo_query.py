"""Pallas TPU kernel: batched Cuckoo-filter query (paper Alg. 2).

TPU mapping of the paper's query design (DESIGN.md §2):

* the filter table lives **entirely in VMEM** for the duration of the kernel
  — the TPU analogue of the paper's L2-resident regime (§5.2). One BlockSpec
  pins the full packed table; the key stream is tiled over the grid.
* per grid step, a tile of keys is hashed on the VPU (emulated-u64 xxHash64
  or the fmix32 fast path — both pure 32-bit lane arithmetic), both candidate
  buckets are gathered from the VMEM table, and matching uses the same
  equality-on-unpacked-lanes algebra as the SWAR masks (exact per lane).
* bucket-major layout means each bucket's ``words_per_bucket`` uint32 words
  are contiguous — a single vector row per bucket, the analogue of the
  paper's 256-bit ``ld.global.nc.v4.u64`` vectorized loads.

VMEM budget: table_bytes + 2 tiles of keys + gathered buckets. With the
paper's 16×16-bit buckets, a 2^18-bucket filter is 16 MiB — the VMEM-resident
ceiling on v5e (recorded in EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..core import layout as L
from ..core.cuckoo_filter import CuckooConfig
from ..core.hashing import hash_key

_U32 = np.uint32


def _query_kernel(config: CuckooConfig, table_ref, keys_lo_ref, keys_hi_ref,
                  out_ref):
    lay = config.layout
    pol = config.placement

    table = table_ref[...]
    keys = jnp.stack([keys_lo_ref[...], keys_hi_ref[...]], axis=-1)
    hi, lo = hash_key(keys, config.hash_kind, config.seed)
    tag = pol.make_tag(hi)
    i1, i2 = pol.initial_buckets(lo, tag)
    t1, t2 = pol.query_match_tags(tag)

    wpb = lay.words_per_bucket
    offs = jnp.arange(wpb, dtype=jnp.int32)

    def bucket_hit(bucket, match_tag):
        idx = bucket.astype(jnp.int32)[:, None] * wpb + offs  # [K, wpb]
        words = table[idx]                                    # VMEM gather
        lanes = L.unpack_words(words, lay.fp_bits)            # [K, b]
        return jnp.any(lanes == match_tag[:, None], axis=-1)

    hit = bucket_hit(i1, t1) | bucket_hit(i2, t2)
    out_ref[...] = hit.astype(jnp.uint32)


def _query_fused_kernel(config: CuckooConfig, table_ref, keys_lo_ref,
                        keys_hi_ref, out_ref):
    """Fused hash + gather + SWAR match (no per-lane unpack).

    Versus ``_query_kernel``: both candidate buckets are fetched with a
    *single* gather (one index vector of ``2 * words_per_bucket`` columns),
    and matching runs the paper's §4.3 SWAR algebra directly on the packed
    words — ``broadcast_tag`` + carry-free zero-mask — instead of widening
    every word to ``tags_per_word`` uint32 lanes first. At fp_bits=8 that
    is a 4x cut in comparison-operand width on the VPU.
    """
    lay = config.layout
    pol = config.placement

    table = table_ref[...]
    keys = jnp.stack([keys_lo_ref[...], keys_hi_ref[...]], axis=-1)
    hi, lo = hash_key(keys, config.hash_kind, config.seed)
    tag = pol.make_tag(hi)
    i1, i2 = pol.initial_buckets(lo, tag)
    t1, t2 = pol.query_match_tags(tag)

    wpb = lay.words_per_bucket
    offs = jnp.arange(wpb, dtype=jnp.int32)
    idx = jnp.concatenate(
        [i1.astype(jnp.int32)[:, None] * wpb + offs,
         i2.astype(jnp.int32)[:, None] * wpb + offs], axis=-1)  # [K, 2*wpb]
    words = table[idx]                                          # one gather

    m1 = L.swar_match_mask(words[:, :wpb], t1[:, None], lay.fp_bits)
    m2 = L.swar_match_mask(words[:, wpb:], t2[:, None], lay.fp_bits)
    hit = jnp.any((m1 | m2) != _U32(0), axis=-1)
    out_ref[...] = hit.astype(jnp.uint32)


def _query_call(kernel_body, config: CuckooConfig, table: jnp.ndarray,
                keys_lo: jnp.ndarray, keys_hi: jnp.ndarray,
                block_keys: int, interpret: bool, name: str) -> jnp.ndarray:
    n = keys_lo.shape[0]
    assert n % block_keys == 0, (n, block_keys)
    grid = (n // block_keys,)
    kernel = functools.partial(kernel_body, config)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(table.shape, lambda i: (0,)),          # whole table
            pl.BlockSpec((block_keys,), lambda i: (i,)),
            pl.BlockSpec((block_keys,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block_keys,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.uint32),
        interpret=interpret,
        name=name,
    )(table, keys_lo, keys_hi)


def cuckoo_query_pallas(config: CuckooConfig, table: jnp.ndarray,
                        keys_lo: jnp.ndarray, keys_hi: jnp.ndarray,
                        *, block_keys: int = 1024,
                        interpret: bool) -> jnp.ndarray:
    """Query ``n`` keys against a VMEM-resident filter table.

    n must be a multiple of ``block_keys`` (callers pad; see ops.py).
    Returns uint32[n] (1 = maybe-present, 0 = definitely absent).
    """
    return _query_call(_query_kernel, config, table, keys_lo, keys_hi,
                       block_keys, interpret, "cuckoo_query")


def cuckoo_query_fused_pallas(config: CuckooConfig, table: jnp.ndarray,
                              keys_lo: jnp.ndarray, keys_hi: jnp.ndarray,
                              *, block_keys: int = 1024,
                              interpret: bool) -> jnp.ndarray:
    """Fused-SWAR variant of :func:`cuckoo_query_pallas` — same contract.

    Kept alongside the unpack-based kernel so the roofline suite can
    measure both (the ``query_kernel_prepr`` baseline row).
    """
    return _query_call(_query_fused_kernel, config, table, keys_lo, keys_hi,
                       block_keys, interpret, "cuckoo_query_fused")

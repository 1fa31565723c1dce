"""Pallas TPU kernels: Blocked Bloom filter query + insert (GBBF baseline).

The blocked Bloom layout is the friendliest possible for TPU: one key maps
to exactly one contiguous block (cache line on GPU, vector row here), so both
operations are a single gather/RMW per key with no conflict structure beyond
word-level merging. Query is fully vectorized; insert uses the same
sequential-grid RMW trick as cuckoo_insert (core-exclusive VMEM ownership
replaces ``atomicOr``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..filters.blocked_bloom import BloomConfig, _bit_positions

_U32 = np.uint32


def _query_kernel(config: BloomConfig, table_ref, keys_lo_ref, keys_hi_ref,
                  out_ref):
    keys = jnp.stack([keys_lo_ref[...], keys_hi_ref[...]], axis=-1)
    block, word, mask = _bit_positions(config, keys)
    table = table_ref[...]
    addr = block[:, None] * config.words_per_block + word     # [K, k]
    words = table[addr]
    out_ref[...] = jnp.all((words & mask) == mask, axis=-1).astype(jnp.uint32)


def bloom_query_pallas(config: BloomConfig, table: jnp.ndarray,
                       keys_lo: jnp.ndarray, keys_hi: jnp.ndarray,
                       *, block_keys: int = 1024,
                       interpret: bool) -> jnp.ndarray:
    n = keys_lo.shape[0]
    assert n % block_keys == 0
    kernel = functools.partial(_query_kernel, config)
    return pl.pallas_call(
        kernel,
        grid=(n // block_keys,),
        in_specs=[
            pl.BlockSpec(table.shape, lambda i: (0,)),
            pl.BlockSpec((block_keys,), lambda i: (i,)),
            pl.BlockSpec((block_keys,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block_keys,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.uint32),
        interpret=interpret,
        name="bloom_query",
    )(table, keys_lo, keys_hi)


def _insert_kernel(config: BloomConfig, block_keys: int,
                   table_in_ref, keys_lo_ref, keys_hi_ref, valid_ref,
                   table_out_ref):
    keys = jnp.stack([keys_lo_ref[...], keys_hi_ref[...]], axis=-1)
    block, word, mask = _bit_positions(config, keys)
    addr = block[:, None] * config.words_per_block + word     # [K, k]
    live_mask = jnp.where((valid_ref[...] != 0)[:, None], mask,
                          jnp.zeros_like(mask))

    @pl.when(pl.program_id(0) == 0)
    def _():
        table_out_ref[...] = table_in_ref[...]

    def body(i, _):
        def set_bit(j, __):
            a = addr[i, j]
            w = table_out_ref[pl.ds(a, 1)]
            table_out_ref[pl.ds(a, 1)] = w | live_mask[i, j][None]
            return 0
        return jax.lax.fori_loop(0, config.k, set_bit, 0)

    jax.lax.fori_loop(0, block_keys, body, 0)


def bloom_insert_pallas(config: BloomConfig, table: jnp.ndarray,
                        keys_lo: jnp.ndarray, keys_hi: jnp.ndarray,
                        valid: jnp.ndarray | None = None,
                        *, block_keys: int = 256,
                        interpret: bool) -> jnp.ndarray:
    n = keys_lo.shape[0]
    assert n % block_keys == 0
    if valid is None:
        valid = jnp.ones((n,), jnp.uint32)
    kernel = functools.partial(_insert_kernel, config, block_keys)
    return pl.pallas_call(
        kernel,
        grid=(n // block_keys,),
        in_specs=[
            pl.BlockSpec(table.shape, lambda i: (0,)),
            pl.BlockSpec((block_keys,), lambda i: (i,)),
            pl.BlockSpec((block_keys,), lambda i: (i,)),
            pl.BlockSpec((block_keys,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec(table.shape, lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct(table.shape, jnp.uint32),
        input_output_aliases={0: 0},
        interpret=interpret,
        name="bloom_insert",
    )(table, keys_lo, keys_hi, valid)

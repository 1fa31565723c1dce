"""Public jit'd wrappers around the Pallas kernels.

Handles padding to block multiples, key packing conventions, and backend
selection: this module alone picks the Pallas mode. Kernels are compiled on
a TPU backend and interpreted on any other; on a TPU, a kernel the compiler
refuses raises the compiler's error (see tests/test_tpu_compile.py for
which kernels the v5e compiler accepts). Interpret mode checks a kernel's
arithmetic, not that it compiles or how fast it runs on the chip.

``block_keys`` defaults to ``None`` on the cuckoo wrappers, meaning "ask
:mod:`.autotune`": the tuned tile for this (op, backend, geometry) cell if
a sweep recorded one, else the static per-op default. Resolution happens
*outside* the jit boundary so a later sweep takes effect on the next call
instead of being baked into a cached trace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.cuckoo_filter import CuckooConfig, CuckooState, prepare_keys
from ..filters.blocked_bloom import BloomConfig, BloomState
from . import autotune
from .bloom import bloom_insert_pallas, bloom_query_pallas
from .cuckoo_insert import (
    cuckoo_insert_bulk_pallas,
    cuckoo_insert_fused_pallas,
    cuckoo_insert_pallas,
)
from .cuckoo_mixed import cuckoo_mixed_pallas
from .cuckoo_query import cuckoo_query_fused_pallas, cuckoo_query_pallas
from .hash64 import hash64_pallas
from .kmer_pack import kmer_pack_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jnp.ndarray, multiple: int, fill=0):
    n = x.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return x, n
    pad = jnp.full((rem,) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([x, pad]), n


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _cuckoo_query_jit(config: CuckooConfig, state: CuckooState,
                      keys: jnp.ndarray, block_keys: int,
                      fused: bool) -> jnp.ndarray:
    keys, n = _pad_to(keys, block_keys)
    kern = cuckoo_query_fused_pallas if fused else cuckoo_query_pallas
    out = kern(config, state.table, keys[:, 0], keys[:, 1],
               block_keys=block_keys, interpret=_interpret())
    return out[:n].astype(bool)


def cuckoo_query(config: CuckooConfig, state: CuckooState,
                 keys: jnp.ndarray, block_keys: int = None,
                 fused: bool = True) -> jnp.ndarray:
    """Kernel-backed batch query. keys: uint32[n, 2] -> bool[n].

    ``fused=True`` (default) runs the single-gather SWAR kernel;
    ``fused=False`` keeps the unpack-based variant measurable (the
    roofline suite's pre-fusion comparison row).
    """
    if block_keys is None:
        block_keys = autotune.resolve_block_keys(config, "query")
    return _cuckoo_query_jit(config, state, keys, block_keys, fused)


@functools.partial(jax.jit, static_argnums=(0, 3, 4), donate_argnums=(1,))
def _cuckoo_insert_direct_jit(config: CuckooConfig, state: CuckooState,
                              keys: jnp.ndarray, block_keys: int,
                              fused: bool):
    n0 = keys.shape[0]
    keys, n = _pad_to(keys, block_keys, fill=0)
    valid = (jnp.arange(keys.shape[0]) < n0).astype(jnp.uint32)
    kern = cuckoo_insert_fused_pallas if fused else cuckoo_insert_pallas
    table, ok = kern(config, state.table,
                     keys[:, 0], keys[:, 1], valid,
                     block_keys=block_keys,
                     interpret=_interpret())
    count = state.count + jnp.sum(ok[:n], dtype=jnp.int32)
    return CuckooState(table, count), ok[:n].astype(bool)


def cuckoo_insert_direct(config: CuckooConfig, state: CuckooState,
                         keys: jnp.ndarray, block_keys: int = None,
                         fused: bool = True):
    """Kernel-backed direct insert (no eviction). -> (state', ok bool[n]).

    ``fused=True`` (default) runs the single-row SWAR free-slot kernel;
    ``fused=False`` keeps the unpack-based variant measurable (the
    roofline suite's pre-fusion comparison row). Both are bit-identical.
    Failed keys (ok==False) should be retried through the eviction-capable
    core.cuckoo_filter.insert.
    """
    if block_keys is None:
        block_keys = autotune.resolve_block_keys(config, "insert")
    return _cuckoo_insert_direct_jit(config, state, keys, block_keys, fused)


@functools.partial(jax.jit, static_argnums=(0, 3), donate_argnums=(1,))
def _cuckoo_insert_bulk_jit(config: CuckooConfig, state: CuckooState,
                            keys: jnp.ndarray, block_keys: int):
    n0 = keys.shape[0]
    _, i1, _ = prepare_keys(config, keys)
    order = jnp.argsort(i1.astype(jnp.int32), stable=True)
    keys_sorted, _ = _pad_to(keys[order], block_keys, fill=0)
    valid = (jnp.arange(keys_sorted.shape[0]) < n0).astype(jnp.uint32)
    table, ok_s = cuckoo_insert_bulk_pallas(
        config, state.table, keys_sorted[:, 0], keys_sorted[:, 1], valid,
        block_keys=block_keys, interpret=_interpret())
    ok = jnp.zeros((n0,), jnp.uint32).at[order].set(ok_s[:n0])
    count = state.count + jnp.sum(ok, dtype=jnp.int32)
    return CuckooState(table, count), ok.astype(bool)


def cuckoo_insert_bulk(config: CuckooConfig, state: CuckooState,
                       keys: jnp.ndarray, block_keys: int = None):
    """Kernel-backed bucket-major direct insert. -> (state', ok bool[n]).

    Sorts the batch by primary bucket once (the bulk-build order, DESIGN.md
    §6) so the kernel streams whole bucket segments; ``ok`` comes back in
    the original batch order. Failed keys need the eviction-capable
    core.cuckoo_filter path.
    """
    if block_keys is None:
        block_keys = autotune.resolve_block_keys(config, "bulk_insert")
    return _cuckoo_insert_bulk_jit(config, state, keys, block_keys)


@functools.partial(jax.jit, static_argnums=(0, 4), donate_argnums=(1,))
def _cuckoo_apply_ops_jit(config: CuckooConfig, state: CuckooState,
                          keys: jnp.ndarray, ops: jnp.ndarray,
                          block_keys: int):
    n0 = keys.shape[0]
    keys, n = _pad_to(keys, block_keys, fill=0)
    ops_p, _ = _pad_to(ops.astype(jnp.int32), block_keys, fill=0)
    valid = (jnp.arange(keys.shape[0]) < n0).astype(jnp.uint32)
    table, ok = cuckoo_mixed_pallas(config, state.table,
                                    keys[:, 0], keys[:, 1], ops_p, valid,
                                    block_keys=block_keys,
                                    interpret=_interpret())
    ok = ok[:n0].astype(bool)
    delta = (jnp.sum(ok & (ops == 1), dtype=jnp.int32)
             - jnp.sum(ok & (ops == 2), dtype=jnp.int32))
    return CuckooState(table, state.count + delta), ok


def cuckoo_apply_ops(config: CuckooConfig, state: CuckooState,
                     keys: jnp.ndarray, ops: jnp.ndarray,
                     block_keys: int = None):
    """Kernel-backed fused mixed-op pass. -> (state', ok bool[n]).

    ``ops``: int32[n] op codes (0 query / 1 insert / 2 delete). The kernel
    realises exact sequential in-batch semantics (DESIGN.md §9); inserts
    are direct-only — failed insert slots (ok==False) should be retried
    through the eviction-capable ``core.cuckoo_filter`` path.
    """
    if block_keys is None:
        block_keys = autotune.resolve_block_keys(config, "apply_ops")
    return _cuckoo_apply_ops_jit(config, state, keys, ops, block_keys)


@functools.partial(jax.jit, static_argnums=(0, 3))
def bloom_query(config: BloomConfig, state: BloomState,
                keys: jnp.ndarray, block_keys: int = 1024) -> jnp.ndarray:
    keys, n = _pad_to(keys, block_keys)
    out = bloom_query_pallas(config, state.table, keys[:, 0], keys[:, 1],
                             block_keys=block_keys,
                             interpret=_interpret())
    return out[:n].astype(bool)


@functools.partial(jax.jit, static_argnums=(0, 3), donate_argnums=(1,))
def bloom_insert(config: BloomConfig, state: BloomState,
                 keys: jnp.ndarray, block_keys: int = 256):
    n0 = keys.shape[0]
    keys, n = _pad_to(keys, block_keys)
    valid = (jnp.arange(keys.shape[0]) < n0).astype(jnp.uint32)
    table = bloom_insert_pallas(config, state.table, keys[:, 0], keys[:, 1],
                                valid, block_keys=block_keys,
                                interpret=_interpret())
    return BloomState(table, state.count + n), jnp.ones((n,), bool)


@functools.partial(jax.jit, static_argnums=(1, 2))
def hash64(keys: jnp.ndarray, seed: int = 0, block_keys: int = 2048):
    """xxHash64 of uint32[n, 2] keys -> (hi, lo) uint32[n]."""
    keys, n = _pad_to(keys, block_keys)
    hi, lo = hash64_pallas(keys[:, 0], keys[:, 1], seed=seed,
                           block_keys=block_keys, interpret=_interpret())
    return hi[:n], lo[:n]


@functools.partial(jax.jit, static_argnums=(1, 2))
def kmer_pack(bases: jnp.ndarray, k: int = 31, block: int = 1024):
    """2-bit base codes uint32[n] -> packed k-mer keys uint32[n-k+1, 2]."""
    bases, n = _pad_to(bases.astype(jnp.uint32), block)
    hi, lo = kmer_pack_pallas(bases, k=k, block=block,
                              interpret=_interpret())
    m = n - k + 1
    return jnp.stack([lo[:m], hi[:m]], axis=-1)

"""Pallas TPU kernel: fused mixed-operation Cuckoo-filter pass (DESIGN.md §9).

One kernel executes an interleaved QUERY/INSERT/DELETE stream against a
VMEM-resident table. Like the insert kernels, grid steps (and the in-kernel
key loop) run **sequentially** on a TPU core, so read-modify-write needs no
CAS — and, unlike the batch-synchronous XLA path in
``core.cuckoo_filter.apply_ops``, the kernel's per-key loop realises the
*exact* sequential semantics of the op stream, including cross-key
fingerprint aliasing: operation ``i`` observes every table mutation of
operations ``j < i``, full stop.

Structure per key (bucket-major, one vector row per bucket):

* Phase A (vectorized over the tile): hash every key on the VPU, derive
  tags, both candidate buckets, and the per-bucket match tags.
* Phase B (sequential): dispatch on the op code —

  - QUERY: SWAR match-mask over both buckets' packed words
    (``layout.swar_match_mask``), any lane set → hit; no write.
  - INSERT: first-empty-slot scan (``layout.swar_zero_mask``) from the
    fingerprint-derived circular start, bucket i1 then i2; write the
    claimed word back. Both full → ``ok=0`` (the direct-insert contract:
    the eviction path stays in ``core.cuckoo_filter``).
  - DELETE: first-match scan, i1 then i2; zero the matched lane.

Each key commits at most one word write, applied as a masked store (failed
or read-only ops write the current word back), so the loop body is a single
homogeneous RMW regardless of op mix — no divergent branches, exactly the
property that makes the mixed stream fuse.
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

# Op codes (mirrors repro.amq.protocol; plain ints so the kernel module
# stays importable without the amq package).
_OP_QUERY, _OP_INSERT, _OP_DELETE = 0, 1, 2


def _mixed_kernel(config: CuckooConfig, block_keys: int,
                  table_in_ref, keys_lo_ref, keys_hi_ref, ops_ref, valid_ref,
                  table_out_ref, ok_ref):
    lay = config.layout
    pol = config.placement
    wpb = lay.words_per_bucket
    warange = jnp.arange(wpb, dtype=jnp.int32)

    # Phase A: vectorized hashing + candidate derivation for the whole tile.
    keys = jnp.stack([keys_lo_ref[...], keys_hi_ref[...]], axis=-1)
    hi, lo = hash_key(keys, config.hash_kind, config.seed)
    base_tag = pol.make_tag(hi)
    i1, i2 = pol.initial_buckets(lo, base_tag)
    tag1 = pol.place_tag(base_tag, jnp.zeros((block_keys,), bool))
    tag2 = pol.place_tag(base_tag, jnp.ones((block_keys,), bool))
    t1, t2 = pol.query_match_tags(base_tag)
    start = L.scan_start(base_tag, lay)

    @pl.when(pl.program_id(0) == 0)
    def _():
        table_out_ref[...] = table_in_ref[...]

    def body(i, _):
        opc = ops_ref[i]
        live = valid_ref[i] != 0
        is_q = opc == _OP_QUERY
        is_i = opc == _OP_INSERT
        is_d = opc == _OP_DELETE

        b1 = i1[i].astype(jnp.int32)
        b2 = i2[i].astype(jnp.int32)
        words1 = table_out_ref[pl.ds(b1 * wpb, wpb)]
        words2 = table_out_ref[pl.ds(b2 * wpb, wpb)]

        # SWAR masks per bucket: match lanes (query/delete) and zero lanes
        # (insert) — the §4.3 algebra, carry-free exact per lane.
        match1 = L.swar_mask_to_bools(
            L.swar_match_mask(words1, t1[i], lay.fp_bits),
            lay.fp_bits).reshape(-1)
        match2 = L.swar_mask_to_bools(
            L.swar_match_mask(words2, t2[i], lay.fp_bits),
            lay.fp_bits).reshape(-1)
        free1 = L.swar_mask_to_bools(
            L.swar_zero_mask(words1, lay.fp_bits), lay.fp_bits).reshape(-1)
        free2 = L.swar_mask_to_bools(
            L.swar_zero_mask(words2, lay.fp_bits), lay.fp_bits).reshape(-1)

        # Per-op slot election, bucket i1 preferred (paper Alg. 1-3 order).
        flags1 = jnp.where(is_i, free1, match1)
        flags2 = jnp.where(is_i, free2, match2)
        f1, s1 = L.first_true_circular(flags1, start[i])
        f2, s2 = L.first_true_circular(flags2, start[i])
        hit = f1 | f2

        use1 = f1
        bucket = jnp.where(use1, b1, b2)
        slot = jnp.where(use1, s1, s2)
        store_tag = jnp.where(
            is_i, jnp.where(use1, tag1[i], tag2[i]), _U32(0))  # delete zeros
        widx, sw = L.slot_to_word(slot, lay)
        word = jnp.where(use1, words1, words2)[widx]
        desired = L.replace_tag(word, sw, store_tag, lay.fp_bits)
        addr = bucket * wpb + widx

        del is_q  # query ok is just "any match found" — same election path
        ok = live & hit
        do_write = ok & (is_i | is_d)

        current = table_out_ref[pl.ds(addr, 1)]
        table_out_ref[pl.ds(addr, 1)] = jnp.where(do_write, desired[None],
                                                  current)
        ok_ref[pl.ds(i, 1)] = ok.astype(jnp.uint32)[None]
        return 0

    jax.lax.fori_loop(0, block_keys, body, 0)


def cuckoo_mixed_pallas(config: CuckooConfig, table: jnp.ndarray,
                        keys_lo: jnp.ndarray, keys_hi: jnp.ndarray,
                        ops: jnp.ndarray,
                        valid: jnp.ndarray | None = None,
                        *, block_keys: int = 256,
                        interpret: bool):
    """Fused mixed-op pass; returns (table', ok uint32[n]).

    ``ops`` is int32[n] op codes (0 query / 1 insert / 2 delete); ``ok``
    is the per-op outcome (hit / landed / removed). Failed inserts
    (``ok==0`` on an insert slot) need the eviction-capable
    ``core.cuckoo_filter`` path. ``valid`` masks padding keys.
    """
    n = keys_lo.shape[0]
    assert n % block_keys == 0, (n, block_keys)
    if valid is None:
        valid = jnp.ones((n,), jnp.uint32)
    grid = (n // block_keys,)
    kernel = functools.partial(_mixed_kernel, config, block_keys)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(table.shape, lambda i: (0,)),
            pl.BlockSpec((block_keys,), lambda i: (i,)),
            pl.BlockSpec((block_keys,), lambda i: (i,)),
            pl.BlockSpec((block_keys,), lambda i: (i,)),
            pl.BlockSpec((block_keys,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec(table.shape, lambda i: (0,)),
            pl.BlockSpec((block_keys,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(table.shape, jnp.uint32),
            jax.ShapeDtypeStruct((n,), jnp.uint32),
        ],
        input_output_aliases={0: 0},
        interpret=interpret,
        name="cuckoo_mixed",
    )(table, keys_lo, keys_hi, ops, valid)

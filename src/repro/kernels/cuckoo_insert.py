"""Pallas TPU kernel: Cuckoo-filter direct insertion (paper Alg. 1 phase 1).

TPU adaptation of the lock-free CAS insert (DESIGN.md §2): a TPU core's grid
steps execute **sequentially**, so read-modify-write on a VMEM-resident table
is race-free *by construction* — the atomicity the GPU buys with CAS, the TPU
gets from exclusive core ownership. Parallel scale-out happens above this
kernel (one filter shard per core via shard_map; see core/sharded_filter.py).

The kernel implements the *direct-insert fast path*: hash a tile of keys on
the VPU (vectorized), then apply them with an in-kernel sequential loop —
scan bucket i1 then i2 from the fingerprint-derived start, take the first
empty slot, store the updated word back to VMEM. Keys whose buckets are both
full are reported in the failure mask; the (rare at <95% load) eviction path
is handled by the general batch machinery in core/cuckoo_filter.py. This
hybrid mirrors the paper's own structure, where phase 2 is the slow path.

The table is input/output-aliased so the update is in-place in VMEM.
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


def _insert_kernel(config: CuckooConfig, block_keys: int,
                   table_in_ref, keys_lo_ref, keys_hi_ref, valid_ref,
                   table_out_ref, ok_ref):
    lay = config.layout
    pol = config.placement
    wpb = lay.words_per_bucket

    # Phase A (vectorized over the tile): hashing + candidate derivation.
    keys = jnp.stack([keys_lo_ref[...], keys_hi_ref[...]], axis=-1)
    hi, lo = hash_key(keys, config.hash_kind, config.seed)
    base_tag = pol.make_tag(hi)
    i1, i2 = pol.initial_buckets(lo, base_tag)
    tag1 = pol.place_tag(base_tag, jnp.zeros((block_keys,), bool))
    tag2 = pol.place_tag(base_tag, jnp.ones((block_keys,), bool))
    start = L.scan_start(base_tag, lay)

    # Phase B (sequential RMW): grid steps and this loop both execute in
    # order on the core, so each iteration sees all prior writes.
    def body(i, _):
        def try_bucket(bucket, tag):
            base = bucket.astype(jnp.int32) * wpb
            words = table_out_ref[pl.ds(base, wpb)]
            lanes = L.unpack_words(words, lay.fp_bits)
            found, slot = L.first_true_circular(lanes == 0, start[i])
            widx, sw = L.slot_to_word(slot, lay)
            desired = L.replace_tag(words[widx], sw, tag, lay.fp_bits)
            return found, base + widx, desired

        f1, addr1_, des1 = try_bucket(i1[i], tag1[i])
        f2, addr2_, des2 = try_bucket(i2[i], tag2[i])
        found = (f1 | f2) & (valid_ref[i] != 0)
        addr = jnp.where(f1, addr1_, addr2_)
        desired = jnp.where(f1, des1, des2)
        # Masked store: failed keys write back the original word.
        current = table_out_ref[pl.ds(addr, 1)]
        table_out_ref[pl.ds(addr, 1)] = jnp.where(found, desired[None],
                                                  current)
        ok_ref[pl.ds(i, 1)] = found.astype(jnp.uint32)[None]
        return 0

    # First grid step: copy the table into the aliased output buffer (no-op
    # under aliasing, but keeps interpret mode and real lowering identical).
    @pl.when(pl.program_id(0) == 0)
    def _():
        table_out_ref[...] = table_in_ref[...]

    jax.lax.fori_loop(0, block_keys, body, 0)


def cuckoo_insert_pallas(config: CuckooConfig, table: jnp.ndarray,
                         keys_lo: jnp.ndarray, keys_hi: jnp.ndarray,
                         valid: jnp.ndarray | None = None,
                         *, block_keys: int = 256,
                         interpret: bool):
    """Direct-insert a key stream; returns (table', ok uint32[n]).

    ok==0 keys need the eviction path (core.cuckoo_filter.insert).
    ``valid`` (uint32[n], nonzero = live) masks padding keys.
    """
    n = keys_lo.shape[0]
    assert n % block_keys == 0, (n, block_keys)
    if valid is None:
        valid = jnp.ones((n,), jnp.uint32)
    grid = (n // block_keys,)
    kernel = functools.partial(_insert_kernel, config, block_keys)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(table.shape, lambda i: (0,)),
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
        name="cuckoo_insert_direct",
    )(table, keys_lo, keys_hi, valid)


# ---------------------------------------------------------------------------
# Fused-SWAR variant (joins the fused kernel family, DESIGN.md §13/§14).
# ---------------------------------------------------------------------------

def _insert_fused_kernel(config: CuckooConfig, block_keys: int,
                         table_in_ref, keys_lo_ref, keys_hi_ref, valid_ref,
                         table_out_ref, ok_ref):
    """Fused hash + double-bucket load + SWAR free-slot scan.

    Versus ``_insert_kernel``: both candidate buckets are read as one
    ``2 * words_per_bucket`` packed row and the free-lane search runs the
    §4.3 SWAR zero-mask directly on the packed words — no per-bucket
    unpack-to-lanes pass — then a single circular-preference scan (bucket
    i1's slots from the fingerprint-derived start, then i2's) picks the
    slot, exactly the order the unfused kernel and the core scan use.
    """
    lay = config.layout
    pol = config.placement
    wpb = lay.words_per_bucket
    b = config.bucket_size

    keys = jnp.stack([keys_lo_ref[...], keys_hi_ref[...]], axis=-1)
    hi, lo = hash_key(keys, config.hash_kind, config.seed)
    base_tag = pol.make_tag(hi)
    i1, i2 = pol.initial_buckets(lo, base_tag)
    tag1 = pol.place_tag(base_tag, jnp.zeros((block_keys,), bool))
    tag2 = pol.place_tag(base_tag, jnp.ones((block_keys,), bool))
    start = L.scan_start(base_tag, lay)
    slots = jnp.arange(b, dtype=jnp.int32)

    @pl.when(pl.program_id(0) == 0)
    def _():
        table_out_ref[...] = table_in_ref[...]

    def body(i, _):
        base1 = i1[i].astype(jnp.int32) * wpb
        base2 = i2[i].astype(jnp.int32) * wpb
        words = jnp.concatenate([table_out_ref[pl.ds(base1, wpb)],
                                 table_out_ref[pl.ds(base2, wpb)]])
        free = L.swar_mask_to_bools(
            L.swar_zero_mask(words, lay.fp_bits), lay.fp_bits).reshape(2 * b)
        # Circular preference order: i1's slots from start[i], then i2's.
        rot = (start[i] + slots) % b
        positions = jnp.concatenate([rot, b + rot])
        cand = free[positions]
        found = jnp.any(cand) & (valid_ref[i] != 0)
        abs_slot = positions[jnp.argmax(cand)]
        in_b2 = abs_slot >= b
        slot = abs_slot - jnp.where(in_b2, b, 0)
        widx, sw = L.slot_to_word(slot, lay)
        word = words[jnp.where(in_b2, wpb, 0) + widx]
        desired = L.replace_tag(
            word, sw, jnp.where(in_b2, tag2[i], tag1[i]), lay.fp_bits)
        addr = jnp.where(in_b2, base2, base1) + widx
        current = table_out_ref[pl.ds(addr, 1)]
        table_out_ref[pl.ds(addr, 1)] = jnp.where(found, desired[None],
                                                  current)
        ok_ref[pl.ds(i, 1)] = found.astype(jnp.uint32)[None]
        return 0

    jax.lax.fori_loop(0, block_keys, body, 0)


def cuckoo_insert_fused_pallas(config: CuckooConfig, table: jnp.ndarray,
                               keys_lo: jnp.ndarray, keys_hi: jnp.ndarray,
                               valid: jnp.ndarray | None = None,
                               *, block_keys: int = 256,
                               interpret: bool):
    """Fused-SWAR variant of :func:`cuckoo_insert_pallas` — same contract,
    bit-identical results (the roofline suite measures both)."""
    n = keys_lo.shape[0]
    assert n % block_keys == 0, (n, block_keys)
    if valid is None:
        valid = jnp.ones((n,), jnp.uint32)
    grid = (n // block_keys,)
    kernel = functools.partial(_insert_fused_kernel, config, block_keys)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(table.shape, lambda i: (0,)),
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
        name="cuckoo_insert_fused",
    )(table, keys_lo, keys_hi, valid)


# ---------------------------------------------------------------------------
# Bucket-major tile variant (bulk-build fast path, DESIGN.md §6).
# ---------------------------------------------------------------------------

def _bulk_insert_kernel(config: CuckooConfig, block_keys: int,
                        table_in_ref, keys_lo_ref, keys_hi_ref, valid_ref,
                        table_out_ref, ok_ref):
    """Direct insert for a tile of keys **pre-sorted by primary bucket**.

    Bucket-major order lets the kernel keep the current primary bucket's
    packed words in registers across the run of keys that target it: the
    bucket is loaded once per segment and flushed once when the segment
    ends, instead of a VMEM read-modify-write per key. Same sequential
    semantics as ``_insert_kernel`` (and ``ref.cuckoo_insert_ref`` on the
    sorted stream) — only the memory traffic pattern changes.
    """
    lay = config.layout
    pol = config.placement
    wpb = lay.words_per_bucket
    warange = jnp.arange(wpb, dtype=jnp.int32)

    keys = jnp.stack([keys_lo_ref[...], keys_hi_ref[...]], axis=-1)
    hi, lo = hash_key(keys, config.hash_kind, config.seed)
    base_tag = pol.make_tag(hi)
    i1, i2 = pol.initial_buckets(lo, base_tag)
    tag1 = pol.place_tag(base_tag, jnp.zeros((block_keys,), bool))
    tag2 = pol.place_tag(base_tag, jnp.ones((block_keys,), bool))
    start = L.scan_start(base_tag, lay)

    @pl.when(pl.program_id(0) == 0)
    def _():
        table_out_ref[...] = table_in_ref[...]

    # Prime the cache with the first key's primary bucket.
    b0 = i1[0].astype(jnp.int32)
    words0 = table_out_ref[pl.ds(b0 * wpb, wpb)]

    def body(i, carry):
        cur_bucket, cur_words = carry
        live = valid_ref[i] != 0
        b1 = i1[i].astype(jnp.int32)
        seg_end = b1 != cur_bucket

        # Segment boundary: flush the cached bucket, then load the new one.
        @pl.when(seg_end)
        def _():
            table_out_ref[pl.ds(cur_bucket * wpb, wpb)] = cur_words

        fresh = table_out_ref[pl.ds(b1 * wpb, wpb)]
        wordsA = jnp.where(seg_end, fresh, cur_words)

        lanesA = L.unpack_words(wordsA, lay.fp_bits)
        foundA, slotA = L.first_true_circular(lanesA == 0, start[i])
        widxA, swA = L.slot_to_word(slotA, lay)
        desiredA = L.replace_tag(wordsA[widxA], swA, tag1[i], lay.fp_bits)
        okA = foundA & live
        wordsA = jnp.where((warange == widxA) & okA, desiredA, wordsA)

        # Secondary bucket: straight to VMEM, except when it aliases the
        # cached primary bucket (possible under XOR when H(fp)&mask == 0).
        b2 = i2[i].astype(jnp.int32)
        sameB = b2 == b1
        wordsB = jnp.where(sameB, wordsA,
                           table_out_ref[pl.ds(b2 * wpb, wpb)])
        lanesB = L.unpack_words(wordsB, lay.fp_bits)
        foundB, slotB = L.first_true_circular(lanesB == 0, start[i])
        widxB, swB = L.slot_to_word(slotB, lay)
        desiredB = L.replace_tag(wordsB[widxB], swB, tag2[i], lay.fp_bits)
        okB = foundB & live & ~okA

        cur_words = jnp.where((warange == widxB) & okB & sameB,
                              desiredB, wordsA)
        addrB = b2 * wpb + widxB
        currentB = table_out_ref[pl.ds(addrB, 1)]
        table_out_ref[pl.ds(addrB, 1)] = jnp.where(okB & ~sameB,
                                                   desiredB[None], currentB)

        ok_ref[pl.ds(i, 1)] = (okA | okB).astype(jnp.uint32)[None]
        return b1, cur_words

    final_bucket, final_words = jax.lax.fori_loop(
        0, block_keys, body, (b0, words0))
    table_out_ref[pl.ds(final_bucket * wpb, wpb)] = final_words


def cuckoo_insert_bulk_pallas(config: CuckooConfig, table: jnp.ndarray,
                              keys_lo: jnp.ndarray, keys_hi: jnp.ndarray,
                              valid: jnp.ndarray | None = None,
                              *, block_keys: int = 256,
                              interpret: bool):
    """Bucket-major direct insert; callers must pass keys sorted by primary
    bucket (``prepare_keys``'s ``i1``). Returns (table', ok uint32[n])."""
    n = keys_lo.shape[0]
    assert n % block_keys == 0, (n, block_keys)
    if valid is None:
        valid = jnp.ones((n,), jnp.uint32)
    grid = (n // block_keys,)
    kernel = functools.partial(_bulk_insert_kernel, config, block_keys)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(table.shape, lambda i: (0,)),
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
        name="cuckoo_insert_bulk",
    )(table, keys_lo, keys_hi, valid)

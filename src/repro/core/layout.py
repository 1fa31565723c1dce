"""Packed-fingerprint bucket layout + SWAR primitives (paper §4.2).

The paper packs 8/16/32-bit fingerprints into 64-bit words. TPU VPU lanes are
32 bits wide, so our machine word is ``uint32`` (hardware-adaptation note in
DESIGN.md §2): a word holds 4×8-bit, 2×16-bit or 1×32-bit fingerprints. The
SWAR zero/match-mask algebra is identical, just on 32-bit constants.

The table is a flat ``uint32[num_buckets * words_per_bucket]`` array; a bucket
is the contiguous word range ``[b * wpb, (b+1) * wpb)`` — bucket-major layout
so one vector load covers a whole bucket (the TPU analogue of the paper's
256-bit vectorized query loads).

The bucket reads name their phases with ``jax.named_scope``, nested in the
caller's: ``row_gather`` (the table gather), ``lane_select`` (picking
words or lanes by index: the row's masked lane reductions, :func:`pick`)
and ``tag_match`` (unpacking words into tags). Each compiled instruction
carries its scope in its metadata, so a device trace of a program can be
read phase by phase (PERF.md §3).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

_U32 = np.uint32

# SWAR constants per fingerprint width: (low-7(15,31)-bits pattern, high-bit pattern).
_SWAR_LOW7 = {8: 0x7F7F7F7F, 16: 0x7FFF7FFF, 32: 0x7FFFFFFF}
_SWAR_HIGH = {8: 0x80808080, 16: 0x80008000, 32: 0x80000000}


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """Static description of the packed bucket layout."""

    num_buckets: int
    bucket_size: int          # b: fingerprints per bucket
    fp_bits: int              # f: bits per stored tag (incl. choice bit if any)

    def __post_init__(self):
        if self.fp_bits not in (8, 16, 32):
            raise ValueError("fp_bits must be 8, 16 or 32 (hardware-friendly widths)")
        if self.bucket_size % self.tags_per_word:
            raise ValueError("bucket_size must be a multiple of tags_per_word")

    @property
    def tags_per_word(self) -> int:
        return 32 // self.fp_bits

    @property
    def words_per_bucket(self) -> int:
        return self.bucket_size // self.tags_per_word

    @property
    def num_words(self) -> int:
        return self.num_buckets * self.words_per_bucket

    @property
    def num_slots(self) -> int:
        return self.num_buckets * self.bucket_size

    @property
    def fp_mask(self) -> int:
        return (1 << self.fp_bits) - 1

    @property
    def table_bytes(self) -> int:
        return self.num_words * 4

    def empty_table(self) -> jnp.ndarray:
        return jnp.zeros((self.num_words,), jnp.uint32)


# ---------------------------------------------------------------------------
# SWAR primitives (paper §4.3 "bitwise SWAR algorithm", §4.4 HasZeroSegment).
# ---------------------------------------------------------------------------

def swar_zero_mask(word: jnp.ndarray, fp_bits: int) -> jnp.ndarray:
    """High bit of each fp lane set iff that lane is zero — *exact* per lane.

    The paper's classic haszero ``(v - 0x01..01) & ~v & 0x80..80`` is only
    exact for the lowest flagged lane (borrows pollute higher lanes); since
    our scans start at a fingerprint-derived circular offset we need the
    carry-free exact variant:

        y = (v & 0x7F..7F) + 0x7F..7F   # high bit <- OR of low bits
        y |= v                           # high bit <- lane nonzero
        mask = ~y & 0x80..80
    """
    low7 = _U32(_SWAR_LOW7[fp_bits])
    high = _U32(_SWAR_HIGH[fp_bits])
    y = ((word & low7) + low7) | word
    return ~y & high


def swar_match_mask(word: jnp.ndarray, tag: jnp.ndarray, fp_bits: int) -> jnp.ndarray:
    """High bit of each fp lane set iff that lane equals ``tag``."""
    return swar_zero_mask(word ^ broadcast_tag(tag, fp_bits), fp_bits)


def broadcast_tag(tag: jnp.ndarray, fp_bits: int) -> jnp.ndarray:
    """Replicate a tag into every lane of a 32-bit word (paper BroadcastTag)."""
    tag = jnp.asarray(tag, jnp.uint32)
    word = tag
    if fp_bits <= 16:
        word = word | (word << 16)
    if fp_bits <= 8:
        word = word | ((word & _U32(0x00FF00FF)) << 8)
    return word


def swar_mask_to_bools(mask: jnp.ndarray, fp_bits: int) -> jnp.ndarray:
    """SWAR high-bit mask (uint32) -> bool[..., tags_per_word] per-lane flags."""
    tpw = 32 // fp_bits
    shifts = (jnp.arange(tpw, dtype=jnp.uint32) * _U32(fp_bits)) + _U32(fp_bits - 1)
    return ((mask[..., None] >> shifts) & _U32(1)).astype(bool)


# ---------------------------------------------------------------------------
# Pack / unpack and slot read-modify-write.
# ---------------------------------------------------------------------------

@jax.named_scope("tag_match")
def unpack_words(words: jnp.ndarray, fp_bits: int) -> jnp.ndarray:
    """uint32[..., W] packed words -> uint32[..., W * tpw] tag values."""
    tpw = 32 // fp_bits
    shifts = jnp.arange(tpw, dtype=jnp.uint32) * _U32(fp_bits)
    tags = (words[..., None] >> shifts) & _U32((1 << fp_bits) - 1)
    return tags.reshape(*words.shape[:-1], words.shape[-1] * tpw)


def extract_tag(word: jnp.ndarray, slot_in_word: jnp.ndarray, fp_bits: int) -> jnp.ndarray:
    """ExtractTag (paper Alg. 1 line 17)."""
    shift = (slot_in_word.astype(jnp.uint32) * _U32(fp_bits))
    return (word >> shift) & _U32((1 << fp_bits) - 1)


def replace_tag(
    word: jnp.ndarray, slot_in_word: jnp.ndarray, tag: jnp.ndarray, fp_bits: int
) -> jnp.ndarray:
    """ReplaceTag (paper Alg. 1 line 18) — returns the ``desired`` word."""
    shift = slot_in_word.astype(jnp.uint32) * _U32(fp_bits)
    lane_mask = _U32((1 << fp_bits) - 1) << shift
    return (word & ~lane_mask) | ((tag.astype(jnp.uint32) << shift) & lane_mask)


# ---------------------------------------------------------------------------
# Bucket gather + circular first-empty / first-match scans (paper TryInsert /
# Find start at a fingerprint-derived pseudo-random offset).
# ---------------------------------------------------------------------------

_ROW_WORDS = 128  # one TPU lane row of uint32 words (512 bytes)


def gather_bucket_words(table: jnp.ndarray, bucket: jnp.ndarray, layout: BucketLayout) -> jnp.ndarray:
    """Gather the packed words of each bucket: -> uint32[..., words_per_bucket].

    Lowered for a TPU, and where the table splits into whole 128-word rows,
    each bucket is read as the one row holding it (:func:`gather_by_row`):
    the v5e runs a gather of 8 scalar words per bucket far slower than one
    of a whole row. Elsewhere each word is gathered (:func:`gather_by_word`):
    on XLA-CPU the row gather slows the CI-gated benchmark suites 4x to 50x
    (PERF.md, Findings). Both give the same words; the tests check that the
    core ops agree under either.
    """
    if _ROW_WORDS % layout.words_per_bucket or layout.num_words % _ROW_WORDS:
        return gather_by_word(table, bucket, layout)
    return jax.lax.platform_dependent(
        table, bucket,
        default=functools.partial(gather_by_word, layout=layout),
        tpu=functools.partial(gather_by_row, layout=layout))


@jax.named_scope("row_gather")
def gather_by_word(table: jnp.ndarray, bucket: jnp.ndarray,
                   layout: BucketLayout) -> jnp.ndarray:
    """One scalar gather per packed word of each bucket."""
    wpb = layout.words_per_bucket
    offs = jnp.arange(wpb, dtype=jnp.int32)
    return table[bucket.astype(jnp.int32)[..., None] * wpb + offs]


def gather_by_row(table: jnp.ndarray, bucket: jnp.ndarray,
                  layout: BucketLayout) -> jnp.ndarray:
    """Gather the 128-word row holding each bucket, then select its words.

    Needs ``num_words`` a multiple of 128 and ``words_per_bucket`` a divisor
    of 128; viewing the flat table as ``[rows, 128]`` is then a bitcast.
    """
    wpb = layout.words_per_bucket
    per_row = _ROW_WORDS // wpb
    bucket = bucket.astype(jnp.int32)
    with jax.named_scope("row_gather"):
        rows = table.reshape(-1, _ROW_WORDS)[bucket // per_row]  # [..., 128]
    with jax.named_scope("lane_select"):
        first = ((bucket % per_row) * wpb)[..., None]
        lane = jnp.arange(_ROW_WORDS, dtype=jnp.int32)
        # One masked lane-reduction per word keeps every temporary 128 lanes
        # wide (a [..., per_row, wpb] view would pad wpb up to 128 lanes).
        return jnp.stack([jnp.sum(jnp.where(lane == first + j, rows, _U32(0)),
                                  axis=-1, dtype=jnp.uint32)
                          for j in range(wpb)], axis=-1)


@jax.named_scope("lane_select")
def pick(values: jnp.ndarray, index: jnp.ndarray, axis: int) -> jnp.ndarray:
    """``values`` indexed by ``index`` along ``axis`` (axis removed).

    ``index`` has the shape of ``values`` without ``axis`` (or broadcasts to
    it) and lies in ``[0, values.shape[axis])``. Same result as
    ``take_along_axis`` with the axis squeezed, computed as a one-hot select
    and reduce: a TPU runs a gather along a short axis much slower than a
    pass over the whole row.
    """
    axis = axis % values.ndim
    lanes = jnp.arange(values.shape[axis], dtype=jnp.int32).reshape(
        [-1 if d == axis else 1 for d in range(values.ndim)])
    hit = lanes == jnp.expand_dims(index.astype(jnp.int32), axis)
    if values.dtype == jnp.bool_:
        return jnp.any(hit & values, axis=axis)
    return jnp.sum(jnp.where(hit, values, 0), axis=axis, dtype=values.dtype)


def bucket_tags(table: jnp.ndarray, bucket: jnp.ndarray, layout: BucketLayout) -> jnp.ndarray:
    """Gather and unpack a bucket: -> uint32[..., bucket_size] tags."""
    return unpack_words(gather_bucket_words(table, bucket, layout), layout.fp_bits)


def scan_start(tag: jnp.ndarray, layout: BucketLayout) -> jnp.ndarray:
    """Pseudo-random slot scan start: ``tag mod bucketSize`` (paper Alg. 1 l.26)."""
    return (tag.astype(jnp.uint32) % _U32(layout.bucket_size)).astype(jnp.int32)


def first_true_circular(flags: jnp.ndarray, start: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """First True position scanning circularly from ``start``.

    flags: bool[..., b]; start: int32[...] in [0, b).
    Returns (found: bool[...], slot: int32[...] absolute index).
    """
    b = flags.shape[-1]
    # Circular distance of every lane from ``start``; the first True lane is
    # the one at the least distance. A min-reduction, not a rotating gather:
    # the TPU compiler handles a gather along the minor axis slowly.
    dist = (jnp.arange(b, dtype=jnp.int32) - start[..., None]) % b
    first_rel = jnp.min(jnp.where(flags, dist, b), axis=-1)
    found = first_rel < b
    slot = (start + jnp.where(found, first_rel, 0)) % b
    return found, slot


# ---------------------------------------------------------------------------
# Segmented-scan helpers for the bulk-build insertion path (DESIGN.md §6).
#
# A bulk placement round sorts the batch by destination bucket, unpacks only
# the rows of the buckets it touches, and adds each placed tag into its
# packed word. The helpers below compute each key's rank within its bucket
# segment and the bucket's rank-th free slot — which together make
# whole-bucket commits conflict-free by construction (every key owns a
# distinct, empty lane).
# ---------------------------------------------------------------------------

def segment_ranks(sorted_ids: jnp.ndarray) -> jnp.ndarray:
    """Rank of each element within its run of equal values.

    sorted_ids: int32[n] ascending (runs = segments). Returns int32[n] with
    0, 1, 2, ... restarting at every segment boundary.
    """
    n = sorted_ids.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.int32)
    idx = jnp.arange(n, dtype=jnp.int32)
    starts = jnp.concatenate([jnp.ones((1,), bool),
                              sorted_ids[1:] != sorted_ids[:-1]])
    # Index of each run's first element, carried forward by a running max
    # (a binary search per element is a loop of gathers, slow on a TPU).
    return idx - jax.lax.cummax(jnp.where(starts, idx, 0))


def nth_free_slot(btags: jnp.ndarray, rank: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Position of the ``rank``-th empty slot in each bucket.

    btags: uint32[..., b] unpacked bucket tags; rank: int32[...] >= 0.
    Returns (placed: bool[...], slot: int32[...]). ``placed`` is False when
    the bucket has <= rank free slots (the key spills to the next phase).
    """
    free = btags == 0
    prefix = jnp.cumsum(free, axis=-1, dtype=jnp.int32)      # inclusive count
    target = rank[..., None] + 1
    hit = free & (prefix == target)
    placed = prefix[..., -1] > rank
    slot = jnp.argmax(hit, axis=-1).astype(jnp.int32)
    return placed, slot


def slot_to_word(slot: jnp.ndarray, layout: BucketLayout) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Absolute slot index in bucket -> (word index in bucket, slot within word)."""
    tpw = layout.tags_per_word
    return slot // tpw, slot % tpw


def word_addr(bucket: jnp.ndarray, word_in_bucket: jnp.ndarray, layout: BucketLayout) -> jnp.ndarray:
    """Flat word address of (bucket, word) — the claim/CAS granule."""
    return (bucket.astype(jnp.int32) * layout.words_per_bucket
            + word_in_bucket.astype(jnp.int32))

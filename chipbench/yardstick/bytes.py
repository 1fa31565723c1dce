"""Minimal bytes each cuckoo-filter operation must move, from the geometry.

Copied from the paper's accounting (bytes per operation, with the table in
device memory): a key is 8 bytes in and its answer 4 bytes out; every
operation reads both candidate buckets (``bucket_size * fp_bits / 8``
bytes each); an insert or a delete also writes the one 4-byte word that
holds its slot. Sort traffic, eviction chains past the first probe and
padding are excluded, so a share of the roofline built on these bytes is a
lower bound that reaches 100% only for a perfect kernel.
"""

from __future__ import annotations

KEY_BYTES = 8
RESULT_BYTES = 4
WORD_BYTES = 4


def bucket_bytes(bucket_size: int, fp_bits: int) -> int:
    """Bytes of one bucket: its slots' fingerprints, packed."""
    return bucket_size * fp_bits // 8


def op_bytes(op: str, bucket_size: int, fp_bits: int) -> int:
    """Minimal bytes one ``query``, ``insert`` or ``delete`` moves."""
    probe = KEY_BYTES + RESULT_BYTES + 2 * bucket_bytes(bucket_size, fp_bits)
    if op == "query":
        return probe
    if op in ("insert", "delete"):
        return probe + WORD_BYTES
    raise ValueError(f"no byte count for op {op!r}")

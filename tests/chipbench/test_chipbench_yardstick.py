"""The benchmark's yardstick on the CPU: peaks, minimal bytes, the key
generator and the plain reference, each against an independent statement
of what it should compute."""

import numpy as np
import pytest

import chipbench_harness  # noqa: F401  (puts chipbench/ on the path)
from yardstick import bytes as B
from yardstick import keys as K
from yardstick import peaks as P
from yardstick import reference as R

M64 = (1 << 64) - 1


def fmix64_py(stream, index, seed):
    x = (((stream << 32) | index) + seed * 0x9E3779B97F4A7C15) & M64
    x ^= x >> 33
    x = (x * 0xFF51AFD7ED558CCD) & M64
    x ^= x >> 33
    x = (x * 0xC4CEB9FE1A85EC53) & M64
    return x ^ (x >> 33)


def test_peaks_are_the_published_v5e_figures_and_unknown_kinds_raise():
    v5e = P.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        P.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("op,want", [("query", 76), ("insert", 80),
                                     ("delete", 80)])
def test_minimal_bytes_at_the_paper_geometry(op, want):
    assert B.op_bytes(op, bucket_size=16, fp_bits=16) == want


def test_minimal_bytes_refuse_an_unknown_op():
    with pytest.raises(ValueError):
        B.op_bytes("scan", 16, 16)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3])
def test_keys_on_device_and_host_equal_fmix64(seed):
    stream = np.array([0, 1, 3, 3, 0], np.uint32)
    index = np.array([0, 1, 2**32 - 1, 12345, 2**31], np.uint32)
    want = [fmix64_py(int(s), int(i), seed) for s, i in zip(stream, index)]
    host = K.keys_np(stream, index, seed)
    dev = K.from_pairs(np.asarray(K.device_keys(stream, index,
                                                K.seed_offset(seed))))
    assert host.tolist() == want and dev.tolist() == want
    block = K.from_pairs(np.asarray(K.bench_key_block(
        8, 3, np.uint32(100), K.seed_offset(seed))))
    assert block.tolist() == K.keys_np(3, np.arange(100, 108), seed).tolist()
    assert (K.from_pairs(K.to_pairs(host)) == host).all()


def test_xxhash64_matches_the_programs_python_oracle():
    from repro.core.hashing import xxhash64_py

    keys = K.keys_np(0, np.arange(500), 11)
    got = R.xxhash64(keys)
    assert got.tolist() == [xxhash64_py(int(k)) for k in keys]


@pytest.mark.parametrize("fp_bits", [8, 16])
def test_signatures_match_the_programs_buckets_and_tags(fp_bits):
    import jax.numpy as jnp

    from repro.core import CuckooConfig
    from repro.core.cuckoo_filter import prepare_keys

    keys = K.keys_np(2, np.arange(4000), 3)
    cfg = CuckooConfig(num_buckets=1 << 12, fp_bits=fp_bits)
    tag, i1, i2 = (np.asarray(a) for a in
                   prepare_keys(cfg, jnp.asarray(K.to_pairs(keys))))
    want = ((np.minimum(i1, i2).astype(np.uint64) << np.uint64(fp_bits))
            | tag.astype(np.uint64))
    assert (R.signatures(keys, 1 << 12, fp_bits) == want).all()


def test_reference_answers_equal_the_program_on_a_full_table():
    from repro import amq
    from repro.core import CuckooConfig

    nb = 1 << 8
    inserted = K.keys_np(0, np.arange(int(0.95 * nb * 16)), 5)
    probe = np.concatenate([inserted[:500], K.keys_np(1, np.arange(20000),
                                                      5)])
    h = amq.make("cuckoo", config=CuckooConfig(num_buckets=nb))
    assert np.asarray(h.insert(K.to_pairs(inserted), bulk=True).ok).all()
    got = np.asarray(h.query(K.to_pairs(probe)).hits)
    table = np.sort(R.signatures(inserted, nb, 16))
    want = R.contains(table, R.signatures(probe, nb, 16))
    assert want[:500].all() and want[500:].any()
    assert (got == want).all()


def test_set_lookups_and_chunked_work():
    table = np.array([2, 3, 3, 9], np.uint64)
    items = np.array([1, 3, 9, 10], np.uint64)
    assert R.contains(table, items).tolist() == [False, True, True, False]
    out = R.chunked(10, lambda a, b: np.arange(a, b, dtype=np.uint64) * 2)
    assert out.tolist() == [2 * i for i in range(10)]

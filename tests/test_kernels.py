"""Per-kernel validation: shape/dtype sweeps vs the ref.py oracles.

All kernels are integer-exact, so comparisons are strict equality
(assert_allclose with rtol=0 == assert_array_equal for ints).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CuckooConfig, CuckooFilter, keys_from_numpy
from repro.core import bits64 as b64
from repro.filters.blocked_bloom import BloomConfig
from repro.kernels import ops as K
from repro.kernels import ref as R
from repro.kernels.bloom import bloom_insert_pallas, bloom_query_pallas
from repro.kernels.cuckoo_insert import (
    cuckoo_insert_bulk_pallas,
    cuckoo_insert_pallas,
)
from repro.kernels.cuckoo_query import cuckoo_query_pallas
from repro.kernels.hash64 import hash64_pallas
from repro.kernels.kmer_pack import kmer_pack_pallas


def rand_keys(rng, n):
    return jnp.asarray(keys_from_numpy(
        rng.integers(0, 2**64, size=n, dtype=np.uint64)))


CUCKOO_SWEEP = [
    # (num_buckets, fp_bits, bucket_size, policy, hash_kind, n, block)
    (64, 16, 16, "xor", "fmix32", 512, 128),
    (128, 8, 8, "xor", "fmix32", 1024, 256),
    (32, 32, 4, "xor", "xxhash64", 256, 64),
    (100, 16, 16, "offset", "fmix32", 512, 512),
    (256, 16, 32, "xor", "xxhash64", 1024, 512),
]


@pytest.mark.parametrize("nb,f,b,pol,hk,n,blk", CUCKOO_SWEEP)
def test_cuckoo_query_kernel_sweep(nb, f, b, pol, hk, n, blk):
    rng = np.random.default_rng(nb + f)
    cfg = CuckooConfig(num_buckets=nb, fp_bits=f, bucket_size=b,
                       policy=pol, hash_kind=hk)
    filt = CuckooFilter(cfg)
    keys = rand_keys(rng, n)
    ok, _ = filt.insert(keys[: n // 2])
    got = cuckoo_query_pallas(cfg, filt.state.table, keys[:, 0], keys[:, 1],
                              block_keys=blk, interpret=True)
    want = R.cuckoo_query_ref(cfg, filt.state.table, keys[:, 0], keys[:, 1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0)
    # inserted keys must be hits — guaranteed only for failure-free batches
    # (failed inserts drop their carried victim fingerprint, paper Alg. 1)
    if np.asarray(ok).all():
        assert np.asarray(got)[: n // 2].all()


@pytest.mark.parametrize("nb,f,b,pol,hk,n,blk", CUCKOO_SWEEP)
def test_cuckoo_insert_kernel_sweep(nb, f, b, pol, hk, n, blk):
    rng = np.random.default_rng(nb * 7 + f)
    cfg = CuckooConfig(num_buckets=nb, fp_bits=f, bucket_size=b,
                       policy=pol, hash_kind=hk)
    table = cfg.layout.empty_table()
    keys = rand_keys(rng, n)
    t_got, ok_got = cuckoo_insert_pallas(cfg, table, keys[:, 0], keys[:, 1],
                                         block_keys=blk, interpret=True)
    t_want, ok_want = R.cuckoo_insert_ref(cfg, table, keys[:, 0], keys[:, 1])
    np.testing.assert_allclose(np.asarray(t_got), np.asarray(t_want), rtol=0)
    np.testing.assert_allclose(np.asarray(ok_got), np.asarray(ok_want), rtol=0)


@pytest.mark.parametrize("nb,f,b,pol,hk,n,blk", CUCKOO_SWEEP)
def test_cuckoo_insert_bulk_kernel_sweep(nb, f, b, pol, hk, n, blk):
    """Bucket-major kernel == sequential ref on the bucket-sorted stream."""
    from repro.core import prepare_keys

    rng = np.random.default_rng(nb * 13 + f)
    cfg = CuckooConfig(num_buckets=nb, fp_bits=f, bucket_size=b,
                       policy=pol, hash_kind=hk)
    table = cfg.layout.empty_table()
    keys = rand_keys(rng, n)
    _, i1, _ = prepare_keys(cfg, keys)
    ks = keys[jnp.argsort(i1.astype(jnp.int32), stable=True)]
    t_got, ok_got = cuckoo_insert_bulk_pallas(cfg, table, ks[:, 0], ks[:, 1],
                                              block_keys=blk, interpret=True)
    t_want, ok_want = R.cuckoo_insert_ref(cfg, table, ks[:, 0], ks[:, 1])
    np.testing.assert_array_equal(np.asarray(t_got), np.asarray(t_want))
    np.testing.assert_array_equal(np.asarray(ok_got), np.asarray(ok_want))


def test_cuckoo_insert_bulk_ops_wrapper():
    """ops.cuckoo_insert_bulk sorts, pads, and restores batch order."""
    cfg = CuckooConfig(num_buckets=128, fp_bits=16, bucket_size=16,
                       hash_kind="fmix32")
    rng = np.random.default_rng(2)
    keys = rand_keys(rng, 1000)  # not a block multiple
    state, ok = K.cuckoo_insert_bulk(cfg, cfg.init(), keys)
    assert ok.shape == (1000,)
    assert int(state.count) == int(np.asarray(ok).sum())
    got = K.cuckoo_query(cfg, state, keys)
    assert np.asarray(got)[np.asarray(ok)].all()


def test_cuckoo_insert_kernel_respects_valid_mask():
    cfg = CuckooConfig(num_buckets=64, fp_bits=16, bucket_size=16,
                       hash_kind="fmix32")
    table = cfg.layout.empty_table()
    rng = np.random.default_rng(0)
    keys = rand_keys(rng, 128)
    valid = jnp.asarray(([1] * 64) + ([0] * 64), jnp.uint32)
    t, ok = cuckoo_insert_pallas(cfg, table, keys[:, 0], keys[:, 1], valid,
                                 block_keys=64, interpret=True)
    assert np.asarray(ok)[:64].all() and not np.asarray(ok)[64:].any()
    # table must contain exactly the 64 valid keys' fingerprints
    t2, _ = R.cuckoo_insert_ref(cfg, table, keys[:64, 0], keys[:64, 1])
    np.testing.assert_array_equal(np.asarray(t), np.asarray(t2))


def test_cuckoo_ops_wrapper_pads_and_hybrid():
    """ops.cuckoo_insert_direct + core eviction fallback round-trip."""
    from repro.core.cuckoo_filter import insert as core_insert

    cfg = CuckooConfig(num_buckets=128, fp_bits=16, bucket_size=16,
                       hash_kind="fmix32")
    state = cfg.init()
    rng = np.random.default_rng(1)
    keys = rand_keys(rng, 1000)  # not a block multiple
    state, ok = K.cuckoo_insert_direct(cfg, state, keys)
    assert ok.shape == (1000,)
    # finish stragglers through the eviction-capable path
    rest = keys[~np.asarray(ok)]
    if rest.shape[0]:
        state, ok2, _ = core_insert(cfg, state, rest)
        assert np.asarray(ok2).all()
    got = K.cuckoo_query(cfg, state, keys)
    assert np.asarray(got).all()
    assert int(state.count) == 1000


BLOOM_SWEEP = [
    (64, 16, 8, 512, 128),
    (256, 8, 4, 1024, 256),
    (31, 16, 12, 256, 64),
]


@pytest.mark.parametrize("blocks,wpb,k,n,blk", BLOOM_SWEEP)
def test_bloom_kernels_sweep(blocks, wpb, k, n, blk):
    rng = np.random.default_rng(blocks)
    cfg = BloomConfig(num_blocks=blocks, words_per_block=wpb, k=k)
    table = cfg.init().table
    keys = rand_keys(rng, n)
    t_got = bloom_insert_pallas(cfg, table, keys[:, 0], keys[:, 1],
                                block_keys=blk, interpret=True)
    t_want = R.bloom_insert_ref(cfg, table, keys[:, 0], keys[:, 1])
    np.testing.assert_array_equal(np.asarray(t_got), np.asarray(t_want))
    q_got = bloom_query_pallas(cfg, t_got, keys[:, 0], keys[:, 1],
                               block_keys=blk, interpret=True)
    q_want = R.bloom_query_ref(cfg, t_want, keys[:, 0], keys[:, 1])
    np.testing.assert_array_equal(np.asarray(q_got), np.asarray(q_want))
    assert np.asarray(q_got).all()  # no false negatives


@pytest.mark.parametrize("n,blk,seed", [(2048, 2048, 0), (4096, 1024, 7)])
def test_hash64_kernel(n, blk, seed):
    rng = np.random.default_rng(n)
    keys = rand_keys(rng, n)
    hi_g, lo_g = hash64_pallas(keys[:, 0], keys[:, 1], seed=seed,
                               block_keys=blk, interpret=True)
    hi_w, lo_w = R.hash64_ref(keys[:, 0], keys[:, 1], seed=seed)
    np.testing.assert_array_equal(np.asarray(hi_g), np.asarray(hi_w))
    np.testing.assert_array_equal(np.asarray(lo_g), np.asarray(lo_w))


@pytest.mark.parametrize("n,k,blk", [(1024, 31, 256), (2048, 15, 512),
                                     (512, 7, 512)])
def test_kmer_pack_kernel(n, k, blk):
    rng = np.random.default_rng(k)
    bases = jnp.asarray(rng.integers(0, 4, size=n), jnp.uint32)
    hi_g, lo_g = kmer_pack_pallas(bases, k=k, block=blk,
                                  interpret=True)
    hi_w, lo_w = R.kmer_pack_ref(bases, k=k)
    m = n - k + 1
    np.testing.assert_array_equal(np.asarray(hi_g)[:m], np.asarray(hi_w)[:m])
    np.testing.assert_array_equal(np.asarray(lo_g)[:m], np.asarray(lo_w)[:m])
    # spot-check against python packing
    arr = np.asarray(bases)
    for i in [0, 5, m - 1]:
        want = 0
        for j in range(k):
            want = (want << 2) | int(arr[i + j])
        got = (int(hi_g[i]) << 32) | int(lo_g[i])
        assert got == want


def test_kmer_ops_wrapper_shapes():
    rng = np.random.default_rng(3)
    bases = jnp.asarray(rng.integers(0, 4, size=1000), jnp.uint32)
    keys = K.kmer_pack(bases, k=31, block=256)
    assert keys.shape == (1000 - 31 + 1, 2)
    assert keys.dtype == jnp.uint32

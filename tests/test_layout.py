"""SWAR primitives and packed layout vs naive unpack oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # degrade to fixed-seed example tests
    from _hypothesis_compat import given, settings
    from _hypothesis_compat import strategies as st

from _tuning import examples

from repro.core import layout as L

u32s = st.integers(min_value=0, max_value=(1 << 32) - 1)


@pytest.mark.parametrize("fp_bits", [8, 16, 32])
@settings(max_examples=examples(200), deadline=None)
@given(word=u32s)
def test_swar_zero_mask_matches_naive(word, fp_bits):
    mask = L.swar_zero_mask(jnp.uint32(word), fp_bits)
    flags = np.asarray(L.swar_mask_to_bools(mask, fp_bits))
    tags = np.asarray(L.unpack_words(jnp.asarray([word], jnp.uint32), fp_bits))
    np.testing.assert_array_equal(flags, tags == 0)


@pytest.mark.parametrize("fp_bits", [8, 16, 32])
@settings(max_examples=examples(200), deadline=None)
@given(word=u32s, tag=u32s)
def test_swar_match_mask_matches_naive(word, tag, fp_bits):
    tag &= (1 << fp_bits) - 1
    mask = L.swar_match_mask(jnp.uint32(word), jnp.uint32(tag), fp_bits)
    flags = np.asarray(L.swar_mask_to_bools(mask, fp_bits))
    tags = np.asarray(L.unpack_words(jnp.asarray([word], jnp.uint32), fp_bits))
    np.testing.assert_array_equal(flags, tags == tag)


@pytest.mark.parametrize("fp_bits", [8, 16, 32])
def test_unpack_words_matches_naive(fp_bits):
    rng = np.random.default_rng(fp_bits)
    words = rng.integers(0, 1 << 32, size=(5, 8), dtype=np.uint32)
    tpw = 32 // fp_bits
    want = np.stack([(words >> (fp_bits * k)) & ((1 << fp_bits) - 1)
                     for k in range(tpw)], axis=-1).reshape(5, 8 * tpw)
    np.testing.assert_array_equal(
        np.asarray(L.unpack_words(jnp.asarray(words), fp_bits)), want)


@pytest.mark.parametrize("fp_bits", [8, 16, 32])
@settings(max_examples=examples(100), deadline=None)
@given(word=u32s, tag=u32s, slot=st.integers(min_value=0, max_value=3))
def test_extract_replace(word, tag, slot, fp_bits):
    tpw = 32 // fp_bits
    slot = slot % tpw
    tag &= (1 << fp_bits) - 1
    w = jnp.uint32(word)
    s = jnp.int32(slot)
    new = L.replace_tag(w, s, jnp.uint32(tag), fp_bits)
    assert int(L.extract_tag(new, s, fp_bits)) == tag
    # other lanes untouched
    for other in range(tpw):
        if other != slot:
            assert int(L.extract_tag(new, jnp.int32(other), fp_bits)) == int(
                L.extract_tag(w, jnp.int32(other), fp_bits))


def test_first_true_circular():
    flags = jnp.asarray([[False, True, False, True],
                         [False, False, False, False],
                         [True, False, False, False]])
    start = jnp.asarray([2, 0, 3], jnp.int32)
    found, slot = L.first_true_circular(flags, start)
    np.testing.assert_array_equal(np.asarray(found), [True, False, True])
    assert int(slot[0]) == 3          # scan 2,3 -> 3
    assert int(slot[2]) == 0          # scan 3,0 -> 0


def test_broadcast_tag():
    assert int(L.broadcast_tag(jnp.uint32(0xAB), 8)) == 0xABABABAB
    assert int(L.broadcast_tag(jnp.uint32(0x1234), 16)) == 0x12341234
    assert int(L.broadcast_tag(jnp.uint32(0xDEADBEEF), 32)) == 0xDEADBEEF


def test_gather_bucket_words():
    lay = L.BucketLayout(num_buckets=4, bucket_size=4, fp_bits=16)
    table = jnp.arange(lay.num_words, dtype=jnp.uint32)
    words = L.gather_bucket_words(table, jnp.asarray([2, 0], jnp.uint32), lay)
    np.testing.assert_array_equal(np.asarray(words),
                                  [[4, 5], [0, 1]])


@pytest.mark.parametrize("nb,b,f", [(1 << 10, 16, 16), (1 << 10, 8, 8),
                                    (64, 4, 32), (256, 32, 16)])
def test_row_gather_equals_word_gather(nb, b, f):
    """The TPU lowering's row gather reads exactly the words of each bucket."""
    lay = L.BucketLayout(nb, b, f)
    table = jax.random.bits(jax.random.key(nb + b), (lay.num_words,),
                            jnp.uint32)
    buckets = jax.random.randint(jax.random.key(f), (37, 5), 0, nb)
    np.testing.assert_array_equal(
        np.asarray(L.gather_by_row(table, buckets, lay)),
        np.asarray(L.gather_by_word(table, buckets, lay)))


def _core_run(cfg, keys, ops):
    """Every core op that reads buckets, on one table: bulk build, the
    incremental engine, query, delete and a mixed stream."""
    from repro.core import cuckoo_filter as C

    half = keys.shape[0] // 2
    st = cfg.init()
    st, ok_bulk, _ = C.insert_bulk(cfg, st, keys[:half])
    st, ok_ins, _ = C.insert(cfg, st, keys[half:])
    hits = C.query(cfg, st, keys)
    st, ok_del = C.delete(cfg, st, keys[::3])
    st, ok_mix, _ = C.apply_ops(cfg, st, keys, ops)
    return st.table, st.count, ok_bulk, ok_ins, hits, ok_del, ok_mix


@pytest.mark.parametrize("b,f,eviction", [(16, 16, "bfs"), (8, 8, "dfs")])
def test_core_ops_agree_under_row_gather(monkeypatch, b, f, eviction):
    """The served ops give the same tables and answers when every bucket
    read takes the TPU lowering's row gather."""
    from repro.core import keys_from_numpy
    from repro.core.cuckoo_filter import CuckooConfig

    cfg = CuckooConfig(num_buckets=256, bucket_size=b, fp_bits=f,
                       hash_kind="fmix32", eviction=eviction)
    rng = np.random.default_rng(b + f)
    n = int(0.9 * cfg.num_slots) // 2 * 2
    keys = jnp.asarray(keys_from_numpy(np.unique(rng.integers(
        0, 2**64, size=2 * n, dtype=np.uint64))[:n]))
    ops = jnp.asarray(rng.integers(0, 3, size=n), jnp.int32)

    want = jax.jit(lambda k, o: _core_run(cfg, k, o))(keys, ops)
    assert bool(np.asarray(want[2]).all())  # the bulk half all landed
    monkeypatch.setattr(L, "gather_bucket_words",
                        lambda t, bk, lay: L.gather_by_row(t, bk, lay))
    got = jax.jit(lambda k, o: _core_run(cfg, k, o))(keys, ops)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

"""Property-based differentials: fused Pallas kernels vs oracles (§4).

Every fused kernel is replayed against *two* independent oracles on
hypothesis-drawn key streams: the sequential reference in
``kernels/ref.py`` (exact equality — table words and per-key outcomes)
and the core ``cuckoo_filter`` jit path where the semantics overlap
(query hits, landed inserts must be queryable). The sweep dimensions are
the ones that change the packed layout under the kernels — bucket size ×
``fp_bits`` × occupancy — plus a ≥95%-load BFS-eviction stress cell: the
filter is driven to the paper's high-load regime through the
eviction-capable core insert, and the fused query kernel must report
**zero false negatives** over everything the filter accepted.

Example counts route through ``tests/_tuning.examples`` (CI caps them via
``REPRO_MAX_EXAMPLES``); the hypothesis import degrades to the in-repo
shim in the bare container.
"""

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # pragma: no cover - exercised in the bare container
    from _hypothesis_compat import given, settings
    from _hypothesis_compat import strategies as st

from _tuning import examples

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CuckooConfig, keys_from_numpy
from repro.core import cuckoo_filter as CF
from repro.kernels import autotune
from repro.kernels import ops as K
from repro.kernels import ref as R
from repro.kernels.cuckoo_insert import cuckoo_insert_pallas
from repro.kernels.cuckoo_mixed import cuckoo_mixed_pallas
from repro.kernels.cuckoo_query import (
    cuckoo_query_fused_pallas,
    cuckoo_query_pallas,
)

NUM_BUCKETS = 64
BLOCK = 64

# bucket_size x fp_bits x target occupancy — every packed-word shape the
# SWAR paths can take (1..32 words/bucket), from near-empty to contended.
CELLS = [
    (4, 8, 0.30),
    (4, 32, 0.70),
    (8, 16, 0.50),
    (16, 8, 0.70),
    (16, 16, 0.30),
    (32, 16, 0.85),
]


def _cfg(bucket_size: int, fp_bits: int, **kw) -> CuckooConfig:
    return CuckooConfig(num_buckets=NUM_BUCKETS, fp_bits=fp_bits,
                        bucket_size=bucket_size, **kw)


def _rand_keys(rng, n: int) -> jnp.ndarray:
    return jnp.asarray(keys_from_numpy(
        rng.integers(1, 2**64, size=n, dtype=np.uint64)))


# Configs are frozen dataclasses (hashable), shapes are fixed per cell, so
# every oracle/kernel compiles exactly once per cell and the hypothesis
# examples replay through the cached executable — the suite would be
# minutes-per-test in op-by-op eager dispatch otherwise.

@functools.lru_cache(maxsize=None)
def _jit(fn, cfg):
    return jax.jit(functools.partial(fn, cfg))


@functools.lru_cache(maxsize=None)
def _jit_blk(fn, cfg):
    return jax.jit(functools.partial(fn, cfg, block_keys=BLOCK,
                                     interpret=True))


def _filled(cfg: CuckooConfig, rng, occupancy: float):
    """(state, accepted_keys): core-inserted stream at ~``occupancy``."""
    n = max(BLOCK, int(cfg.num_buckets * cfg.bucket_size * occupancy))
    keys = _rand_keys(rng, n)
    state, ok, _ = _jit(CF.insert, cfg)(cfg.init(), keys)
    return state, keys[np.asarray(ok)]


def _eq(got, want, **ctx):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                  err_msg=repr(ctx))


# ---------------------------------------------------------------------------
# Fused query: vs the unpack kernel, the ref oracle, and the core path.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bs,fb,occ", CELLS)
@settings(max_examples=examples(10), deadline=None)
@given(seed=st.integers(0, 2**32 - 1))
def test_fused_query_differential(bs, fb, occ, seed):
    cfg = _cfg(bs, fb)
    rng = np.random.default_rng(seed)
    state, _ = _filled(cfg, rng, occ)
    # Probe a mix of resident-ish and definitely-fresh keys.
    probe = _rand_keys(rng, 4 * BLOCK)
    fused = _jit_blk(cuckoo_query_fused_pallas, cfg)(
        state.table, probe[:, 0], probe[:, 1])
    _eq(fused, _jit_blk(cuckoo_query_pallas, cfg)(
            state.table, probe[:, 0], probe[:, 1]),
        cell=(bs, fb, occ), seed=seed, vs="prepr kernel")
    _eq(fused, _jit(R.cuckoo_query_ref, cfg)(
            state.table, probe[:, 0], probe[:, 1]),
        cell=(bs, fb, occ), seed=seed, vs="ref oracle")
    _eq(fused.astype(bool), _jit(CF.query, cfg)(state, probe),
        cell=(bs, fb, occ), seed=seed, vs="core jit path")


@pytest.mark.parametrize("bs,fb,occ", CELLS[:3])
@settings(max_examples=examples(6), deadline=None)
@given(seed=st.integers(0, 2**32 - 1))
def test_ops_wrapper_query_matches_core(bs, fb, occ, seed):
    """The public wrapper (autotune-resolved blocks, padding) == core."""
    cfg = _cfg(bs, fb)
    rng = np.random.default_rng(seed)
    state, _ = _filled(cfg, rng, occ)
    # A deliberately non-multiple length exercises the padding path.
    probe = _rand_keys(rng, 3 * BLOCK + 17)
    want = _jit(CF.query, cfg)(state, probe)
    for fused in (True, False):
        _eq(K.cuckoo_query(cfg, state, probe, fused=fused), want,
            cell=(bs, fb, occ), seed=seed, fused=fused)


# ---------------------------------------------------------------------------
# Direct insert: kernel vs sequential ref, then queryable through fusion.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bs,fb,occ", CELLS)
@settings(max_examples=examples(8), deadline=None)
@given(seed=st.integers(0, 2**32 - 1))
def test_insert_differential(bs, fb, occ, seed):
    cfg = _cfg(bs, fb)
    rng = np.random.default_rng(seed)
    n = max(BLOCK, (int(cfg.num_buckets * cfg.bucket_size * occ)
                    // BLOCK) * BLOCK)
    keys = _rand_keys(rng, n)
    table = cfg.layout.empty_table()
    t_got, ok_got = _jit_blk(cuckoo_insert_pallas, cfg)(
        table, keys[:, 0], keys[:, 1])
    t_want, ok_want = _jit(R.cuckoo_insert_ref, cfg)(
        table, keys[:, 0], keys[:, 1])
    _eq(t_got, t_want, cell=(bs, fb, occ), seed=seed, what="table")
    _eq(ok_got, ok_want, cell=(bs, fb, occ), seed=seed, what="ok")
    # Everything the kernel accepted must be a fused-query hit.
    hit = _jit_blk(cuckoo_query_fused_pallas, cfg)(
        t_got, keys[:, 0], keys[:, 1])
    landed = np.asarray(ok_got).astype(bool)
    assert np.asarray(hit).astype(bool)[landed].all(), (bs, fb, occ, seed)


# ---------------------------------------------------------------------------
# Mixed op stream: fused kernel vs the sequential ref oracle.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bs,fb,occ", CELLS)
@settings(max_examples=examples(8), deadline=None)
@given(seed=st.integers(0, 2**32 - 1))
def test_mixed_stream_differential(bs, fb, occ, seed):
    cfg = _cfg(bs, fb)
    rng = np.random.default_rng(seed)
    state, _ = _filled(cfg, rng, occ)
    n = 2 * BLOCK
    # Draw from a small universe so deletes/queries collide with inserts
    # inside one stream (the order-sensitive cases).
    uni = _rand_keys(rng, 24)
    picks = rng.integers(0, uni.shape[0], size=n)
    keys = uni[picks]
    ops = jnp.asarray(rng.integers(0, 3, size=n, dtype=np.int32))
    valid = jnp.asarray((rng.random(n) < 0.9).astype(np.uint32))
    t_got, ok_got = _jit_blk(cuckoo_mixed_pallas, cfg)(
        state.table, keys[:, 0], keys[:, 1], ops, valid)
    t_want, ok_want = _jit(R.cuckoo_mixed_ref, cfg)(
        state.table, keys[:, 0], keys[:, 1], ops, valid)
    _eq(t_got, t_want, cell=(bs, fb, occ), seed=seed, what="table")
    _eq(ok_got, ok_want, cell=(bs, fb, occ), seed=seed, what="ok")


# ---------------------------------------------------------------------------
# ≥95%-occupancy BFS-eviction stress: zero false negatives through fusion.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fb", [8, 16])
@settings(max_examples=examples(5), deadline=None)
@given(seed=st.integers(0, 2**32 - 1))
def test_bfs_high_load_zero_false_negatives(fb, seed):
    """Fill to >=95% via BFS eviction; every resident key must hit.

    The eviction cascade relocates fingerprints far from their insert-time
    slots — exactly the table state where a query kernel bug (wrong
    alternate bucket, SWAR lane mixup at packed widths) shows up as a
    false negative, which a cuckoo filter must never produce.
    """
    cfg = _cfg(16, fb, eviction="bfs", max_evictions=256)
    rng = np.random.default_rng(seed)
    slots = cfg.num_buckets * cfg.bucket_size
    # 0.97 of capacity: bucket-size-16 BFS absorbs this failure-free, and
    # failure-free is what makes zero-FN a theorem — every failed insert
    # drops exactly the victim fingerprint it was carrying (Alg. 1), so
    # the general sound bound is misses <= fails.
    keys = _rand_keys(rng, int(slots * 0.97))
    state, ok, _ = _jit(CF.insert, cfg)(cfg.init(), keys)
    accepted = np.asarray(ok)
    fails = int((~accepted).sum())
    load = accepted.sum() / slots
    assert load >= 0.95, f"stress cell under-filled: load={load:.3f}"

    pad = (-keys.shape[0]) % BLOCK
    probe = jnp.pad(keys, ((0, pad), (0, 0)))
    hit = np.asarray(_jit_blk(cuckoo_query_fused_pallas, cfg)(
        state.table, probe[:, 0], probe[:, 1]))[: keys.shape[0]].astype(bool)
    misses = accepted & ~hit
    assert misses.sum() <= fails, (
        f"{misses.sum()} false negatives vs {fails} failed inserts "
        f"at load {load:.3f} (seed {seed})")
    assert fails == 0 and not misses.any(), (
        f"fill not failure-free (fails={fails}) at load {load:.3f}")
    # The core path agrees lane-for-lane on the same stressed table.
    _eq(hit, _jit(CF.query, cfg)(state, keys), fb=fb, seed=seed)


# ---------------------------------------------------------------------------
# Autotune plumbing: resolved blocks never change results.
# ---------------------------------------------------------------------------

def test_block_resolution_is_semantics_free():
    cfg = _cfg(8, 16)
    rng = np.random.default_rng(7)
    state, _ = _filled(cfg, rng, 0.5)
    probe = _rand_keys(rng, 1000)   # not a multiple of any candidate
    want = np.asarray(_jit(CF.query, cfg)(state, probe))
    try:
        for bk in (64, 256, 1024):
            autotune.record(cfg, "query", bk)
            got = np.asarray(K.cuckoo_query(cfg, state, probe))
            np.testing.assert_array_equal(got, want, err_msg=f"bk={bk}")
    finally:
        autotune.clear()

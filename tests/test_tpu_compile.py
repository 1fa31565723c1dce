"""What the TPU v5e compiler accepts, checked without a chip.

Every case compiles ahead of time for one chip of a described ``v5e:2x2``
topology (the TPU compiler is installed; no device is attached):

* the served path — the ``FilterHandle`` jits of bulk insert, insert, query,
  delete and ``apply_ops``, with the handle's own state donation — at a
  512 MiB table, asserting each program fits one chip's HBM and that its
  temporaries stay within one table copy plus the batch. Before the bulk
  build stopped unpacking the whole table, bulk insert and ``apply_ops``
  were refused here with ``RESOURCE_EXHAUSTED`` (a 64 GiB temporary);
* ``hash64_pallas``, the one filter kernel the compiler accepts;
* every other filter kernel, each pinned to the error the compiler raises
  today. A change that makes one compile must flip its case on purpose.

The topology is described inside a module fixture (never at import), and
JAX's persistent compilation cache is off around these compiles.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import amq
from repro.amq.protocol import OpBatch
from repro.core import CuckooConfig
from repro.filters.blocked_bloom import BloomConfig
from repro.kernels.bloom import bloom_insert_pallas, bloom_query_pallas
from repro.kernels.cuckoo_insert import (
    cuckoo_insert_bulk_pallas,
    cuckoo_insert_fused_pallas,
    cuckoo_insert_pallas,
)
from repro.kernels.cuckoo_mixed import cuckoo_mixed_pallas
from repro.kernels.cuckoo_query import (
    cuckoo_query_fused_pallas,
    cuckoo_query_pallas,
)
from repro.kernels.hash64 import hash64_pallas
from repro.kernels.kmer_pack import kmer_pack_pallas
from repro.launch.hlo_analysis import peaks

TABLE_BUCKETS = 1 << 24      # x 16 slots x 16 bits = 512 MiB
BATCH = 1 << 10
BATCH_ALLOWANCE = 64 << 20   # temporaries allowed on top of one table copy
KERNEL_KEYS = 1 << 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def hbm_bytes(topo):
    return peaks(topo.devices[0].device_kind)["hbm_bytes"]


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.fixture(scope="module")
def handle(one_chip):
    """A cuckoo handle whose state is shapes on the described chip."""
    cfg = CuckooConfig(num_buckets=TABLE_BUCKETS, fp_bits=16, bucket_size=16,
                       policy="xor", hash_kind="xxhash64")
    state = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                         jax.eval_shape(cfg.init))
    return amq.make("cuckoo", config=cfg, state=state)


def _lower_served(h, op, keys, valid, ops):
    if op == "apply_ops":
        batch = OpBatch(keys, ops, valid)
        return h._fn(op).lower(h.state, batch.keys, batch.ops,
                               valid=batch.valid)
    static = ({"dedup_within_batch": False}
              if op in ("insert", "insert_bulk") else {})
    return h._fn(op, **static).lower(h.state, keys, valid=valid)


@pytest.mark.parametrize("op", ["insert_bulk", "insert", "query", "delete",
                                "apply_ops"])
def test_served_program_fits_one_v5e_chip(op, handle, one_chip, hbm_bytes):
    keys = _spec(one_chip, (BATCH, 2), jnp.uint32)
    valid = _spec(one_chip, (BATCH,), jnp.bool_)
    ops = _spec(one_chip, (BATCH,), jnp.int32)
    mem = _lower_served(handle, op, keys, valid, ops).compile() \
        .memory_analysis()
    table = handle.table_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= hbm_bytes, (op, total)
    assert mem.temp_size_in_bytes <= table + BATCH_ALLOWANCE, (
        op, mem.temp_size_in_bytes)
    if op != "query":  # donated state: the table is updated in place
        assert mem.alias_size_in_bytes >= table, (op, mem.alias_size_in_bytes)


def test_hash64_kernel_compiles(one_chip):
    keys = _spec(one_chip, (KERNEL_KEYS,), jnp.uint32)
    fn = functools.partial(hash64_pallas, seed=0, block_keys=2048,
                           interpret=False)
    text = jax.jit(fn).lower(keys, keys).compile().as_text()
    assert "tpu_custom_call" in text


_CUCKOO = CuckooConfig(num_buckets=1 << 16, fp_bits=16, bucket_size=16,
                       hash_kind="xxhash64")
_BLOOM = BloomConfig(num_blocks=1 << 14, words_per_block=16, k=8)
_GATHER = "Only 2D gather is supported"
_DYN_SLICE = "Unimplemented primitive in Pallas TPU lowering.*dynamic_slice"
_ANY_LOAD = "Loads are only allowed on VMEM and SMEM references"

# name -> (kernel, config (None: no table), uint32[n] arguments after the
#          table, whether an int32 op-code argument comes third, the error
#          the v5e compiler raises today)
REFUSED = {
    "cuckoo_query": (cuckoo_query_pallas, _CUCKOO, 2, False, _GATHER),
    "cuckoo_query_fused": (cuckoo_query_fused_pallas, _CUCKOO, 2, False,
                           _GATHER),
    "cuckoo_insert": (cuckoo_insert_pallas, _CUCKOO, 3, False, _DYN_SLICE),
    "cuckoo_insert_fused": (cuckoo_insert_fused_pallas, _CUCKOO, 3, False,
                            _DYN_SLICE),
    "cuckoo_insert_bulk": (cuckoo_insert_bulk_pallas, _CUCKOO, 3, False,
                           _DYN_SLICE),
    "cuckoo_mixed": (cuckoo_mixed_pallas, _CUCKOO, 2, True, _DYN_SLICE),
    "bloom_query": (bloom_query_pallas, _BLOOM, 2, False, _GATHER),
    "bloom_insert": (bloom_insert_pallas, _BLOOM, 3, False, _DYN_SLICE),
    "kmer_pack": (kmer_pack_pallas, None, 1, False, _ANY_LOAD),
}


def _table_words(cfg) -> int:
    if isinstance(cfg, BloomConfig):
        return cfg.num_blocks * cfg.words_per_block
    return cfg.layout.num_words


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_kernel_refused_by_v5e_compiler(name, one_chip):
    kernel, cfg, n_keyargs, with_ops, error = REFUSED[name]
    keyarg = _spec(one_chip, (KERNEL_KEYS,), jnp.uint32)
    args = [keyarg] * n_keyargs
    if with_ops:  # (lo, hi, ops, valid)
        args = [keyarg, keyarg, _spec(one_chip, (KERNEL_KEYS,), jnp.int32),
                keyarg]
    if cfg is None:
        fn = functools.partial(kernel, interpret=False)
    else:
        fn = functools.partial(kernel, cfg, interpret=False)
        args = [_spec(one_chip, (_table_words(cfg),), jnp.uint32)] + args
    with pytest.raises(Exception, match=error):
        jax.jit(fn).lower(*args).compile()

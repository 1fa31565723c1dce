"""Quickstart: the unified AMQ API in 60 lines.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro import amq
from repro.compile_cache import disable_tpu_logs, enable_compile_cache
from repro.core import CuckooConfig, keys_from_numpy

disable_tpu_logs()
enable_compile_cache()

# 1. One registry, every filter family. Pick a backend by name and size it
#    by capacity — paper defaults (16-bit fingerprints, 16-slot buckets,
#    XOR placement, BFS eviction) apply for "cuckoo".
filt = amq.make("cuckoo", capacity=100_000, load_factor=0.95)
print(f"{filt.name}: {filt.table_bytes / 1024:.0f} KiB, expected FPR at "
      f"95% load: {filt.expected_fpr(0.95):.5f}, caps={filt.capabilities}")

# 2. Insert a batch of 64-bit keys (uint32[n, 2] little-endian pairs).
#    bulk=True takes the bucket-sorted bulk-build fast path (DESIGN.md §6).
rng = np.random.default_rng(0)
raw = rng.integers(0, 2**63, size=95_000, dtype=np.uint64)
keys = jnp.asarray(keys_from_numpy(raw))
report = filt.insert(keys, bulk=True)
print(f"inserted {int(report.ok.sum())}/{len(raw)} "
      f"(load {filt.load_factor:.2%}, {int(report.rounds)} rounds, "
      f"max eviction chain {int(np.max(np.asarray(report.evictions)))})")

# 3. Query: no false negatives, bounded false positives.
assert bool(filt.query(keys).hits.all())
neg = jnp.asarray(keys_from_numpy(
    rng.integers(2**63, 2**64, size=50_000, dtype=np.uint64)))
print(f"empirical FPR: {float(filt.query(neg).hits.mean()):.5f}")

# 4. Delete — the paper's headline capability vs Bloom filters, and a
#    capability flag here: handles raise on unsupported ops instead of
#    silently corrupting (try backend='bloom').
filt.delete(keys[:10_000])
print(f"after deleting 10k: count={filt.count()}")

# 5. Same program, any backend: iterate the registry and branch on
#    capabilities, never on names.
demo = jnp.asarray(keys_from_numpy(
    rng.integers(0, 2**63, size=4_096, dtype=np.uint64)))
for name in amq.names():
    h = amq.make(name, capacity=8_192)
    caps = h.capabilities
    h.insert(demo)
    hits = float(np.asarray(h.query(demo).hits).mean())
    deleted = bool(caps.supports_delete) and bool(h.delete(demo).ok.any())
    print(f"  {name:15s} hits={hits:.3f} delete={'yes' if deleted else 'no'} "
          f"exact={caps.exact} bulk={caps.supports_bulk}")

# 6. Auto-expansion: streaming workloads need no a-priori sizing. Start at
#    1e5 and stream 1e6 keys — the handle grows as a geometric cascade of
#    levels (DESIGN.md §8): inserts land in the newest level, queries fan
#    over all of them in one fused pass, and the FPR budget is split across
#    levels so the aggregate stays bounded however far it grows.
stream = amq.make("cuckoo", capacity=100_000, auto_expand=True)
total = 1_000_000
chunk = 1 << 17
streamed = jnp.asarray(keys_from_numpy(
    rng.integers(0, 2**63, size=total, dtype=np.uint64)))
for start in range(0, total, chunk):
    stream.insert(streamed[start:start + chunk], bulk=True)
print(f"streamed {total} keys into an initial-1e5 cascade: "
      f"{len(stream.levels)} levels, aggregate load "
      f"{stream.load_factor:.2%}, fpr budget {stream.fpr_budget:.1e}")
assert bool(stream.query(streamed[:chunk]).hits.all())  # no false negatives

# 7. The classic config surface still exists (and sizes tables exactly with
#    the OFFSET policy — no power-of-two over-provisioning, paper §4.6.2);
#    pre-built configs drop straight into the registry.
flex = CuckooConfig.for_capacity(100_000, load_factor=0.95, policy="offset")
print(f"offset policy: {flex.table_bytes / 1024:.0f} KiB vs XOR "
      f"{filt.table_bytes / 1024:.0f} KiB")
exact = amq.make("cuckoo", config=flex)
print(f"handle from config: {exact.name}, {exact.table_bytes / 1024:.0f} KiB")

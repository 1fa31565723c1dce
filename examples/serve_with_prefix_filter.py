"""Serving example: batched generation with AMQ-guarded prefix caching.

Half the requests repeat earlier prompts; the cuckoo filter in front of the
prefix cache answers "never cached" in O(1) for fresh prompts (skipping the
probe) and stays in sync under LRU eviction via deletions.

    PYTHONPATH=src python examples/serve_with_prefix_filter.py
"""

import time

import jax
import numpy as np

from repro.compile_cache import disable_tpu_logs, enable_compile_cache
from repro.configs import get_config
from repro.models import build_model
from repro.serve import ServeEngine

disable_tpu_logs()
enable_compile_cache()

cfg = get_config("gemma2_2b").reduced()
model = build_model(cfg)
params = model.init(jax.random.key(0))

BATCH, PROMPT, STEPS = 2, 24, 8
engine = ServeEngine(model, params, batch=BATCH, max_len=PROMPT + STEPS,
                     prefix_cache_entries=4,
                     # serving SLO knobs flow to the guard-filter service
                     # (DESIGN.md §11): 1ms deadline, bounded queue.
                     prefix_cache_service_kw={"max_delay": 0.001,
                                              "max_pending": 32})

rng = np.random.default_rng(0)
pool = [rng.integers(0, cfg.vocab_size, (BATCH, PROMPT)).astype(np.int32)
        for _ in range(6)]

# fill the 4-entry cache, re-serve two prompts (hits), then push three fresh
# prompts (LRU evictions + filter deletions), then repeat an evicted one.
sequence = [0, 1, 2, 3, 1, 2, 4, 5, 0, 1]
t0 = time.perf_counter()
for i in sequence:
    tokens, stats = engine.generate(pool[i], steps=STEPS)
dt = time.perf_counter() - t0
print(f"{len(sequence)} requests in {dt:.1f}s")
slo = stats.pop("filter_service")
print("prefix cache stats:", stats)
print(f"guard-filter SLO: p99 enqueue-to-ready "
      f"{slo['ready']['p99_s'] * 1e6:.0f}us over {slo['ready']['count']} "
      f"ops, dispatch causes {slo['dispatch_kinds']}")
assert stats["hits"] > 0, "repeat prompts must hit the prefix cache"
assert stats["filtered"] > 0, "fresh prompts must be filtered (neg lookup)"
if stats["evictions"]:
    print(f"LRU evicted {stats['evictions']} entries — filter deletions "
          "kept the AMQ in sync (a Bloom filter would rot here)")

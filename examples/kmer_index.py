"""Genomic k-mer indexing case study (paper §5.5) end to end.

Synthetic genome -> 2-bit pack -> canonical 31-mers (Pallas kernel) ->
cuckoo filter membership, with deletion demonstrating contamination removal
(the dynamic-AMQ workflow of NGSReadsTreatment / Cleanifier).

    PYTHONPATH=src python examples/kmer_index.py
"""

import numpy as np

from repro.compile_cache import disable_tpu_logs, enable_compile_cache
from repro.core import CuckooConfig, CuckooFilter
from repro.data.kmer import kmer_keys, synthetic_genome

disable_tpu_logs()
enable_compile_cache()

K = 31
N_BASES = 200_000

print(f"generating {N_BASES} bases of synthetic genome...")
genome = synthetic_genome(N_BASES, seed=42)
keys = kmer_keys(genome, k=K, canonical=True)
print(f"extracted {keys.shape[0]} canonical {K}-mers")

cfg = CuckooConfig.for_capacity(keys.shape[0], load_factor=0.9)
index = CuckooFilter(cfg)
ok, _ = index.insert(keys)
print(f"indexed {int(ok.sum())} k-mers "
      f"({cfg.table_bytes / 2**20:.1f} MiB filter, "
      f"load {index.load_factor:.2%})")

# membership of reads from the same genome: every k-mer must hit
read = genome[1000:1200]
read_keys = kmer_keys(read, k=K, canonical=True)
hits = index.query(read_keys)
print(f"read lookup: {int(hits.sum())}/{read_keys.shape[0]} k-mers found "
      "(expect all)")
assert bool(hits.all())

# contamination: foreign sequence k-mers should mostly miss
foreign = synthetic_genome(5_000, seed=777)
fk = kmer_keys(foreign, k=K, canonical=True)
fpr = float(index.query(fk).mean())
print(f"foreign-genome hit rate: {fpr:.5f} (~filter FPR)")

# deletion: remove a contaminating region from the index (Bloom can't!)
region = genome[50_000:60_000]
rk = kmer_keys(region, k=K, canonical=True)
removed = index.delete(rk)
print(f"removed {int(removed.sum())} k-mers of a contaminating region; "
      f"count={int(index.state.count)}")
post = index.query(rk)
print(f"region k-mers still positive after removal: "
      f"{float(post.mean()):.4f} (residual = shared k-mers elsewhere in "
      "the genome + FPR)")

"""Pallas TPU kernels for the filter hot paths.

Layout per kernel: ``<name>.py`` holds the ``pl.pallas_call`` + BlockSpec
tiling, ``ops.py`` the jit'd public wrappers, ``ref.py`` the pure-jnp
oracles. The kernels target VMEM-resident tables (the paper's L2-resident
regime analogue). Interpret-mode tests on the CPU check their arithmetic
only: the v5e compiler refuses every cuckoo, Bloom and k-mer kernel here and
accepts ``hash64`` (tests/test_tpu_compile.py). The served path calls none.
"""

from . import ops, ref  # noqa: F401
from .flash_attention import flash_attention_pallas  # noqa: F401
from .ops import (  # noqa: F401
    bloom_insert,
    bloom_query,
    cuckoo_insert_bulk,
    cuckoo_insert_direct,
    cuckoo_query,
    hash64,
    kmer_pack,
)

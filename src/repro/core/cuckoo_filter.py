"""Batch-parallel Cuckoo filter — the paper's core contribution in JAX.

Faithful mapping of Cuckoo-GPU's Algorithms 1–3 to the TPU execution model
(see DESIGN.md §2 for the full adaptation table):

* The GPU runs one CUDA thread per key and synchronises with word-granular
  atomic CAS. Here one *batch* of keys advances in lock-step rounds inside a
  ``lax.while_loop``; within a round every key proposes a write to a 32-bit
  table word, and conflicts are resolved **per word** by a deterministic
  priority rule (lowest batch index wins — the batch-synchronous analogue of
  a CAS winner). Losers re-scan and retry next round, exactly like the
  paper's reload-on-CAS-failure loops.
* Eviction follows Alg. 1 phase 2: a stuck key picks a pseudo-random victim,
  swaps in, and carries the displaced tag to that tag's alternate bucket.
  With ``eviction="bfs"`` the §4.6.1 heuristic is used instead: inspect up to
  b/2 victims, relocate one whose alternate bucket has a free slot (a
  two-word transaction committed only if both word claims are won).
* Queries are read-only gathers + SWAR-style matching, trivially parallel.

Every operation is a pure function of ``(config, state, keys)`` and is
jit-compatible with ``config`` static; state is a small pytree so filters can
live inside larger jitted programs (data pipelines, serving engines) and be
checkpointed like any other state.

Progress guarantee: claims are resolved by (address, batch-index) priority,
so the lowest-indexed pending key always wins every word it touches; each
round therefore commits at least one action and the round loop terminates.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import layout as L
from .hashing import fmix32, hash_key, normalize_keys
from .policies import make_policy

_U32 = np.uint32
_GOLDEN = _U32(0x9E3779B9)


class CuckooState(NamedTuple):
    """Filter state — a pytree of device arrays."""

    table: jnp.ndarray   # uint32[num_words] packed fingerprints
    count: jnp.ndarray   # int32[] stored-fingerprint count


class InsertStats(NamedTuple):
    """Per-key insertion statistics (feeds the Fig. 5/6 benchmarks).

    ``failed``/``load`` are the loud failure report: callers that drop the
    ``ok`` mask still get an explicit count of keys the engine could not
    place (table effectively full — grow or rebuild) plus the post-batch
    load factor that explains *why*. :meth:`CuckooFilter.insert` turns a
    non-zero ``failed`` into a ``RuntimeWarning``.
    """

    evictions: jnp.ndarray  # int32[n] eviction-chain length per key
    rounds: jnp.ndarray     # int32[]  rounds the batch loop ran
    failed: jnp.ndarray     # int32[]  valid keys left unplaced (failures)
    load: jnp.ndarray       # float32[] post-batch load factor


@dataclasses.dataclass(frozen=True)
class CuckooConfig:
    """Static filter configuration (hashable; safe as a jit static arg).

    Defaults follow the paper's GPU configuration: 16-bit fingerprints,
    bucket size 16, XOR placement, xxHash64, BFS eviction.
    """

    num_buckets: int
    fp_bits: int = 16
    bucket_size: int = 16
    policy: str = "xor"          # "xor" | "offset"   (§4.6.2)
    hash_kind: str = "xxhash64"  # "xxhash64" | "fmix32"
    eviction: str = "bfs"        # "bfs" | "dfs"      (§4.6.1)
    max_evictions: int = 64
    max_rounds: Optional[int] = None
    seed: int = 0
    # High-load insertion engine (DESIGN.md §14):
    #   "auto"        — insert_bulk takes the graph-orientation bulk build;
    #                   incremental insert takes the batched BFS frontier
    #                   when eviction == "bfs", else the legacy round loop.
    #   "legacy"      — the original lock-step eviction round loop.
    #   "frontier"    — fixed-depth batched BFS frontier search.
    #   "orientation" — graph-orientation bulk build (+ round-loop residue).
    insert_engine: str = "auto"
    frontier_depth: int = 2      # chain hops per frontier commit (>= 1)
    # Max edge-flip sweeps before committing. Small on purpose: the
    # two-phase commit gives every edge a second chance on its opposite
    # bucket and the residue loop can truly evict, so a handful of sweeps
    # already reaches zero failures at 0.95 load — extra sweeps only
    # oscillate on contended buckets and cost wall-clock.
    orient_sweeps: int = 4

    @property
    def layout(self) -> L.BucketLayout:
        return L.BucketLayout(self.num_buckets, self.bucket_size, self.fp_bits)

    @property
    def placement(self):
        return make_policy(self.policy, self.num_buckets, self.fp_bits)

    @property
    def num_slots(self) -> int:
        return self.layout.num_slots

    @property
    def table_bytes(self) -> int:
        return self.layout.table_bytes

    @property
    def effective_fp_bits(self) -> int:
        return self.placement.effective_fp_bits

    def expected_fpr(self, load_factor: float) -> float:
        """Paper Eq. (4): eps ~= 1 - (1 - 2^-f)^(2 b alpha)."""
        f = self.effective_fp_bits
        return 1.0 - (1.0 - 2.0 ** -f) ** (2 * self.bucket_size * load_factor)

    def init(self) -> CuckooState:
        return CuckooState(self.layout.empty_table(), jnp.zeros((), jnp.int32))

    @staticmethod
    def for_capacity(
        capacity: int,
        load_factor: float = 0.95,
        fp_bits: int = 16,
        bucket_size: int = 16,
        policy: str = "xor",
        **kw,
    ) -> "CuckooConfig":
        """Size a filter for ``capacity`` items at a target load factor.

        With the XOR policy the bucket count is rounded up to a power of two
        (paper's over-provisioning problem); the OFFSET policy sizes exactly
        (§4.6.2's motivation).
        """
        buckets = max(2, int(np.ceil(capacity / (load_factor * bucket_size))))
        if policy == "xor":
            buckets = 1 << int(np.ceil(np.log2(buckets)))
        return CuckooConfig(
            num_buckets=buckets, fp_bits=fp_bits, bucket_size=bucket_size,
            policy=policy, **kw)


# ---------------------------------------------------------------------------
# Key preparation (Alg. 1 lines 2-5).
# ---------------------------------------------------------------------------

@jax.named_scope("hash")
def prepare_keys(config: CuckooConfig, keys: jnp.ndarray):
    """keys uint32[n, 2] -> (base_tag, i1, i2), all uint32[n]."""
    hi, lo = hash_key(keys, config.hash_kind, config.seed)
    pol = config.placement
    tag = pol.make_tag(hi)           # fingerprint from the upper hash word
    i1, i2 = pol.initial_buckets(lo, tag)  # bucket index from the lower word
    return tag, i1, i2


def _prng(x: jnp.ndarray, salt: jnp.ndarray) -> jnp.ndarray:
    """Deterministic per-key pseudo-randomness (fingerprint-derived, like the
    paper's tag-based starts; salted by the round counter to break livelock)."""
    return fmix32(x ^ (salt.astype(jnp.uint32) * _GOLDEN + _U32(1)))


# ---------------------------------------------------------------------------
# Word-claim resolution: the batch-synchronous CAS.
# ---------------------------------------------------------------------------

def _resolve_claims(addr1: jnp.ndarray, addr2: jnp.ndarray, invalid: int):
    """Per-word winner election.

    addr1/addr2: int32[n] flat word addresses (``invalid`` = no claim).
    Returns (win1, win2): bool[n] — whether this key won each address.
    Winner of an address = lowest (batch index, claim slot) touching it,
    which guarantees the lowest pending key wins all of its claims.
    """
    n = addr1.shape[0]
    flat = jnp.stack([addr1, addr2], axis=1).reshape(-1)        # interleaved
    order = jnp.argsort(flat, stable=True)
    sa = flat[order]
    first = jnp.concatenate([jnp.ones((1,), bool), sa[1:] != sa[:-1]])
    win_sorted = first & (sa != invalid)
    win_flat = jnp.zeros((2 * n,), bool).at[order].set(win_sorted)
    return win_flat[0::2], win_flat[1::2]


def _resolve_claims_multi(addrs: jnp.ndarray, invalid: int) -> jnp.ndarray:
    """K-column generalisation of :func:`_resolve_claims`.

    addrs: int32[n, K] flat word addresses (``invalid`` = no claim).
    Returns win: bool[n, K]. Claims are interleaved so the flat priority of
    key ``i``'s column ``k`` is ``i * K + k`` — the lowest pending key with
    any action still wins *all* of its claims, preserving the round-loop
    progress guarantee for multi-word transactions (frontier chains).
    """
    n, k = addrs.shape
    flat = addrs.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sa = flat[order]
    first = jnp.concatenate([jnp.ones((1,), bool), sa[1:] != sa[:-1]])
    win_sorted = first & (sa != invalid)
    win = jnp.zeros((n * k,), bool).at[order].set(win_sorted)
    return win.reshape(n, k)


def _masked_write(table, addr, desired, mask, invalid):
    a = jnp.where(mask, addr, invalid)
    return table.at[a].set(desired, mode="drop")


def _batch_dedup(keys: jnp.ndarray, valid: jnp.ndarray):
    """First-occurrence mask + representative index for duplicated batches.

    Returns (first: bool[n], rep: int32[n]): ``first[i]`` marks the earliest
    occurrence of key i's 64-bit value among *valid* entries (``rep[i]`` is
    that occurrence's batch index; ``rep[i] == i`` for firsts). Valid keys
    sort ahead of invalid ones within a value run, so a padding key can never
    become the representative of a live duplicate.
    """
    n = keys.shape[0]
    lo, hi = keys[..., 0], keys[..., 1]
    inv = (~valid).astype(jnp.uint32)
    order = jnp.lexsort((inv, lo, hi))          # by (hi, lo), valid first
    lo_s, hi_s = lo[order], hi[order]
    first_s = jnp.concatenate([
        jnp.ones((1,), bool),
        (lo_s[1:] != lo_s[:-1]) | (hi_s[1:] != hi_s[:-1]),
    ])
    head_pos = jax.lax.cummax(
        jnp.where(first_s, jnp.arange(n, dtype=jnp.int32), 0))
    rep_s = order[head_pos].astype(jnp.int32)
    first = jnp.zeros((n,), bool).at[order].set(first_s)
    rep = jnp.zeros((n,), jnp.int32).at[order].set(rep_s)
    return first, rep


# ---------------------------------------------------------------------------
# Insertion (Alg. 1 + §4.6.1 BFS).
# ---------------------------------------------------------------------------

# Action codes for a round.
_DIRECT, _EVICT, _RELOC = 0, 1, 2


def _insert_rounds(
    config: CuckooConfig, state: CuckooState, keys: jnp.ndarray,
    valid: Optional[jnp.ndarray] = None,
    *, dedup_within_batch: bool = False,
) -> Tuple[CuckooState, jnp.ndarray, InsertStats]:
    """The legacy lock-step eviction round loop (Alg. 1 + §4.6.1 BFS).

    Kept reachable via ``insert_engine="legacy"`` — it is the oracle the
    new engines are differentially tested against, and the benchmark
    baseline the frontier/orientation rows are compared with.
    """
    lay = config.layout
    pol = config.placement
    n = keys.shape[0]
    invalid = lay.num_words  # out-of-range sentinel (dropped by scatter)
    b = config.bucket_size
    wpb = lay.words_per_bucket
    n_cand = max(1, b // 2)  # BFS inspects up to half the bucket (§4.6.1)
    use_bfs = config.eviction == "bfs"
    max_rounds = config.max_rounds or (4 * config.max_evictions + 64)

    base_tag, i1, i2 = prepare_keys(config, keys)
    tag1 = pol.place_tag(base_tag, jnp.zeros((n,), bool))   # stored form @ i1
    tag2 = pol.place_tag(base_tag, jnp.ones((n,), bool))    # stored form @ i2

    def gather_words(table, bucket):
        return L.gather_bucket_words(table, bucket, lay)

    def round_fn(carry):
        (table, count, cur_tag, cur_bucket, evict_mode, pending, success,
         n_evict, rnd) = carry

        # --- expire keys whose eviction budget ran out (Alg. 1 line 24).
        failed = pending & (n_evict >= config.max_evictions) & evict_mode
        pending = pending & ~failed

        # --- scan phase: fresh keys look at (i1, i2); evicting keys look at
        #     their current bucket only (Alg. 1 line 22).
        bucketA = jnp.where(evict_mode, cur_bucket, i1)
        wordsA = gather_words(table, bucketA)                  # [n, wpb]
        wordsB = gather_words(table, i2)                       # [n, wpb]
        tagsA = L.unpack_words(wordsA, lay.fp_bits)            # [n, b]
        tagsB = L.unpack_words(wordsB, lay.fp_bits)

        scan_tag = jnp.where(evict_mode, cur_tag, base_tag)
        start = L.scan_start(scan_tag, lay)
        foundA, slotA = L.first_true_circular(tagsA == 0, start)
        foundB, slotB = L.first_true_circular(tagsB == 0, start)
        foundB = foundB & ~evict_mode

        direct_found = foundA | foundB
        d_bucket = jnp.where(foundA, bucketA, i2)
        d_slot = jnp.where(foundA, slotA, slotB)
        d_tag = jnp.where(
            evict_mode, cur_tag, jnp.where(foundA, tag1, tag2))
        d_widx, d_sw = L.slot_to_word(d_slot, lay)
        d_words = jnp.where(foundA[:, None], wordsA, wordsB)
        d_word = L.pick(d_words, d_widx, 1)
        d_desired = L.replace_tag(d_word, d_sw, d_tag, lay.fp_bits)
        d_addr = L.word_addr(d_bucket, d_widx, lay)

        # --- eviction phase for keys whose candidate bucket(s) are full.
        needs_evict = pending & ~direct_found
        # Fresh keys choose a random bucket to evict from (Alg. 1 line 8).
        coin = (_prng(base_tag, rnd) & _U32(1)).astype(bool)
        e_bucket = jnp.where(evict_mode, cur_bucket,
                             jnp.where(coin, i2, i1))
        e_tag = jnp.where(evict_mode, cur_tag,
                          jnp.where(coin, tag2, tag1))
        e_words = jnp.where(
            evict_mode[:, None] | ~coin[:, None], wordsA, wordsB)
        e_tags = jnp.where(
            evict_mode[:, None] | ~coin[:, None], tagsA, tagsB)

        def eviction_actions(_):
            # DFS victim (also the BFS fallback): pseudo-random occupied slot.
            vic = (_prng(e_tag ^ e_bucket, rnd) % _U32(b)).astype(jnp.int32)

            if use_bfs:
                # §4.6.1: inspect n_cand candidates starting at a prng offset;
                # relocate the first whose alternate bucket has a free slot.
                cstart = (_prng(e_tag, rnd + 1) % _U32(b)).astype(jnp.int32)
                cslots = (cstart[:, None]
                          + jnp.arange(n_cand, dtype=jnp.int32)) % b  # [n,c]
                ctags = L.pick(e_tags[:, None, :], cslots, 2)       # [n,c]
                calt = pol.alt_bucket(e_bucket[:, None], ctags)       # [n,c]
                cwords = gather_words(table, calt)                # [n,c,wpb]
                cfree = L.unpack_words(cwords, lay.fp_bits) == 0  # [n,c,b]
                reloc_tag = pol.on_relocate(ctags)
                fstart = L.scan_start(reloc_tag, lay)
                cfound, cslot_dst = L.first_true_circular(cfree, fstart)
                has_viable = jnp.any(cfound, axis=1)
                jstar = jnp.argmax(cfound, axis=1).astype(jnp.int32)

                take = lambda a: L.pick(a, jstar, 1)
                r_src_slot = take(cslots)
                r_tag = take(ctags)
                r_reloc = take(reloc_tag)
                r_dst_bucket = take(calt)
                r_dst_slot = take(cslot_dst)
                r_dst_words = L.pick(cwords, jstar[:, None], 1)  # [n, wpb]

                dst_widx, dst_sw = L.slot_to_word(r_dst_slot, lay)
                dst_word = L.pick(r_dst_words, dst_widx, 1)
                dst_desired = L.replace_tag(dst_word, dst_sw, r_reloc,
                                            lay.fp_bits)
                dst_addr = L.word_addr(r_dst_bucket, dst_widx, lay)

                src_widx, src_sw = L.slot_to_word(r_src_slot, lay)
                src_word = L.pick(e_words, src_widx, 1)
                src_desired = L.replace_tag(src_word, src_sw, e_tag,
                                            lay.fp_bits)
                src_addr = L.word_addr(e_bucket, src_widx, lay)

                # Same-word transaction: compose both lane updates into one
                # write (the batch analogue of the paper's two-step relocation
                # with CAS-failure compensation — impossible to half-apply).
                same = src_addr == dst_addr
                merged = L.replace_tag(
                    L.replace_tag(src_word, dst_sw, r_reloc, lay.fp_bits),
                    src_sw, e_tag, lay.fp_bits)
                src_desired = jnp.where(same, merged, src_desired)
                dst_addr = jnp.where(same, invalid, dst_addr)

                # Fall back to DFS-evicting the last inspected candidate.
                vic_bfs = (cstart + (n_cand - 1)) % b
                vic = jnp.where(has_viable, vic, vic_bfs)
            else:
                has_viable = jnp.zeros((n,), bool)
                src_addr = jnp.full((n,), invalid, jnp.int32)
                src_desired = jnp.zeros((n,), jnp.uint32)
                dst_addr = jnp.full((n,), invalid, jnp.int32)
                dst_desired = jnp.zeros((n,), jnp.uint32)

            # DFS eviction action (Alg. 1 lines 10-21).
            v_widx, v_sw = L.slot_to_word(vic, lay)
            v_word = L.pick(e_words, v_widx, 1)
            v_desired = L.replace_tag(v_word, v_sw, e_tag, lay.fp_bits)
            v_evicted = L.extract_tag(v_word, v_sw, lay.fp_bits)
            v_addr = L.word_addr(e_bucket, v_widx, lay)

            return (has_viable, src_addr, src_desired, dst_addr, dst_desired,
                    v_addr, v_desired, v_evicted)

        def no_eviction(_):
            z32 = jnp.zeros((n,), jnp.uint32)
            inv = jnp.full((n,), invalid, jnp.int32)
            return (jnp.zeros((n,), bool), inv, z32, inv, z32, inv, z32, z32)

        (has_viable, r_src_addr, r_src_desired, r_dst_addr, r_dst_desired,
         v_addr, v_desired, v_evicted) = jax.lax.cond(
            jnp.any(needs_evict), eviction_actions, no_eviction, None)

        # --- assemble one action per pending key.
        is_reloc = needs_evict & has_viable
        is_evict = needs_evict & ~has_viable
        is_direct = pending & direct_found

        addr1 = jnp.where(is_direct, d_addr,
                          jnp.where(is_reloc, r_src_addr,
                                    jnp.where(is_evict, v_addr, invalid)))
        desired1 = jnp.where(is_direct, d_desired,
                             jnp.where(is_reloc, r_src_desired, v_desired))
        addr2 = jnp.where(is_reloc, r_dst_addr, invalid)
        addr1 = jnp.where(pending, addr1, invalid)
        addr2 = jnp.where(pending, addr2, invalid)

        win1, win2 = _resolve_claims(addr1, addr2, invalid)
        has2 = addr2 != invalid
        commit = pending & win1 & (win2 | ~has2) & (addr1 != invalid)

        # --- apply winning writes.
        table = _masked_write(table, addr1, desired1, commit, invalid)
        table = _masked_write(table, addr2, r_dst_desired, commit & has2,
                              invalid)

        # --- state transitions.
        done = commit & (is_direct | is_reloc)
        success = success | done
        count = count + jnp.sum(done, dtype=jnp.int32)
        pending = pending & ~done

        did_evict = commit & is_evict
        new_cur_tag = pol.on_relocate(v_evicted)
        new_cur_bucket = pol.alt_bucket(e_bucket, v_evicted)
        cur_tag = jnp.where(did_evict, new_cur_tag, cur_tag)
        cur_bucket = jnp.where(did_evict, new_cur_bucket, cur_bucket)
        evict_mode = evict_mode | did_evict
        n_evict = n_evict + did_evict.astype(jnp.int32)

        return (table, count, cur_tag, cur_bucket, evict_mode, pending,
                success, n_evict, rnd + 1)

    def cond_fn(carry):
        pending, rnd = carry[5], carry[8]
        return jnp.any(pending) & (rnd < max_rounds)

    pending0 = jnp.ones((n,), bool) if valid is None else valid.astype(bool)
    valid0 = pending0
    if dedup_within_batch:
        first, rep = _batch_dedup(keys, valid0)
        pending0 = pending0 & first
    carry0 = (
        state.table, state.count,
        base_tag.astype(jnp.uint32),              # cur_tag (evict mode)
        i1.astype(jnp.uint32),                    # cur_bucket (evict mode)
        jnp.zeros((n,), bool),                    # evict_mode
        pending0,                                 # pending
        jnp.zeros((n,), bool),                    # success
        jnp.zeros((n,), jnp.int32),               # n_evict
        jnp.zeros((), jnp.int32),                 # round
    )
    out = jax.lax.while_loop(cond_fn, round_fn, carry0)
    (table, count, _, _, _, pending, success, n_evict, rnd) = out
    # Keys still pending at max_rounds are reported as failures.
    ok = success & ~pending
    if dedup_within_batch:
        ok = jnp.where(first, ok, ok[rep] & valid0)
    failed = jnp.sum(valid0 & ~ok, dtype=jnp.int32)
    load = count.astype(jnp.float32) / lay.num_slots
    return CuckooState(table, count), ok, InsertStats(n_evict, rnd, failed,
                                                      load)


# ---------------------------------------------------------------------------
# Batched BFS frontier insertion (DESIGN.md §14).
# ---------------------------------------------------------------------------

def _insert_frontier(
    config: CuckooConfig, state: CuckooState, keys: jnp.ndarray,
    valid: Optional[jnp.ndarray] = None,
    *, dedup_within_batch: bool = False,
) -> Tuple[CuckooState, jnp.ndarray, InsertStats]:
    """Fixed-depth, width-``bucket_size`` frontier search per round.

    Where the legacy loop advances one eviction hop per *global* round (the
    whole batch waits on the longest chain), a frontier round resolves an
    entire chain in one multi-word transaction: a stuck key picks a root
    bucket, treats each of its ``b`` occupied slots as a branch, expands
    the branch set one gather per depth level (all slots of every frontier
    bucket inspected at once), and commits the shortest free path found —
    up to ``frontier_depth + 1`` word writes, won all-or-nothing through
    the claim election. Chains therefore cost O(depth) data-parallel steps
    instead of O(chain length) rounds.

    A key whose shortest eviction chain exceeds ``frontier_depth`` can
    never commit here no matter how many salted retries it gets, so the
    round loop exits once a few consecutive rounds make no progress and
    the stragglers spill to the legacy round loop, which walks chains up
    to ``max_evictions`` — the frontier engine keeps the oracle's
    placement guarantees without paying its per-hop global rounds on the
    fast path.
    """
    lay = config.layout
    pol = config.placement
    n = keys.shape[0]
    invalid = lay.num_words
    b = config.bucket_size
    wpb = lay.words_per_bucket
    depth = max(1, config.frontier_depth)
    K = depth + 1  # claim columns: root write + one per chain hop
    max_rounds = config.max_rounds or (4 * config.max_evictions + 64)

    base_tag, i1, i2 = prepare_keys(config, keys)
    tag1 = pol.place_tag(base_tag, jnp.zeros((n,), bool))
    tag2 = pol.place_tag(base_tag, jnp.ones((n,), bool))

    def gather_words(table, bucket):
        return L.gather_bucket_words(table, bucket, lay)

    def round_fn(carry):
        table, count, pending, success, n_evict, rnd, stall = carry

        # --- direct phase: identical to the legacy scan of (i1, i2).
        words1 = gather_words(table, i1)                       # [n, wpb]
        words2 = gather_words(table, i2)
        tags_1 = L.unpack_words(words1, lay.fp_bits)           # [n, b]
        tags_2 = L.unpack_words(words2, lay.fp_bits)

        start = L.scan_start(base_tag, lay)
        found1, slot1 = L.first_true_circular(tags_1 == 0, start)
        found2, slot2 = L.first_true_circular(tags_2 == 0, start)
        direct_found = found1 | found2

        d_bucket = jnp.where(found1, i1, i2)
        d_slot = jnp.where(found1, slot1, slot2)
        d_tag = jnp.where(found1, tag1, tag2)
        d_widx, d_sw = L.slot_to_word(d_slot, lay)
        d_words = jnp.where(found1[:, None], words1, words2)
        d_word = L.pick(d_words, d_widx, 1)
        d_desired = L.replace_tag(d_word, d_sw, d_tag, lay.fp_bits)
        d_addr = L.word_addr(d_bucket, d_widx, lay)

        is_direct = pending & direct_found
        needs_chain = pending & ~direct_found

        def frontier_actions(_):
            # Both candidate buckets are full for every chaining key, so the
            # root (picked by a salted coin) is a full bucket: each of its b
            # occupied slots seeds one branch of the frontier.
            coin = (_prng(base_tag, rnd) & _U32(1)).astype(bool)
            e_bucket = jnp.where(coin, i2, i1)
            e_tag = jnp.where(coin, tag2, tag1)
            e_words = jnp.where(coin[:, None], words2, words1)
            e_tags = jnp.where(coin[:, None], tags_2, tags_1)

            branch = jnp.broadcast_to(
                jnp.arange(b, dtype=jnp.int32), (n, b))
            # Lanes the chain displaces so far — the cycle guard kills any
            # branch whose next victim revisits one (a revisit would make
            # two writes race on one lane and silently drop a resident tag).
            pos_b = [jnp.broadcast_to(
                e_bucket.astype(jnp.int32)[:, None], (n, b))]
            pos_s = [branch]
            move = pol.on_relocate(e_tags)          # tag entering level 1
            nxt = pol.alt_bucket(e_bucket[:, None], e_tags)        # [n, b]
            alive = jnp.ones((n, b), bool)
            lv_bucket, lv_words, lv_found, lv_slot, lv_move, lv_vic = (
                [], [], [], [], [], [])
            for d in range(1, depth + 1):
                wds = gather_words(table, nxt)                 # [n, b, wpb]
                tgs = L.unpack_words(wds, lay.fp_bits)         # [n, b, b]
                fnd, fslot = L.first_true_circular(
                    tgs == 0, L.scan_start(move, lay))
                fnd = fnd & alive
                lv_bucket.append(nxt)
                lv_words.append(wds)
                lv_found.append(fnd)
                lv_slot.append(fslot)
                lv_move.append(move)
                if d < depth:
                    vic = (_prng(move ^ nxt.astype(jnp.uint32), rnd + d)
                           % _U32(b)).astype(jnp.int32)        # [n, b]
                    clash = jnp.zeros((n, b), bool)
                    for pb, ps in zip(pos_b, pos_s):
                        clash = clash | ((pb == nxt.astype(jnp.int32))
                                         & (ps == vic))
                    alive = alive & ~clash
                    pos_b.append(nxt.astype(jnp.int32))
                    pos_s.append(vic)
                    lv_vic.append(vic)
                    vtag = L.pick(tgs, vic, 2)
                    move = pol.on_relocate(vtag)
                    nxt = pol.alt_bucket(nxt, vtag)

            # Shortest free path: first level with any live branch found.
            taken = jnp.zeros((n,), bool)
            use_lv = []
            for fnd in lv_found:
                fa = jnp.any(fnd, axis=1)
                use_lv.append(fa & ~taken)
                taken = taken | fa
            has_chain = needs_chain & taken
            jstar = jnp.zeros((n,), jnp.int32)
            depth_star = jnp.zeros((n,), jnp.int32)
            for d in reversed(range(depth)):
                jd = jnp.argmax(lv_found[d], axis=1).astype(jnp.int32)
                jstar = jnp.where(use_lv[d], jd, jstar)
                depth_star = jnp.where(use_lv[d], d + 1, depth_star)
            depth_star = jnp.where(has_chain, depth_star, 0)

            take1 = lambda a, j: L.pick(a, j, 1)
            take2 = lambda a, j: L.pick(a, j[:, None], 1)

            # Column 0: the root slot receives the key's own tag.
            r_widx, r_sw = L.slot_to_word(jstar, lay)
            r_word = L.pick(e_words, r_widx, 1)
            r_addr = L.word_addr(e_bucket, r_widx, lay)
            addrs = [jnp.where(has_chain, r_addr, invalid)]
            sws, wtags, cwords = [r_sw], [e_tag], [r_word]

            # Columns 1..depth: hop t shifts the displaced tag one level
            # deeper; the final hop lands it in the free slot found there.
            for t in range(1, depth + 1):
                lvl = t - 1
                bkt = take1(lv_bucket[lvl], jstar)
                wds = take2(lv_words[lvl], jstar)              # [n, wpb]
                mv = take1(lv_move[lvl], jstar)
                lane_free = take1(lv_slot[lvl], jstar)
                lane_vic = (take1(lv_vic[lvl], jstar) if t < depth
                            else jnp.zeros((n,), jnp.int32))
                lane = jnp.where(depth_star == t, lane_free, lane_vic)
                used = has_chain & (depth_star >= t)
                widx, sw = L.slot_to_word(lane, lay)
                word = L.pick(wds, widx, 1)
                addr = L.word_addr(bkt, widx, lay)
                addrs.append(jnp.where(used, addr, invalid))
                sws.append(sw)
                wtags.append(mv)
                cwords.append(word)

            A = jnp.stack(addrs, axis=1)                       # [n, K]
            # Same-word composition: every write of the chain that targets
            # this address folds into one desired word (all lanes distinct
            # by the cycle guard, so the fold order is immaterial).
            desired = []
            for k in range(K):
                w = cwords[k]
                for j in range(K):
                    hit = (A[:, j] == A[:, k]) & (A[:, j] != invalid)
                    w = jnp.where(
                        hit, L.replace_tag(w, sws[j], wtags[j], lay.fp_bits),
                        w)
                desired.append(w)
            # Only the last claim per duplicated address scatters (it holds
            # the fully-composed word); earlier duplicates drop out.
            scat = []
            for k in range(K):
                superseded = jnp.zeros((n,), bool)
                for j in range(k + 1, K):
                    superseded = superseded | (A[:, j] == A[:, k])
                scat.append(jnp.where(superseded, invalid, A[:, k]))
            return (has_chain, jnp.stack(scat, axis=1),
                    jnp.stack(desired, axis=1), depth_star)

        def no_chain(_):
            return (jnp.zeros((n,), bool),
                    jnp.full((n, K), invalid, jnp.int32),
                    jnp.zeros((n, K), jnp.uint32),
                    jnp.zeros((n,), jnp.int32))

        has_chain, c_addrs, c_desired, depth_star = jax.lax.cond(
            jnp.any(needs_chain), frontier_actions, no_chain, None)

        # --- one claim matrix for the whole batch: direct keys use column
        #     0 alone; chain keys use their (deduped) chain columns.
        addr0 = jnp.where(is_direct, d_addr, c_addrs[:, 0])
        des0 = jnp.where(is_direct, d_desired, c_desired[:, 0])
        all_addrs = jnp.concatenate([addr0[:, None], c_addrs[:, 1:]], axis=1)
        all_des = jnp.concatenate([des0[:, None], c_desired[:, 1:]], axis=1)
        all_addrs = jnp.where(pending[:, None], all_addrs, invalid)

        win = _resolve_claims_multi(all_addrs, invalid)
        valid_claim = all_addrs != invalid
        has_action = is_direct | (pending & has_chain)
        commit = has_action & jnp.all(win | ~valid_claim, axis=1)

        for k in range(K):
            table = _masked_write(table, all_addrs[:, k], all_des[:, k],
                                  commit & valid_claim[:, k], invalid)

        success = success | commit
        count = count + jnp.sum(commit, dtype=jnp.int32)
        pending = pending & ~commit
        n_evict = n_evict + jnp.where(commit, depth_star, 0)
        stall = jnp.where(jnp.any(commit), jnp.int32(0), stall + 1)
        return table, count, pending, success, n_evict, rnd + 1, stall

    # Consecutive no-commit rounds before giving up on the frontier: each
    # round re-salts the coin and the victim lanes, so a handful of
    # retries resolves transient claim contention — anything still stuck
    # after that is depth-limited and belongs to the residue loop.
    stall_limit = jnp.int32(8)

    def cond_fn(carry):
        return (jnp.any(carry[2]) & (carry[5] < max_rounds)
                & (carry[6] < stall_limit))

    pending0 = jnp.ones((n,), bool) if valid is None else valid.astype(bool)
    valid0 = pending0
    if dedup_within_batch:
        first, rep = _batch_dedup(keys, valid0)
        pending0 = pending0 & first
    carry0 = (state.table, state.count, pending0,
              jnp.zeros((n,), bool), jnp.zeros((n,), jnp.int32),
              jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    table, count, pending, success, n_evict, rnd, _ = jax.lax.while_loop(
        cond_fn, round_fn, carry0)

    # Residue: chains longer than ``depth`` (or claim-starved stragglers)
    # take the legacy eviction loop — a no-op when nothing is pending.
    state2, ok_res, res_stats = _insert_rounds(
        config, CuckooState(table, count), keys, valid=pending)

    ok = (success & ~pending) | ok_res
    if dedup_within_batch:
        ok = jnp.where(first, ok, ok[rep] & valid0)
    failed = jnp.sum(valid0 & ~ok, dtype=jnp.int32)
    load = state2.count.astype(jnp.float32) / lay.num_slots
    stats = InsertStats(n_evict + res_stats.evictions,
                        rnd + res_stats.rounds, failed, load)
    return state2, ok, stats


# ---------------------------------------------------------------------------
# Graph-orientation bulk build (DESIGN.md §14; SNIPPETS.md Snippet 1).
# ---------------------------------------------------------------------------

def _insert_orient(
    config: CuckooConfig, state: CuckooState, keys: jnp.ndarray,
    valid: Optional[jnp.ndarray] = None,
    *, dedup_within_batch: bool = False,
) -> Tuple[CuckooState, jnp.ndarray, InsertStats]:
    """Orient the batch's bucket-graph edges, then commit conflict-free.

    Each key is a directed edge ``i1 -> i2`` of the bucket graph; its
    orientation picks the bucket it will occupy. Sweeps flip edges incident
    to over-full vertices (vectorized scatter-add indegree against each
    bucket's *actual* free capacity, masked flip selection preferring edges
    whose other endpoint has headroom) until every indegree fits, then a
    single sorted pass commits every tag conflict-free — no eviction loop.
    Existing table entries never move during orientation, so keys that
    would require a true eviction (both candidate buckets already full)
    are excluded from the sweep up front and spill to the round-loop
    residue pass, which can evict. The sweep exits early at feasibility
    *or* at a fixed point (no productive flips left) — both are salt-
    independent, so contended regimes don't burn the full sweep budget.
    """
    lay = config.layout
    pol = config.placement
    n = keys.shape[0]
    nb = config.num_buckets
    sweeps = max(1, config.orient_sweeps)

    pending = jnp.ones((n,), bool) if valid is None else valid.astype(bool)
    valid0 = pending
    if dedup_within_batch:
        first, rep = _batch_dedup(keys, valid0)
        pending = pending & first

    base_tag, i1, i2 = prepare_keys(config, keys)
    i1s = i1.astype(jnp.int32)
    i2s = i2.astype(jnp.int32)
    aliased = i1s == i2s  # XOR degenerate: both endpoints coincide

    # Free capacity of each edge's two endpoints, read from the packed
    # words of just the buckets this batch touches (SWAR zero-lane count) —
    # never a per-slot view of the whole table.
    with jax.named_scope("orient"):
        free1 = _bucket_free(config, state.table, i1s)
        free2 = _bucket_free(config, state.table, i2s)

    # Edges whose candidate buckets are both already full can never be
    # placed by orientation (existing entries never move); dropping them
    # from the sweep keeps the feasibility exit reachable — they go
    # straight to the residue pass. Active edges start pointing at an
    # endpoint that actually has headroom.
    active = pending & ((free1 > 0) | (free2 > 0))
    orient0 = active & (free1 == 0) & ~aliased

    def sweep_body(carry):
        orient, _, s = carry
        dest = jnp.where(orient, i2s, i1s)
        other = jnp.where(orient, i1s, i2s)
        free_dest = jnp.where(orient, free2, free1)
        free_other = jnp.where(orient, free1, free2)
        dkey = jnp.where(active, dest, nb)
        indeg = jnp.zeros((nb + 1,), jnp.int32).at[dkey].add(1)
        done = ~jnp.any(active & (indeg[dest] > free_dest))

        # Flip priority within an over-full bucket: edges whose other
        # endpoint still has headroom net of its own inflow move first
        # (spare, bit 31), then edges whose other endpoint is at least
        # non-full (flippable, bit 30); ties break pseudo-randomly (salted
        # per sweep so repeated sweeps explore new orientations).
        flippable = free_other > 0
        spare = (free_other - indeg[other]) > 0
        r = _prng(base_tag, s) >> _U32(2)
        score = (r
                 | jnp.where(spare, _U32(0x80000000), _U32(0))
                 | jnp.where(flippable, _U32(0x40000000), _U32(0)))

        sort_key = jnp.where(active, dest, nb)
        order = jnp.lexsort((score, sort_key))
        sd = sort_key[order]
        rank = L.segment_ranks(sd)
        cap = free_dest[order]
        flip_s = (rank >= cap) & (sd < nb)
        flip = jnp.zeros((n,), bool).at[order].set(flip_s)
        # A flip into a full bucket is pointless; masking it makes "no
        # flips happened" salt-independent (flippable edges always outrank
        # non-flippable ones), i.e. a true fixed point — the second exit.
        flip = flip & ~aliased & flippable
        return orient ^ flip, done | ~jnp.any(flip), s + 1

    def sweep_cond(carry):
        return (~carry[1]) & (carry[2] < sweeps)

    with jax.named_scope("orient"):
        orient, _, _ = jax.lax.while_loop(
            sweep_cond, sweep_body,
            (orient0, jnp.zeros((), bool), jnp.zeros((), jnp.int32)))

    # Conflict-free commit of the oriented edges, then a second chance on
    # the opposite bucket for the few keys an unconverged sweep left over.
    dest = jnp.where(orient, i2s, i1s)
    stored = pol.place_tag(base_tag, orient)
    table, placed1 = _bulk_place_phase(
        config, state.table, dest, stored, pending)
    pending = pending & ~placed1
    dest2 = jnp.where(orient, i1s, i2s)
    stored2 = pol.place_tag(base_tag, ~orient)
    table, placed2 = _bulk_place_phase(
        config, table, dest2, stored2, pending)
    pending = pending & ~placed2

    placed = placed1 | placed2
    count = state.count + jnp.sum(placed, dtype=jnp.int32)

    # Residue: both candidate buckets genuinely full — these keys need a
    # real eviction, which orientation (by construction) never performs.
    # The round loop handles them regardless of the eviction policy: its
    # per-round claim pass is much cheaper at full batch width than the
    # frontier's gather tree, and the residue is a small tail.
    with jax.named_scope("residue"):
        state2, ok_res, res_stats = _insert_rounds(
            config, CuckooState(table, count), keys, valid=pending)

    ok = placed | ok_res
    if dedup_within_batch:
        ok = jnp.where(first, ok, ok[rep] & valid0)
    failed = jnp.sum(valid0 & ~ok, dtype=jnp.int32)
    load = state2.count.astype(jnp.float32) / lay.num_slots
    stats = InsertStats(res_stats.evictions, res_stats.rounds + 2, failed,
                        load)
    return state2, ok, stats


# ---------------------------------------------------------------------------
# Engine routing.
# ---------------------------------------------------------------------------

INSERT_ENGINES = ("auto", "legacy", "frontier", "orientation")


def resolve_engine(config: CuckooConfig, bulk: bool) -> str:
    """The concrete engine a (config, entry point) pair routes to."""
    eng = config.insert_engine
    if eng not in INSERT_ENGINES:
        raise ValueError(f"unknown insert_engine {eng!r} "
                         f"(want one of {INSERT_ENGINES})")
    if eng == "auto":
        if bulk:
            return "orientation"
        return "frontier" if config.eviction == "bfs" else "legacy"
    return eng


_ENGINE_FNS = {"legacy": _insert_rounds, "frontier": _insert_frontier,
               "orientation": _insert_orient}


def insert(
    config: CuckooConfig, state: CuckooState, keys: jnp.ndarray,
    valid: Optional[jnp.ndarray] = None,
    *, dedup_within_batch: bool = False,
) -> Tuple[CuckooState, jnp.ndarray, InsertStats]:
    """Insert a batch of keys. Returns (state', ok[n], stats).

    ``ok[i]`` False means the table was too full for key i (paper Alg. 1
    "Failure — caller will have to rebuild"). The same information is
    surfaced loudly in ``stats.failed`` (count of unplaced valid keys) and
    ``stats.load`` (post-batch load factor) — the round loop gives up after
    ``max_rounds`` (default ``4 * max_evictions + 64``) rounds, which near
    ~0.98 load silently turned into failures callers could ignore by
    dropping the ``ok`` mask. ``valid`` masks padding keys (used by the
    sharded filter's fixed-capacity routing).

    Engine routing (``config.insert_engine``, DESIGN.md §14): ``"auto"``
    runs the batched BFS frontier when ``eviction == "bfs"`` and the legacy
    round loop otherwise; the other values force one engine.

    Duplicate semantics: by default the filter is a *multiset* — two equal
    keys in one batch insert two copies (each needs its own ``delete``),
    exactly like two sequential single-key inserts. With
    ``dedup_within_batch=True`` (a static flag) only the first occurrence of
    each 64-bit key value is inserted; later copies report the first copy's
    ``ok`` (idempotent set semantics within the batch). See DESIGN.md §4.
    """
    fn = _ENGINE_FNS[resolve_engine(config, bulk=False)]
    return fn(config, state, keys, valid,
              dedup_within_batch=dedup_within_batch)


# ---------------------------------------------------------------------------
# Bulk-build insertion (paper §4.6.3 sorted-insertion, made the fast path;
# DESIGN.md §6).
# ---------------------------------------------------------------------------


def _bucket_free(config: CuckooConfig, table: jnp.ndarray,
                 bucket: jnp.ndarray) -> jnp.ndarray:
    """Empty slots of each given bucket: int32[n], from its packed words."""
    lay = config.layout
    words = L.gather_bucket_words(table, bucket, lay)           # [n, wpb]
    zeros = jax.lax.population_count(L.swar_zero_mask(words, lay.fp_bits))
    return jnp.sum(zeros, axis=-1, dtype=jnp.int32)


@jax.named_scope("bulk_place")
def _bulk_place_phase(config: CuckooConfig, table: jnp.ndarray,
                      bucket: jnp.ndarray, stored_tag: jnp.ndarray,
                      pend: jnp.ndarray):
    """One whole-bucket placement round, committed as packed-word writes.

    Sorts the pending keys by destination bucket, ranks each key within its
    bucket segment, and gives every key the rank-th free slot of its bucket
    — read from the unpacked rows of only the buckets the batch touches.
    Every key owns a distinct, currently empty lane by construction, so the
    commit is one scatter-*add* of each tag shifted into its lane: keys that
    share a word add disjoint bits, which makes the result independent of
    the order in which the device applies duplicate word addresses.

    Returns (table', placed: bool[n] in original batch order).
    """
    lay = config.layout
    n = bucket.shape[0]
    nb = config.num_buckets

    # One sort per phase — the whole point: pending keys grouped by bucket,
    # masked-out keys pushed past every real segment via the nb sentinel.
    sort_key = jnp.where(pend, bucket.astype(jnp.int32), nb)
    order = jnp.argsort(sort_key, stable=True)
    sb = sort_key[order]
    rank = L.segment_ranks(sb)

    safe_b = jnp.minimum(sb, nb - 1)
    btags = L.bucket_tags(table, safe_b, lay)                   # [n, b]
    placed_s, slot_s = L.nth_free_slot(btags, rank)
    placed_s = placed_s & (sb < nb)
    widx, sw = L.slot_to_word(slot_s, lay)
    addr = jnp.where(placed_s, L.word_addr(safe_b, widx, lay), lay.num_words)
    lane = ((stored_tag[order].astype(jnp.uint32) & _U32(lay.fp_mask))
            << (sw.astype(jnp.uint32) * _U32(lay.fp_bits)))
    table = table.at[addr].add(lane, mode="drop")

    placed = jnp.zeros((n,), bool).at[order].set(placed_s)
    return table, placed


def insert_bulk(
    config: CuckooConfig, state: CuckooState, keys: jnp.ndarray,
    valid: Optional[jnp.ndarray] = None,
    *, dedup_within_batch: bool = False,
) -> Tuple[CuckooState, jnp.ndarray, InsertStats]:
    """Bulk-build insertion fast path. Same contract as :func:`insert`.

    Where :func:`insert` re-elects per-word winners with a full stable sort
    of all claim addresses in *every* round of its while-loop, this entry
    point sorts the batch by primary bucket **once** and commits whole
    buckets per round (paper §4.6.3's sorted insertion, promoted from a
    rejected GPU ablation to the batch-synchronous fast path — DESIGN.md §6):

    1. phase 1: place up to ``bucket_size`` keys per *primary* bucket —
       each key takes the rank-th free slot of its bucket segment, read
       from the unpacked rows of the buckets the batch touches and
       committed as packed-word writes (the table is never unpacked
       whole, so temporaries stay O(batch) at any table size);
    2. phase 2: re-sort the overflow by *alternate* bucket, place again;
    3. spill the residue (both candidate buckets full — rare below ~0.9
       load) into the general eviction round loop;
    4. restore original batch order for ``ok``/stats outputs (the sorted
       view never escapes).

    ``stats.rounds`` counts the two bulk phases plus the residue loop's
    rounds, so it is directly comparable with :func:`insert`'s round count.

    Engine routing (``config.insert_engine``, DESIGN.md §14): ``"auto"``
    and ``"orientation"`` take the graph-orientation bulk build —
    :func:`_insert_orient` replaces the eviction loop entirely for this
    entry point; ``"legacy"``/``"frontier"`` keep the two sorted phases
    here and spill the residue through that engine's round loop.
    """
    eng = resolve_engine(config, bulk=True)
    if eng == "orientation":
        return _insert_orient(config, state, keys, valid,
                              dedup_within_batch=dedup_within_batch)
    residue_fn = _ENGINE_FNS[eng]
    lay = config.layout
    pol = config.placement
    n = keys.shape[0]

    pending = jnp.ones((n,), bool) if valid is None else valid.astype(bool)
    valid0 = pending
    if dedup_within_batch:
        first, rep = _batch_dedup(keys, valid0)
        pending = pending & first

    base_tag, i1, i2 = prepare_keys(config, keys)
    tag1 = pol.place_tag(base_tag, jnp.zeros((n,), bool))
    tag2 = pol.place_tag(base_tag, jnp.ones((n,), bool))

    table, placed1 = _bulk_place_phase(config, state.table, i1, tag1, pending)
    pending = pending & ~placed1
    table, placed2 = _bulk_place_phase(config, table, i2, tag2, pending)
    pending = pending & ~placed2

    placed = placed1 | placed2
    count = state.count + jnp.sum(placed, dtype=jnp.int32)

    # Residue: both candidate buckets full — hand the stragglers to the
    # eviction-capable round loop against the bulk-updated table.
    with jax.named_scope("residue"):
        state2, ok_res, res_stats = residue_fn(
            config, CuckooState(table, count), keys, valid=pending)

    ok = placed | ok_res
    if dedup_within_batch:
        ok = jnp.where(first, ok, ok[rep] & valid0)
    failed = jnp.sum(valid0 & ~ok, dtype=jnp.int32)
    load = state2.count.astype(jnp.float32) / lay.num_slots
    stats = InsertStats(res_stats.evictions, res_stats.rounds + 2, failed,
                        load)
    return state2, ok, stats


# ---------------------------------------------------------------------------
# Query (Alg. 2) — read-only, trivially parallel.
# ---------------------------------------------------------------------------

def query(config: CuckooConfig, state: CuckooState, keys: jnp.ndarray) -> jnp.ndarray:
    """Membership test for a batch of keys -> bool[n]."""
    lay = config.layout
    pol = config.placement
    base_tag, i1, i2 = prepare_keys(config, keys)
    with jax.named_scope("hash"):
        t1, t2 = pol.query_match_tags(base_tag)
    tags1 = L.bucket_tags(state.table, i1, lay)
    tags2 = L.bucket_tags(state.table, i2, lay)
    with jax.named_scope("tag_match"):
        hit1 = jnp.any(tags1 == t1[:, None], axis=-1)
        hit2 = jnp.any(tags2 == t2[:, None], axis=-1)
        return hit1 | hit2


# ---------------------------------------------------------------------------
# Deletion (Alg. 3).
# ---------------------------------------------------------------------------

def delete(
    config: CuckooConfig, state: CuckooState, keys: jnp.ndarray,
    valid: Optional[jnp.ndarray] = None,
) -> Tuple[CuckooState, jnp.ndarray]:
    """Delete one stored copy per key. Returns (state', ok[n])."""
    lay = config.layout
    pol = config.placement
    n = keys.shape[0]
    invalid = lay.num_words
    max_rounds = 2 * config.bucket_size + 2  # duplicate deleters serialise

    base_tag, i1, i2 = prepare_keys(config, keys)
    with jax.named_scope("hash"):
        t1, t2 = pol.query_match_tags(base_tag)

    def round_fn(carry):
        table, count, pending, success, rnd = carry
        words1 = L.gather_bucket_words(table, i1, lay)
        words2 = L.gather_bucket_words(table, i2, lay)
        tags1 = L.unpack_words(words1, lay.fp_bits)
        tags2 = L.unpack_words(words2, lay.fp_bits)

        with jax.named_scope("tag_match"):
            start = L.scan_start(base_tag, lay)
            f1, s1 = L.first_true_circular(tags1 == t1[:, None], start)
            f2, s2 = L.first_true_circular(tags2 == t2[:, None], start)

        found = f1 | f2
        bucket = jnp.where(f1, i1, i2)
        slot = jnp.where(f1, s1, s2)
        words = jnp.where(f1[:, None], words1, words2)
        widx, sw = L.slot_to_word(slot, lay)
        word = L.pick(words, widx, 1)
        desired = L.replace_tag(word, sw, jnp.zeros((n,), jnp.uint32),
                                lay.fp_bits)
        addr = L.word_addr(bucket, widx, lay)

        # Keys with no remaining match fail out (Alg. 3 line 21).
        pending = pending & found

        addr = jnp.where(pending, addr, invalid)
        win, _ = _resolve_claims(addr, jnp.full((n,), invalid, jnp.int32),
                                 invalid)
        commit = pending & win & (addr != invalid)
        table = _masked_write(table, addr, desired, commit, invalid)
        success = success | commit
        pending = pending & ~commit
        count = count - jnp.sum(commit, dtype=jnp.int32)
        return table, count, pending, success, rnd + 1

    def cond_fn(carry):
        return jnp.any(carry[2]) & (carry[4] < max_rounds)

    pending0 = jnp.ones((n,), bool) if valid is None else valid.astype(bool)
    carry0 = (state.table, state.count, pending0,
              jnp.zeros((n,), bool), jnp.zeros((), jnp.int32))
    table, count, _, success, _ = jax.lax.while_loop(cond_fn, round_fn, carry0)
    return CuckooState(table, count), success


# ---------------------------------------------------------------------------
# Fused mixed-operation execution (DESIGN.md §9).
# ---------------------------------------------------------------------------

# Op codes shared with the AMQ protocol (repro.amq.protocol is
# dependency-light by contract, so this import cannot cycle).
from ..amq.protocol import OP_DELETE, OP_INSERT, OP_QUERY  # noqa: E402


def _count_matches(config: CuckooConfig, state: CuckooState,
                   keys: jnp.ndarray):
    """Stored copies matching each key across its two candidate buckets.

    Returns int32[n]. When XOR placement degenerates to ``i1 == i2`` (and
    the match tags coincide), the single bucket is counted once — exactly
    the pool of copies a sequential delete chain could consume.
    """
    lay = config.layout
    pol = config.placement
    base_tag, i1, i2 = prepare_keys(config, keys)
    with jax.named_scope("hash"):
        t1, t2 = pol.query_match_tags(base_tag)
    # The bucket reads nest their own phases inside ``tag_match``.
    with jax.named_scope("tag_match"):
        cnt1 = jnp.sum(L.bucket_tags(state.table, i1, lay) == t1[:, None],
                       axis=-1, dtype=jnp.int32)
        cnt2 = jnp.sum(L.bucket_tags(state.table, i2, lay) == t2[:, None],
                       axis=-1, dtype=jnp.int32)
    aliased = (i1 == i2) & (t1 == t2)
    return jnp.where(aliased, cnt1, cnt1 + cnt2)


def apply_ops(
    config: CuckooConfig, state: CuckooState, keys: jnp.ndarray,
    ops: jnp.ndarray, valid: Optional[jnp.ndarray] = None,
) -> Tuple[CuckooState, jnp.ndarray, InsertStats]:
    """Execute an interleaved QUERY/INSERT/DELETE stream in one fused pass.

    ``ops`` is int32[n] of op codes; returns ``(state', ok[n], stats)``
    where ``ok[i]`` is that slot's outcome under its op code (query → hit,
    insert → landed, delete → removed a stored copy).

    Intra-batch semantics (validated against the per-op sequential oracle
    in tests/test_mixed_ops.py): **operations on the same 64-bit key
    resolve in batch order** — a query at index i observes exactly that
    key's inserts and deletes at indices j < i, and a delete consumes the
    oldest available copy. Rather than serialising per-key chains, the
    pass materialises them algebraically:

    1. one gather over the table counts each key's stored copies ``c0``
       (the SWAR-unpacked match count over both candidate buckets);
    2. a segmented associative scan over the batch (grouped by key value,
       batch order within groups) runs the saturating counter
       ``c_t = max(c_{t-1} + a_t, 0)`` (+1 insert, −1 delete, 0 query)
       from ``c0``, which answers every query (``c > 0``) and delete
       (``c_before > 0``) in its correct intra-batch position;
    3. only each key's *net* effect touches the table: ``d = c_last − c0``
       surplus copies are inserted (the last ``d`` insert slots) or
       ``−d`` copies deleted (the first ``−d`` delete slots) through the
       existing claim machinery — insert/delete pairs that cancel within
       the batch never generate memory traffic.

    Documented deviations from the sequential oracle (DESIGN.md §9): a
    cancelled insert reports ``ok=True`` even when a sequential execution
    would have failed it against a full table, and *cross-key* fingerprint
    aliasing within one batch is observed as-if-reordered (net effects are
    applied deletes-then-inserts). Neither can produce a false negative
    for a key's own inserts, and both vanish below the design load.
    """
    n = keys.shape[0]
    if n == 0:  # static: the segmented scans assume at least one slot
        return state, jnp.zeros((0,), bool), InsertStats(
            jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
            state.count.astype(jnp.float32) / config.num_slots)
    v = (jnp.ones((n,), bool) if valid is None else valid.astype(bool))
    ops = ops.astype(jnp.int32)
    is_ins = v & (ops == OP_INSERT)
    is_del = v & (ops == OP_DELETE)
    is_qry = v & (ops == OP_QUERY)

    c0 = _count_matches(config, state, keys)

    # --- group by 64-bit key value; batch order within groups (stable).
    lo, hi = keys[..., 0], keys[..., 1]
    order = jnp.lexsort((lo, hi))
    lo_s, hi_s = lo[order], hi[order]
    seg_start = jnp.concatenate([
        jnp.ones((1,), bool),
        (lo_s[1:] != lo_s[:-1]) | (hi_s[1:] != hi_s[:-1]),
    ])
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    idx = jnp.arange(n, dtype=jnp.int32)
    head_pos = jax.lax.cummax(jnp.where(seg_start, idx, 0))

    def seg_cumsum(x_s):
        c = jnp.cumsum(x_s)
        return c - (c[head_pos] - x_s[head_pos])

    a = (is_ins.astype(jnp.int32) - is_del.astype(jnp.int32))[order]
    c0_s = c0[order]

    # --- segmented saturating-counter scan. Each op is the map
    #     c -> max(c + a, 0); maps compose as (A, M): c -> max(c + A, M)
    #     with A = A1 + A2, M = max(M1 + A2, M2) — associative, and the
    #     segment-start flag resets composition at key-group boundaries.
    def combine(left, right):
        A1, M1, r1 = left
        A2, M2, r2 = right
        A = jnp.where(r2, A2, A1 + A2)
        M = jnp.where(r2, M2, jnp.maximum(M1 + A2, M2))
        return A, M, r1 | r2

    A, M, _ = jax.lax.associative_scan(
        combine, (a, jnp.zeros((n,), jnp.int32), seg_start))
    c_incl = jnp.maximum(c0_s + A, M)
    c_before = jnp.where(seg_start, c0_s, jnp.roll(c_incl, 1))

    # --- net effect per key group: surplus inserts / deficit deletes.
    last_pos = jnp.clip(
        jax.ops.segment_max(idx, seg_id, num_segments=n), 0, n - 1)
    c_last = c_incl[last_pos][seg_id]
    d = c_last - c0_s                       # net copies to add (+) / drop (−)
    ins_rank = seg_cumsum(is_ins[order].astype(jnp.int32))    # 1-based
    del_rank = seg_cumsum(is_del[order].astype(jnp.int32))
    ins_total = ins_rank[last_pos][seg_id]
    net_ins_s = is_ins[order] & (ins_rank > ins_total - jnp.maximum(d, 0))
    net_del_s = is_del[order] & (del_rank <= jnp.maximum(-d, 0))

    unsort = lambda x_s, fill: jnp.full((n,), fill, x_s.dtype).at[order].set(x_s)
    net_ins = unsort(net_ins_s, False)
    net_del = unsort(net_del_s, False)
    q_ok = unsort(c_incl > 0, False)
    d_ok_prov = unsort(c_before > 0, False)

    # --- apply net mutations through the existing claim machinery
    #     (deletes first: they free slots the surplus inserts may claim).
    #     The claim loops pay full-batch-width sorts per round, so sparse
    #     net slices (the common case for read-heavy traffic) are first
    #     *compacted* into a narrow static sub-batch with one cumsum
    #     scatter — no sort — and only dense slices run full width, where
    #     net inserts take the bulk-build fast path (DESIGN.md §6; the
    #     fused pass already paid for the batch analysis). lax.cond picks
    #     the branch at runtime; shapes stay static either way.
    sub = max(8, n // 8)

    def _compact(mask, width):
        pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
        slot = jnp.where(mask, pos, width)
        sub_keys = jnp.zeros((width, 2), jnp.uint32).at[slot].set(
            keys, mode="drop")
        sub_valid = jnp.zeros((width,), bool).at[slot].set(mask, mode="drop")
        return jnp.clip(pos, 0, width - 1), sub_keys, sub_valid

    def sparse_delete(st):
        pos, skeys, svalid = _compact(net_del, sub)
        st, ok_sub = delete(config, st, skeys, valid=svalid)
        return st, net_del & ok_sub[pos]

    def dense_delete(st):
        return delete(config, st, keys, valid=net_del)

    state, del_ok = jax.lax.cond(
        jnp.sum(net_del, dtype=jnp.int32) <= sub,
        sparse_delete, dense_delete, state)

    def sparse_insert(st):
        pos, skeys, svalid = _compact(net_ins, sub)
        st, ok_sub, st_stats = insert(config, st, skeys, valid=svalid)
        ev = jnp.where(net_ins, st_stats.evictions[pos], 0)
        return st, net_ins & ok_sub[pos], ev, st_stats.rounds

    def dense_insert(st):
        st, ok_f, st_stats = insert_bulk(config, st, keys, valid=net_ins)
        return st, ok_f, st_stats.evictions, st_stats.rounds

    state, ins_ok, evictions, rounds = jax.lax.cond(
        jnp.sum(net_ins, dtype=jnp.int32) <= sub,
        sparse_insert, dense_insert, state)

    ok = jnp.where(
        is_qry, q_ok,
        jnp.where(is_ins, jnp.where(net_ins, ins_ok, True),
                  jnp.where(is_del,
                            d_ok_prov & jnp.where(net_del, del_ok, True),
                            False)))
    failed = jnp.sum(net_ins & ~ins_ok, dtype=jnp.int32)
    load = state.count.astype(jnp.float32) / config.num_slots
    return state, ok, InsertStats(evictions, rounds, failed, load)


# ---------------------------------------------------------------------------
# Convenience object API (functional; methods return new state).
# ---------------------------------------------------------------------------

class CuckooFilter:
    """Thin OO wrapper with per-config cached jitted entry points.

    New code should prefer :func:`repro.amq.make`\\ ("cuckoo", ...) — this
    class is kept as a stable shim and mirrors the unified keyword surface:
    ``insert(keys, bulk=..., dedup_within_batch=...)`` (matching
    ``ShardedCuckooFilter.insert``).
    """

    def __init__(self, config: CuckooConfig, state: Optional[CuckooState] = None,
                 dedup_within_batch: bool = False):
        self.config = config
        self.state = config.init() if state is None else state
        self._default_dedup = dedup_within_batch
        self._jits = {}

    def _op(self, fn, **static):
        key = (fn.__name__, tuple(sorted(static.items())))
        if key not in self._jits:
            self._jits[key] = jax.jit(
                functools.partial(fn, self.config, **static))
        return self._jits[key]

    def insert(self, keys, *, bulk: bool = False,
               dedup_within_batch: Optional[bool] = None
               ) -> Tuple[jnp.ndarray, InsertStats]:
        """Insert a batch; ``bulk=True`` takes the bucket-sorted fast path.

        A batch the engine could not fully place raises a loud
        ``RuntimeWarning`` carrying the failure count and the load factor
        (``stats.failed`` / ``stats.load``) — the round loop's
        ``max_rounds`` budget (default ``4 * max_evictions + 64``) means
        near-full tables fail keys rather than spin, and that must never
        pass silently just because the caller dropped the ``ok`` mask.
        """
        import warnings

        dd = (self._default_dedup if dedup_within_batch is None
              else dedup_within_batch)
        fn = self._op(insert_bulk if bulk else insert, dedup_within_batch=dd)
        self.state, ok, stats = fn(self.state, normalize_keys(keys))
        failed = int(stats.failed)
        if failed:
            warnings.warn(
                f"cuckoo insert left {failed} of {ok.shape[0]} keys "
                f"unplaced at load factor {float(stats.load):.3f} — the "
                f"filter is effectively full; grow it "
                f"(CuckooConfig.for_capacity) or rebuild",
                RuntimeWarning, stacklevel=2)
        return ok, stats

    def insert_bulk(self, keys) -> Tuple[jnp.ndarray, InsertStats]:
        """Deprecated alias for ``insert(keys, bulk=True)``."""
        import warnings

        warnings.warn("CuckooFilter.insert_bulk is deprecated; use "
                      "insert(keys, bulk=True)", DeprecationWarning,
                      stacklevel=2)
        return self.insert(keys, bulk=True)

    def query(self, keys) -> jnp.ndarray:
        return self._op(query)(self.state, normalize_keys(keys))

    def delete(self, keys) -> jnp.ndarray:
        self.state, ok = self._op(delete)(self.state, normalize_keys(keys))
        return ok

    def apply_ops(self, keys, ops, valid=None
                  ) -> Tuple[jnp.ndarray, InsertStats]:
        """Run an interleaved query/insert/delete stream in one fused pass."""
        self.state, ok, stats = self._op(apply_ops)(
            self.state, normalize_keys(keys), ops, valid)
        return ok, stats

    @property
    def load_factor(self) -> float:
        return float(self.state.count) / self.config.num_slots

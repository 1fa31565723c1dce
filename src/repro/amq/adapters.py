"""Adapters: every filter family of the repo behind the unified AMQ protocol.

One :class:`AMQAdapter` per backend normalizes the family's native surface
(``CuckooFilter.insert`` returning ``(ok, InsertStats)``, baselines returning
bare masks, the sharded filter's ``(ok, routed)`` pairs, the Python oracle's
host-side batches) to the protocol of :mod:`repro.amq.protocol`:

    insert/insert_bulk(config, state, keys, *, valid, dedup_within_batch)
        -> (state', InsertReport)
    query(config, state, keys, *, valid) -> (state, QueryResult)
    delete(config, state, keys, *, valid) -> (state', DeleteReport)

Adapters are *static* objects: all jit-compilation lives in the
:class:`repro.amq.handle.FilterHandle` (or, for the sharded backend, in a
shard_map builder cache below), so the functional ops stay composable inside
larger jitted programs.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import cuckoo_filter as CF
from ..core import sharded_filter as SF
from ..core.hashing import keys_to_numpy
from ..filters import bcht as HT
from ..filters import blocked_bloom as BB
from ..filters import cpu_reference as PYREF
from ..filters import quotient as QF
from ..filters import two_choice as TC
from .protocol import (
    OP_DELETE,
    OP_INSERT,
    OP_QUERY,
    Capabilities,
    DeleteReport,
    InsertReport,
    MixedReport,
    OpBatch,
    QueryResult,
    all_routed,
    ensure_valid,
)


@dataclasses.dataclass(frozen=True)
class AMQAdapter:
    """One backend behind the unified AMQ protocol.

    Fields are plain callables (not bound methods), so
    ``adapter.insert(config, state, keys)`` works directly and composes
    with ``functools.partial`` + ``jax.jit``.

    ``jit=False`` marks backends whose ops must not be re-jitted by the
    handle (the host-side oracle; the sharded backend, which jits its own
    shard_map'd programs per batch shape).

    ``growth_sizings`` is the backend's growth hook for the auto-expanding
    cascade (DESIGN.md §8): an ordered tuple of sizing-kwarg overlays, from
    loosest/cheapest to tightest. When the cascade allocates a level it
    merges each overlay over the caller's base kwargs in turn and picks the
    first whose config meets the level's FPR share; ``({},)`` means the
    backend needs no per-level tightening (exact structures). Required when
    ``capabilities.supports_expand`` is True.

    ``grow_config`` optionally derives level ``i+1``'s config from level
    ``i``'s — ``(prev_config, factor, **overlay) -> config`` — instead of
    re-running ``make_config`` from scratch. Backends whose configs carry
    placement state use it to pin that state across levels (the sharded
    backend keeps one mesh for the whole cascade).

    ``apply_ops`` is the native fused mixed-batch path (DESIGN.md §9):
    ``(config, state, keys, ops, *, valid) -> (state', MixedReport)``
    executing an interleaved query/insert/delete stream in one program.
    Required when ``capabilities.supports_mixed`` is True; backends
    without it are served by :func:`segmented_apply_ops`.

    ``snapshot``/``restore`` are the lifecycle hooks (DESIGN.md §10):
    ``snapshot(config, state) -> dict[str, np.ndarray]`` pulls the packed
    state to host; ``restore(config, arrays) -> state`` places it back
    under the *same* config (the handle validates the config fingerprint
    before calling it). Both required when
    ``capabilities.supports_snapshot`` is True. ``fingerprint`` overrides
    the default config-identity string (:func:`config_fingerprint`) —
    the sharded backend uses it to exclude placement (mesh, shard count)
    from identity, which is what makes restore-onto-a-new-mesh and exact
    resharding legal.

    ``host_query``/``host_delete`` are the cold-tier hooks (DESIGN.md §12):
    ``host_query(config, arrays, keys) -> bool[n]`` probes the packed
    snapshot arrays *in host RAM* with vectorized numpy (per-key hash
    scalars may go through the backend's jax hashing — they are tiny; the
    table gather must not touch the device), and
    ``host_delete(config, arrays, keys, valid) -> ok bool[n]`` clears one
    matching slot per key in the arrays in place (updating ``count``).
    ``host_query`` is required when ``capabilities.supports_tiering`` is
    True; ``host_delete`` additionally when the backend supports deletes.
    """

    name: str
    capabilities: Capabilities
    make_config: Callable[..., Any]      # (capacity, **kw) -> config
    init: Callable[[Any], Any]           # config -> fresh state
    insert: Callable[..., Any]
    query: Callable[..., Any]
    delete: Optional[Callable[..., Any]] = None
    insert_bulk: Optional[Callable[..., Any]] = None
    apply_ops: Optional[Callable[..., Any]] = None
    jit: bool = True
    growth_sizings: Optional[tuple] = None
    grow_config: Optional[Callable[..., Any]] = None
    snapshot: Optional[Callable[..., Any]] = None
    restore: Optional[Callable[..., Any]] = None
    fingerprint: Optional[Callable[[Any], str]] = None
    host_query: Optional[Callable[..., Any]] = None
    host_delete: Optional[Callable[..., Any]] = None


def program_name(backend: str, op: str) -> str:
    """The name a backend's op compiles under, less JAX's ``jit_`` prefix.

    ``<backend>_<op>`` with ``-`` mapped to ``_``: ``cuckoo_query``,
    ``sharded_cuckoo_apply_ops``. Trace readers find the programs by it.
    """
    return f"{backend}_{op}".replace("-", "_")


def named(fn, name: str):
    """``fn`` with ``__name__`` and ``__qualname__`` set to ``name``, which
    ``jax.jit`` names its program after. Not ``functools.wraps``: that also
    sets ``__wrapped__``, which ``jax.jit`` follows to read the signature it
    donates arguments by."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _zero_stats(n):
    return jnp.zeros((n,), jnp.int32), jnp.zeros((), jnp.int32)


# ---------------------------------------------------------------------------
# Lifecycle hooks (DESIGN.md §10): snapshot / restore / config fingerprints.
# ---------------------------------------------------------------------------

def default_fingerprint(config) -> str:
    """Config identity for snapshot validation: the frozen-dataclass repr.

    Every backend config is a frozen dataclass of primitives, so its repr
    is deterministic and covers exactly the knobs that shape the packed
    state (layout, hashes, seeds). Backends whose configs carry placement
    state override this (see ``ShardedAMQConfig``).
    """
    return repr(config)


def config_fingerprint(adapter: AMQAdapter, config) -> str:
    """The adapter's fingerprint for ``config`` (custom hook or default)."""
    fn = adapter.fingerprint or default_fingerprint
    return fn(config)


def state_snapshot(config, state) -> Dict[str, Any]:
    """Generic snapshot: pull every field of a NamedTuple state to host."""
    del config
    return {f: np.asarray(getattr(state, f)) for f in state._fields}


def _validated_state_arrays(config, arrays):
    """Check snapshot arrays against the config's abstract state template.

    The template comes from ``jax.eval_shape(config.init)`` — authoritative
    shapes and dtypes with **no device allocation** (restore latency is a
    tracked metric; materializing a zero table just to read its shapes
    would double it). Returns ``(state_cls, host_arrays_in_field_order)``;
    any disagreement raises
    :class:`~repro.amq.protocol.SnapshotMismatchError`.
    """
    from .protocol import SnapshotMismatchError

    template = jax.eval_shape(config.init)
    missing = set(template._fields) - set(arrays)
    if missing:
        raise SnapshotMismatchError(
            f"snapshot is missing state arrays {sorted(missing)} "
            f"(has {sorted(arrays)})")
    values = []
    for f in template._fields:
        t = getattr(template, f)
        a = np.asarray(arrays[f])
        if tuple(a.shape) != tuple(t.shape) or a.dtype != np.dtype(t.dtype):
            raise SnapshotMismatchError(
                f"state array {f!r}: snapshot has {a.dtype}"
                f"{list(a.shape)}, config expects {np.dtype(t.dtype)}"
                f"{list(t.shape)}")
        values.append(a)
    return type(template), values


def state_restore(config, arrays):
    """Generic restore: validate against the abstract template, place on
    the default device(s). Backends whose state is mesh-placed provide a
    custom hook (``_sharded_restore``)."""
    state_cls, values = _validated_state_arrays(config, arrays)
    return state_cls(*(jnp.asarray(a) for a in values))


# ---------------------------------------------------------------------------
# Cold-tier host probes (DESIGN.md §12): vectorized numpy queries (and
# slot-clear deletes) over the packed snapshot arrays a demoted level left
# in host RAM. Per-key hash scalars reuse the backend's own jax hashing
# (bit-exactness is non-negotiable and the [n]-sized outputs are tiny);
# only the table-sized gathers must stay host-side.
# ---------------------------------------------------------------------------

def _np_bucket_tags(table: np.ndarray, buckets: np.ndarray, lay) -> np.ndarray:
    """Numpy mirror of ``layout.bucket_tags``: -> uint32[n, bucket_size]."""
    wpb = lay.words_per_bucket
    base = buckets.astype(np.int64) * wpb
    words = table[base[:, None] + np.arange(wpb, dtype=np.int64)]  # [n, wpb]
    shifts = np.arange(lay.tags_per_word, dtype=np.uint32) * np.uint32(
        lay.fp_bits)
    tags = (words[:, :, None] >> shifts) & np.uint32(lay.fp_mask)
    return tags.reshape(words.shape[0], lay.bucket_size)


def _cuckoo_host_prepare(config, keys):
    """Per-key probe scalars (match tags + candidate buckets), as numpy."""
    tag, i1, i2 = CF.prepare_keys(config, jnp.asarray(keys, jnp.uint32))
    t1, t2 = config.placement.query_match_tags(tag)
    return (np.asarray(t1), np.asarray(t2),
            np.asarray(i1), np.asarray(i2))


def _cuckoo_host_query(config, arrays, keys) -> np.ndarray:
    """Vectorized numpy membership probe over packed snapshot arrays."""
    lay = config.layout
    table = np.asarray(arrays["table"])
    t1, t2, i1, i2 = _cuckoo_host_prepare(config, keys)
    hit1 = (_np_bucket_tags(table, i1, lay) == t1[:, None]).any(axis=-1)
    hit2 = (_np_bucket_tags(table, i2, lay) == t2[:, None]).any(axis=-1)
    return hit1 | hit2


def _cuckoo_host_delete(config, arrays, keys, valid=None) -> np.ndarray:
    """Clear one matching slot per key in the host-RAM table, in place.

    Candidate slots are located with the same vectorized probe as
    ``host_query``; the actual clears run serially per key so duplicate
    deletes of one key in a batch consume distinct stored copies, exactly
    like the device path's per-round claim resolution. Cold-tier deletes
    are the rare path (DESIGN.md §12) — the loop runs only over keys whose
    candidate buckets matched at all.
    """
    lay = config.layout
    table = arrays["table"]
    if not (isinstance(table, np.ndarray) and table.flags.writeable):
        table = arrays["table"] = np.array(table, np.uint32)
    n = int(np.asarray(keys).shape[0])
    v = (np.ones((n,), bool) if valid is None
         else np.asarray(valid, bool))
    ok = np.zeros((n,), bool)
    if not v.any():
        return ok
    t1, t2, i1, i2 = _cuckoo_host_prepare(config, keys)
    cand1 = (_np_bucket_tags(table, i1, lay) == t1[:, None]).any(axis=-1)
    cand2 = (_np_bucket_tags(table, i2, lay) == t2[:, None]).any(axis=-1)
    wpb, tpw = lay.words_per_bucket, lay.tags_per_word
    fp_mask, fp_bits = np.uint32(lay.fp_mask), lay.fp_bits
    removed = 0
    for i in np.flatnonzero(v & (cand1 | cand2)):
        for bucket, t in ((int(i1[i]), int(t1[i])),
                          (int(i2[i]), int(t2[i]))):
            done = False
            for s in range(lay.bucket_size):
                widx = bucket * wpb + s // tpw
                shift = np.uint32((s % tpw) * fp_bits)
                if int((table[widx] >> shift) & fp_mask) == t:
                    table[widx] &= ~np.uint32(fp_mask << shift)
                    done = True
                    break
            if done:
                ok[i] = True
                removed += 1
                break
    if removed:
        count = arrays["count"]
        arrays["count"] = np.asarray(int(count) - removed,
                                     np.asarray(count).dtype)
    return ok


def _bloom_host_query(config, arrays, keys) -> np.ndarray:
    """Vectorized numpy probe of a blocked-Bloom snapshot (k bits all set)."""
    table = np.asarray(arrays["table"])
    block, word, mask = BB._bit_positions(config, jnp.asarray(keys,
                                                              jnp.uint32))
    block, word, mask = (np.asarray(block), np.asarray(word),
                         np.asarray(mask))
    addr = block[:, None].astype(np.int64) * config.words_per_block + word
    words = table[addr]                                  # [n, k]
    return ((words & mask) == mask).all(axis=-1)


# ---------------------------------------------------------------------------
# Mixed-batch execution: the generic segmented fallback (DESIGN.md §9).
# ---------------------------------------------------------------------------

def segmented_apply_ops(target, batch: OpBatch) -> MixedReport:
    """Execute an :class:`OpBatch` on any handle by segmenting it.

    The universal fallback behind ``FilterHandle.apply_ops`` for backends
    without a native fused path: the batch is split into **maximal
    same-op runs** (host-side — run boundaries are data-dependent) and
    each run replays the existing per-op entry point as one full-width,
    ``valid``-masked call. Shapes never vary, so each op compiles once;
    the cost is one dispatch per run — which is exactly the per-op
    round-trip tax the fused paths erase (benchmarks/mixed_workload.py).

    Correctness is inherited: runs execute in batch order, duplicates
    within a same-op run already serialise inside the batch ops, so
    same-key operations resolve in batch order exactly like the native
    paths. ``target`` is anything with the handle op surface
    (:class:`~repro.amq.handle.FilterHandle`, a cascade, ...).
    """
    ops = np.asarray(batch.ops)
    v = np.asarray(batch.valid, bool)
    n = ops.shape[0]
    ok = np.zeros((n,), bool)
    routed = np.ones((n,), bool)
    evictions = np.zeros((n,), np.int32)
    rounds = 0

    live = np.flatnonzero(v)
    if live.size == 0:  # all-padding batch (e.g. a forced flush): no-op
        return MixedReport(ok, routed, evictions, np.int32(rounds))
    if ((ops[live] == OP_DELETE).any()
            and not target.capabilities.supports_delete):
        raise NotImplementedError(
            f"{target.name}: mixed batch contains deletes but the backend "
            "is append-only (capabilities.supports_delete is False)")

    o = ops[live]
    bounds = np.flatnonzero(np.diff(o) != 0) + 1
    starts = np.concatenate([[0], bounds])
    ends = np.concatenate([bounds, [o.size]])
    for s, e in zip(starts, ends):
        mask = np.zeros((n,), bool)
        mask[live[s:e]] = True
        vmask = jnp.asarray(mask)
        code = o[s]
        if code == OP_QUERY:
            r = target.query(batch.keys, valid=vmask)
            r_ok, r_routed = r.hits, r.routed
        elif code == OP_INSERT:
            r = target.insert(batch.keys, valid=vmask)
            r_ok, r_routed = r.ok, r.routed
            evictions = np.where(mask, np.asarray(r.evictions), evictions)
            rounds += int(np.asarray(r.rounds))
        else:
            r = target.delete(batch.keys, valid=vmask)
            r_ok, r_routed = r.ok, r.routed
        ok = np.where(mask, np.asarray(r_ok, bool), ok)
        routed = np.where(mask, np.asarray(r_routed, bool), routed)
    return MixedReport(ok, routed, evictions, np.int32(rounds))


# ---------------------------------------------------------------------------
# Growth hooks (cascade level sizing, DESIGN.md §8): ordered loosest->tightest
# sizing overlays; the cascade picks the first that meets a level's FPR share.
# ---------------------------------------------------------------------------

# The packed bucket layout quantizes tag widths to 32-bit-word fractions
# (core.layout), so the cuckoo ladder is the three hardware-friendly widths.
_CUCKOO_SIZINGS = tuple({"fp_bits": f} for f in (8, 16, 32))

# Blocked Bloom tightens by raising the per-key bit budget with the
# matching near-optimal hash count k ~= bits_per_key * ln 2.
_BLOOM_SIZINGS = tuple(
    {"bits_per_key": b, "k": max(1, round(b * 0.693))}
    for b in (8, 12, 16, 20, 24, 32, 40))

# The GQF's remainder is an arbitrary bit slice of a uint32 slot word.
_GQF_SIZINGS = tuple({"remainder_bits": r} for r in (8, 12, 16, 20, 24, 28))


# ---------------------------------------------------------------------------
# Core cuckoo filter (the paper's contribution).
# ---------------------------------------------------------------------------

def _cuckoo_insert(config, state, keys, *, valid=None,
                   dedup_within_batch=False, _fn=CF.insert):
    state, ok, stats = _fn(config, state, keys, valid,
                           dedup_within_batch=dedup_within_batch)
    return state, InsertReport(ok, stats.evictions, stats.rounds,
                               all_routed(keys))


def _cuckoo_query(config, state, keys, *, valid=None):
    hits = CF.query(config, state, keys) & ensure_valid(keys, valid)
    return state, QueryResult(hits, all_routed(keys))


def _cuckoo_delete(config, state, keys, *, valid=None):
    state, ok = CF.delete(config, state, keys, valid)
    return state, DeleteReport(ok, all_routed(keys))


def _cuckoo_apply_ops(config, state, keys, ops, *, valid=None):
    state, ok, stats = CF.apply_ops(config, state, keys, ops, valid)
    return state, MixedReport(ok, all_routed(keys), stats.evictions,
                              stats.rounds)


def _cuckoo_make_config(capacity, **kw):
    # Registry default: the vectorized fmix32 pair-hash (the paper's
    # xxhash64 stays available via hash_kind="xxhash64").
    kw.setdefault("hash_kind", "fmix32")
    return CF.CuckooConfig.for_capacity(capacity, **kw)


CUCKOO = AMQAdapter(
    name="cuckoo",
    capabilities=Capabilities(supports_delete=True, supports_bulk=True,
                              counting=True, supports_expand=True,
                              supports_mixed=True, supports_snapshot=True,
                              supports_tiering=True),
    make_config=_cuckoo_make_config,
    init=lambda cfg: cfg.init(),
    insert=_cuckoo_insert,
    insert_bulk=functools.partial(_cuckoo_insert, _fn=CF.insert_bulk),
    query=_cuckoo_query,
    delete=_cuckoo_delete,
    apply_ops=_cuckoo_apply_ops,
    growth_sizings=_CUCKOO_SIZINGS,
    snapshot=state_snapshot,
    restore=state_restore,
    host_query=_cuckoo_host_query,
    host_delete=_cuckoo_host_delete,
)


# ---------------------------------------------------------------------------
# Blocked Bloom (append-only baseline).
# ---------------------------------------------------------------------------

def _bloom_insert(config, state, keys, *, valid=None,
                  dedup_within_batch=False):
    del dedup_within_batch  # idempotent by construction
    state, ok = BB.insert(config, state, keys, valid)
    return state, InsertReport(ok, *_zero_stats(keys.shape[0]),
                               all_routed(keys))


def _bloom_query(config, state, keys, *, valid=None):
    hits = BB.query(config, state, keys) & ensure_valid(keys, valid)
    return state, QueryResult(hits, all_routed(keys))


BLOOM = AMQAdapter(
    name="bloom",
    capabilities=Capabilities(supports_delete=False, counting=False,
                              supports_expand=True, supports_snapshot=True,
                              supports_tiering=True),
    make_config=lambda capacity, **kw: BB.BloomConfig.for_capacity(
        capacity, **kw),
    init=lambda cfg: cfg.init(),
    insert=_bloom_insert,
    query=_bloom_query,
    growth_sizings=_BLOOM_SIZINGS,
    snapshot=state_snapshot,
    restore=state_restore,
    host_query=_bloom_host_query,
)


# ---------------------------------------------------------------------------
# Two-Choice Filter.
# ---------------------------------------------------------------------------

def _tcf_insert(config, state, keys, *, valid=None, dedup_within_batch=False):
    if dedup_within_batch:
        raise NotImplementedError("tcf: dedup_within_batch not supported")
    state, ok = TC.insert(config, state, keys, valid)
    return state, InsertReport(ok, *_zero_stats(keys.shape[0]),
                               all_routed(keys))


def _tcf_query(config, state, keys, *, valid=None):
    hits = TC.query(config, state, keys) & ensure_valid(keys, valid)
    return state, QueryResult(hits, all_routed(keys))


def _tcf_delete(config, state, keys, *, valid=None):
    state, ok = TC.delete(config, state, keys, valid)
    return state, DeleteReport(ok, all_routed(keys))


TCF = AMQAdapter(
    name="tcf",
    capabilities=Capabilities(supports_delete=True, counting=True,
                              supports_snapshot=True),
    make_config=lambda capacity, **kw: TC.TCFConfig.for_capacity(
        capacity, **kw),
    init=lambda cfg: cfg.init(),
    insert=_tcf_insert,
    query=_tcf_query,
    delete=_tcf_delete,
    snapshot=state_snapshot,
    restore=state_restore,
)


# ---------------------------------------------------------------------------
# GPU Quotient Filter analogue (serial Robin Hood).
# ---------------------------------------------------------------------------

def _gqf_insert(config, state, keys, *, valid=None, dedup_within_batch=False):
    if dedup_within_batch:
        raise NotImplementedError("gqf: dedup_within_batch not supported")
    state, ok = QF.insert(config, state, keys, valid)
    return state, InsertReport(ok, *_zero_stats(keys.shape[0]),
                               all_routed(keys))


def _gqf_query(config, state, keys, *, valid=None):
    hits = QF.query(config, state, keys) & ensure_valid(keys, valid)
    return state, QueryResult(hits, all_routed(keys))


def _gqf_delete(config, state, keys, *, valid=None):
    state, ok = QF.delete(config, state, keys, valid)
    return state, DeleteReport(ok, all_routed(keys))


GQF = AMQAdapter(
    name="gqf",
    capabilities=Capabilities(supports_delete=True, counting=True,
                              serial_insert=True, supports_expand=True,
                              supports_snapshot=True),
    make_config=lambda capacity, **kw: QF.GQFConfig.for_capacity(
        capacity, **kw),
    init=lambda cfg: cfg.init(),
    insert=_gqf_insert,
    query=_gqf_query,
    delete=_gqf_delete,
    growth_sizings=_GQF_SIZINGS,
    snapshot=state_snapshot,
    restore=state_restore,
)


# ---------------------------------------------------------------------------
# BCHT (exact membership).
# ---------------------------------------------------------------------------

def _bcht_insert(config, state, keys, *, valid=None, dedup_within_batch=False):
    if dedup_within_batch:
        raise NotImplementedError("bcht: dedup_within_batch not supported")
    state, ok = HT.insert(config, state, keys, valid)
    return state, InsertReport(ok, *_zero_stats(keys.shape[0]),
                               all_routed(keys))


def _bcht_query(config, state, keys, *, valid=None):
    hits = HT.query(config, state, keys) & ensure_valid(keys, valid)
    return state, QueryResult(hits, all_routed(keys))


def _bcht_delete(config, state, keys, *, valid=None):
    state, ok = HT.delete(config, state, keys, valid)
    return state, DeleteReport(ok, all_routed(keys))


BCHT = AMQAdapter(
    name="bcht",
    capabilities=Capabilities(supports_delete=True, counting=True,
                              exact=True, supports_expand=True,
                              supports_snapshot=True),
    make_config=lambda capacity, **kw: HT.BCHTConfig.for_capacity(
        capacity, **kw),
    init=lambda cfg: cfg.init(),
    insert=_bcht_insert,
    query=_bcht_query,
    delete=_bcht_delete,
    growth_sizings=({},),  # exact: any level trivially meets its FPR share
    snapshot=state_snapshot,
    restore=state_restore,
)


# ---------------------------------------------------------------------------
# Mesh-sharded cuckoo filter.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedAMQConfig:
    """Protocol config for the sharded backend: inner config + its mesh.

    Hashable (``jax.sharding.Mesh`` is) so it stays a valid static arg for
    the shard_map builder cache below.
    """

    inner: SF.ShardedCuckooConfig
    mesh: Any  # jax.sharding.Mesh

    @property
    def num_slots(self) -> int:
        """Aggregate nominal capacity across all shards."""
        return self.inner.num_slots

    @property
    def table_bytes(self) -> int:
        """Aggregate device memory footprint across all shards."""
        return self.inner.table_bytes

    def expected_fpr(self, load_factor: float) -> float:
        """Aggregate FPR equals the per-shard filter's (paper Eq. 4), because shards are independent same-config cuckoo filters."""
        return self.inner.expected_fpr(load_factor)

    @property
    def batch_align(self) -> int:
        """Dispatch widths must divide across the mesh (DESIGN.md §11)."""
        return self.inner.batch_align

    def init(self) -> SF.ShardedCuckooState:
        """Fresh empty sharded state, placed along the mesh axis."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(
            self.inner.init(),
            NamedSharding(self.mesh, P(self.inner.axis_name)))

    def resharded(self, num_shards: Optional[int] = None, *,
                  mesh: Any = None,
                  axis_name: Optional[str] = None) -> "ShardedAMQConfig":
        """The same filter over a different device set — exactly.

        Key→partition is fixed (``SF.partition_of`` hashes modulo the
        partition count, never the device count), so only the
        partition→device placement changes: a state restored under the
        resharded config answers every query bit-for-bit identically
        (DESIGN.md §10). Pass ``num_shards`` (a divisor of the partition
        count; a default mesh of that size is derived) and/or an explicit
        new ``mesh``.
        """
        ax = axis_name or self.inner.axis_name
        if mesh is None and num_shards is None:
            mesh, num_shards = _default_mesh(ax, None)
        elif num_shards is None:
            num_shards = mesh.shape[ax]
        # Validate the partition math first: a divisibility error should
        # name partitions, not fail while deriving a default mesh.
        inner = self.inner.resharded(num_shards, axis_name=axis_name)
        if mesh is None:
            mesh, _ = _default_mesh(ax, num_shards)
        return ShardedAMQConfig(inner, mesh)


def _default_mesh(axis_name: str, num_shards: Optional[int]):
    devices = jax.devices()
    n = num_shards or len(devices)
    if n > len(devices):
        raise ValueError(
            f"num_shards={n} exceeds the {len(devices)} available "
            "device(s); pass an explicit mesh= spanning the target devices")
    return jax.sharding.Mesh(np.asarray(devices[:n]), (axis_name,)), n


def _sharded_make_config(capacity, *, num_shards=None, mesh=None,
                         axis_name="data", **kw):
    if mesh is None:
        mesh, num_shards = _default_mesh(axis_name, num_shards)
    elif num_shards is None:
        num_shards = mesh.shape[axis_name]
    kw.setdefault("hash_kind", "fmix32")
    inner = SF.ShardedCuckooConfig.for_capacity(
        capacity, num_shards, axis_name=axis_name, **kw)
    return ShardedAMQConfig(inner, mesh)


@functools.lru_cache(maxsize=128)
def _sharded_fn(config: ShardedAMQConfig, op: str, local_batch: int,
                dedup: bool):
    from jax.sharding import PartitionSpec as P

    ax = config.inner.axis_name
    fn = SF._make_sharded_op(config.inner, op, local_batch,
                             dedup_within_batch=dedup)
    n_in = 5 if op == "apply_ops" else 4
    mapped = jax.shard_map(fn, mesh=config.mesh,
                           in_specs=(P(ax),) * n_in,
                           out_specs=(P(ax), P(ax), P(ax), P(ax)),
                           check_vma=False)
    return jax.jit(named(mapped, program_name("sharded-cuckoo", op)))


def _sharded_run(config, state, keys, op, valid, dedup=False, ops=None):
    valid = ensure_valid(keys, valid)
    # shard_map splits the global batch across the mesh axis; bin capacity
    # must be sized from the *per-device* slice, not the global batch.
    num_shards = config.inner.num_shards
    n = keys.shape[0]
    if n % num_shards:
        raise ValueError(
            f"sharded-cuckoo: batch size {n} not divisible by "
            f"num_shards={num_shards}")
    fn = _sharded_fn(config, op, n // num_shards, dedup)
    args = (state.table, state.count, keys, valid)
    if op == "apply_ops":
        args += (jnp.asarray(ops, jnp.int32),)
    table, count, result, routed = fn(*args)
    return SF.ShardedCuckooState(table, count), result, routed


def _sharded_insert(config, state, keys, *, valid=None,
                    dedup_within_batch=False, _op="insert"):
    state, ok, routed = _sharded_run(config, state, keys, _op, valid,
                                     dedup_within_batch)
    n = keys.shape[0]
    return state, InsertReport(ok, *_zero_stats(n), routed)


def _sharded_query(config, state, keys, *, valid=None):
    state, hits, routed = _sharded_run(config, state, keys, "query", valid)
    return state, QueryResult(hits, routed)


def _sharded_delete(config, state, keys, *, valid=None):
    state, ok, routed = _sharded_run(config, state, keys, "delete", valid)
    return state, DeleteReport(ok, routed)


def _sharded_apply_ops(config, state, keys, ops, *, valid=None):
    state, ok, routed = _sharded_run(config, state, keys, "apply_ops",
                                     valid, ops=ops)
    n = keys.shape[0]
    return state, MixedReport(ok, routed, *_zero_stats(n))


def _sharded_restore(config: ShardedAMQConfig, arrays):
    """Sharded restore: validated arrays placed along the config's mesh.

    The partition axis is re-placed under the *target* config's mesh and
    shard count — which may differ from the snapshot's, since the sharded
    fingerprint excludes placement: this is the exact-reshard path.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    state_cls, values = _validated_state_arrays(config, arrays)
    sharding = NamedSharding(config.mesh, P(config.inner.axis_name))
    return state_cls(*(jax.device_put(a, sharding) for a in values))


def _sharded_fingerprint(config: ShardedAMQConfig) -> str:
    """Sharded config identity: per-partition filter + partition count.

    Placement (mesh, shard count, axis name) and routing overprovision are
    deliberately *excluded*: they shape where partitions live, not what
    they contain — which is exactly what licenses snapshot-restore onto a
    new mesh / shard count as the zero-membership-change migration path
    (DESIGN.md §10).
    """
    inner = config.inner
    return f"sharded-cuckoo[P={inner.partitions}]:{inner.shard!r}"


def _sharded_grow_config(prev: ShardedAMQConfig, factor: float,
                         **overlay) -> ShardedAMQConfig:
    """Next cascade level: grow the per-shard filter, keep the *same* mesh.

    Carrying ``prev.mesh`` over (rather than re-deriving a default mesh per
    level) pins the cascade's placement: every level exchanges keys over
    one all-to-all pattern (DESIGN.md §8 "cascade of shards").
    """
    return ShardedAMQConfig(
        prev.inner.grown(factor, fp_bits=overlay.pop("fp_bits", None)),
        prev.mesh)


SHARDED_CUCKOO = AMQAdapter(
    name="sharded-cuckoo",
    capabilities=Capabilities(supports_delete=True, supports_bulk=True,
                              supports_sharding=True, counting=True,
                              supports_expand=True, supports_mixed=True,
                              supports_snapshot=True),
    make_config=_sharded_make_config,
    init=lambda cfg: cfg.init(),
    insert=_sharded_insert,
    insert_bulk=functools.partial(_sharded_insert, _op="insert_bulk"),
    query=_sharded_query,
    delete=_sharded_delete,
    apply_ops=_sharded_apply_ops,
    jit=False,  # ops are shard_map programs jitted per batch shape above
    growth_sizings=_CUCKOO_SIZINGS,  # fp_bits flows to the per-shard config
    grow_config=_sharded_grow_config,
    snapshot=state_snapshot,
    restore=_sharded_restore,
    fingerprint=_sharded_fingerprint,
)


# ---------------------------------------------------------------------------
# Pure-Python oracle (host-side; the conformance reference).
# ---------------------------------------------------------------------------

def _py_mask(keys, valid):
    if valid is None:
        return np.ones((np.asarray(keys).shape[0],), bool)
    return np.asarray(valid, bool)


def _py_insert(config, state, keys, *, valid=None, dedup_within_batch=False):
    raw = keys_to_numpy(keys)
    v = _py_mask(keys, valid)
    ok = np.zeros((raw.shape[0],), bool)
    seen = set()
    for i, k in enumerate(raw.tolist()):
        if not v[i]:
            continue
        if dedup_within_batch and k in seen:
            ok[i] = ok[np.flatnonzero((raw == k) & v)[0]]
            continue
        seen.add(k)
        ok[i] = state.insert(k)
    n = raw.shape[0]
    return state, InsertReport(ok, np.zeros((n,), np.int32),
                               np.zeros((), np.int32), np.ones((n,), bool))


def _py_query(config, state, keys, *, valid=None):
    hits = state.query_batch(keys_to_numpy(keys)) & _py_mask(keys, valid)
    return state, QueryResult(hits, np.ones((hits.shape[0],), bool))


def _py_delete(config, state, keys, *, valid=None):
    raw = keys_to_numpy(keys)
    v = _py_mask(keys, valid)
    ok = np.array([v[i] and state.delete(int(k))
                   for i, k in enumerate(raw)], bool)
    return state, DeleteReport(ok, np.ones((raw.shape[0],), bool))


def _py_apply_ops(config, state, keys, ops, *, valid=None):
    """The mixed-batch *definition*: a literal sequential replay.

    One op at a time, in batch order — this is the oracle the fused paths
    are differentially tested against (tests/test_mixed_ops.py).
    """
    raw = keys_to_numpy(keys)
    ops = np.asarray(ops)
    v = _py_mask(keys, valid)
    n = raw.shape[0]
    ok = np.zeros((n,), bool)
    for i in range(n):
        if not v[i]:
            continue
        k = int(raw[i])
        if ops[i] == OP_QUERY:
            ok[i] = state.query(k)
        elif ops[i] == OP_INSERT:
            ok[i] = state.insert(k)
        elif ops[i] == OP_DELETE:
            ok[i] = state.delete(k)
        else:
            raise ValueError(f"unknown op code {ops[i]} at slot {i}")
    return state, MixedReport(ok, np.ones((n,), bool),
                              np.zeros((n,), np.int32), np.zeros((), np.int32))


def _py_snapshot(config, state) -> Dict[str, Any]:
    """Oracle snapshot: the bucket grid + count as plain arrays.

    The eviction RNG's position is not captured — snapshots preserve
    *membership* exactly; future insert eviction choices may differ from a
    never-snapshotted oracle (irrelevant to correctness, which never
    depends on which victim a cuckoo walk picks).
    """
    del config
    return {"buckets": np.asarray(state.buckets, np.uint32),
            "count": np.asarray(state.count, np.int64)}


def _py_restore(config, arrays):
    from .protocol import SnapshotMismatchError

    filt = config.init()
    want = (config.num_buckets, config.bucket_size)
    buckets = np.asarray(arrays.get("buckets"))
    if "buckets" not in arrays or tuple(buckets.shape) != want:
        raise SnapshotMismatchError(
            f"state array 'buckets': snapshot has "
            f"{None if 'buckets' not in arrays else list(buckets.shape)}, "
            f"config expects {list(want)}")
    if "count" not in arrays:
        raise SnapshotMismatchError(
            "snapshot is missing state array 'count' "
            f"(has {sorted(arrays)})")
    filt.buckets = [[int(t) for t in row] for row in buckets]
    filt.count = int(arrays["count"])
    return filt


CPU_CUCKOO = AMQAdapter(
    name="cpu-cuckoo",
    capabilities=Capabilities(supports_delete=True, counting=True,
                              serial_insert=True, supports_expand=True,
                              supports_mixed=True, supports_snapshot=True),
    make_config=lambda capacity, **kw: PYREF.PyCuckooConfig.for_capacity(
        capacity, **kw),
    init=lambda cfg: cfg.init(),
    insert=_py_insert,
    query=_py_query,
    delete=_py_delete,
    apply_ops=_py_apply_ops,
    jit=False,
    growth_sizings=_CUCKOO_SIZINGS,
    snapshot=_py_snapshot,
    restore=_py_restore,
)


DEFAULT_ADAPTERS: Dict[str, AMQAdapter] = {
    a.name: a for a in
    (CUCKOO, BLOOM, TCF, GQF, BCHT, SHARDED_CUCKOO, CPU_CUCKOO)
}

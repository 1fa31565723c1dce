"""Mesh-sharded Cuckoo filter — the distributed scale-out layer.

Partitioning scheme (DESIGN.md §5, refined in §10): the key space is hashed
into a *fixed* number of independent sub-filter **partitions** (default: one
per device), and each device along a mesh axis owns a contiguous block of
whole partitions. Both cuckoo candidate buckets of a key live in the same
partition, so eviction chains never cross devices — the PCF partitioning of
Schmidt et al. promoted to the accelerator mesh. Aggregate filter bandwidth
scales linearly with devices (the TPU analogue of the paper's "saturate
global memory bandwidth": here we saturate *n_devices x* HBM bandwidth).

Fixing the partition count (rather than hashing modulo the device count)
is what makes the filter's *lifecycle* operations exact (DESIGN.md §10):
key→partition never changes, so a K→K′ reshard or a migration to a new
mesh relocates whole partitions — every packed word moves verbatim and
membership answers are bit-for-bit preserved (:meth:`ShardedCuckooConfig.
resharded`, :meth:`ShardedCuckooFilter.resharded`). Create filters with
``partitions_per_shard > 1`` to leave resharding headroom.

Routing is a fixed-capacity all-to-all (no data-dependent shapes — a
straggler-mitigation requirement at scale, DESIGN.md §5): each device sorts
its local keys by destination shard into ``[num_shards, capacity]`` bins,
exchanges bins with one ``lax.all_to_all``, applies the local filter op with
a validity mask, and routes results back with the inverse exchange. Keys
beyond a bin's capacity are reported in the ``routed`` mask so callers can
retry them next step (they are never silently dropped).

All ops run inside ``shard_map`` over the chosen axis and are jit-compatible;
the sharded state is an ordinary pytree (stacked per-shard tables), so it
checkpoints/restores like model state.

The sharded filter also composes with the auto-expanding cascade
(``repro.amq.cascade``, DESIGN.md §8) as a *cascade of shards*: each
cascade level is an independently mesh-sharded filter, so aggregate
capacity grows geometrically while every level keeps the linear
n-devices-× bandwidth scaling above. :meth:`ShardedCuckooConfig.grown`
is the growth hook — it scales per-shard capacity while pinning the mesh
topology (shard count, axis, routing overprovision) so all levels of one
cascade exchange keys over the same all-to-all pattern.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .cuckoo_filter import CuckooConfig, CuckooState
from .cuckoo_filter import apply_ops as _apply_ops
from .cuckoo_filter import delete as _delete
from .cuckoo_filter import insert as _insert
from .cuckoo_filter import insert_bulk as _insert_bulk
from .cuckoo_filter import query as _query
from .hashing import fmix32, normalize_keys
from .layout import segment_ranks

_U32 = np.uint32
_SHARD_SALT = _U32(0x51ED270C)


class ShardedCuckooState(NamedTuple):
    table: jnp.ndarray  # uint32[num_partitions, num_words] (sharded over axis)
    count: jnp.ndarray  # int32[num_partitions]


@dataclasses.dataclass(frozen=True)
class ShardedCuckooConfig:
    """Mesh-sharded filter config: fixed partitions mapped onto devices.

    The unit of distribution is the *partition* — an independent sub-filter
    (``shard`` is its per-partition :class:`CuckooConfig`) owned by exactly
    one device. ``num_partitions`` (default: ``num_shards``) is fixed at
    creation and is what the routing hash is taken modulo, so it is baked
    into the stored state; ``num_shards`` is merely how many devices the
    partitions are currently spread over (device d owns the contiguous
    partition range ``[d*P/K, (d+1)*P/K)``). Because key→partition never
    changes, a K→K′ reshard (or a move to a new mesh) relocates whole
    partitions — every packed word moves exactly, zero membership change
    (:meth:`resharded`). Create with ``partitions_per_shard > 1`` to leave
    resharding headroom (K′ must divide ``num_partitions``).
    """

    shard: CuckooConfig          # per-partition filter config
    num_shards: int
    axis_name: str = "data"
    capacity_factor: float = 2.0  # bin capacity overprovision vs n/partitions
    num_partitions: Optional[int] = None  # default: one per shard

    def __post_init__(self):
        p, k = self.partitions, self.num_shards
        if p % k:
            raise ValueError(
                f"num_partitions={p} must be divisible by "
                f"num_shards={k} (each device owns P/K whole partitions)")

    @property
    def partitions(self) -> int:
        return self.num_partitions or self.num_shards

    @property
    def partitions_per_shard(self) -> int:
        return self.partitions // self.num_shards

    def bin_capacity(self, local_batch: int) -> int:
        cap = int(np.ceil(
            local_batch / self.partitions * self.capacity_factor))
        return max(8, cap)

    def init(self) -> ShardedCuckooState:
        lay = self.shard.layout
        return ShardedCuckooState(
            jnp.zeros((self.partitions, lay.num_words), jnp.uint32),
            jnp.zeros((self.partitions,), jnp.int32))

    @property
    def total_slots(self) -> int:
        return self.partitions * self.shard.num_slots

    @property
    def batch_align(self) -> int:
        """Required batch-width divisor: ops split across ``num_shards``.

        Front-ends that choose dispatch shapes (the serving engine's shape
        ladder, DESIGN.md §11) read this to keep every padded batch legal
        for the per-device ``shard_map`` split.
        """
        return self.num_shards

    # -- AMQ protocol surface (repro.amq.protocol.AMQConfig) ----------------
    @property
    def num_slots(self) -> int:
        return self.total_slots

    @property
    def table_bytes(self) -> int:
        return self.partitions * self.shard.table_bytes

    def expected_fpr(self, load_factor: float) -> float:
        """Partitions are independent same-config filters: FPR is theirs."""
        return self.shard.expected_fpr(load_factor)

    @staticmethod
    def for_capacity(capacity: int, num_shards: int, load_factor: float = 0.95,
                     axis_name: str = "data", **kw) -> "ShardedCuckooConfig":
        cf = kw.pop("capacity_factor", 2.0)
        pps = kw.pop("partitions_per_shard", 1)
        partitions = num_shards * pps
        per_partition = int(np.ceil(capacity / partitions))
        return ShardedCuckooConfig(
            CuckooConfig.for_capacity(per_partition, load_factor, **kw),
            num_shards, axis_name, cf, partitions)

    def grown(self, factor: float, *, fp_bits: Optional[int] = None
              ) -> "ShardedCuckooConfig":
        """Next cascade level's config: ``factor``-times the capacity.

        Scales the per-partition filter while keeping the mesh topology
        (``num_shards``, ``num_partitions``, ``axis_name``,
        ``capacity_factor``) fixed, so all levels of a cascade share one
        all-to-all routing pattern. ``fp_bits`` optionally tightens the
        level's fingerprints to meet a smaller FPR share (DESIGN.md §8).

        Every per-partition field other than the sizing ones is carried
        over verbatim via ``dataclasses.replace`` — a grown level keeps the
        parent's eviction policy, insert-engine routing, frontier depth,
        etc. without this method having to enumerate (and silently drop)
        new ``CuckooConfig`` knobs.
        """
        sized = CuckooConfig.for_capacity(
            int(np.ceil(self.shard.num_slots * factor)),
            load_factor=1.0,  # num_slots is already post-load sizing
            fp_bits=self.shard.fp_bits if fp_bits is None else fp_bits,
            bucket_size=self.shard.bucket_size,
            policy=self.shard.policy)
        grown_shard = dataclasses.replace(
            self.shard, num_buckets=sized.num_buckets,
            fp_bits=sized.fp_bits)
        return ShardedCuckooConfig(
            grown_shard,
            self.num_shards, self.axis_name, self.capacity_factor,
            self.num_partitions)

    def resharded(self, num_shards: int, *,
                  axis_name: Optional[str] = None) -> "ShardedCuckooConfig":
        """The same filter spread over ``num_shards`` devices — exactly.

        Only the partition→device mapping changes; the partition count,
        per-partition filter, and therefore every stored word stay fixed,
        so a state restored under the resharded config answers every query
        identically (DESIGN.md §10). ``num_shards`` must divide
        ``num_partitions``.
        """
        p = self.partitions
        if p % num_shards:
            raise ValueError(
                f"cannot reshard {p} partitions onto {num_shards} shards: "
                "each device must own whole partitions (create the filter "
                "with partitions_per_shard > 1 for resharding headroom)")
        return ShardedCuckooConfig(
            self.shard, num_shards,
            self.axis_name if axis_name is None else axis_name,
            self.capacity_factor, p)


def partition_of(config: ShardedCuckooConfig,
                 keys: jnp.ndarray) -> jnp.ndarray:
    """Owner partition per key — a hash independent of in-partition hashes.

    Taken modulo the *fixed* partition count, never the device count, so
    key placement survives resharding.
    """
    mix = fmix32(keys[..., 0] ^ fmix32(keys[..., 1] ^ _SHARD_SALT))
    return (mix % _U32(config.partitions)).astype(jnp.int32)


def shard_of(config: ShardedCuckooConfig, keys: jnp.ndarray) -> jnp.ndarray:
    """Owner device per key: its partition's current home."""
    return partition_of(config, keys) // config.partitions_per_shard


def _route(config: ShardedCuckooConfig, keys: jnp.ndarray, cap: int,
           valid: Optional[jnp.ndarray] = None):
    """Local routing: sort keys into [num_partitions, cap] bins.

    ``valid`` masks caller-side padding keys: they are given the ``P``
    sentinel destination, sort past every real partition group, and never
    claim a bin slot (so they cannot crowd out live keys).

    Returns (bins uint32[P, cap, 2], bin_valid bool[P, cap],
             order, dest_sorted, idx_in_group, routed_sorted, slot).

    ``slot`` is the flat bin address per *sorted* key (``P*cap`` sentinel =
    unrouted); extra per-key channels (the mixed batch's op codes) are
    binned with the same scatter so they travel the identical all-to-all.
    Partitions are contiguous per device, so reshaping the leading ``P``
    axis to ``[num_shards, P/K * cap]`` is exactly the per-device exchange
    layout.
    """
    P = config.partitions
    dest = partition_of(config, keys)
    if valid is not None:
        dest = jnp.where(valid.astype(bool), dest, P)
    order = jnp.argsort(dest, stable=True)
    dest_s = dest[order]
    keys_s = keys[order]
    idx_in_group = segment_ranks(dest_s)
    routed = (idx_in_group < cap) & (dest_s < P)
    slot = jnp.where(routed, dest_s * cap + idx_in_group, P * cap)
    bins = jnp.zeros((P * cap, 2), jnp.uint32).at[slot].set(keys_s, mode="drop")
    bin_valid = jnp.zeros((P * cap,), bool).at[slot].set(routed, mode="drop")
    return (bins.reshape(P, cap, 2), bin_valid.reshape(P, cap),
            order, dest_s, idx_in_group, routed, slot)


def _unroute(order, dest_s, idx_in_group, routed, back, fill=False):
    """Inverse of _route for a per-key result channel ``back[S, cap]``."""
    n = order.shape[0]
    got = back[dest_s, jnp.minimum(idx_in_group, back.shape[1] - 1)]
    got = jnp.where(routed, got, fill)
    return jnp.zeros((n,), back.dtype).at[order].set(got)


def _make_sharded_op(config: ShardedCuckooConfig, op: str, local_batch: int,
                     dedup_within_batch: bool = False):
    """Build the per-device function for one op (runs under shard_map).

    Each device owns ``p_local = P/K`` whole partitions; the filter op is
    vmapped over them. Keys are binned per destination *partition*, the
    ``P``-row bin stack reshaped to ``[K, p_local*cap]`` is exchanged with
    one all-to-all (partitions are contiguous per device), and each
    receiver regroups its ``K`` incoming blocks into per-partition key
    streams.

    ``dedup_within_batch`` is globally correct because duplicates of a key
    hash to the same owner partition: per-partition first-occurrence dedup
    IS whole-batch dedup.

    ``op == "apply_ops"`` is the mixed-batch path: the per-key op codes are
    binned with the same scatter as the keys and travel the same
    all-to-all, so every partition replays its slice of the interleaved
    stream with ``cuckoo_filter.apply_ops``. In-batch order is preserved
    end-to-end: all copies of a key land on its owner partition, the
    routing sort is stable, and the regrouped exchange concatenates source
    devices in mesh order — so same-key operations arrive in global batch
    order.
    """
    cap = config.bin_capacity(local_batch)
    ax = config.axis_name
    K = config.num_shards
    p_local = config.partitions_per_shard

    def regroup(x):
        # [K, p_local*cap, ...] received blocks -> [p_local, K*cap, ...]
        # per-partition streams (source-device-major, preserving order).
        x = x.reshape((K, p_local, cap) + x.shape[2:])
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((p_local, K * cap) + x.shape[3:])

    def ungroup(x):
        # inverse of regroup for result channels.
        x = x.reshape((p_local, K, cap) + x.shape[2:])
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((K, p_local * cap) + x.shape[3:])

    def exchange(x):
        return jax.lax.all_to_all(x, ax, split_axis=0, concat_axis=0,
                                  tiled=False)

    def per_partition(table, count, keys, valid, ops):
        state = CuckooState(table, count)
        if op == "apply_ops":
            state, ok, _ = _apply_ops(config.shard, state, keys, ops,
                                      valid=valid)
        elif op == "insert":
            state, ok, _ = _insert(config.shard, state, keys, valid=valid,
                                   dedup_within_batch=dedup_within_batch)
        elif op == "insert_bulk":
            # The all-to-all already binned keys by owner partition; the
            # bulk path's bucket-major sort composes on top of that binning
            # (DESIGN.md §6) — whole-bucket commits, residue to the loop.
            state, ok, _ = _insert_bulk(config.shard, state, keys,
                                        valid=valid,
                                        dedup_within_batch=dedup_within_batch)
        elif op == "delete":
            state, ok = _delete(config.shard, state, keys, valid=valid)
        elif op == "query":
            ok = _query(config.shard, state, keys) & valid
        else:  # pragma: no cover
            raise ValueError(op)
        return state.table, state.count, ok

    def fn(table, count, keys, valid, ops=None):
        # table: [p_local, num_words] local partitions; keys: [local_batch, 2]
        bins, bin_valid, order, dest_s, idxg, routed, slot = _route(
            config, keys, cap, valid)
        part_keys = regroup(exchange(bins.reshape(K, p_local * cap, 2)))
        part_valid = regroup(exchange(bin_valid.reshape(K, p_local * cap)))

        if op == "apply_ops":
            P = config.partitions
            bin_ops = jnp.zeros((P * cap,), jnp.int32).at[slot].set(
                ops.astype(jnp.int32)[order], mode="drop")
            part_ops = regroup(exchange(bin_ops.reshape(K, p_local * cap)))
        else:
            part_ops = jnp.zeros((p_local, K * cap), jnp.int32)

        table, count, ok = jax.vmap(per_partition)(
            table, count, part_keys, part_valid, part_ops)

        back = exchange(ungroup(ok)).reshape(config.partitions, cap)
        result = _unroute(order, dest_s, idxg, routed, back)
        routed_out = jnp.zeros((keys.shape[0],), bool).at[order].set(routed)
        return table, count, result, routed_out

    return fn


class ShardedCuckooFilter:
    """Driver: owns the mesh-placed state and jitted sharded ops.

    ``mesh`` must contain ``config.axis_name`` with size ``num_shards``.
    Keys arrive sharded along the same axis (global batch split across
    devices); results come back in the same layout.
    """

    def __init__(self, config: ShardedCuckooConfig, mesh: Mesh,
                 local_batch: int,
                 state: Optional[ShardedCuckooState] = None):
        if mesh.shape[config.axis_name] != config.num_shards:
            raise ValueError(
                f"mesh axis {config.axis_name} has size "
                f"{mesh.shape[config.axis_name]}, want {config.num_shards}")
        self.config = config
        self.mesh = mesh
        self.local_batch = local_batch
        self._ops = {}  # (op, dedup) -> jitted shard_map — built lazily
        self.state = jax.device_put(
            config.init() if state is None else state,
            NamedSharding(mesh, P(config.axis_name)))

    def _op(self, op: str, dedup: bool = False):
        key = (op, dedup)
        if key not in self._ops:
            ax = self.config.axis_name
            fn = _make_sharded_op(self.config, op, self.local_batch,
                                  dedup_within_batch=dedup)
            n_in = 5 if op == "apply_ops" else 4
            mapped = jax.shard_map(
                fn, mesh=self.mesh,
                in_specs=(P(ax),) * n_in,
                out_specs=(P(ax), P(ax), P(ax), P(ax)),
                check_vma=False,
            )
            self._ops[key] = jax.jit(mapped)
        return self._ops[key]

    def _run(self, op, keys, valid=None, dedup=False, ops=None):
        keys = normalize_keys(keys)
        if valid is None:
            valid = jnp.ones((keys.shape[0],), bool)
        args = (self.state.table, self.state.count, keys, valid)
        if op == "apply_ops":
            args += (ops,)
        table, count, result, routed = self._op(op, dedup)(*args)
        if op != "query":
            self.state = ShardedCuckooState(table, count)
        return result, routed

    def insert(self, keys, bulk: bool = False, *,
               dedup_within_batch: bool = False,
               valid: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """-> (ok, routed): ok[i] requires routed[i]; retry ~routed keys.

        ``bulk=True`` routes through the bucket-sorted bulk-build fast path
        (core.cuckoo_filter.insert_bulk) on every shard. ``valid`` masks
        caller padding (masked keys report ``routed=False``).
        """
        return self._run("insert_bulk" if bulk else "insert", keys,
                         valid, dedup_within_batch)

    def query(self, keys, valid: Optional[jnp.ndarray] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return self._run("query", keys, valid)

    def delete(self, keys, valid: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return self._run("delete", keys, valid)

    def apply_ops(self, keys, ops, valid: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Mixed-batch pass: -> (ok, routed), ok per that slot's op code.

        Op codes travel the same all-to-all as their keys, so every shard
        replays its slice of the interleaved stream in global batch order
        (see _make_sharded_op).
        """
        return self._run("apply_ops", keys, valid,
                         ops=jnp.asarray(ops, jnp.int32))

    @property
    def total_count(self) -> int:
        return int(jnp.sum(self.state.count))

    def resharded(self, mesh: Mesh,
                  num_shards: Optional[int] = None) -> "ShardedCuckooFilter":
        """Exact K→K′ / new-mesh migration: relocate partitions, keep state.

        Returns a new driver on ``mesh`` whose state arrays are the *same
        values* re-placed over the new device set (key→partition is fixed,
        so membership is bit-for-bit preserved — DESIGN.md §10). The new
        shard count must divide ``num_partitions``.
        """
        k = num_shards or mesh.shape[self.config.axis_name]
        # keep the *global* batch: per-device batches scale inversely with K
        return ShardedCuckooFilter(
            self.config.resharded(k), mesh,
            max(1, self.local_batch * self.config.num_shards // k),
            state=ShardedCuckooState(*map(jnp.asarray, self.state)))

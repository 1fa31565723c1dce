"""FilterHandle: the one stateful object every consumer programs against.

Wraps (adapter, config, state) with per-op cached jits. State buffers are
donated to mutating ops on every backend (the handle immediately replaces
its state, so the old buffers are dead — donation lets XLA update the table
in place, the batch analogue of the paper's in-place CAS writes). A caller
that kept a reference to an old ``handle.state`` finds it deleted after the
next mutating op, on the CPU exactly as on a TPU.

Each jitted op compiles under the name ``<backend>_<op>``
(:func:`~repro.amq.adapters.program_name`), so a device trace names the
program ``jit_cuckoo_query``; each op is also the host span
``amq.handle.<op>`` (:mod:`repro.amq.spans`).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import numpy as np

from ..core.hashing import normalize_keys
from .adapters import (
    AMQAdapter,
    config_fingerprint,
    named,
    program_name,
    segmented_apply_ops,
)
from .protocol import (
    Capabilities,
    DeleteReport,
    InsertReport,
    MixedReport,
    OpBatch,
    QueryResult,
    Snapshot,
    SnapshotMismatchError,
    load_factor as _load_factor,
)
from .spans import span


def _check_snapshot_target(adapter: AMQAdapter, config: Any,
                           snap: Snapshot) -> None:
    """Validate that ``snap`` may restore onto (adapter, config) — loudly."""
    if snap.kind != "filter":
        raise SnapshotMismatchError(
            f"cannot restore a {snap.kind!r} snapshot onto a static "
            "FilterHandle (cascade snapshots restore onto cascades)")
    if snap.backend != adapter.name:
        raise SnapshotMismatchError(
            f"snapshot is from backend {snap.backend!r}, "
            f"this handle is {adapter.name!r}")
    fp = config_fingerprint(adapter, config)
    if snap.fingerprint != fp:
        raise SnapshotMismatchError(
            f"config fingerprint mismatch:\n  snapshot: "
            f"{snap.fingerprint}\n  target:   {fp}")


class FilterHandle:
    """Stateful AMQ handle with capability-driven, uniform ops.

    Obtain via :func:`repro.amq.make`. All ops take ``uint32[n, 2]`` key
    batches and return the protocol's standardized reports; ``insert`` takes
    the unified keyword options (``bulk``, ``dedup_within_batch``,
    ``valid``) and raises on capability violations instead of silently
    degrading.
    """

    def __init__(self, adapter: AMQAdapter, config: Any, state: Any = None):
        """Wrap (adapter, config, state); a fresh state is built if None."""
        self.adapter = adapter
        self.config = config
        self.state = adapter.init(config) if state is None else state
        self._jits = {}

    # -- introspection -------------------------------------------------------

    @property
    def name(self) -> str:
        """Registry name of the wrapped backend (e.g. ``"cuckoo"``)."""
        return self.adapter.name

    @property
    def capabilities(self) -> Capabilities:
        """The backend's capability flags — branch on these, not on names.

        Example::

            >>> if handle.capabilities.supports_delete:
            ...     handle.delete(expired_keys)
        """
        return self.adapter.capabilities

    @property
    def load_factor(self) -> float:
        """Current occupancy: stored keys / nominal capacity."""
        return _load_factor(self.config, self.state)

    @property
    def table_bytes(self) -> int:
        """Device memory footprint of the filter state."""
        return self.config.table_bytes

    def expected_fpr(self, load_factor: Optional[float] = None) -> float:
        """Analytic FPR at ``load_factor`` (default: current occupancy).

        Example::

            >>> amq.make("cuckoo", capacity=1000).expected_fpr(0.95)
            0.000463...
        """
        lf = self.load_factor if load_factor is None else load_factor
        return self.config.expected_fpr(lf)

    def __repr__(self) -> str:  # pragma: no cover — debugging aid
        """Summarize backend, size, and capabilities."""
        return (f"FilterHandle({self.adapter.name!r}, "
                f"slots={self.config.num_slots}, "
                f"bytes={self.config.table_bytes}, "
                f"caps={self.adapter.capabilities})")

    # -- ops -----------------------------------------------------------------

    def _fn(self, op: str, **static):
        key = (op, tuple(sorted(static.items())))
        if key not in self._jits:
            raw = functools.partial(getattr(self.adapter, op), self.config,
                                    **static)
            if self.adapter.jit:
                donate = (0,) if op != "query" else ()
                raw = jax.jit(named(raw, program_name(self.name, op)),
                              donate_argnums=donate)
            self._jits[key] = raw
        return self._jits[key]

    def compiled(self, op: str, *args, valid=None,
                 **static) -> jax.stages.Compiled:
        """The compiled program of ``op`` for these arguments.

        ``args`` are what the op takes after the state (``keys``, and
        ``ops`` for ``apply_ops``), as arrays or ``jax.ShapeDtypeStruct``;
        ``static`` are the op's static options (``dedup_within_batch``).
        The program is the one the op runs at these shapes, so its
        ``as_text()`` names the instructions a device trace of the op shows.
        Only for backends the handle jits (``adapter.jit``).

        Example::

            >>> keys = jax.ShapeDtypeStruct((1 << 20, 2), jnp.uint32)
            >>> text = handle.compiled("query", keys).as_text()
        """
        if not self.adapter.jit:
            raise NotImplementedError(
                f"{self.name}: the handle does not compile this backend's "
                "ops (adapter.jit is False)")
        return self._fn(op, **static).lower(self.state, *args,
                                            valid=valid).compile()

    def insert(self, keys, *, bulk: bool = False,
               dedup_within_batch: bool = False,
               valid=None) -> InsertReport:
        """Insert a batch of ``uint32[n, 2]`` keys.

        ``bulk=True`` takes the bucket-sorted bulk-build fast path
        (requires ``supports_bulk``); ``dedup_within_batch`` degrades the
        batch to set semantics; ``valid`` masks caller padding.

        Example::

            >>> report = handle.insert(keys, bulk=True)
            >>> bool(report.ok.all())          # everything landed
            True
        """
        op = "insert"
        if bulk:
            if not self.adapter.capabilities.supports_bulk:
                raise NotImplementedError(
                    f"{self.name}: no bulk-build path "
                    "(capabilities.supports_bulk is False)")
            op = "insert_bulk"
        fn = self._fn(op, dedup_within_batch=dedup_within_batch)
        with span("handle." + op) as s:
            keys = normalize_keys(keys)
            s.set_metadata(keys=keys.shape[0])
            self.state, report = fn(self.state, keys, valid=valid)
        return report

    def query(self, keys, *, valid=None) -> QueryResult:
        """Batch membership: no false negatives, FPR-bounded positives.

        Example::

            >>> hits = handle.query(keys).hits  # bool[n]
        """
        with span("handle.query") as s:
            keys = normalize_keys(keys)
            s.set_metadata(keys=keys.shape[0])
            _, result = self._fn("query")(self.state, keys, valid=valid)
        return result

    def delete(self, keys, *, valid=None) -> DeleteReport:
        """Remove one stored copy per key (requires ``supports_delete``).

        Example::

            >>> report = handle.delete(keys)    # raises on e.g. "bloom"
            >>> bool(report.ok.all())
            True
        """
        if not self.adapter.capabilities.supports_delete:
            raise NotImplementedError(
                f"{self.name}: append-only structure "
                "(capabilities.supports_delete is False)")
        with span("handle.delete") as s:
            keys = normalize_keys(keys)
            s.set_metadata(keys=keys.shape[0])
            self.state, report = self._fn("delete")(self.state, keys,
                                                    valid=valid)
        return report

    def apply_ops(self, batch: OpBatch) -> MixedReport:
        """Execute an interleaved query/insert/delete stream (one OpBatch).

        Backends with ``capabilities.supports_mixed`` run the batch as one
        fused program (one dispatch, one pass over the table); every other
        backend is served by :func:`repro.amq.adapters.segmented_apply_ops`
        (one dispatch per maximal same-op run). Same-key operations resolve
        in batch order either way (DESIGN.md §9).

        Example::

            >>> from repro.amq import OpBatch, OP_INSERT, OP_QUERY
            >>> batch = OpBatch.make(keys, [OP_INSERT, OP_QUERY])
            >>> bool(handle.apply_ops(batch).ok.all())   # doctest: +SKIP
            True
        """
        with span("handle.apply_ops", keys=batch.keys.shape[0]):
            if self.adapter.apply_ops is None:
                return segmented_apply_ops(self, batch)
            fn = self._fn("apply_ops")
            self.state, report = fn(self.state, batch.keys, batch.ops,
                                    valid=batch.valid)
        return report

    def count(self) -> int:
        """Stored-key count (summed across shards where applicable)."""
        c = getattr(self.state, "count")
        return int(np.sum(np.asarray(c)))

    # -- lifecycle (DESIGN.md §10) -------------------------------------------

    @property
    def fingerprint(self) -> str:
        """This handle's config-identity string (snapshot compatibility)."""
        return config_fingerprint(self.adapter, self.config)

    def snapshot(self) -> Snapshot:
        """Pull the filter state to host as a versioned :class:`Snapshot`.

        The payload (config fingerprint + packed table arrays) survives
        process restarts (:func:`repro.amq.save_snapshot`), restores onto
        any handle whose config fingerprint matches — including, for the
        sharded backend, a different mesh or shard count — and feeds
        :meth:`repro.amq.FilterService.hot_swap`.

        Example::

            >>> snap = handle.snapshot()
            >>> twin = amq.make(handle.name, config=handle.config,
            ...                 snapshot=snap)      # bit-exact replica
        """
        if not self.adapter.capabilities.supports_snapshot:
            raise NotImplementedError(
                f"{self.name}: state cannot be snapshotted "
                "(capabilities.supports_snapshot is False)")
        arrays = self.adapter.snapshot(self.config, self.state)
        return Snapshot(
            backend=self.name, kind="filter", fingerprint=self.fingerprint,
            arrays=arrays,
            meta={"count": self.count(),
                  "num_slots": int(self.config.num_slots),
                  "table_bytes": int(self.config.table_bytes)},
            configs=(self.config,))

    def restore(self, snap: Snapshot) -> "FilterHandle":
        """Replace this handle's state with a snapshot's — validated.

        The snapshot must come from the same backend and a config with an
        identical fingerprint; anything else raises
        :class:`~repro.amq.protocol.SnapshotMismatchError` (a partial-key
        table is meaningless under different hashes/layout). Returns
        ``self`` for chaining.
        """
        _check_snapshot_target(self.adapter, self.config, snap)
        self.state = self.adapter.restore(self.config, snap.arrays)
        return self

    @classmethod
    def from_snapshot(cls, adapter: AMQAdapter, config: Any,
                      snap: Snapshot) -> "FilterHandle":
        """Build a handle whose initial state *is* the snapshot's.

        Equivalent to ``FilterHandle(adapter, config).restore(snap)`` but
        without allocating (and immediately discarding) a fresh zero
        table first — restore latency is a tracked serving metric.
        """
        _check_snapshot_target(adapter, config, snap)
        return cls(adapter, config, adapter.restore(config, snap.arrays))

    def resharded(self, num_shards: Optional[int] = None,
                  **kw) -> "FilterHandle":
        """Exact reshard: the same filter on a different device layout.

        Only meaningful for backends whose config exposes a ``resharded``
        hook (the mesh-sharded cuckoo filter): returns a *new* handle
        whose state holds the same partitions re-placed over ``num_shards``
        devices (or an explicit ``mesh=``), with zero membership change —
        the config fingerprint deliberately excludes placement, so the
        snapshot round-trip is legal by construction (DESIGN.md §10).

        Example::

            >>> h2 = h.resharded(num_shards=2)     # K -> K' migration
            >>> svc.hot_swap(h2)                   # and into service
        """
        hook = getattr(self.config, "resharded", None)
        if hook is None:
            raise NotImplementedError(
                f"{self.name}: backend config has no resharding surface "
                "(only mesh-sharded backends relocate partitions)")
        return FilterHandle.from_snapshot(
            self.adapter, hook(num_shards, **kw), self.snapshot())

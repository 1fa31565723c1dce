"""The AMQ registry: name -> adapter, and the ``make`` front door.

    from repro import amq

    handle = amq.make("cuckoo", capacity=1_000_000)
    report = handle.insert(keys, bulk=True)
    hits = handle.query(keys).hits

Backends registered by default: ``cuckoo``, ``bloom``, ``tcf``, ``gqf``,
``bcht``, ``sharded-cuckoo``, plus the host-side conformance oracle
``cpu-cuckoo``. Register additional backends with :func:`register` — the
conformance suite (tests/test_amq_api.py) and the benchmark consumers pick
them up automatically.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional

from .adapters import DEFAULT_ADAPTERS, AMQAdapter, program_name
from .handle import FilterHandle


def _validate(adapter: AMQAdapter) -> None:
    """Capability flags must match the ops actually provided, so consumers
    that branch on a flag get the documented NotImplementedError — never a
    'NoneType is not callable' deep inside a jit cache."""
    caps = adapter.capabilities
    if program_name(adapter.name, "").startswith("bench_"):
        raise ValueError(
            f"{adapter.name!r}: backend names may not start with 'bench' "
            "(device traces count programs named jit_bench_* as the "
            "benchmark's own, not the filter's)")
    if caps.supports_delete and not callable(adapter.delete):
        raise ValueError(
            f"{adapter.name!r}: supports_delete=True but no delete op")
    if caps.supports_bulk and not callable(adapter.insert_bulk):
        raise ValueError(
            f"{adapter.name!r}: supports_bulk=True but no insert_bulk op")
    if caps.supports_expand and not adapter.growth_sizings:
        raise ValueError(
            f"{adapter.name!r}: supports_expand=True but no growth_sizings "
            "hook (the cascade cannot size levels to their FPR shares)")
    if caps.supports_mixed and not callable(adapter.apply_ops):
        raise ValueError(
            f"{adapter.name!r}: supports_mixed=True but no apply_ops op "
            "(the fused mixed-batch path it advertises)")
    if caps.supports_snapshot and not (callable(adapter.snapshot)
                                       and callable(adapter.restore)):
        raise ValueError(
            f"{adapter.name!r}: supports_snapshot=True but missing "
            "snapshot/restore hooks (the lifecycle surface it advertises)")
    if caps.supports_tiering:
        if not callable(adapter.host_query):
            raise ValueError(
                f"{adapter.name!r}: supports_tiering=True but no host_query "
                "hook (cold levels could never be probed)")
        if not (caps.supports_snapshot and caps.supports_expand):
            raise ValueError(
                f"{adapter.name!r}: supports_tiering=True requires "
                "supports_snapshot and supports_expand (demotion freezes "
                "cascade levels through the snapshot path)")
        if caps.supports_delete and not callable(adapter.host_delete):
            raise ValueError(
                f"{adapter.name!r}: supports_tiering with supports_delete "
                "needs a host_delete hook (cold-tier deletes are host-side "
                "slot clears)")


def register(adapter: AMQAdapter, *, overwrite: bool = False) -> None:
    """Add a backend to the registry (``overwrite=True`` to replace)."""
    _validate(adapter)
    if adapter.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {adapter.name!r} already registered")
    _REGISTRY[adapter.name] = adapter


_REGISTRY: Dict[str, AMQAdapter] = {}
for _adapter in DEFAULT_ADAPTERS.values():
    register(_adapter)


def get(name: str) -> AMQAdapter:
    """Look up a backend adapter by registry name."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown AMQ backend {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def names() -> Iterable[str]:
    """Registered backend names, in registration order."""
    return tuple(_REGISTRY)


def make(name: str, capacity: Optional[int] = None, *,
         config: Any = None, state: Any = None, snapshot: Any = None,
         auto_expand=False, tiered: bool = False, **kw):
    """Build a ready-to-use filter handle.

    Either pass ``capacity`` (+ backend-specific sizing kwargs, forwarded to
    the adapter's ``make_config``) or a pre-built ``config``. ``state``
    resumes from an existing state pytree; ``snapshot`` restores a
    :class:`~repro.amq.protocol.Snapshot` (taken with ``handle.snapshot()``
    or loaded with :func:`~repro.amq.protocol.load_snapshot`) onto the
    freshly built handle — the snapshot's config fingerprint must match
    the config built here, else
    :class:`~repro.amq.protocol.SnapshotMismatchError` (DESIGN.md §10).

    ``auto_expand=True`` returns a :class:`repro.amq.cascade.CascadeHandle`
    instead of a static :class:`FilterHandle`: ``capacity`` becomes the
    *initial* level size and the filter grows online as a geometric cascade
    (DESIGN.md §8), so streaming workloads need no a-priori sizing. Cascade
    tuning knobs (``growth``, ``watermark``, ``fpr_budget``,
    ``split_ratio``, ``max_levels``) ride along in ``**kw`` next to the
    backend's sizing kwargs. Requires ``capabilities.supports_expand``;
    ``auto_expand="auto"`` expands when the backend supports it and falls
    back to a static handle otherwise (the consumer-friendly default for
    backend-generic callers).

    ``tiered=True`` returns a :class:`repro.amq.tiering.TieredHandle`: an
    auto-expanding cascade whose device footprint is capped at
    ``device_budget_bytes`` (required in ``**kw`` unless a tiered
    ``snapshot`` carries it) — older levels are frozen into host-RAM numpy
    arrays and probed off-device (DESIGN.md §12). Mutually exclusive with
    ``auto_expand`` (a tiered handle *is* an auto-expanding cascade).
    Requires ``capabilities.supports_tiering``.

    Example::

        >>> h = amq.make("cuckoo", capacity=100_000, auto_expand=True)
        >>> h.insert(keys)                # any volume; levels allocate lazily
        >>> len(h.levels)                 # doctest: +SKIP
        4
    """
    adapter = get(name)
    if auto_expand == "auto":
        auto_expand = adapter.capabilities.supports_expand
    if snapshot is not None and state is not None:
        raise TypeError("pass state= or snapshot=, not both")
    if tiered:
        if auto_expand:
            raise TypeError(
                "tiered=True already auto-expands; drop auto_expand=")
        if config is not None or state is not None:
            raise TypeError(
                "tiered=True sizes and allocates levels itself; pass "
                "capacity=..., not config=/state=")
        if capacity is None:
            raise TypeError("make(tiered=True) needs capacity=...")
        if "device_budget_bytes" not in kw and snapshot is not None:
            kw["device_budget_bytes"] = snapshot.meta["device_budget_bytes"]
        if "device_budget_bytes" not in kw:
            raise TypeError("make(tiered=True) needs device_budget_bytes=...")
        from .tiering import TieredHandle

        handle = TieredHandle(adapter, capacity, **kw)
        if snapshot is not None:
            handle.restore(snapshot)
        return handle
    if auto_expand:
        if config is not None or state is not None:
            raise TypeError(
                "auto_expand=True sizes and allocates levels itself; pass "
                "capacity=..., not config=/state=")
        if capacity is None:
            raise TypeError("make(auto_expand=True) needs capacity=...")
        from .cascade import CascadeHandle

        handle = CascadeHandle(adapter, capacity, **kw)
        if snapshot is not None:
            handle.restore(snapshot)
        return handle
    if config is None:
        if capacity is None:
            raise TypeError("make() needs capacity=... or config=...")
        config = adapter.make_config(capacity, **kw)
    elif capacity is not None or kw:
        extra = (["capacity"] if capacity is not None else []) + sorted(kw)
        raise TypeError(f"config= given; conflicting arguments {extra}")
    if snapshot is not None:
        # Build straight from the snapshot: no discarded fresh table.
        return FilterHandle.from_snapshot(adapter, config, snapshot)
    return FilterHandle(adapter, config, state)

"""Host spans at the AMQ layer boundaries, on the profiler's clock.

``span("handle.query", keys=n)`` is a ``jax.profiler.TraceAnnotation``
named ``amq.handle.query`` that carries ``keys=n``. While a JAX profiler
trace records, it is an event of the trace's host plane, on the same clock
as the device's programs; otherwise it records nothing and costs about a
microsecond. The profiler being on is the only switch.

The spans (PERF.md §3 names what reads each):

* ``amq.handle.<op>``: one ``FilterHandle`` op (``keys``: batch width);
* ``amq.service.submit``: one ``FilterService.submit`` (``ticket``: the
  ticket's sequence number, ``ops``: its op count);
* ``amq.service.dispatch``: one micro-batch leaving the queue
  (``dispatch``, ``live``, ``shape``, ``kind``, and the ``first`` and
  ``last`` ticket it serves); the handle's span nests inside it;
* ``amq.service.ready``: the host waiting for one dispatch's results
  (``dispatch``).

``ServiceMetrics`` stays the operator's aggregate of the same boundaries;
the spans are the per-event timeline.
"""

from __future__ import annotations

import jax

PREFIX = "amq."


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    """The span ``amq.<name>`` with ``counts`` as its arguments; more can be
    added inside it with ``set_metadata``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **counts)

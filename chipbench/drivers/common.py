"""What the drivers share: the filter a config describes, its fill, spans.

A config names the backend and its geometry; :func:`make_handle` builds it
through the program's public entry point, ``repro.amq.make``. The fill runs
the program's bulk insert over key blocks made on the device, with the
failure count kept on the device and read once.
"""

from __future__ import annotations

import contextlib
import functools
import math
import time
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import keys as K


def make_handle(config: dict):
    """The config's filter, built by ``repro.amq.make``."""
    from repro import amq
    from repro.core import CuckooConfig

    if config["backend"] != "cuckoo":
        raise ValueError(f"no driver support for backend {config['backend']!r}")
    return amq.make("cuckoo", config=CuckooConfig(
        num_buckets=config["num_buckets"], fp_bits=config["fp_bits"],
        bucket_size=config["bucket_size"], policy=config["policy"],
        hash_kind=config["hash_kind"]))


def fill_size(config: dict) -> int:
    """Keys that bring the table to the config's load."""
    return math.ceil(config["load"] * config["num_buckets"]
                     * config["bucket_size"])


@jax.jit
def bench_failed(acc, ok, routed, valid):
    """Running count of valid keys an insert did not place."""
    return acc + jnp.sum(valid & ~(ok & routed), dtype=jnp.int32)


@functools.partial(jax.jit, static_argnums=0)
def bench_valid(width: int, count):
    """``arange(width) < count`` as a device mask."""
    return jnp.arange(width) < count


def fill(handle, stream: int, count: int, offset, width: int) -> int:
    """Bulk-insert keys ``(stream, [0, count))`` in ``width``-key batches;
    returns the number of keys not placed."""
    failed = jnp.zeros((), jnp.int32)
    for a in range(0, count, width):
        keys = K.bench_key_block(width, stream, np.uint32(a), offset)
        valid = bench_valid(width, np.int32(min(width, count - a)))
        rep = handle.insert(keys, bulk=True, valid=valid)
        failed = bench_failed(failed, rep.ok, rep.routed, valid)
    return int(failed)


class Spans:
    """Host time per benchmark span, and the same spans in a device trace.

    ``with spans("submit"): ...`` adds the block's host-clock seconds to
    ``spans.seconds["submit"]``; while a trace records, the block is also a
    ``TraceAnnotation`` named ``bench.submit``, so the trace reduction can
    name the device's idle gaps by what the host was doing.
    """

    def __init__(self):
        self.seconds = defaultdict(float)
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        if self.tracing:
            with jax.profiler.TraceAnnotation("bench." + name):
                yield
        else:
            yield
        self.seconds[name] += time.perf_counter() - t


@contextlib.contextmanager
def traced(trace_dir, spans: Spans):
    """Record a device trace of the block (no-op when ``trace_dir`` is None),
    with the block as the ``bench.window`` span."""
    if trace_dir is None:
        yield
        return
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # Python calls would swamp the trace
    options.host_tracer_level = 1     # user spans, not every runtime event
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    spans.tracing = True
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield
    finally:
        spans.tracing = False
        jax.profiler.stop_trace()

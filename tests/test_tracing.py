"""The filter's programs, phases and host spans, as a device trace sees them.

* every op a backend jits compiles under ``jit_<backend>_<op>`` (never
  ``jit__unknown``, never the benchmark's ``jit_bench_*``), with the state
  still donated to the mutating ops;
* a profiler trace of a small ``FilterService`` run holds the service's and
  the handle's spans, nested, with their ticket and count arguments;
* the compiled query program charges every fusion to one of its phases;
* the service's latency histograms read percentiles at most 2^(1/4) high.
"""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import amq
from repro.amq import adapters, registry
from repro.amq.dispatch import LatencyHistogram
from repro.core import keys_from_numpy

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT / "chipbench") not in sys.path:
    sys.path.insert(0, str(ROOT / "chipbench"))

from yardstick import scopes  # noqa: E402

N = 256
OPS = ("insert", "insert_bulk", "query", "delete", "apply_ops")
QUERY_PHASES = {"hash", "row_gather", "lane_select", "tag_match", "-"}


def _keys(n=N, seed=0):
    rng = np.random.default_rng(seed)
    return keys_from_numpy(np.unique(
        rng.integers(1, 2**63, size=2 * n, dtype=np.uint64))[:n])


def _ops_of(adapter):
    return [op for op in OPS if getattr(adapter, op) is not None]


JITTED = [(name, op) for name in registry.names()
          for op in _ops_of(registry.get(name))
          if registry.get(name).jit]


def _header(text: str) -> str:
    return next(ln for ln in text.splitlines() if ln.startswith("HloModule"))


@pytest.mark.parametrize("backend,op", JITTED)
def test_handle_op_compiles_under_its_program_name(backend, op):
    h = amq.make(backend, capacity=4 * N)
    keys = jax.ShapeDtypeStruct((N, 2), jnp.uint32)
    args = (keys, jax.ShapeDtypeStruct((N,), jnp.int32)) \
        if op == "apply_ops" else (keys,)
    static = ({"dedup_within_batch": False}
              if op in ("insert", "insert_bulk") else {})
    header = _header(h.compiled(op, *args, **static).as_text())
    module = header.split()[1].rstrip(",")
    assert module == f"jit_{backend}_{op}".replace("-", "_")
    assert not module.startswith(("jit__unknown", "jit_bench_"))
    # The name is set on the callable, not through functools.wraps, so the
    # state (argument 0) is still donated to every mutating op.
    assert ("input_output_alias" in header) == (op != "query"), header


@pytest.mark.parametrize("op", _ops_of(registry.get("sharded-cuckoo")))
def test_sharded_op_compiles_under_its_program_name(op):
    h = amq.make("sharded-cuckoo", capacity=4 * N)
    fn = adapters._sharded_fn(h.config, op, N // h.config.inner.num_shards,
                              False)
    args = [h.state.table, h.state.count, _keys(), jnp.ones((N,), bool)]
    if op == "apply_ops":
        args.append(jnp.zeros((N,), jnp.int32))
    module = _header(fn.lower(*args).compile().as_text()).split()[1]
    assert module.rstrip(",") == f"jit_sharded_cuckoo_{op}"


def test_backend_names_may_not_claim_the_benchmark_prefix():
    import dataclasses

    bad = dataclasses.replace(registry.get("bloom"), name="bench-bloom")
    with pytest.raises(ValueError, match="may not start with 'bench'"):
        registry.register(bad)
    assert "bench-bloom" not in registry.names()


def test_compiled_refuses_backends_the_handle_does_not_jit():
    h = amq.make("cpu-cuckoo", capacity=4 * N)
    with pytest.raises(NotImplementedError, match="adapter.jit is False"):
        h.compiled("query", _keys())


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    profile = ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in profile.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith("amq.")]


def test_service_trace_holds_nested_spans_with_their_arguments(tmp_path):
    svc = amq.FilterService(amq.make("cuckoo", capacity=8 * N),
                            batch_size=N, max_in_flight=1)
    keys = _keys(2 * N, seed=1)
    svc.insert(keys[:N])                      # warm the one shape untraced
    svc.drain()
    with jax.profiler.trace(str(tmp_path)):
        t1 = svc.insert(keys[N:N + 100])
        t2 = svc.query(keys[:N])               # fills a batch: dispatches
        assert t2.result().all()
        svc.drain()
        assert t1.result().all()
    events = _host_events(tmp_path)
    by_name = {}
    for ev in events:
        by_name.setdefault(ev[0], []).append(ev)

    submits = by_name["amq.service.submit"]
    assert [e[3]["ticket"] for e in submits] == [t1.seq, t2.seq]
    assert [e[3]["ops"] for e in submits] == [100, N]
    assert (t1.seq, t2.seq) == (1, 2)

    dispatches = by_name["amq.service.dispatch"]
    assert len(dispatches) == 2
    first = dispatches[0][3]
    assert (first["live"], first["shape"], first["kind"]) == (N, N, "full")
    assert (first["first"], first["last"]) == (t1.seq, t2.seq)
    handle_spans = by_name["amq.handle.apply_ops"]
    assert len(handle_spans) == 2
    for (_, ds, de, d), (_, hs, he, h) in zip(dispatches, handle_spans):
        assert ds <= hs and he <= de
        assert h["keys"] == d["shape"]
    # The ready spans name the dispatches they waited for, by sequence.
    assert sorted(e[3]["dispatch"] for e in by_name["amq.service.ready"]) \
        == [1, 2]


def test_handle_spans_name_the_op_and_its_batch_width(tmp_path):
    h = amq.make("cuckoo", capacity=4 * N)
    keys = _keys()
    h.insert(keys, bulk=True)
    h.query(keys)
    with jax.profiler.trace(str(tmp_path)):
        h.insert(keys[:64], bulk=True)
        h.query(keys)
        h.delete(keys[:32])
    spans = [(n, s["keys"]) for n, _, _, s in _host_events(tmp_path)]
    assert spans == [("amq.handle.insert_bulk", 64), ("amq.handle.query", N),
                     ("amq.handle.delete", 32)]


def test_query_program_charges_every_fusion_to_a_phase():
    h = amq.make("cuckoo", capacity=4 * N)
    text = h.compiled("query", _keys()).as_text()
    table = scopes.phases(text)
    fusions = [ln.split(" = ", 1)[0].split("%", 1)[1]
               for ln in text.splitlines()
               if " fusion(" in ln and ln.startswith("  ")]
    assert fusions
    charged = {table[f] for f in fusions}
    assert charged <= QUERY_PHASES, charged
    assert {"hash", "row_gather", "tag_match"} <= set(table.values())


@pytest.mark.parametrize("dist", ["uniform", "lognormal"])
def test_latency_percentiles_are_at_most_a_quarter_octave_high(dist):
    rng = np.random.default_rng(7)
    sample = (rng.uniform(1e-5, 2e-1, 20_000) if dist == "uniform"
              else rng.lognormal(np.log(5e-3), 1.5, 20_000))
    hist = LatencyHistogram()
    hist.observe(sample)
    summary = hist.summary()
    for q, key in ((0.50, "p50_s"), (0.99, "p99_s")):
        true = np.quantile(sample, q, method="inverted_cdf")
        assert true <= hist.percentile(q) <= true * 2 ** 0.25
        assert summary[key] == hist.percentile(q)
    assert summary["count"] == sample.size

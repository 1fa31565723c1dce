"""The trace reduction on a trace recorded on one TPU v5e.

``data/v5e_query_apply_ops.xplane.pb`` was recorded through the harness's
own tracing (``drivers.common.traced`` and ``Spans``) on a 2^16-bucket
table: four query calls of 2^16 keys, each followed by the benchmark's hit
count program and a wait; a 5 ms ``bench.sleep``; one 4,096-op
``apply_ops`` dispatch and the wait for its result. The ``/host:metadata``
plane, which holds only the programs' HLO and which the reduction does not
read, was removed to keep the file small.
"""

import pathlib

import pytest

import chipbench_harness  # noqa: F401  (puts chipbench/ on the path)
from yardstick import trace as tr

FIXTURE = pathlib.Path(__file__).parent / "data" / "v5e_query_apply_ops.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    return tr.reduce(ProfileData.from_file(str(FIXTURE)))


def test_fixture_is_small():
    assert FIXTURE.stat().st_size < 512 * 1024


def test_window_and_busy_time(summary):
    assert summary["devices"] == 1
    assert 0.015 < summary["window_s"] < 0.02
    assert 0 < summary["busy_s"] < summary["window_s"]
    # Busy is the union of operations inside the programs' executions.
    assert summary["busy_s"] <= sum(summary["module_s"].values()) + 1e-9


def test_program_time_splits_system_from_benchmark(summary):
    assert set(summary["module_s"]) == {"jit__unknown", "jit_bench_hit_count"}
    assert summary["system_s"] == pytest.approx(
        summary["module_s"]["jit__unknown"])
    assert summary["module_s"]["jit_bench_hit_count"] < 1e-4


def test_top_operations_are_named_by_program(summary):
    ops = summary["top_ops"]
    assert 0 < len(ops) <= tr.TOP
    assert all(name.startswith("jit__unknown/") for name, _ in ops[:4])
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)


def test_idle_gaps_are_named_by_the_host_span_open_over_them(summary):
    gaps = summary["idle_gaps"]
    assert gaps[0][0] == "bench.sleep"
    assert 0.005 <= gaps[0][1] < 0.012
    assert {name for name, _ in gaps} <= {
        "bench.sleep", "bench.wait", "bench.dispatch", "bench.submit",
        "bench.result", "-"}


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert tr.op_name("%fusion.1 = u32[8] fusion(x)") == "fusion.1"
    assert tr.module_name("jit__unknown(123)") == "jit__unknown"

"""The phase readers on a window of ``bulk.query95`` recorded on one TPU v5e.

``data/v5e_query95.xplane.pb`` is a 0.12 s traced window of the cell at its
size (2^22 buckets at load 0.95, 2^20-key query calls, 4 in flight; 8
calls), recorded through the harness's own ``Cell.window``, with only the
``/host:CPU`` and ``/device:TPU:0`` planes kept (the ``/host:metadata``
plane holds the programs' HLO, which no reader uses).
``data/v5e_query95.query.hlo.txt`` is the compiled text of the query
program that ran, from ``FilterHandle.compiled`` as the readers get it;
``data/v5e_query95.json`` holds the seed, the keys the window answered and
what each reader returned on the chip. The older trace,
``data/v5e_query_apply_ops.xplane.pb``, predates the named programs: its
summary, as the reduction gave it before them, is pinned in
``data/v5e_query_apply_ops.summary.json``.
"""

import json
import pathlib

import pytest

import chipbench_harness as H
from yardstick import scopes
from yardstick import trace as tr

DATA = pathlib.Path(__file__).parent / "data"
TRACE = DATA / "v5e_query95.xplane.pb"
HLO = DATA / "v5e_query95.query.hlo.txt"
RECORDED = json.loads((DATA / "v5e_query95.json").read_text())
OLD_TRACE = DATA / "v5e_query_apply_ops.xplane.pb"
QUERY_PHASES = {"hash", "row_gather", "lane_select", "tag_match", "-"}
READERS = ("query_gather_ns", "query_select_ns")


def _profile(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


@pytest.fixture(scope="module")
def profile():
    return _profile(TRACE)


@pytest.fixture(scope="module")
def table():
    return scopes.phases(HLO.read_text())


@pytest.fixture(scope="module")
def every_op(profile):
    """The window's summary with every operation listed, not the top ten."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tr, "TOP", 10**9)
        return tr.reduce(profile)


def _record(profile, **extra):
    return dict({"trace": tr.reduce(profile),
                 "traced_ops": {"query": RECORDED["keys"]}}, **extra)


def _read(name, record):
    return H.run.load_module(
        H.ROOT / "chipbench" / "metrics" / f"{name}.py").read(record)


def test_fixtures_are_small():
    assert TRACE.stat().st_size < 512 * 1024
    assert HLO.stat().st_size < 512 * 1024


def test_the_older_trace_reduces_as_before():
    want = json.loads((DATA / "v5e_query_apply_ops.summary.json").read_text())
    assert tr.reduce(_profile(OLD_TRACE)) == want


def test_breakdown_names_the_query_program(profile):
    summary = tr.reduce(profile)
    assert set(summary["module_s"]) == {scopes.QUERY_PROGRAM,
                                        "jit_bench_hit_count"}
    names = [name for name, _ in summary["top_ops"]]
    assert all(n.startswith(scopes.QUERY_PROGRAM + "/") for n in names)
    assert not any("jit__unknown" in n for n in names)


def test_every_query_operation_is_an_instruction_with_a_query_phase(
        every_op, table):
    seconds = scopes.phase_seconds(every_op["top_ops"], scopes.QUERY_PROGRAM,
                                   table)
    assert seconds is not None
    assert set(seconds) <= QUERY_PHASES
    assert {"row_gather", "lane_select"} <= set(seconds)


def test_phase_seconds_sum_to_the_program_time(every_op, table):
    seconds = scopes.phase_seconds(every_op["top_ops"], scopes.QUERY_PROGRAM,
                                   table)
    module = every_op["module_s"][scopes.QUERY_PROGRAM]
    assert sum(seconds.values()) == pytest.approx(module, rel=0.01)


def test_readers_return_the_values_recorded_on_the_chip(profile):
    record = _record(profile, query_hlo=HLO.read_text())
    for name in READERS:
        assert _read(name, record) == pytest.approx(
            RECORDED["values"][name], rel=1e-9)


def test_readers_are_silent_where_the_program_is_unnamed():
    record = _record(_profile(OLD_TRACE))
    for name in READERS:
        assert _read(name, record) is None
    assert "query_hlo" not in record  # nothing was compiled to find out


def test_readers_are_silent_when_the_text_is_not_of_the_program(profile):
    record = _record(profile, query_hlo="HloModule jit_other\n")
    for name in READERS:
        assert _read(name, record) is None


SYNTHETIC = """HloModule jit_cuckoo_query, is_scheduled=true

%fused_gather (p0: u32[8], p1: s32[4]) -> u32[4] {
  %p0 = u32[8]{0} parameter(0)
  %p1 = s32[4]{0} parameter(1)
  %idx = s32[4]{0} add(%p1, %p1), metadata={op_name="jit(q)/hash/add"}
  ROOT %g = u32[4]{0} gather(%p0, %idx), metadata={op_name="jit(q)/cond/branch_0_fun/row_gather/gather"}
}

%fused_pair (p0.1: u32[4]) -> (u32[4], u32[4]) {
  %p0.1 = u32[4]{0} parameter(0)
  %s = u32[4]{0} add(%p0.1, %p0.1), metadata={op_name="jit(q)/row_gather/lane_select/add"}
  %t = u32[4]{0} and(%p0.1, %p0.1), metadata={op_name="jit(q)/tag_match/and"}
  ROOT %tuple.1 = (u32[4]{0}, u32[4]{0}) tuple(%s, %t)
}

ENTRY %main (a: u32[8], b: s32[4]) -> u32[4] {
  %a = u32[8]{0} parameter(0)
  %b = s32[4]{0} parameter(1)
  %fusion = u32[4]{0} fusion(%a, %b), kind=kCustom, calls=%fused_gather, metadata={op_name="jit(q)/hash/add"}
  %copy-start = (u32[4]{0}, u32[4]{0}, u32[]) copy-start(%fusion)
  %copy-done = u32[4]{0} copy-done(%copy-start)
  %pair_fusion = (u32[4]{0}, u32[4]{0}) fusion(%copy-done), kind=kLoop, calls=%fused_pair
  %gte = u32[4]{0} get-tuple-element(%pair_fusion), index=0
  ROOT %or.1 = u32[4]{0} or(%gte, %gte), metadata={op_name="jit(q)/or"}
}
"""


def test_the_phase_rule_on_a_small_program():
    table = scopes.phases(SYNTHETIC)
    # A fusion is charged to its root, not to its own metadata.
    assert table["fusion"] == "row_gather"
    # A tuple root takes its first operand's phase; the innermost scope wins.
    assert table["pair_fusion"] == "lane_select"
    # No metadata, or a path with no phase: "-".
    assert table["copy-start"] == table["copy-done"] == "-"
    assert table["or.1"] == "-"
    ops = [["jit_cuckoo_query/fusion", 2.0], ["jit_cuckoo_query/copy-done", 1.0],
           ["jit_bench_hit_count/fusion", 5.0]]
    assert scopes.phase_seconds(ops, "jit_cuckoo_query", table) == {
        "row_gather": 2.0, "-": 1.0}
    assert scopes.phase_seconds(ops + [["jit_cuckoo_query/nope", 1.0]],
                                "jit_cuckoo_query", table) is None

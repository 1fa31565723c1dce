"""Each benchmark cell end to end on the CPU at a tiny size.

The last line holds the keys the benchmark's contract names, with the
compared numbers last; ``correct`` is true for the program as it is and
false for the program at the next lower precision, 8-bit fingerprints in
place of the configured 16 (the cell's control).
"""

import pytest

import chipbench_harness as H

CELLS = ("bulk.query95",)
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_with_its_result_line(monkeypatch, cell, trace):
    rc, line, err = H.run_tiny(monkeypatch, cell, trace=trace)
    assert rc == 0, err
    want = KEYS + (["breakdown"] if trace else []) + ["checks"]
    assert list(line) == want
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    assert line["checks"]["compiles_in_window"]["value"] == 0
    if trace:
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert "window_s" in line["device"] and "busy_s" in line["device"]
    else:
        assert "setup_s" in line["metrics"]
    for name, check in line["checks"].items():
        assert f"check {name}: {check['value']} (limit {check['limit']})" \
            in err


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_program_is_not_correct(monkeypatch, cell):
    (line,) = H.control_tiny(monkeypatch, cell)
    assert (line["program_fp_bits"], line["reference_fp_bits"]) == (8, 16)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_no_tpu_exits_without_a_result(monkeypatch, capsys):
    monkeypatch.setattr(H.run, "configure_jax", lambda: None)
    with pytest.raises(SystemExit) as exc:
        H.run.main(["--workload", "bulk.query95", "--seed", "1",
                    "--seconds", "1", "--trace", "0"])
    assert exc.value.code == 2
    assert capsys.readouterr().out == ""

"""A configuration, a traffic mix and a per-layer metric added as new files,
with new entries in BENCHMARK.json, are found by name: no file of the
harness is edited."""

import json
import shutil

import chipbench_harness as H

METRIC = '''"""Keys the traced window queried, in millions."""


def read(run):
    ops = run.get("traced_ops") or {}
    return ops["query"] / 1e6 if ops.get("query") else None
'''


def test_new_files_and_entries_are_found_by_name(monkeypatch, tmp_path):
    shutil.copytree(H.ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((H.ROOT / "BENCHMARK.json").read_text())
    here = tmp_path / "chipbench"
    before = {p.relative_to(here): p.read_bytes()
              for p in here.rglob("*") if p.is_file()}

    config = json.loads((here / "configs" / "paper_bulk.json").read_text())
    config.update(name="paper_bulk_b", load=0.5)
    (here / "configs" / "paper_bulk_b.json").write_text(json.dumps(config))
    traffic = json.loads((here / "traffic" / "bulk_query.json").read_text())
    traffic["pool_batches"] = 3
    (here / "traffic" / "bulk_query_b.json").write_text(json.dumps(traffic))
    (here / "metrics" / "queried_m.bulk_b.py").write_text(METRIC)
    bench["configs"].append(dict(bench["configs"][0], name="paper_bulk_b",
                                 file="chipbench/configs/paper_bulk_b.json"))
    bench["workloads"].append({"name": "bulk.b", "config": "paper_bulk_b",
                               "traffic": "bulk_query_b", "chips": 1,
                               "why": "a cell added as data"})
    bench["per_layer"].append({
        "name": "queried_m.bulk_b", "unit": "Mkeys", "better": "higher",
        "source": "program_counter", "layer": "core ops",
        "moves": "ops_per_s", "workloads": ["bulk.b"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    copy = H.run.load_module(here / "run.py")
    spec = copy.load_cell("bulk.b")
    assert spec["config"]["load"] == 0.5
    assert spec["traffic"]["pool_batches"] == 3
    assert [m["name"] for m in spec["per_layer"]] == ["queried_m.bulk_b"]

    rc, line, err = H.run_tiny(monkeypatch, "bulk.b", trace=1, module=copy)
    assert rc == 0, err
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["queried_m.bulk_b"]["unit"] == "Mkeys"
    assert line["metrics"]["queried_m.bulk_b"]["value"] > 0
    for rel, data in before.items():
        assert (here / rel).read_bytes() == data, f"{rel} was edited"

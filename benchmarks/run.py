"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
With ``--json-dir`` each suite additionally writes a machine-readable
``BENCH_<suite>.json`` (CSV rows parsed into records, plus any structured
payload the suite attached via ``common.emit_json`` — op mixes,
throughputs, load factors). CI's bench-smoke job uploads these as
artifacts, seeding the perf trajectory across commits.

A suite that raises still prints its ``*_SUITE_ERROR`` row and the harness
goes on to the next suite, but the process then exits non-zero.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only fig3,...]
                                            [--json-dir bench-json]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

from . import (
    bucket_policy,
    eviction,
    expansion,
    fpr,
    kmer_case_study,
    lifecycle,
    mixed_workload,
    roofline,
    roofline_filters,
    serving_slo,
    sorted_insertion,
    throughput,
    tiering,
)
from repro.compile_cache import disable_tpu_logs, enable_compile_cache

from .common import JSON_RECORDS, ROWS

SUITES = {
    "fig3": lambda fast: (throughput.run(fast),
                          throughput.run_cpu_reference(fast)),
    "fig4": fpr.run,
    "fig5_6": eviction.run,
    "fig7": bucket_policy.run,
    "fig8": kmer_case_study.run,
    "s463": sorted_insertion.run,
    "expansion": expansion.run,
    "mixed": mixed_workload.run,
    "lifecycle": lifecycle.run,
    "serving_slo": serving_slo.run,
    # One BENCH_roofline.json: the dryrun-projection rows (skipped cleanly
    # when no artifacts exist — the CI default) plus the filter roofline
    # suite's achieved-vs-model-minimal rows.
    "roofline": lambda fast: (roofline.run(fast), roofline_filters.run(fast)),
    "tiering": tiering.run,
}


def _parse_rows(rows) -> list:
    out = []
    for row in rows:
        name, us, derived = row.split(",", 2)
        out.append({"name": name, "us_per_call": float(us),
                    "derived": derived})
    return out


def _write_json(json_dir: pathlib.Path, name: str, fast: bool,
                elapsed_s: float, rows, error: str = "") -> None:
    payload = {
        "suite": name,
        "fast": fast,
        "elapsed_s": round(elapsed_s, 3),
        "rows": _parse_rows(rows),
        "data": JSON_RECORDS.get(name, {}),
    }
    if error:
        payload["error"] = error
    path = json_dir / f"BENCH_{name}.json"
    path.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# wrote {path}", file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced sizes (CI smoke)")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--json-dir", default=None,
                    help="directory for machine-readable BENCH_<suite>.json")
    args = ap.parse_args(argv)
    names = (args.only.split(",") if args.only else list(SUITES))
    json_dir = None
    if args.json_dir is not None:
        json_dir = pathlib.Path(args.json_dir)
        json_dir.mkdir(parents=True, exist_ok=True)
    disable_tpu_logs()
    enable_compile_cache()
    print("name,us_per_call,derived")
    errored = []
    for name in names:
        t0 = time.time()
        row_start = len(ROWS)
        error = ""
        try:
            SUITES[name](args.fast)
        except Exception as e:  # noqa: BLE001 — keep the harness running
            error = f"{type(e).__name__}:{e}"
            errored.append(name)
            print(f"{name}_SUITE_ERROR,0.0,{error}", file=sys.stderr)
            print(f"{name}_suite_error,0.0,{type(e).__name__}")
        elapsed = time.time() - t0
        if json_dir is not None:
            _write_json(json_dir, name, args.fast, elapsed,
                        ROWS[row_start:], error)
        print(f"# {name} done in {elapsed:.0f}s", file=sys.stderr)
    if errored:
        print(f"# suites errored: {','.join(errored)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""A cell's control: the program at the next lower precision, judged by the
reference at the configured one. Its ``correct`` has to come out false.

    python chipbench/control.py --workload <cell> --seconds <s> \\
        --seeds <n> <n> <n>

The configuration states 16-bit fingerprints; the control builds the same
cell with the program's filter at :data:`CONTROL_FP_BITS` (8: the step
down a later change might take to halve the table), drives it through the
cell's own set-up and window, and compares what it answered with the plain reference
at the configuration's own 16 bits. Each seed prints one JSON line with
every compared number beside its limit; the readings set the upper end of
each limit (PERF.md).
"""

from __future__ import annotations

import argparse
import json
import sys

import run

CONTROL_FP_BITS = 8


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    spec = run.load_cell(args.workload)
    run.configure_jax()
    devices = run.require_chips(spec["cell"]["chips"])
    driver = run.load_module(spec["root"] / "chipbench" / "drivers"
                             / f"{spec['traffic']['driver']}.py")
    lower = dict(spec["config"], fp_bits=CONTROL_FP_BITS)
    for seed in args.seeds:
        cell = driver.Cell(lower, spec["traffic"], seed, args.seconds,
                           devices)
        cell.setup()
        cell.window(args.seconds, None)
        cell.release()
        checks = cell.compare(cell.observed(),
                              cell.reference(spec["config"]["fp_bits"]))
        print(json.dumps({
            "seed": seed, "program_fp_bits": CONTROL_FP_BITS,
            "reference_fp_bits": spec["config"]["fp_bits"],
            "correct": all(c["value"] <= c["limit"]
                           for c in checks.values()),
            "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

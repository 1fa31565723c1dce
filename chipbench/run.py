"""Run one benchmark cell once on the chip and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name:
``BENCHMARK.json`` at the checkout's root names the cell's ``config`` and
``traffic``; ``chipbench/configs/<config>.json`` holds the deployment,
``chipbench/traffic/<traffic>.json`` the mix, whose ``driver`` names the
general generator in ``chipbench/drivers/<driver>.py``; each per-layer
metric is read by ``chipbench/metrics/<metric>.py``. Adding a cell, a
configuration, a mix or a metric adds files and entries and edits none.

A run: find the chip (no TPU, or fewer chips than the cell asks for, exits
2 with no result); build the cell's inputs from ``--seed`` and warm up
every shape the traffic uses (set-up, timed as ``setup_s`` from process
start); measure for ``--seconds``; read the peak device memory; free the
program's state; check what the window produced against the plain
reference in ``chipbench/yardstick/reference.py``; print each compared
number beside its limit on standard error, then the result as the last
line of standard output. ``--trace 1`` records a device trace of the window
and reports the per-layer metrics in place of the end-to-end ones.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_DIR = ROOT / ".chipbench_trace"


def load_module(path: pathlib.Path):
    """Import a file by path (names may hold dots: ``idle_share.bulk.py``)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The cell's entry, its config and traffic data, and its metrics."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r} (known: {sorted(cells)})")
    cell = cells[name]
    here = root / "chipbench"
    config = json.loads((here / "configs" / f"{cell['config']}.json")
                        .read_text())
    traffic = json.loads((here / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [])
             or ("workloads" not in m and m["moves"] in reported)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer, "root": root}


def require_chips(chips: int):
    """The devices to measure on; exits 2 without a TPU or enough chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: jax found {devices[0].platform!r} devices; this "
              "benchmark measures only on the chip", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"the cell needs {chips} chips, jax found {len(devices)}",
              file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


def configure_jax() -> None:
    """Before JAX touches a device: libtpu's logs off /tmp, the persistent
    compilation cache in the checkout (or where the environment puts it),
    and every program cached, however fast it compiled."""
    from repro.compile_cache import disable_tpu_logs, enable_compile_cache

    disable_tpu_logs()
    import jax

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def peak_memory(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest device, where reported."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
             for d in devices]
    peak = max(peaks) if peaks else -1
    return peak if peak >= 0 else None


class CompileCounter:
    """Counts XLA compilations (or persistent-cache loads) JAX performs."""

    def __init__(self):
        import jax

        self.total = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.total += 1


def read_layer_metrics(specs, record: dict) -> dict:
    """Run each per-layer metric's reader; a reader with nothing to read
    returns None and its metric is left out of the line."""
    out = {}
    for spec in specs:
        reader = load_module(record["root"] / "chipbench" / "metrics"
                             / f"{spec['name']}.py")
        value = reader.read(record)
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_cell(args.workload)
    configure_jax()
    devices = require_chips(spec["cell"]["chips"])
    compiles = CompileCounter()

    driver = load_module(spec["root"] / "chipbench" / "drivers"
                         / f"{spec['traffic']['driver']}.py")
    cell = driver.Cell(spec["config"], spec["traffic"], args.seed,
                       args.seconds, devices)
    cell.setup()
    # Set-up's objects (compiled programs, the key pool) leave the cyclic
    # collector's reach, as in a server after start-up; the collector stays
    # on, so the window pays for whatever garbage its own calls make.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - PROCESS_START
    print(f"# set-up {setup_s:.3f} s, {compiles.total} programs compiled or "
          "loaded", file=sys.stderr, flush=True)

    trace_dir = TRACE_DIR / args.workload if args.trace else None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    before = compiles.total
    record = cell.window(args.seconds, trace_dir)
    in_window = compiles.total - before
    print(f"# compilations inside the window: {in_window}", file=sys.stderr,
          flush=True)
    for name, value in record["end_to_end"].items():
        print(f"# {name} {value}", file=sys.stderr, flush=True)
    memory = peak_memory(devices)
    cell.release()
    checks = cell.check()

    record.update(root=spec["root"], config=spec["config"],
                  traffic=spec["traffic"], device_kind=devices[0].device_kind)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values())
           and in_window == 0,
           "attempted": int(record["attempted"]),
           "failed": int(record["failed"])}
    if args.trace:
        from yardstick import trace as tr

        summary = tr.reduce(tr.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        record["trace"] = summary
        out["metrics"] = read_layer_metrics(spec["per_layer"], record)
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["device"] = device
        out["breakdown"] = {"device_ops": summary["top_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    else:
        e2e = dict(record["end_to_end"], setup_s=setup_s)
        out["metrics"] = {m["name"]: {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
                          for m in spec["end_to_end"]}
        out["device"] = device
    out["checks"] = dict(checks, compiles_in_window={
        "value": in_window, "limit": 0})
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

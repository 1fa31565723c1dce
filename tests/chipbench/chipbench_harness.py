"""Drive ``chipbench/run.py`` on the CPU at a tiny size, for the tests.

The harness refuses to measure without a TPU. A test steers round that
itself: it replaces ``run.require_chips`` with the CPU devices and
``run.configure_jax`` with nothing (tests never turn the persistent
compilation cache on), and shrinks the cell's table and traffic through
``run.load_cell``. Nothing in the harness has an option for this.
"""

from __future__ import annotations

import contextlib
import io
import json
import pathlib
import sys

import jax

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT / "chipbench") not in sys.path:
    sys.path.insert(0, str(ROOT / "chipbench"))

import run  # noqa: E402

TINY_CONFIG = {"num_buckets": 1 << 10, "fill_batch": 1 << 10}
TINY_TRAFFIC = {"batch": 1 << 10, "pool_batches": 2, "sampled_every": 4}
SEED = 2**32 + 12345


def shrink(spec: dict) -> dict:
    """The cell at the tiny size."""
    cfg = spec["config"]
    cfg.update({k: v for k, v in TINY_CONFIG.items() if k in cfg})
    traffic = spec["traffic"]
    traffic.update({k: v for k, v in TINY_TRAFFIC.items() if k in traffic})
    return spec


def steer(monkeypatch, module=run) -> None:
    """Point ``module`` (``run`` or a copy of it) at the CPU and the tiny
    size."""
    load = module.load_cell
    monkeypatch.setattr(module, "load_cell",
                        lambda name, root=module.ROOT:
                        shrink(load(name, root)))
    monkeypatch.setattr(module, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(module, "configure_jax", lambda: None)


def _call(main, argv):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = main(argv)
    lines = [json.loads(x) for x in out.getvalue().strip().splitlines()
             if x.startswith("{")]
    return rc, lines, err.getvalue()


def run_tiny(monkeypatch, workload: str, *, trace: int = 0,
             seed: int = SEED, seconds: float = 1.0, module=run):
    """One run of a cell through ``module.main``; returns (exit code, the
    last line of standard output as JSON, standard error)."""
    steer(monkeypatch, module)
    rc, lines, err = _call(module.main, [
        "--workload", workload, "--seed", str(seed), "--seconds",
        str(seconds), "--trace", str(trace)])
    return rc, lines[-1] if lines else None, err


def control_tiny(monkeypatch, workload: str, *, seeds=(SEED,),
                 seconds: float = 1.0):
    """The cell's control (``chipbench/control.py``); one line per seed."""
    import control

    steer(monkeypatch)
    rc, lines, err = _call(control.main, [
        "--workload", workload, "--seconds", str(seconds), "--seeds",
        *map(str, seeds)])
    assert rc == 0, err
    return lines

"""Shares the per-layer readers report, from a reduced trace and op counts."""

from __future__ import annotations

from yardstick.bytes import op_bytes
from yardstick.peaks import peaks


def idle_share(run: dict):
    """Percent of the traced window in which no operation ran on the chip;
    None without a device trace."""
    t = run.get("trace")
    if not t or not t["devices"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def roofline_share(run: dict):
    """Percent of the HBM roofline the system's programs reached: the
    minimal bytes of the ops they ran in the traced window over their device
    time at peak bandwidth. None without a trace, program time or ops."""
    t, ops = run.get("trace"), run.get("traced_ops") or {}
    if not t or t["system_s"] <= 0 or not sum(ops.values()):
        return None
    cfg = run["config"]
    need = sum(n * op_bytes(op, cfg["bucket_size"], cfg["fp_bits"])
               for op, n in ops.items())
    bw = peaks(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * need / (t["system_s"] * bw)

"""Post-SPMD HLO analysis: collective-op inventory + roofline terms.

``cost_analysis()`` gives per-device FLOPs and HBM bytes but not collective
traffic, so we parse ``compiled.as_text()``: every line defining an
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
contributes its result-shape bytes, scaled to *wire bytes per device* with
the standard ring-algorithm factors and the parsed replica-group size.

Hardware peaks come from :data:`PEAKS`, keyed by ``jax.Device.device_kind``;
a device that is not in the table raises instead of borrowing another's.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List

# Published per-chip peaks. Source: Google Cloud documentation, "TPU v5e"
# (system architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of inter-chip interconnect. ``hbm_bytes`` is the
# capacity the v5e compiler itself enforces (17179869184 bytes).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16 * 2**30,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind`` — never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; add them "
            f"to PEAKS with their source (known: {sorted(PEAKS)})") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLL = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute")

# `%x.1 = bf16[8,128]{1,0} all-gather(...)` or tuple results
_DEF_RE = re.compile(
    r"=\s*(\(?[a-z0-9]+\[[^=]*?)\s+(" + "|".join(_COLL) + r")(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_V1_RE = re.compile(r"replica_groups=\{([^}]*)\}")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_V1_RE.search(line)
    if m:
        first = m.group(1).split("}")[0].split(",")
        return max(1, len([x for x in first if x.strip().isdigit()]))
    return default


def collective_stats(hlo_text: str, n_devices: int) -> Dict[str, Dict]:
    """Per-kind counts / result bytes / estimated wire bytes per device."""
    done_seen = set()
    stats: Dict[str, Dict] = defaultdict(
        lambda: {"count": 0, "result_bytes": 0, "wire_bytes": 0})
    for line in hlo_text.splitlines():
        m = _DEF_RE.search(line)
        if not m:
            continue
        type_str, kind = m.group(1), m.group(2)
        if "-done(" in line:  # async pair: count the start only
            continue
        b = _shape_bytes(type_str)
        g = _group_size(line, n_devices)
        frac = (g - 1) / max(g, 1)
        if kind == "all-reduce":
            wire = 2 * b * frac            # ring: reduce-scatter + all-gather
        elif kind == "all-gather":
            wire = b * frac                # result is the gathered buffer
        elif kind == "reduce-scatter":
            wire = b * g * frac            # result is the scattered shard
        elif kind == "all-to-all":
            wire = b * frac
        else:  # collective-permute
            wire = b
        s = stats[kind]
        s["count"] += 1
        s["result_bytes"] += b
        s["wire_bytes"] += int(wire)
    return dict(stats)


def roofline_terms(flops: float, bytes_hbm: float, colls: Dict[str, Dict],
                   device_kind: str) -> Dict[str, float]:
    """Three roofline terms in seconds (per device, per step)."""
    pk = peaks(device_kind)
    wire = float(sum(s["wire_bytes"] for s in colls.values()))
    return {
        "compute_s": flops / pk["bf16_flops_per_s"],
        "memory_s": bytes_hbm / pk["hbm_bytes_per_s"],
        "collective_s": wire / pk["ici_bytes_per_s"],
        "hlo_flops": flops,
        "hlo_bytes": bytes_hbm,
        "collective_wire_bytes": wire,
    }


def dominant_term(terms: Dict[str, float]) -> str:
    three = {k: terms[k] for k in ("compute_s", "memory_s", "collective_s")}
    return max(three, key=three.get)


def analytic_model_flops(cfg, kind: str, batch: int, seq: int) -> float:
    """MODEL_FLOPS: 6·N·D (train) / 2·N·D (inference) **plus** attention
    score/value flops, which dominate parameter flops at 4k+ context for
    the small-d archs. MoE counts active params only. Per the whole job
    (divide by device count for per-device)."""
    n = cfg.param_count(active_only=cfg.moe)
    per_param = {"train": 6, "prefill": 2, "decode": 2}[kind]
    tokens = batch * (seq if kind != "decode" else 1)
    total = float(per_param) * n * tokens

    # attention term
    mult = 3.0 if kind == "train" else 1.0  # bwd ~= 2x fwd
    for k in cfg.layer_kinds():
        mixer = k.split("+")[0]
        if mixer in ("attn", "attn_local", "mla"):
            H = cfg.num_heads
            if mixer == "mla":
                d_qk = cfg.mla_qk_nope_dim + cfg.mla_qk_rope_dim
                d_v = cfg.mla_v_dim
            else:
                d_qk = d_v = cfg.head_dim_()
            if kind == "decode":
                kv = min(cfg.sliding_window, seq) \
                    if mixer == "attn_local" and cfg.sliding_window else seq
                per_tok = 2 * kv * H * (d_qk + d_v)
                total += batch * per_tok
            else:
                w = cfg.sliding_window if mixer == "attn_local" else None
                kv_avg = min(w, seq / 2) if w else (
                    seq if not cfg.causal else seq / 2)
                total += mult * batch * seq * 2 * kv_avg * H * (d_qk + d_v)
        elif mixer == "ssm":
            Hs, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
            if kind == "decode":
                total += batch * 4 * Hs * P * N      # recurrent state step
            else:
                L = cfg.ssm_chunk                    # intra-chunk quadratic
                per_tok = 2 * L * Hs * P + 4 * Hs * P * N
                total += mult * batch * seq * per_tok
        elif mixer == "rglru":
            W = cfg.rglru_width or cfg.d_model
            toks = batch if kind == "decode" else batch * seq
            total += (1.0 if kind == "decode" else mult) * toks * 8 * W
    return total

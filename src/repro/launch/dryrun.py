import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each live cell this driver builds the production mesh, constructs
ShapeDtypeStruct stand-ins for every input (params and optimizer state via
``jax.eval_shape`` — no allocation anywhere), jits the appropriate step with
explicit in/out shardings, runs ``.lower().compile()``, and records:

  * ``memory_analysis()``   — per-device argument/temp/peak bytes (fits?)
  * ``cost_analysis()``     — per-device HLO FLOPs + HBM bytes
  * collective inventory    — parsed from the post-SPMD HLO text
  * the three roofline terms (launch/hlo_analysis.py)

Results land in ``results/dryrun/<arch>__<shape>__<mesh>.json`` and feed
EXPERIMENTS.md §Dry-run / §Roofline and benchmarks/roofline.py.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2_2b \
        --shape train_4k [--multi-pod] [--all]
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ..configs import ARCHS, get_config  # noqa: E402
from ..models import build_model  # noqa: E402
from ..train import AdamWConfig, adamw_init, make_train_step  # noqa: E402
from . import hlo_analysis as H  # noqa: E402
from . import hlo_cost as HC  # noqa: E402
from .input_specs import SHAPES, SKIPS, input_specs, live_cells  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402
from .shardings import (  # noqa: E402
    make_batch_shardings,
    make_cache_shardings,
    make_opt_shardings,
    make_param_shardings,
)

# The chip these projections are for: the CPU-compiled HLO is costed
# against this device's published peaks.
TARGET_KIND = "TPU v5 lite"

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _quantize_state(cfg) -> bool:
    # int8 Adam for >=30B-param configs (fits 16 GB/chip budget)
    return cfg.param_count() > 30e9


def spec_kind_is_decode(arch: str, shape_name: str) -> bool:
    return SHAPES[shape_name]["kind"] == "decode"


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               microbatches: int = 1, overrides: dict | None = None,
               no_hints: bool = False, param_mode: str | None = None):
    """Build + lower + compile one cell. Returns (compiled, meta).

    ``overrides`` patches ModelConfig fields; ``no_hints`` disables the
    shard_ctx constraints and ``param_mode`` forces train/serve shardings —
    both used to reproduce §Perf baselines under the final cost model.
    """
    import dataclasses

    from ..models import shard_ctx

    mesh = make_production_mesh(multi_pod=multi_pod)
    if not no_hints:
        shard_ctx.set_dp_axes(("pod", "data") if multi_pod else ("data",))
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    model = build_model(cfg)
    spec = input_specs(cfg, shape_name)
    kind = spec["kind"]

    params_shape = jax.eval_shape(model.init, jax.random.key(0))
    # decode = latency path: TP/EP weights (no per-token FSDP gathers)
    if param_mode is None:
        param_mode = ("serve" if spec_kind_is_decode(arch, shape_name)
                      else "train")
    param_sh = make_param_shardings(mesh, params_shape, mode=param_mode)

    # `with mesh:` provides the context for P-only sharding constraints
    # (shard_ctx hints inside model code)
    with mesh:
        if kind == "train":
            opt_cfg = AdamWConfig(quantize_state=_quantize_state(cfg))
            opt_shape = jax.eval_shape(
                lambda p: adamw_init(opt_cfg, p), params_shape)
            opt_sh = make_opt_shardings(mesh, opt_shape,
                                        quantized=opt_cfg.quantize_state)
            batch_sh = make_batch_shardings(mesh, spec["batch_spec"])
            step = make_train_step(model, opt_cfg, microbatches=microbatches)
            metrics_sh = {"loss": NamedSharding(mesh, P()),
                          "grad_norm": NamedSharding(mesh, P()),
                          "lr": NamedSharding(mesh, P())}
            fn = jax.jit(step,
                         in_shardings=(param_sh, opt_sh, batch_sh),
                         out_shardings=(param_sh, opt_sh, metrics_sh),
                         donate_argnums=(0, 1))
            lowered = fn.lower(params_shape, opt_shape, spec["batch_spec"])
        elif kind == "prefill":
            batch_sh = make_batch_shardings(mesh, spec["batch_spec"])
            if cfg.frontend == "frames":
                fn = jax.jit(lambda p, b: model.encode(p, b["frames"]),
                             in_shardings=(param_sh, batch_sh))
            else:
                fn = jax.jit(model.prefill,
                             in_shardings=(param_sh, batch_sh))
            lowered = fn.lower(params_shape, spec["batch_spec"])
        else:  # decode
            cache_shape = spec["cache_spec"]
            cache_sh = make_cache_shardings(mesh, cache_shape, spec["seq"],
                                            spec["batch"])
            tok_sh = make_batch_shardings(mesh, spec["token_spec"])
            pos_sh = NamedSharding(mesh, P())
            fn = jax.jit(model.decode_step,
                         in_shardings=(param_sh, tok_sh, cache_sh, pos_sh),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(2,))
            lowered = fn.lower(params_shape, spec["token_spec"], cache_shape,
                               spec["pos_spec"])

        compiled = lowered.compile()
    shard_ctx.set_dp_axes(None)
    return compiled, {"mesh": dict(zip(mesh.axis_names,
                                       [int(s) for s in mesh.devices.shape])),
                      "n_devices": int(mesh.size), "cfg": cfg, "spec": spec}


def analyse(compiled, meta, *, keep_hlo: bool = False):
    cfg, spec = meta["cfg"], meta["spec"]
    n_dev = meta["n_devices"]
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    txt = compiled.as_text()
    # Primary costs come from the text-based model (hlo_cost) because XLA's
    # cost_analysis counts while(scan) bodies once — under-counting a
    # 61-layer scanned stack ~61x. Validated against known matmuls.
    tc = HC.analyse_text(txt, n_dev)
    colls = tc["collectives"]
    terms = H.roofline_terms(tc["flops"], tc["bytes"], colls, TARGET_KIND)

    # MODEL_FLOPS: 6/2 N D (active params for MoE) + analytic attention/SSM
    # terms (hlo_analysis.analytic_model_flops)
    model_flops = H.analytic_model_flops(cfg, spec["kind"], spec["batch"],
                                         spec["seq"])
    model_flops_per_dev = model_flops / n_dev

    out = {
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.peak_memory_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "fits_16gb": (mem.argument_size_in_bytes - mem.alias_size_in_bytes
                          + mem.output_size_in_bytes + mem.temp_size_in_bytes)
            < H.peaks(TARGET_KIND)["hbm_bytes"],
        },
        "cost_xla_unscaled": {k: float(v) for k, v in cost.items()
                              if "flops" in k or k == "bytes accessed"},
        "collectives": colls,
        "roofline": terms,
        "dominant": H.dominant_term(terms),
        "model_flops_per_device": model_flops_per_dev,
        "useful_flop_ratio": (model_flops_per_dev
                              / max(terms["hlo_flops"], 1.0)),
    }
    if keep_hlo:
        out["hlo_len"] = len(txt)
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = RESULTS_DIR, microbatches: int = 1,
             tag: str = "") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    label = f"{arch}__{shape_name}__{mesh_name}{tag}"
    t0 = time.time()
    try:
        compiled, meta = lower_cell(arch, shape_name, multi_pod,
                                    microbatches=microbatches)
        result = analyse(compiled, meta)
        result.update(status="ok", arch=arch, shape=shape_name,
                      mesh=mesh_name, microbatches=microbatches,
                      compile_s=round(time.time() - t0, 1))
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        result = {"status": "error", "arch": arch, "shape": shape_name,
                  "mesh": mesh_name, "error": f"{type(e).__name__}: {e}",
                  "trace": traceback.format_exc()[-2000:],
                  "compile_s": round(time.time() - t0, 1)}
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, label + ".json"), "w") as f:
        json.dump(result, f, indent=1, default=str)
    print(f"[{result['status']}] {label} ({result['compile_s']}s) "
          + (result.get("dominant", "") or result.get("error", "")[:120]))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--out-dir", default=RESULTS_DIR)
    args = ap.parse_args()

    cells = []
    if args.all:
        cells = list(live_cells())
    elif args.arch and args.shape:
        if (args.arch, args.shape) in SKIPS:
            print(f"SKIP {args.arch} {args.shape}: "
                  f"{SKIPS[(args.arch, args.shape)]}")
            return
        cells = [(args.arch, args.shape)]
    elif args.arch:
        cells = [(args.arch, s) for s in SHAPES
                 if (args.arch, s) not in SKIPS]
    else:
        ap.error("pass --all or --arch [--shape]")

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    ok = err = 0
    for arch, shape in cells:
        for mp in meshes:
            r = run_cell(arch, shape, mp, out_dir=args.out_dir,
                         microbatches=args.microbatches)
            ok += r["status"] == "ok"
            err += r["status"] != "ok"
    print(f"done: {ok} ok, {err} failed")
    if err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

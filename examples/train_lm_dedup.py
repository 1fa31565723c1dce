"""End-to-end training driver: LM + streaming filter dedup + checkpoints.

Trains a reduced-config model for a few hundred steps on CPU with the
cuckoo-filter dedup stage masking duplicate sequences, checkpointing and
surviving a simulated mid-run failure. Use --full-100m for a ~100M-parameter
run (sized for a real accelerator; slow on CPU).

    PYTHONPATH=src python examples/train_lm_dedup.py [--steps 200]

``--device-budget-bytes N`` switches the dedup stage to the tiered
GPU-hot / host-cold filter (DESIGN.md §12): the dedup keyset may grow
several times past the device budget — old filter levels freeze into host
RAM and are probed off the hot path — demonstrating corpus dedup beyond
device memory:

    PYTHONPATH=src python examples/train_lm_dedup.py \\
        --steps 400 --device-budget-bytes 4096
"""

import argparse
import dataclasses
import tempfile

import jax
import numpy as np

from repro.compile_cache import disable_tpu_logs, enable_compile_cache
from repro.configs import get_config
from repro.core import CuckooConfig
from repro.data import DataConfig, DedupConfig, dedup_batch, make_batch
from repro.models import build_model
from repro.train import (
    AdamWConfig,
    TrainingRunner,
    checkpoint,
    init_train_state,
    make_train_step,
)

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--full-100m", action="store_true")
ap.add_argument("--device-budget-bytes", type=int, default=None,
                help="cap the dedup filter's device footprint; older "
                     "levels tier out to host RAM (DESIGN.md §12)")
args = ap.parse_args()
disable_tpu_logs()
enable_compile_cache()

cfg = get_config("mamba2_130m")
if args.full_100m:
    cfg = dataclasses.replace(cfg, num_layers=12)   # ~100M params
    batch, seq = 8, 1024
else:
    cfg = cfg.reduced()
    batch, seq = 8, 128

model = build_model(cfg)
opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps)
params, opt_state = init_train_state(model, opt_cfg, jax.random.key(0))
n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
print(f"model: {cfg.name} ({n / 1e6:.1f}M params)")

data_cfg = DataConfig(vocab_size=cfg.vocab_size, batch=batch, seq_len=seq,
                      duplicate_fraction=0.3)
dup_total = 0

if args.device_budget_bytes is not None:
    # Beyond-HBM mode: the dedup keyset is allowed to outgrow the device
    # budget — the tiered handle freezes old levels into host RAM and
    # probes them off the padded hot path (DESIGN.md §12).
    from repro.data import make_deduper

    deduper = make_deduper(1024, "cuckoo", service_batch=batch,
                           device_budget_bytes=args.device_budget_bytes)

    def data_fn(step):
        global dup_total
        batch_, stats = deduper.dedup(make_batch(data_cfg, step))
        dup_total += int(stats["duplicates"])
        return batch_
else:
    dcfg = DedupConfig(CuckooConfig.for_capacity(args.steps * batch + 4096,
                                                 hash_kind="fmix32"))
    filter_state = dcfg.filter.init()
    dedup = jax.jit(lambda s, b: dedup_batch(dcfg, s, b))

    def data_fn(step):
        global filter_state, dup_total
        batch_ = make_batch(data_cfg, step)
        filter_state, batch_, stats = dedup(filter_state, batch_)
        dup_total += int(stats["duplicates"])
        return batch_


step_fn = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0, 1))

ckpt_dir = tempfile.mkdtemp(prefix="repro_example_")
fail_at = args.steps // 2
print(f"training {args.steps} steps; injecting a failure at {fail_at} "
      "to demonstrate checkpoint/restart...")
runner = TrainingRunner(train_step=step_fn, data_fn=data_fn,
                        ckpt_dir=ckpt_dir, ckpt_every=25,
                        fail_at_step=fail_at)
try:
    runner.run(params, opt_state, num_steps=args.steps, log_every=25)
except RuntimeError as e:
    print(f"  !! {e} — restarting from checkpoint")

runner2 = TrainingRunner(train_step=step_fn, data_fn=data_fn,
                         ckpt_dir=ckpt_dir, ckpt_every=25)
params, opt_state, start = runner2.resume(params, opt_state)
print(f"  resumed at step {start}")
params, opt_state, monitor = runner2.run(params, opt_state,
                                         num_steps=args.steps,
                                         start_step=start, log_every=25)
print(f"done. duplicates masked: {dup_total}; "
      f"straggler stats: {monitor.summary()}")
if args.device_budget_bytes is not None:
    deduper.flush()
    h = deduper.handle
    ts = h.tier_stats()
    print(f"tiered dedup: {h.count()} keys over a "
          f"{ts['device_budget_bytes']}B device budget "
          f"(device {ts['device_bytes']}B + host {ts['host_bytes']}B; "
          f"{ts['cold_levels']} cold levels, "
          f"{ts['cold_probe_keys']} cold-probed keys)")
print(f"final checkpoint: step {checkpoint.latest_step(ckpt_dir)} "
      f"in {ckpt_dir}")

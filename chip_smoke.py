#!/usr/bin/env python3
"""Smoke run of the filter's served path on a TPU, at a table the size of HBM.

    python3 chip_smoke.py                 # one chip: cuckoo + FilterService
    python3 chip_smoke.py --chips 4       # only the sharded-cuckoo 4-chip phase
    JAX_PLATFORMS=cpu python3 chip_smoke.py --cpu-rehearsal   # tiny, on CPU

One process drives every phase through the public entry points
(``repro.amq.make`` -> ``FilterHandle`` -> ``FilterService``) with the paper's
configuration: 16-bit fingerprints, 16-slot buckets, XOR placement, xxHash64,
at a 1 GiB table (2^25 buckets, 2^29 slots).
Keys are generated on the device from ``--seed`` and an index (fmix64 of
``(stream, index)``, a bijection, so distinct indices are distinct keys):
stream 0 holds the inserted keys, stream 1 keys that are never inserted,
stream 2 the service stream's new inserts.

One-chip phases, one line each (wall time, table bytes, load, peak bytes):

1. ``fill``    bulk-insert to load 0.95 with ``handle.insert(bulk=True)``;
2. ``query``   query every inserted key;
3. ``absent``  query as many never-inserted keys;
4. ``delete``  delete the first ``DELETE_FRACTION`` (1/8) of the inserted
   keys, then re-query the kept and the deleted keys;
5. ``service`` ``--dispatches`` full micro-batches of a mixed
   query/insert/delete stream through ``FilterService`` (``max_in_flight=2``,
   so donation and the in-flight window run), and a replay of every op on a
   sample of keys on the ``cpu-cuckoo`` sequential reference.

Checks (any failure exits 1 and prints no result line):

* every insert lands: ``ok`` is true for every valid key, so the failure
  count ``sum(valid & ~ok)`` (``InsertStats.failed``'s definition) is 0;
* inserted keys give no false negatives, before and after the deletes;
* the hit rate on absent keys lies in the band
  ``[0.5, 1.5] * handle.expected_fpr()`` (paper Eq. 4 at the current load),
  widened by 6 binomial standard deviations for small samples;
* ``handle.count()`` equals successful inserts minus successful deletes;
* on the sampled keys, the service's answers agree with the ``cpu-cuckoo``
  replay wherever the semantics are exact: queries of present keys,
  inserts, and deletes of present keys (all must be true).

``--chips 4`` runs only the sharded phase: ``sharded-cuckoo`` over a 4-device
mesh built from ``jax.devices()``, 1 GiB of table per chip, bulk-filled to
``LOAD_4`` (0.25: the one-chip phase covers the fill to 0.95, and this phase
checks what exists only across chips at a quarter of that phase's fill
time); its answers on a shared sample are checked against a one-chip
``cuckoo`` holding the same sample, and every shard's count must be non-zero.

The fill prints a progress line (``# fill: ...``) every 128 batches.

The last line of standard output is ``{"ok": true, "device": {...}}``, and it
is printed only on a TPU. Without a TPU the script fails, unless
``--cpu-rehearsal`` is given: that shrinks every size and ends with a plain
text line instead.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import amq  # noqa: E402
from repro.amq.protocol import OP_DELETE, OP_INSERT, OP_QUERY, OpBatch  # noqa: E402
from repro.compile_cache import disable_tpu_logs, enable_compile_cache  # noqa: E402
from repro.core import bits64 as b64  # noqa: E402
from repro.core import keys_to_numpy  # noqa: E402
from repro.core.cuckoo_filter import CuckooConfig  # noqa: E402

PRESENT, ABSENT, NEW = 0, 1, 2
LOAD = 0.95
LOAD_4 = 0.25          # the --chips 4 fill, per chip
DELETE_FRACTION = 0.125
FPR_BAND = (0.5, 1.5)  # absent-key hit rate / handle.expected_fpr()
FPR_SIGMAS = 6.0
_FMIX1, _FMIX2 = 0xFF51AFD7ED558CCD, 0xC4CEB9FE1A85EC53
_GOLDEN64 = 0x9E3779B97F4A7C15

FAILURES: list = []


def check(cond, what: str) -> None:
    if not bool(cond):
        FAILURES.append(what)
        print(f"FAIL: {what}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Seeded keys, made on the device.
# ---------------------------------------------------------------------------

def _fmix64(x):
    shape = x[0].shape
    x = b64.xor(x, b64.shr(x, 33))
    x = b64.mul(x, b64.from_py(_FMIX1, shape))
    x = b64.xor(x, b64.shr(x, 33))
    x = b64.mul(x, b64.from_py(_FMIX2, shape))
    return b64.xor(x, b64.shr(x, 33))


@functools.partial(jax.jit, static_argnums=0)
def keys_at(seed: int, stream, index):
    """uint32[n, 2] (lo, hi) keys: fmix64((stream, index) + seed * phi)."""
    x = b64.u64(stream, index)
    x = b64.add(x, b64.from_py(seed * _GOLDEN64, x[0].shape))
    hi, lo = _fmix64(x)
    return jnp.stack([lo, hi], axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def key_range(seed: int, stream: int, width: int, start, stop):
    """Keys of indices ``start + [0, width)`` of a stream, masked to ``stop``."""
    idx = start.astype(jnp.uint32) + jnp.arange(width, dtype=jnp.uint32)
    valid = idx < stop.astype(jnp.uint32)
    return keys_at(seed, jnp.full((width,), stream, jnp.uint32), idx), valid


def fmix64_py(stream: int, index: int, seed: int) -> int:
    m = (1 << 64) - 1
    x = (((stream << 32) | index) + seed * _GOLDEN64) & m
    x ^= x >> 33
    x = (x * _FMIX1) & m
    x ^= x >> 33
    x = (x * _FMIX2) & m
    return x ^ (x >> 33)


def batches(seed, stream, start, stop, width):
    for s in range(start, stop, width):
        yield key_range(seed, stream, width, jnp.uint32(s), jnp.uint32(stop))


# ---------------------------------------------------------------------------
# Reporting.
# ---------------------------------------------------------------------------

def peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def report(phase: str, t0: float, handle, devices, **extra) -> None:
    line = {"phase": phase, "wall_s": time.perf_counter() - t0,
            "table_bytes": int(handle.table_bytes),
            "load": handle.load_factor,
            "peak_bytes_in_use": peak_bytes(devices)}
    line.update(extra)
    print(json.dumps(line), flush=True)


def fpr_band(expected: float, n: int):
    slack = FPR_SIGMAS * math.sqrt(max(expected * n, 1.0))
    return (FPR_BAND[0] * expected * n - slack,
            FPR_BAND[1] * expected * n + slack)


def check_absent_rate(label: str, hits: int, n: int, expected: float) -> dict:
    lo, hi = fpr_band(expected, n)
    check(lo <= hits <= hi,
          f"{label}: {hits} absent-key hits of {n} outside [{lo:.1f}, "
          f"{hi:.1f}] (expected_fpr {expected:.3e})")
    return {"absent_hits": hits, "absent_n": n, "absent_rate": hits / n,
            "expected_fpr": expected, "band_hits": [lo, hi]}


# ---------------------------------------------------------------------------
# Phase helpers (every loop keeps its counters on the device: one sync per
# phase, one compiled shape per op).
# ---------------------------------------------------------------------------

def fill(handle, seed, stream, n, width, progress_every=128):
    failed = jnp.zeros((), jnp.int32)
    rounds = jnp.zeros((), jnp.int32)
    t0 = time.perf_counter()
    for i, (keys, valid) in enumerate(batches(seed, stream, 0, n, width)):
        rep = handle.insert(keys, bulk=True, valid=valid)
        failed += jnp.sum(valid & ~(rep.ok & rep.routed), dtype=jnp.int32)
        rounds = jnp.maximum(rounds, rep.rounds)
        if (i + 1) % progress_every == 0:  # syncs: a progress mark
            print(f"# fill: {(i + 1) * width} keys, {int(failed)} failed, "
                  f"{time.perf_counter() - t0:.1f}s", flush=True)
    return int(failed), int(rounds)


def count_hits(handle, seed, stream, start, stop, width):
    hits = jnp.zeros((), jnp.int32)
    unrouted = jnp.zeros((), jnp.int32)
    for keys, valid in batches(seed, stream, start, stop, width):
        res = handle.query(keys, valid=valid)
        hits += jnp.sum(res.hits & valid, dtype=jnp.int32)
        unrouted += jnp.sum(valid & ~res.routed, dtype=jnp.int32)
    check(int(unrouted) == 0, f"query: {int(unrouted)} keys never routed")
    return int(hits)


def delete_range(handle, seed, stream, start, stop, width):
    removed = jnp.zeros((), jnp.int32)
    for keys, valid in batches(seed, stream, start, stop, width):
        rep = handle.delete(keys, valid=valid)
        removed += jnp.sum(rep.ok & rep.routed & valid, dtype=jnp.int32)
    return int(removed)


# ---------------------------------------------------------------------------
# One chip.
# ---------------------------------------------------------------------------

def one_chip(args, devices) -> None:
    seed = args.seed
    cfg = CuckooConfig(num_buckets=1 << args.log2_buckets, fp_bits=16,
                       bucket_size=16, policy="xor", hash_kind="xxhash64")
    handle = amq.make("cuckoo", config=cfg)
    n_fill = math.ceil(LOAD * cfg.num_slots)
    wf, wq = args.fill_batch, args.query_batch

    # Generator check: device keys equal the documented fmix64 on the host.
    probe = np.asarray(keys_to_numpy(keys_at(
        seed, jnp.asarray([PRESENT, ABSENT, NEW], jnp.uint32),
        jnp.asarray([0, 1, 2**32 - 1], jnp.uint32))))
    want = [fmix64_py(s, i, seed) for s, i in
            ((PRESENT, 0), (ABSENT, 1), (NEW, 2**32 - 1))]
    check(probe.tolist() == want, "device key generator != host fmix64")

    # 1. bulk fill.
    t0 = time.perf_counter()
    failed, rounds = fill(handle, seed, PRESENT, n_fill, wf)
    check(failed == 0, f"fill: {failed} of {n_fill} inserts failed")
    check(handle.count() == n_fill,
          f"fill: count {handle.count()} != inserts {n_fill}")
    report("fill", t0, handle, devices, inserted=n_fill, failed=failed,
           batch=wf, max_rounds=rounds)

    # 2. every inserted key.
    t0 = time.perf_counter()
    hits = count_hits(handle, seed, PRESENT, 0, n_fill, wq)
    check(hits == n_fill, f"query: {n_fill - hits} false negatives")
    report("query", t0, handle, devices, queried=n_fill,
           false_negatives=n_fill - hits)

    # 3. as many never-inserted keys.
    t0 = time.perf_counter()
    absent = count_hits(handle, seed, ABSENT, 0, n_fill, wq)
    report("absent", t0, handle, devices, **check_absent_rate(
        "absent", absent, n_fill, handle.expected_fpr()))

    # 4. delete a fraction, re-query kept and deleted keys.
    n_del = int(n_fill * DELETE_FRACTION)
    t0 = time.perf_counter()
    removed = delete_range(handle, seed, PRESENT, 0, n_del, wq)
    check(removed == n_del, f"delete: {n_del - removed} of {n_del} failed")
    check(handle.count() == n_fill - removed,
          f"delete: count {handle.count()} != {n_fill} - {removed}")
    kept = count_hits(handle, seed, PRESENT, n_del, n_fill, wq)
    check(kept == n_fill - n_del,
          f"delete: {n_fill - n_del - kept} false negatives on kept keys")
    gone = count_hits(handle, seed, PRESENT, 0, n_del, wq)
    report("delete", t0, handle, devices, deleted=removed,
           kept_false_negatives=n_fill - n_del - kept,
           **check_absent_rate("deleted keys", gone, n_del,
                               handle.expected_fpr()))

    # 5. a mixed stream through FilterService.
    service_phase(args, handle, devices, n_fill, n_del)


def service_stream(args, n_fill, n_del, rng):
    """Per dispatch: (stream ids, indices, op codes, exact) host arrays.

    Shares of a ``W``-op batch: 45% queries of kept fill keys, 5% queries of
    keys this stream inserted in earlier dispatches, 20% queries of
    never-inserted keys (the only inexact answers), 20% inserts of new keys,
    10% deletes of kept fill keys, each deleted once and never queried.
    """
    w, d = args.service_batch, args.dispatches
    n_ins, n_delw = w // 5, w // 10
    n_abs, n_new_q = w // 5, w // 20
    n_q = w - n_ins - n_delw - n_abs - n_new_q
    pool_end = n_fill - d * n_delw          # delete pool: [pool_end, n_fill)
    if pool_end <= n_del:
        raise ValueError("service stream needs more fill keys than it deletes")
    absent_next = n_fill                     # past the absent phase's range
    for k in range(d):
        q_new = (rng.integers(0, k * n_ins, n_new_q) if k
                 else rng.integers(n_del, pool_end, n_new_q))
        parts = [  # (stream, indices, op, exact)
            (PRESENT, rng.integers(n_del, pool_end, n_q), OP_QUERY, True),
            (NEW if k else PRESENT, q_new, OP_QUERY, True),
            (ABSENT, absent_next + np.arange(n_abs), OP_QUERY, False),
            (NEW, k * n_ins + np.arange(n_ins), OP_INSERT, True),
            (PRESENT, pool_end + k * n_delw + np.arange(n_delw), OP_DELETE,
             True),
        ]
        absent_next += n_abs
        stream = np.concatenate([np.full(len(i), s, np.uint32)
                                 for s, i, _, _ in parts])
        index = np.concatenate([np.asarray(i, np.uint32)
                                for _, i, _, _ in parts])
        ops = np.concatenate([np.full(len(i), o, np.int32)
                              for _, i, o, _ in parts])
        exact = np.concatenate([np.full(len(i), e) for _, i, _, e in parts])
        perm = rng.permutation(w)
        yield stream[perm], index[perm], ops[perm], exact[perm]


def service_phase(args, handle, devices, n_fill, n_del) -> None:
    seed = args.seed
    rng = np.random.default_rng(seed)
    svc = amq.FilterService(handle, batch_size=args.service_batch,
                            max_in_flight=2)
    count0 = handle.count()
    t0 = time.perf_counter()
    sent = []
    for stream, index, ops, exact in service_stream(args, n_fill, n_del, rng):
        keys = np.asarray(keys_at(seed, stream, index))
        sent.append((keys, stream, ops, exact, svc.submit(keys, ops)))
    svc.drain()
    results = [t.result() for *_, t in sent]
    wall = time.perf_counter() - t0

    keys = np.concatenate([s[0] for s in sent])
    streams = np.concatenate([s[1] for s in sent])
    ops = np.concatenate([s[2] for s in sent])
    exact = np.concatenate([s[3] for s in sent])
    ok = np.concatenate(results)
    n_ops = ok.size
    wrong = int((exact & ~ok).sum())
    check(wrong == 0, f"service: {wrong} exact ops answered false "
                      f"(present-key queries, inserts, deletes)")
    ins_ok = int((ok & (ops == OP_INSERT)).sum())
    del_ok = int((ok & (ops == OP_DELETE)).sum())
    check(handle.count() == count0 + ins_ok - del_ok,
          f"service: count {handle.count()} != {count0} + {ins_ok} - {del_ok}")
    absent = ~exact
    extra = check_absent_rate("service absent", int(ok[absent].sum()),
                              int(absent.sum()), handle.expected_fpr())

    # Replay every op on a sample of keys on the sequential reference.
    raw = keys_to_numpy(keys)
    pick = (raw >> np.uint64(64 - args.sample_bits)) == 0
    present0 = pick & (streams == PRESENT)
    init = np.unique(raw[present0])
    ref = amq.make("cpu-cuckoo", capacity=max(1024, 2 * int(pick.sum())))
    t_ref = time.perf_counter()
    ref_ins = ref.insert(init)
    rep = ref.apply_ops(OpBatch.make(raw[pick], ops[pick]))
    ref_ok = np.asarray(rep.ok)
    ex = exact[pick]
    agree = int((ref_ok[ex] == ok[pick][ex]).sum())
    check(bool(np.asarray(ref_ins.ok).all()), "reference: initial inserts")
    check(int(pick.sum()) >= args.min_sample,
          f"service: sample of {int(pick.sum())} ops < {args.min_sample}")
    check(agree == int(ex.sum()) and ref_ok[ex].all(),
          f"service: {int(ex.sum()) - agree} of {int(ex.sum())} exact "
          f"sampled ops disagree with the cpu-cuckoo replay")
    extra.update(ops=n_ops, dispatches=svc.stats["dispatches"],
                 batch=args.service_batch, ops_per_s=n_ops / wall,
                 exact_wrong=wrong, inserts_ok=ins_ok, deletes_ok=del_ok,
                 sample_ops=int(pick.sum()), sample_exact=int(ex.sum()),
                 sample_agree=agree,
                 sample_absent_hits_device=int(ok[pick][~ex].sum()),
                 sample_absent_hits_reference=int(ref_ok[~ex].sum()),
                 reference_replay_s=time.perf_counter() - t_ref)
    report("service", t0, handle, devices, **extra)


# ---------------------------------------------------------------------------
# Four chips.
# ---------------------------------------------------------------------------

def four_chips(args, devices) -> None:
    seed = args.seed
    n_dev = 4
    per_chip_slots = 16 << args.log2_buckets_4
    capacity = n_dev * int(LOAD * per_chip_slots)
    handle = amq.make("sharded-cuckoo", capacity=capacity, num_shards=n_dev,
                      fp_bits=16, bucket_size=16, policy="xor",
                      hash_kind="xxhash64",
                      capacity_factor=args.shard_bin_factor)
    check(handle.config.inner.shard.num_buckets == 1 << args.log2_buckets_4,
          "sharded: per-chip table is not the requested size")
    n_fill = math.ceil(LOAD_4 * handle.config.num_slots)

    t0 = time.perf_counter()
    failed, _ = fill(handle, seed, PRESENT, n_fill, args.fill_batch_4)
    counts = [int(c) for c in np.asarray(handle.state.count)]
    check(failed == 0, f"sharded fill: {failed} of {n_fill} inserts failed")
    check(handle.count() == n_fill,
          f"sharded fill: count {handle.count()} != {n_fill}")
    check(all(c > 0 for c in counts), f"sharded: a shard is empty {counts}")
    placement = sorted({d.id for d in handle.state.table.devices()})
    check(len(placement) == n_dev, f"sharded: table on devices {placement}")
    report("sharded_fill", t0, handle, devices, inserted=n_fill,
           failed=failed, shard_counts=counts, table_devices=placement)

    # The shared sample, answered by the sharded filter and by a one-chip
    # cuckoo filter that holds just the sample at the same load.
    one = amq.make("cuckoo", config=CuckooConfig(
        num_buckets=1 << args.log2_buckets_sample, fp_bits=16,
        bucket_size=16, policy="xor", hash_kind="xxhash64"))
    n_s = math.ceil(LOAD * one.config.num_slots)
    check(n_s <= n_fill, f"sample of {n_s} keys > {n_fill} inserted")
    t0 = time.perf_counter()
    failed1, _ = fill(one, seed, PRESENT, n_s, args.fill_batch)
    check(failed1 == 0, f"one-chip sample fill: {failed1} failed")
    lines = {}
    for name, h in (("sharded", handle), ("one_chip", one)):
        hits = count_hits(h, seed, PRESENT, 0, n_s, args.query_batch)
        check(hits == n_s, f"{name}: {n_s - hits} false negatives on sample")
        absent = count_hits(h, seed, ABSENT, 0, n_s, args.query_batch)
        lines[name] = dict(false_negatives=n_s - hits, **check_absent_rate(
            f"{name} absent", absent, n_s, h.expected_fpr()))
    report("sharded_vs_one_chip", t0, handle, devices, sample=n_s,
           one_chip_load=one.load_factor, **lines)


# ---------------------------------------------------------------------------

def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU at a tiny size; prints no result")
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        sizes = dict(log2_buckets=12, log2_buckets_4=10,
                     log2_buckets_sample=9, fill_batch=1 << 10,
                     fill_batch_4=1 << 10, query_batch=1 << 10,
                     service_batch=1 << 10, dispatches=32, sample_bits=0,
                     min_sample=1 << 15, shard_bin_factor=2.0)
    else:
        # 2^25 buckets x 16 slots x 16 bits = 1 GiB, on one chip and on
        # each of four: the size whose fill keeps the whole run well inside
        # its 20-minute limit; 2^19 keys per chip per sharded fill batch,
        # routed into bins 1.25x the mean a partition receives (over 70
        # binomial sigmas at these widths; the tiny rehearsal needs 2x);
        # 256 dispatches of 2^16 ops, 1/128 of the keys replayed on the
        # reference.
        sizes = dict(log2_buckets=25, log2_buckets_4=25,
                     log2_buckets_sample=20, fill_batch=1 << 19,
                     fill_batch_4=1 << 21, query_batch=1 << 20,
                     service_batch=1 << 16, dispatches=256, sample_bits=7,
                     min_sample=1 << 16, shard_bin_factor=1.25)
    for k, v in sizes.items():
        setattr(args, k, v)
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    disable_tpu_logs()
    enable_compile_cache()
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    print(f"# jax {jax.__version__}: {len(devices)} x {platform} ({kind})",
          flush=True)
    if platform != "tpu" and not args.cpu_rehearsal:
        print(f"no TPU: jax found {platform!r} devices (use --cpu-rehearsal "
              "for a tiny CPU run, which prints no result)", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, jax found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    used = devices[:args.chips]
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args, used)
    else:
        one_chip(args, used)
    wall = time.perf_counter() - t0
    if FAILURES:
        print(f"{len(FAILURES)} check(s) failed after {wall:.1f}s",
              file=sys.stderr)
        return 1
    if args.cpu_rehearsal:
        print(f"rehearsal passed on {platform} in {wall:.1f}s "
              "(not a chip result)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

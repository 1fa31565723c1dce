"""Closed loop of bulk calls on a filled table: one batch in flight after another.

Traffic parameters (``chipbench/traffic/<mix>.json``):

* ``batch``: keys per call; ``present_share``: the share of each batch
  drawn uniformly from the inserted keys, the rest never inserted, the
  two shuffled together;
* ``pool_batches``: distinct batches made on the device in set-up, cycled
  through by the window;
* ``in_flight``: calls dispatched ahead of the oldest unfinished one;
* ``sampled_every``: the first step, and every this-many steps from a
  seed-drawn phase, keep their whole answer arrays for the check; every
  step keeps its hit count.

Set-up fills the table to the config's load with the program's bulk insert
and warms up the one query shape. The window dispatches
``FilterHandle.query`` back to back for ``--seconds`` and ends when the last
call's answers are ready; ``ops_per_s`` is every key answered over that
time. An answer is a hit that was routed. The check compares each step's
hit count, and the kept answers one by one, with the plain reference.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from drivers.common import Spans, fill, fill_size, make_handle, traced
from yardstick import keys as K
from yardstick import reference as R

INSERTED, ABSENT = 0, 1
MAX_KEPT = 64


@jax.jit
def bench_hit_count(hits, routed):
    """Answered hits of one step, kept on the device."""
    return jnp.sum(hits & routed, dtype=jnp.int32)


@jax.jit
def bench_batch(present_index, absent_start, order, offset):
    """One pool batch: the given inserted keys and as many fresh absent
    keys, shuffled together by ``order``."""
    n_abs = present_index.shape[0]
    present = K.device_keys(jnp.full(present_index.shape, INSERTED,
                                     jnp.uint32), present_index, offset)
    absent = K.device_keys(
        jnp.full((n_abs,), ABSENT, jnp.uint32),
        absent_start + jnp.arange(n_abs, dtype=jnp.uint32), offset)
    return jnp.concatenate([present, absent])[order]


class Cell:
    """One run of a closed-loop bulk cell (see the module docstring)."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, devices):
        if traffic["op"] != "query":
            raise ValueError(f"closed_loop_bulk drives queries, not "
                             f"{traffic['op']!r}")
        self.config, self.traffic, self.seed = config, traffic, seed
        self.offset = K.seed_offset(seed)
        self.spans = Spans()
        batch, share = traffic["batch"], traffic["present_share"]
        if batch % 2 or abs(share - 0.5) > 1e-9:
            raise ValueError("closed_loop_bulk makes half-present batches "
                             "of an even width")
        self.batch = batch
        self.n_fill = fill_size(config)
        rng = np.random.default_rng(seed)
        self.present_index = rng.integers(
            0, self.n_fill, (traffic["pool_batches"], batch // 2),
            dtype=np.uint32)
        self.order = np.stack([rng.permutation(batch).astype(np.int32)
                               for _ in range(traffic["pool_batches"])])
        self.phase = int(rng.integers(traffic["sampled_every"]))
        self.record = {}

    def setup(self) -> None:
        self.handle = make_handle(self.config)
        self.fill_failed = fill(self.handle, INSERTED, self.n_fill,
                                self.offset, self.config["fill_batch"])
        half = self.batch // 2
        self.pool = [bench_batch(idx, np.uint32(j * half), order, self.offset)
                     for j, (idx, order) in enumerate(zip(self.present_index,
                                                          self.order))]
        res = self.handle.query(self.pool[0])
        bench_hit_count(res.hits, res.routed).block_until_ready()

    def window(self, seconds: float, trace_dir) -> dict:
        handle, pool, spans = self.handle, self.pool, self.spans
        depth, every = self.traffic["in_flight"], self.traffic["sampled_every"]
        counts, kept = [], {}
        step = 0
        with traced(trace_dir, spans):
            t0 = time.perf_counter()
            t_end = t0 + seconds
            while True:
                with spans("dispatch"):
                    res = handle.query(pool[step % len(pool)])
                    counts.append(bench_hit_count(res.hits, res.routed))
                if (step == 0 or step % every == self.phase) and (
                        len(kept) < MAX_KEPT):
                    kept[step] = res
                step += 1
                if len(counts) > depth:
                    with spans("wait"):
                        counts[-depth - 1].block_until_ready()
                if time.perf_counter() >= t_end:
                    break
            with spans("drain"):
                jax.block_until_ready(counts)
            t1 = time.perf_counter()
        self.counts = np.asarray(jax.device_get(counts), np.int64)
        self.kept = {s: np.asarray(r.hits) & np.asarray(r.routed)
                     for s, r in kept.items()}
        keys = step * self.batch
        self.record.update(
            attempted=keys, failed=0, window_host_s=t1 - t0,
            end_to_end={"ops_per_s": keys / (t1 - t0)},
            traced_ops={"query": keys}, spans=dict(spans.seconds))
        return self.record

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        del self.handle, self.pool

    # -- the check ------------------------------------------------------------

    def reference(self, fp_bits: int) -> list:
        """Per pool batch, the plain reference's answer to every key."""
        nb, seed = self.config["num_buckets"], self.seed
        table = R.chunked(self.n_fill, lambda a, b: R.signatures(
            K.keys_np(INSERTED, np.arange(a, b, dtype=np.uint64), seed),
            nb, fp_bits))
        table.sort()
        half = self.batch // 2
        out = []
        for j, idx in enumerate(self.present_index):
            keys = np.concatenate([
                K.keys_np(INSERTED, idx, seed),
                K.keys_np(ABSENT, np.arange(j * half, (j + 1) * half,
                                            dtype=np.uint64), seed)])
            keys = keys[self.order[j]]
            out.append(R.contains(table, R.signatures(keys, nb, fp_bits)))
        return out

    def observed(self) -> dict:
        """What the window produced, as the check reads it."""
        return {"fill_failed": self.fill_failed, "counts": self.counts,
                "kept": self.kept}

    def compare(self, observed: dict, answers: list) -> dict:
        """Each compared number beside its limit (all exact: limit 0)."""
        pool = len(answers)
        want = np.array([a.sum() for a in answers], np.int64)
        steps = np.arange(observed["counts"].size)
        wrong_counts = int((observed["counts"] != want[steps % pool]).sum())
        wrong_answers = sum(int((h != answers[s % pool]).sum())
                            for s, h in observed["kept"].items())
        self.record["failed"] = wrong_answers
        return {"fill_failed": {"value": int(observed["fill_failed"]),
                                "limit": 0},
                "wrong_counts": {"value": wrong_counts, "limit": 0},
                "wrong_answers": {"value": wrong_answers, "limit": 0}}

    def check(self) -> dict:
        return self.compare(self.observed(),
                            self.reference(self.config["fp_bits"]))

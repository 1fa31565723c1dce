"""Handle state is donated to every mutating op, on the CPU as on a TPU.

``FilterHandle`` jits donate the state to insert, bulk insert, delete and
``apply_ops`` on every backend, so a read of a donated buffer fails here
("Array has been deleted") exactly where it would on the chip. These cases
drive the layers that hold state across calls — the service's in-flight
window and hot swap, the cascade's multi-level query, tier demotion — and
check that nothing reads a buffer after it was donated.
"""

import numpy as np

from repro import amq
from repro.core import keys_from_numpy


def _keys(seed, n):
    rng = np.random.default_rng(seed)
    return keys_from_numpy(np.unique(
        rng.integers(1, 2**63, size=2 * n, dtype=np.uint64))[:n])


def test_mutating_ops_donate_the_state_and_query_does_not():
    h = amq.make("cuckoo", capacity=4096)
    keys = _keys(0, 2048)
    steps = [
        (lambda: h.insert(keys[:1024], bulk=True), True),
        (lambda: h.insert(keys[1024:]), True),
        (lambda: h.query(keys), False),
        (lambda: h.delete(keys[:16]), True),
        (lambda: h.apply_ops(amq.OpBatch.make(
            keys[:32], np.full(32, amq.OP_QUERY, np.int32))), True),
    ]
    for step, donates in steps:
        before = h.state
        step()
        assert before.table.is_deleted() == donates
        assert not h.state.table.is_deleted()
    assert np.asarray(h.query(keys[16:]).hits).all()
    assert h.count() == 2048 - 16


def test_service_window_and_hot_swap_read_only_live_state():
    h = amq.make("cuckoo", capacity=1 << 14)
    svc = amq.FilterService(h, batch_size=256, max_in_flight=2)
    keys = _keys(1, 4096)
    inserted = [svc.insert(keys[i:i + 512]) for i in range(0, 2048, 512)]
    queried = svc.query(keys[:2048])
    replica = amq.make("cuckoo", config=h.config)
    svc.hot_swap(replica)
    after = svc.insert(keys[2048:])
    svc.drain()
    assert all(t.result().all() for t in inserted)
    assert queried.result().all()
    assert after.result().all()
    assert np.asarray(replica.query(keys).hits).all()


def test_cascade_and_tiers_survive_donation():
    keys = _keys(2, 1024)
    grown = amq.make("cuckoo", capacity=256, auto_expand=True)
    for i in range(0, 1024, 256):
        assert np.asarray(grown.insert(keys[i:i + 256], bulk=True).ok).all()
    assert len(grown.levels) > 1
    assert np.asarray(grown.query(keys).hits).all()
    assert np.asarray(grown.delete(keys[:512]).ok).all()
    grown.compact()
    assert np.asarray(grown.query(keys[512:]).hits).all()

    tiered = amq.make("cuckoo", capacity=128, tiered=True,
                      device_budget_bytes=2 * 1024)
    assert np.asarray(tiered.insert(keys).ok).all()
    assert len(tiered.cold) >= 1
    assert np.asarray(tiered.query(keys).hits).all()
    assert np.asarray(tiered.delete(keys[:64]).ok).all()
    assert np.asarray(tiered.query(keys[64:]).hits).all()

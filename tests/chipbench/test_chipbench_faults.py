"""A run with the timed path broken underneath comes out not correct.

Each fault is planted in the program's handle from the test, then the rest
of a run goes as on the chip (the chip check and sizes steered as in the
rehearsal): a step that returns its state unchanged, half of each batch
left out, and one answer altered where it is produced. One chip, so no
exchange between chips can be left out.
"""

import jax
import jax.numpy as jnp
import pytest

import chipbench_harness as H
from repro.amq.handle import FilterHandle


def _keep_state(orig):
    def op(self, *args, **kw):
        before = jax.tree.map(jnp.copy, self.state)
        out = orig(self, *args, **kw)
        self.state = before
        return out
    return op


def _first_half(valid, n):
    half = jnp.arange(n) < n // 2
    return half if valid is None else jnp.asarray(valid) & half


def _half_query(orig):
    def query(self, keys, *, valid=None):
        return orig(self, keys, valid=_first_half(valid, keys.shape[0]))
    return query


def _flip_query(orig):
    def query(self, keys, *, valid=None):
        res = orig(self, keys, valid=valid)
        return res._replace(hits=res.hits.at[0].set(~res.hits[0]))
    return query


FAULTS = {
    ("bulk.query95", "state_unchanged"): ("insert", _keep_state),
    ("bulk.query95", "half_batch"): ("query", _half_query),
    ("bulk.query95", "answer_altered"): ("query", _flip_query),
}


@pytest.mark.parametrize("cell,fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(monkeypatch, cell, fault):
    method, plant = FAULTS[cell, fault]
    monkeypatch.setattr(FilterHandle, method,
                        plant(getattr(FilterHandle, method)))
    rc, line, err = H.run_tiny(monkeypatch, cell)
    assert rc == 0, err
    assert line["correct"] is False, line["checks"]

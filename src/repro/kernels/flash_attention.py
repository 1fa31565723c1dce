"""Pallas TPU kernel: fused flash attention (forward).

§Perf follow-up for the memory-bound prefill/train cells: the XLA-level
online-softmax attention (models/attention.flash_attention) materializes
each [Cq, Ck] score chunk in HBM per scan step — the dominant memory-term
contributor for every long-sequence cell. This kernel keeps the score block,
running max/denominator and output accumulator in VMEM scratch across the
KV-block grid steps; HBM traffic collapses to the q/k/v reads + out write.

Grid: (B * KVH, g, nq, nk) — nk innermost, so scratch accumulators persist
across a q-row's KV sweep (TPU grid steps run sequentially on a core).
GQA is handled by indexing k/v blocks with the leading B*KVH coordinate
while q/out carry the per-kv-group head dim g.

Validated in interpret mode against models.attention.flash_attention and
kernels/ref.py; on this CPU container the interpret lowering necessarily
re-materializes blocks (no VMEM), so the §Perf effect is reported as a
projection (EXPERIMENTS.md §Perf B4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(causal: bool, window, scale: float, blk_q: int, blk_k: int,
                  seq_k: int,
                  q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    k_pos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)

    # skip fully-masked blocks (causal upper triangle / outside the window)
    relevant = True
    if causal:
        relevant = (ik * blk_k) <= (iq * blk_q + blk_q - 1)
    if window is not None:
        relevant = relevant & ((iq * blk_q) - (ik * blk_k + blk_k - 1)
                               < window)

    @pl.when(relevant)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)          # [blk_q, D]
        k = k_ref[0].astype(jnp.float32)             # [blk_k, D]
        v = v_ref[0].astype(jnp.float32)             # [blk_k, Dv]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = k_pos < seq_k
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        m_safe = jnp.maximum(m_new, NEG_INF * 1e-10)
        p = jnp.exp(s - m_safe[:, None])
        corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=None, scale=None,
                           blk_q: int = 512, blk_k: int = 512,
                           interpret: bool):
    """Fused attention forward.

    q: [BK, g, Sq, D]; k: [BK, Sk, D]; v: [BK, Sk, Dv] where BK = B * KVH
    and g = query heads per KV head. Returns [BK, g, Sq, Dv].
    """
    BK, g, Sq, D = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Sk)
    pq = (-Sq) % blk_q
    pk = (-Sk) % blk_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    nq = (Sq + pq) // blk_q
    nk = (Sk + pk) // blk_k

    kernel = functools.partial(_flash_kernel, causal, window, scale,
                               blk_q, blk_k, Sk)
    out = pl.pallas_call(
        kernel,
        grid=(BK, g, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, h, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, Dv), lambda b, h, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, Dv),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BK, g, Sq + pq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, Dv), jnp.float32),   # acc
            pltpu.VMEM((blk_q,), jnp.float32),      # running max
            pltpu.VMEM((blk_q,), jnp.float32),      # running denom
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return out[:, :, :Sq]

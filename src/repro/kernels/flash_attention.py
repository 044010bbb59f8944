"""Pallas TPU flash attention (prefill/train path) with GQA, causal and
sliding-window masking.

Grid: (B·H, n_q_blocks, n_kv_blocks); the kv axis is the innermost
(sequential on TPU), carrying the online-softmax state in VMEM scratch.
Blocks are (block_q, head_dim) / (block_kv, head_dim) tiles — head_dim and
block sizes should be multiples of the 128-lane MXU tile on real hardware.
Fully-masked kv blocks (above the causal diagonal / outside the window) are
skipped via pl.when, so HLO work matches the useful work.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            sq: int, skv: int, block_q: int, block_kv: int,
            causal: bool, window: Optional[int], scale: float):
    iq = pl.program_id(1)
    ikv = pl.program_id(2)
    nkv = pl.num_programs(2)

    @pl.when(ikv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_first = iq * block_q
    q_last = q_first + block_q - 1
    kv_first = ikv * block_kv
    kv_last = kv_first + block_kv - 1

    relevant = True
    if causal:
        relevant = kv_first <= q_last                 # at/below diagonal
    if window is not None:
        relevant = jnp.logical_and(relevant, kv_last > q_first - window)

    @pl.when(relevant)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale       # (bq, hd)
        k = k_ref[0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = kv_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < skv                              # kv padding
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                            # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())))
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    # last relevant kv block for this q block
    if causal:
        last = jnp.minimum(nkv - 1, ((iq + 1) * block_q - 1) // block_kv)
    else:
        last = nkv - 1

    @pl.when(ikv == last)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("num_heads", "num_kv_heads", "causal", "window",
                     "block_q", "block_kv", "interpret"))
def flash_attention_bhsd(q, k, v, *, num_heads: int, num_kv_heads: int,
                         causal: bool = True, window: Optional[int] = None,
                         block_q: int = 128, block_kv: int = 128,
                         interpret: bool):
    """q: (B·H, Sq, hd); k, v: (B·KVH, Skv, hd) -> (B·H, Sq, hd)."""
    bh, sq, hd = q.shape
    bkv, skv, _ = k.shape
    h, kvh = num_heads, num_kv_heads
    g = h // kvh
    block_q = min(block_q, max(sq, 8))
    block_kv = min(block_kv, max(skv, 8))
    pq = (-sq) % block_q
    pkv = (-skv) % block_kv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, pkv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pkv), (0, 0)))
    nq = (sq + pq) // block_q
    nkv = (skv + pkv) // block_kv

    def kv_index(bhi, iq, ikv):
        return ((bhi // h) * kvh + (bhi % h) // g, ikv, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, sq=sq, skv=skv, block_q=block_q,
                          block_kv=block_kv, causal=causal, window=window,
                          scale=1.0 / math.sqrt(hd)),
        grid=(bh, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, iq, ikv: (b, iq, 0)),
            pl.BlockSpec((1, block_kv, hd), kv_index),
            pl.BlockSpec((1, block_kv, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda b, iq, ikv: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq + pq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]

"""Pallas TPU decode attention: one query token against a (ring-buffer) KV
cache, GQA-packed.

Grid: (B·KVH, n_kv_blocks).  The G query heads that share one KV head are
processed together as the rows of a (G, hd) tile — this keeps the MXU busy
at G×block_kv×hd per step instead of vector-only work, the standard
flash-decode GQA packing.  Slot validity (ring buffers may be partially
filled) comes from a scalar ``valid`` operand.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            block_kv: int, s_cache: int, scale: float):
    ikv = pl.program_id(1)
    nkv = pl.num_programs(1)

    @pl.when(ikv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = valid_ref[0, 0]
    kv_first = ikv * block_kv

    @pl.when(kv_first < valid)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale          # (G, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, bk)
        kpos = kv_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.logical_and(kpos < valid, kpos < s_cache)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())))
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(ikv == nkv - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("num_heads", "num_kv_heads", "block_kv", "interpret"))
def decode_attention_packed(q, k, v, valid, *, num_heads: int,
                            num_kv_heads: int, block_kv: int = 512,
                            interpret: bool):
    """q: (B·KVH, G, hd); k, v: (B·KVH, Sc, hd); valid: () int32
    (number of valid cache slots) -> (B·KVH, G, hd)."""
    bkv, g, hd = q.shape
    _, sc, _ = k.shape
    block_kv = min(block_kv, max(sc, 8))
    pkv = (-sc) % block_kv
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, pkv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pkv), (0, 0)))
    nkv = (sc + pkv) // block_kv
    valid2d = jnp.reshape(valid.astype(jnp.int32), (1, 1))

    out = pl.pallas_call(
        functools.partial(_kernel, block_kv=block_kv, s_cache=sc,
                          scale=1.0 / math.sqrt(hd)),
        grid=(bkv, nkv),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, ikv: (0, 0)),
            pl.BlockSpec((1, g, hd), lambda b, ikv: (b, 0, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, ikv: (b, ikv, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, ikv: (b, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, hd), lambda b, ikv: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bkv, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(valid2d, q, k, v)
    return out

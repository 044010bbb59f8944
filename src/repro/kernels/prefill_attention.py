"""Pallas TPU kernel for the attention core of a prompt that fits one VMEM
block: per-head RMS qk-norm, RoPE and grouped causal attention, fused.

The kernel reads q, k and v in the layout the projections write them,
``(B, S, heads·hd)``, and writes its output in the layout ``wo`` reads, so
no head-major relayout happens around it.  Each grid step takes a block of
batch rows and one 128-lane column of k/v: one KV head when ``hd`` is 128,
two when it is 64.  The q columns of the query heads that share those KV
heads come with it.

qk-norm and RoPE run over the whole block at once.  Then, for each row,
every query head is one "piece": its 128-lane q chunk, masked to the
head's lanes and rolled so that they sit over its KV head's lanes.  The
pieces of a row are stacked along sublanes, so the row's scores and its PV
product are one matmul each against the unrepeated k/v column.
Statistics, RoPE and softmax are f32; q, k and the probabilities enter the
matmuls in the inputs' dtype (bf16 when served), as ``models.attention``
rounds them.  Scores for the whole prompt stay in VMEM: no online softmax,
no KV loop.
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
LANES = 128
# f32 scores of one row's query heads against its prompt, (pieces·S, S)
SCORE_BYTES = 4 * 1024 * 1024
# q block of one grid step: batch rows are added while they fit
Q_BLOCK_BYTES = 512 * 1024


def fits(seq_len: int, num_heads: int, num_kv_heads: int,
         head_dim: int) -> bool:
    """Whether a prompt of ``seq_len`` fits the kernel's single block: a
    head size of 64 or 128, k/v columns in whole 128-lane tiles, whole bf16
    sublane tiles of rows, and one row's scores within ``SCORE_BYTES``."""
    if head_dim not in (64, 128) or (num_kv_heads * head_dim) % LANES:
        return False
    if seq_len % 16:
        return False
    pieces = LANES // head_dim * num_heads // num_kv_heads
    return pieces * seq_len * seq_len * 4 <= SCORE_BYTES


def _rms_norm(x, scale, lane, hd: int, eps: float):
    """Per-head RMS norm of f32 128-lane chunks, each holding 128 // hd
    heads."""
    sq = x * x
    if hd == LANES:
        ssq = jnp.sum(sq, axis=-1, keepdims=True)
    else:
        lo = lane < hd
        s_lo = jnp.sum(jnp.where(lo, sq, 0.0), axis=-1, keepdims=True)
        s_hi = jnp.sum(jnp.where(lo, 0.0, sq), axis=-1, keepdims=True)
        ssq = jnp.where(lo, s_lo, s_hi)
    var = ssq * (1.0 / hd)                     # exact: hd is a power of 2
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, cos, sin, lane, hd: int):
    """``common.apply_rope`` on f32 128-lane chunks: each head's lower half
    pairs with its upper half."""
    half = hd // 2
    axis = x.ndim - 1
    upper = pltpu.roll(x, LANES - half, axis)  # lane i <- lane i + half
    lower = upper if 2 * half == LANES else pltpu.roll(x, half, axis)
    rot = jnp.where(lane % hd < half, -upper, lower)
    return x * cos + rot * sin


def _kernel(*refs, rows: int, seq: int, hd: int, group: int, causal: bool,
            window: Optional[int], eps: float, qk_norm: bool, rope: bool):
    q_ref, k_ref, v_ref = refs[:3]
    rest = list(refs[3:-1])
    o_ref = refs[-1]
    qs_ref = ks_ref = cos_ref = sin_ref = None
    if qk_norm:
        qs_ref, ks_ref = rest[:2]
        rest = rest[2:]
    if rope:
        cos_ref, sin_ref = rest

    per_chunk = LANES // hd                    # heads in a 128-lane chunk
    n_chunks = q_ref.shape[-1] // LANES
    n_pieces = n_chunks * per_chunk
    scale = 1.0 / math.sqrt(hd)
    lane = jax.lax.broadcasted_iota(jnp.int32, (seq, LANES), 1)
    # the stacked pieces' query positions, and the key positions
    qpos = jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 0)
    if n_pieces > 1:
        qpos = jnp.concatenate([qpos] * n_pieces, axis=0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, qpos.shape, 1)
    mask = kpos <= qpos if causal else kpos >= 0
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qpos - window)

    def prep(x, scale_ref):
        """qk-norm, then RoPE, of a (rows, S, 128) chunk of the block; each
        rounded to the input's dtype as the model rounds them."""
        dtype = x.dtype
        lane3 = lane[None]
        if qk_norm:
            x = _rms_norm(x.astype(jnp.float32),
                          scale_ref[...].astype(jnp.float32), lane3, hd, eps)
            x = x.astype(dtype)
        if rope:
            x = _rope(x.astype(jnp.float32), cos_ref[...], sin_ref[...],
                      lane3, hd)
        return x.astype(dtype)

    # over the whole block at once: at qwen3-0.6b's shapes on a v5e this
    # halved the kernel's time against a pass per row
    ks = prep(k_ref[...], ks_ref)
    qcs = [prep(q_ref[:, :, c * LANES:(c + 1) * LANES], qs_ref)
           for c in range(n_chunks)]
    for r in range(rows):
        k = ks[r]
        v = v_ref[r]
        pieces, shifts = [], []
        for c in range(n_chunks):
            qc = qcs[c][r]
            for h in range(per_chunk):
                kv_local = (c * per_chunk + h) // group
                shift = (kv_local - h) * hd % LANES
                piece = qc
                if per_chunk > 1:
                    own = (lane >= h * hd) & (lane < (h + 1) * hd)
                    piece = jnp.where(own, qc, jnp.zeros_like(qc))
                if shift:                       # Mosaic rotates 32-bit data
                    piece = pltpu.roll(piece.astype(jnp.float32), shift,
                                       1).astype(qc.dtype)
                pieces.append(piece)
                shifts.append(shift)
        qs = jnp.concatenate(pieces, axis=0) if n_pieces > 1 else pieces[0]
        s = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        out = pv / jnp.maximum(l, 1e-30)
        for c in range(n_chunks):
            acc = None
            for h in range(per_chunk):
                i = c * per_chunk + h
                oi = out[i * seq:(i + 1) * seq]
                if shifts[i]:
                    oi = pltpu.roll(oi, LANES - shifts[i], 1)
                if acc is None:
                    acc = oi
                else:
                    own = (lane >= h * hd) & (lane < (h + 1) * hd)
                    acc = jnp.where(own, oi, acc)
            o_ref[r, :, c * LANES:(c + 1) * LANES] = acc.astype(o_ref.dtype)


def _lane_tile(x, hd: int):
    """A per-head (…, hd) vector repeated across a 128-lane chunk."""
    return jnp.tile(x, (1,) * (x.ndim - 1) + (LANES // hd,))


def prefill_attention(q, k, v, *, num_heads: int, num_kv_heads: int,
                      q_scale=None, k_scale=None, eps: float = 1e-6,
                      cos=None, sin=None, causal: bool = True,
                      window: Optional[int] = None, interpret: bool):
    """Attention core of a prompt in one VMEM block.

    q: (B, S, H·hd); k, v: (B, S, KVH·hd), projected with bias added.
    q_scale, k_scale: (hd,) qk-norm scales, or None for no qk-norm.
    cos, sin: (Bp, S, hd) f32 RoPE tables (Bp is 1 or B), or None.
    Returns (B, S, H·hd) in q's dtype.  ``fits`` must hold.
    """
    b, s, qw = q.shape
    hd = qw // num_heads
    group = num_heads // num_kv_heads
    assert fits(s, num_heads, num_kv_heads, hd), (s, num_heads, hd)
    qk_norm = q_scale is not None
    rope = cos is not None
    kv_per_block = LANES // hd
    qb = kv_per_block * group * hd             # q columns of one k/v column
    row_bytes = s * qb * q.dtype.itemsize
    rows = max(r for r in range(1, b + 1)
               if b % r == 0 and (r == 1 or r * row_bytes <= Q_BLOCK_BYTES))
    grid = (b // rows, num_kv_heads // kv_per_block)

    in_specs = [pl.BlockSpec((rows, s, qb), lambda i, j: (i, 0, j)),
                pl.BlockSpec((rows, s, LANES), lambda i, j: (i, 0, j)),
                pl.BlockSpec((rows, s, LANES), lambda i, j: (i, 0, j))]
    args = [q, k, v]
    if qk_norm:
        for x in (q_scale, k_scale):
            args.append(_lane_tile(x.reshape(1, hd), hd))
            in_specs.append(pl.BlockSpec((1, LANES), lambda i, j: (0, 0)))
    if rope:
        if cos.shape[0] > 1:                   # a table per row
            spec = pl.BlockSpec((rows, s, LANES), lambda i, j: (i, 0, 0))
        else:
            spec = pl.BlockSpec((1, s, LANES), lambda i, j: (0, 0, 0))
        for x in (cos, sin):
            args.append(_lane_tile(x.astype(jnp.float32), hd))
            in_specs.append(spec)

    flops = 4 * b * num_heads * s * s * hd
    nbytes = sum(x.size * x.dtype.itemsize for x in args + [q])
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows, seq=s, hd=hd, group=group,
                          causal=causal, window=window, eps=eps,
                          qk_norm=qk_norm, rope=rope),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, s, qb), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=b * num_heads * s * s,
            bytes_accessed=nbytes),
        name="prefill_attention",
        interpret=interpret,
    )(*args)

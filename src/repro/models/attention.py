"""Attention: GQA/MQA/MHA, RoPE, qk-norm, sliding window, KV-cache decode.

Execution paths:
- ``prefill_attention`` — the Pallas TPU kernel of
                        repro.kernels.prefill_attention: qk-norm, RoPE and
                        grouped attention of a prompt in one VMEM block,
                        on q/k/v as the projections write them.  Prefill on
                        a TPU, where the prompt fits (``attn_path``).
- ``flash_attn``      — chunked online-softmax attention (pure-XLA scan over
                        KV blocks; bounded transients at 32k prefill).  Used by
                        train, and by prefill elsewhere.  A Pallas TPU kernel
                        implementing the same contract lives in
                        repro.kernels.flash_attention.
- ``decode_attn``     — one-token attention over a (possibly ring-buffer)
                        KV cache.  Pallas twin: repro.kernels.decode_attention.
- naive reference     — in repro.kernels.ref (oracle for all).
"""
from __future__ import annotations

import collections
import contextlib
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import prefill_attention as prefill_kernel
from repro.models.common import (apply_rope, constrain, dense_init,
                                 get_sharding_rules, head_rms_norm,
                                 rope_frequencies)

NEG_INF = -1e30
KERNEL_PATH = "prefill_attention"
FLASH_PATH = "flash_attn"

_paths = threading.local()


class KVCache(NamedTuple):
    """Per-layer KV cache.  ``k``/``v``: (B, S_cache, KVH, hd).

    S_cache is the full context for dense decode or the window size for the
    ring-buffer (sliding-window / long-context) variant.  Writes go to slot
    ``pos % S_cache``; with S_cache == max context this is a plain cache.
    """
    k: jax.Array
    v: jax.Array


def make_kv_cache(batch: int, s_cache: int, kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, s_cache, kv_heads, head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def init_attn_params(key, cfg: ModelConfig, dtype=jnp.bfloat16,
                     cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kvh = cfg.num_heads, cfg.num_kv_heads
    keys = jax.random.split(key, 8)
    p = {
        "wq": dense_init(keys[0], (d, h * hd), dtype=dtype),
        "wk": dense_init(keys[1], (d, kvh * hd), dtype=dtype),
        "wv": dense_init(keys[2], (d, kvh * hd), dtype=dtype),
        "wo": dense_init(keys[3], (h * hd, d), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kvh * hd,), dtype)
        p["bv"] = jnp.zeros((kvh * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project(x: jax.Array, p: dict, cfg: ModelConfig):
    """x: (B, S, d) -> q (B,S,H·hd), k/v (B,S,KVH·hd), bias added."""
    q = jnp.einsum("bsd,dk->bsk", x, p["wq"])
    k = jnp.einsum("bsd,dk->bsk", x, p["wk"])
    v = jnp.einsum("bsd,dk->bsk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


def _heads(x: jax.Array, heads: int, norm: Optional[jax.Array],
           cfg: ModelConfig, positions: Optional[jax.Array]) -> jax.Array:
    """(B, S, heads·hd) -> (B, S, heads, hd); qk-norm and RoPE applied."""
    b, s, _ = x.shape
    x = x.reshape(b, s, heads, cfg.resolved_head_dim)
    if cfg.qk_norm:
        x = head_rms_norm(x, norm, cfg.norm_eps)
    if cfg.rope and positions is not None:
        x = apply_rope(x, positions, cfg.rope_theta)
    return x


def project_qkv(x: jax.Array, p: dict, cfg: ModelConfig,
                positions: Optional[jax.Array]):
    """x: (B, S, d) -> q (B,S,H,hd), k/v (B,S,KVH,hd); RoPE applied."""
    b, s, _ = x.shape
    q, k, v = _project(x, p, cfg)
    q = _heads(q, cfg.num_heads, p.get("q_norm"), cfg, positions)
    k = _heads(k, cfg.num_kv_heads, p.get("k_norm"), cfg, positions)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.resolved_head_dim)
    q = constrain(q, "act_q")
    k = constrain(k, "act_kv")
    v = constrain(v, "act_kv")
    return q, k, v


# --------------------------------------------------------------------------
# Chunked flash attention (train / prefill path)
# --------------------------------------------------------------------------

def flash_attn(q: jax.Array, k: jax.Array, v: jax.Array, *,
               causal: bool = True,
               window: Optional[int] = None,
               q_block: int = 1024,
               kv_block: int = 1024,
               q_offset: int = 0) -> jax.Array:
    """Online-softmax attention with bounded transients.

    q: (B, Sq, H, hd); k, v: (B, Skv, KVH, hd) with H % KVH == 0.
    KV heads are repeated to full H first, then everything runs in a
    (B, H, S, hd) layout — a single head axis shards cleanly over the model
    mesh axis (the GQA repeat is local when heads are sharded).
    Scans q blocks (outer) and kv blocks (inner, online softmax carry).
    Causality/window handled by masking; block skipping is a perf-pass item
    (see EXPERIMENTS.md §Perf).
    """
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    assert h % kvh == 0
    g = h // kvh
    q_block = min(q_block, sq)
    kv_block = min(kv_block, skv)
    # pad to block multiples
    pq = (-sq) % q_block
    pkv = (-skv) % kv_block
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))
    nq, nkv = (sq + pq) // q_block, (skv + pkv) // kv_block
    scale = hd ** -0.5

    # repeat KV to full heads; constrain to the head-sharded layout
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1)   # (B, H, Skv, hd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1)
    kf = constrain(kf, "attn_heads")
    vf = constrain(vf, "attn_heads")
    qf = constrain(q.transpose(0, 2, 1, 3), "attn_heads")  # (B, H, Sq, hd)

    qr = qf.reshape(b, h, nq, q_block, hd).transpose(2, 0, 1, 3, 4)
    # qr: (nq, B, H, qb, hd)
    kr = kf.reshape(b, h, nkv, kv_block, hd).transpose(2, 0, 1, 3, 4)
    vr = vf.reshape(b, h, nkv, kv_block, hd).transpose(2, 0, 1, 3, 4)
    # kr/vr: (nkv, B, H, kb, hd)

    q_pos = jnp.arange(nq * q_block, dtype=jnp.int32) + q_offset
    kv_pos = jnp.arange(nkv * kv_block, dtype=jnp.int32)
    kv_valid = kv_pos < skv

    def q_block_body(_, inputs):
        qb, qi = inputs                       # qb: (B,H,qb,hd)
        qp = jax.lax.dynamic_slice_in_dim(q_pos, qi * q_block, q_block)

        def kv_body(carry, kv_inputs):
            acc, m, l = carry
            kb, vb, ki = kv_inputs            # kb/vb: (B,H,kb,hd)
            kp = jax.lax.dynamic_slice_in_dim(kv_pos, ki * kv_block, kv_block)
            kval = jax.lax.dynamic_slice_in_dim(kv_valid, ki * kv_block,
                                                kv_block)
            s = jnp.einsum("bhqd,bhcd->bhqc", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = kval[None, :]
            if causal:
                mask = mask & (kp[None, :] <= qp[:, None])
            if window is not None:
                mask = mask & (kp[None, :] > qp[:, None] - window)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhqc,bhcd->bhqd", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, h, q_block, hd), jnp.float32)
        m0 = jnp.full((b, h, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_block), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_body, (acc0, m0, l0),
            (kr, vr, jnp.arange(nkv, dtype=jnp.int32)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, out = jax.lax.scan(q_block_body, None,
                          (qr, jnp.arange(nq, dtype=jnp.int32)))
    # out: (nq, B, H, qb, hd) -> (B, S, H, hd)
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, h, nq * q_block, hd)
    return out.transpose(0, 2, 1, 3)[:, :sq]


# --------------------------------------------------------------------------
# Decode attention (single new token vs cache)
# --------------------------------------------------------------------------

def decode_attn(q: jax.Array, cache: KVCache, pos: jax.Array) -> jax.Array:
    """q: (B, 1, H, hd); cache.k/v: (B, Sc, KVH, hd); pos: current absolute
    position (scalar int32) — number of tokens already written including this
    step's token (the cache already contains the current token's k/v).

    Validity: a ring-buffer slot i is valid iff i < min(pos, Sc).  Softmax is
    computed in fp32; with the cache sequence dim sharded, XLA lowers the
    max/sum reductions to all-reduces (distributed flash-decode).
    """
    b, _, h, hd = q.shape
    _, sc, kvh, _ = cache.k.shape
    g = h // kvh
    scale = hd ** -0.5
    qh = q.reshape(b, kvh, g, hd)
    s = jnp.einsum("bkgh,bckh->bkgc", qh, cache.k,
                   preferred_element_type=jnp.float32) * scale
    idx = jnp.arange(sc, dtype=jnp.int32)
    valid = idx < jnp.minimum(pos, sc)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgc,bckh->bkgh", p.astype(cache.v.dtype), cache.v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype).reshape(b, 1, h, hd)


def cache_write(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                pos: jax.Array) -> KVCache:
    """Write one token's k/v (B, 1, KVH, hd) at ring slot pos % Sc."""
    sc = cache.k.shape[1]
    slot = jnp.mod(pos, sc)
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new.astype(cache.k.dtype),
                                            slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new.astype(cache.v.dtype),
                                            slot, axis=1)
    return KVCache(k=k, v=v)


def _prefill_cache(k: jax.Array, v: jax.Array, cache: Optional[KVCache],
                   cfg: ModelConfig) -> KVCache:
    """The decode cache prefill hands on: the last ``s_cache`` tokens of
    k/v (B, S, KVH, hd), ring-aligned, or the prompt in a longer cache."""
    b, s = k.shape[:2]
    s_cache = cache.k.shape[1] if cache is not None else s
    if s >= s_cache:
        kc, vc = k[:, -s_cache:], v[:, -s_cache:]
        # ring alignment: slot of token t is t % s_cache
        shift = jnp.mod(s - s_cache, s_cache)
        return KVCache(jnp.roll(kc, shift=shift, axis=1),
                       jnp.roll(vc, shift=shift, axis=1))
    base = cache if cache is not None else make_kv_cache(
        b, s_cache, cfg.num_kv_heads, cfg.resolved_head_dim, k.dtype)
    return KVCache(
        jax.lax.dynamic_update_slice_in_dim(base.k, k.astype(base.k.dtype),
                                            0, axis=1),
        jax.lax.dynamic_update_slice_in_dim(base.v, v.astype(base.v.dtype),
                                            0, axis=1))


# --------------------------------------------------------------------------
# Path choice
# --------------------------------------------------------------------------

def _backend() -> str:
    return jax.default_backend()


def attn_path(cfg: ModelConfig, mode: str, seq_len: int,
              platform: str) -> str:
    """The path ``attn_forward`` takes: the fused prefill kernel for a
    prefill on one TPU (no sharding rules installed) whose prompt fits its
    single VMEM block; ``flash_attn`` otherwise (train, whose kernel has no
    VJP; longer prompts; other platforms)."""
    if (mode == "prefill" and platform == "tpu"
            and get_sharding_rules() is None
            and prefill_kernel.fits(seq_len, cfg.num_heads,
                                    cfg.num_kv_heads,
                                    cfg.resolved_head_dim)):
        return KERNEL_PATH
    return FLASH_PATH


@contextlib.contextmanager
def count_attn_paths():
    """Count, by path, the self-attention sub-blocks this thread traces
    while the block is open; yields the ``collections.Counter``.  A traced
    layer scan counts its body once."""
    counts = collections.Counter()
    outer = getattr(_paths, "counts", None)
    _paths.counts = counts
    try:
        yield counts
    finally:
        _paths.counts = outer
        if outer is not None:
            outer.update(counts)


def _rope_tables(positions: jax.Array, seq_len: int, hd: int, theta: float):
    """cos and sin (Bp, S, hd) f32 of ``apply_rope``'s angles, each half of
    the head dim repeating the frequencies."""
    angles = (positions.reshape(-1, seq_len)[..., None].astype(jnp.float32)
              * rope_frequencies(hd, theta))
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


# --------------------------------------------------------------------------
# Full attention sub-block (projection + attend + output)
# --------------------------------------------------------------------------

def attn_forward(x: jax.Array, p: dict, cfg: ModelConfig, *,
                 positions: jax.Array,
                 mode: str,
                 cache: Optional[KVCache] = None,
                 pos: Optional[jax.Array] = None,
                 cross_kv: Optional[KVCache] = None):
    """Self-attention sub-block.

    mode: "train" | "prefill" | "decode".
    Returns (out (B,S,d), new_cache or None).
    For prefill, a cache sized to x's sequence (or the config window) is
    produced; for decode, x is (B, 1, d), ``pos`` is the 0-based absolute
    index of the new token, and the cache is read+written.
    """
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    path = attn_path(cfg, mode, s, _backend())
    counts = getattr(_paths, "counts", None)
    if counts is not None:
        counts[path] += 1
    new_cache = None
    if path == KERNEL_PATH:
        qf, kf, vf = _project(x, p, cfg)
        # the cache's k is normed and rotated as the flash path has it;
        # a caller that drops the cache drops this too
        k = _heads(kf, cfg.num_kv_heads, p.get("k_norm"), cfg, positions)
        v = vf.reshape(b, s, cfg.num_kv_heads, hd)
        new_cache = _prefill_cache(k, v, cache, cfg)

        def fused(qf, kf, vf):
            cos = sin = None
            if cfg.rope and positions is not None:
                cos, sin = _rope_tables(positions, s, hd, cfg.rope_theta)
            return prefill_kernel.prefill_attention(
                qf, kf, vf, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, q_scale=p.get("q_norm"),
                k_scale=p.get("k_norm"), eps=cfg.norm_eps, cos=cos, sin=sin,
                causal=cfg.causal, window=cfg.sliding_window,
                interpret=False)

        def unfused(qf, kf, vf):
            # a TPU process may still compile this step for its host CPU
            q = _heads(qf, cfg.num_heads, p.get("q_norm"), cfg, positions)
            out = flash_attn(q, k, v, causal=cfg.causal,
                             window=cfg.sliding_window)
            return out.reshape(qf.shape)

        out = jax.lax.platform_dependent(qf, kf, vf, tpu=fused,
                                         default=unfused)
    elif mode == "decode":
        q, k, v = project_qkv(x, p, cfg, positions)
        assert cache is not None and pos is not None
        new_cache = cache_write(cache, k, v, pos)
        out = decode_attn(q, new_cache, pos + 1)
    else:
        q, k, v = project_qkv(x, p, cfg, positions)
        out = flash_attn(q, k, v, causal=cfg.causal,
                         window=cfg.sliding_window)
        if mode == "prefill":
            new_cache = _prefill_cache(k, v, cache, cfg)
    out = constrain(out, "act_attn_out")
    out = out.reshape(b, s, cfg.num_heads * hd)
    out = jnp.einsum("bsk,kd->bsd", out, p["wo"])
    return out, new_cache


def cross_attn_forward(x: jax.Array, p: dict, cfg: ModelConfig,
                       enc_kv: KVCache):
    """Cross-attention: queries from x, K/V precomputed from the encoder."""
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dk->bsk", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(b, s, cfg.num_heads, hd)
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_norm"], cfg.norm_eps)
    out = flash_attn(q, enc_kv.k, enc_kv.v, causal=False, window=None)
    out = out.reshape(b, s, cfg.num_heads * hd)
    return jnp.einsum("bsk,kd->bsd", out, p["wo"])


def encode_cross_kv(enc_out: jax.Array, p: dict, cfg: ModelConfig) -> KVCache:
    """Precompute cross-attention K/V from encoder output (B, S_enc, d)."""
    b, s, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = jnp.einsum("bsd,dk->bsk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dk->bsk", enc_out, p["wv"])
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    k = k.reshape(b, s, cfg.num_kv_heads, hd)
    v = v.reshape(b, s, cfg.num_kv_heads, hd)
    return KVCache(k=k, v=v)

"""Plain float32 forward pass of a dense decoder (Qwen3, Qwen1.5).

Pre-norm blocks: RMSNorm, grouped-query self-attention with rotary
position embeddings (half-split rotation, as the Hugging Face Qwen
models), optional bias on the q/k/v projections (Qwen1.5) and optional
RMSNorm of each query and key head (Qwen3); then RMSNorm and a SwiGLU
feed-forward block.  A final RMSNorm of the last position and the output
head (the embedding, transposed, where tied) give the last-token logits.
Attention is computed whole, causal mask and softmax in float32.

The family serves attention blocks each with a dense feed-forward block,
and gives their useful FLOPs (``bench/flops.py``): the q/k/v/o and SwiGLU
weights at every position, and attention over the causal pairs.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import weights as W
from bench.reference.ops import mm, rms_norm, silu


def _served(m):
    for kind, mlp in zip(m["block_pattern"], m["mlp_pattern"]):
        if (kind, mlp) != ("attn", "dense"):
            raise ValueError(f"the transformer reference serves attention "
                             f"blocks with a dense feed-forward block, not "
                             f"{kind!r} with {mlp!r}")


def matmul_params(m: dict) -> int:
    """Weights applied by matrix products to each position, all layers."""
    _served(m)
    d, hd = m["d_model"], m["head_dim"]
    h, kvh = m["num_heads"], m["num_kv_heads"]
    total = 0
    for _ in m["block_pattern"]:
        # q, k, v, o; then the SwiGLU block's gate, up and down
        total += d * h * hd + 2 * d * kvh * hd + h * hd * d
        total += 3 * d * m["d_ff"]
    return total * (m["num_layers"] // len(m["block_pattern"]))


def mixing_flops(m: dict, s: int) -> float:
    """Attention over the causal pairs of one prompt: scores and the
    weighted sum of values."""
    pairs = s * (s + 1) / 2
    n_sb = m["num_layers"] // len(m["block_pattern"])
    total = 0.0
    for _ in m["block_pattern"]:
        total += 2 * 2 * pairs * m["num_heads"] * m["head_dim"]
    return total * n_sb


def _attn_shapes(m):
    d, hd = m["d_model"], m["head_dim"]
    h, kvh, ff = m["num_heads"], m["num_kv_heads"], m["d_ff"]
    return (
        (((4, 0), (8, 0)), (d, h * hd)),
        (((4, 0), (8, 1)), (d, kvh * hd)),
        (((4, 0), (8, 2)), (d, kvh * hd)),
        (((4, 0), (8, 3)), (h * hd, d)),
        (((4, 1), (3, 0)), (d, ff)),
        (((4, 1), (3, 1)), (d, ff)),
        (((4, 1), (3, 2)), (ff, d)),
    )


def _rope(x, theta: float):
    """x: (B, S, H, hd); rotate the two halves of each head."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs   # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnums=(2, 3))
def _layer(h, raw, mkey, quant):
    m = dict(mkey)
    d, hd = m["d_model"], m["head_dim"]
    nh, kvh = m["num_heads"], m["num_kv_heads"]
    eps = m["norm_eps"]
    wq, wk, wv, wo, wg, wu, wd = (W.scaled(x, x.shape[-2]) for x in raw)
    b, s, _ = h.shape
    x = rms_norm(h, eps)
    q = mm("bsd,dk->bsk", x, wq, quant).reshape(b, s, nh, hd)
    k = mm("bsd,dk->bsk", x, wk, quant).reshape(b, s, kvh, hd)
    v = mm("bsd,dk->bsk", x, wv, quant).reshape(b, s, kvh, hd)
    # q/k/v biases and the head-norm scales are zeros and ones as served
    if m["qk_norm"]:
        q, k = rms_norm(q, eps), rms_norm(k, eps)
    if m["rope"]:
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    g = nh // kvh
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    sc = mm("bqhd,bkhd->bhqk", q, k, quant) * (hd ** -0.5)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = mm("bhqk,bkhd->bqhd", p, v, quant).reshape(b, s, nh * hd)
    h = h + mm("bsk,kd->bsd", o, wo, quant)
    x = rms_norm(h, eps)
    f = silu(mm("bsd,df->bsf", x, wg, quant)) * mm("bsd,df->bsf", x, wu,
                                                   quant)
    return h + mm("bsf,fd->bsd", f, wd, quant)


@partial(jax.jit, static_argnums=(2, 3))
def _head(h_last, table, mkey, quant):
    m = dict(mkey)
    x = rms_norm(h_last, m["norm_eps"])
    return mm("bd,vd->bv", x, table, quant)


def last_logits(m: dict, seed: int, prompts: np.ndarray, quant=None):
    """Last-position logits (B, V) of ``prompts`` (B, S) int32."""
    _served(m)
    mkey = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))
    keys = W.root_keys(seed)
    (e,) = W.normals(keys[0], (((), (m["vocab_size"], m["d_model"])),))
    table = W.embed(e)
    del e
    h = table[jnp.asarray(prompts)]
    shapes = _attn_shapes(m)
    for i in range(m["num_layers"]):
        raw = W.normals(W.layer_key(keys, i, 0), shapes)
        h = _layer(h, raw, mkey, quant)
        del raw
    if not m["tie_embeddings"]:
        (o,) = W.normals(keys[1], (((), (m["d_model"], m["vocab_size"])),))
        table = W.scaled(o, m["d_model"]).T
    return _head(h[:, -1], table, mkey, quant)

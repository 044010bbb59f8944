"""Plain float32 forward pass of xLSTM [arXiv:2405.04517] as configured.

Residual pre-norm blocks in the configured pattern (seven mLSTM blocks,
then one sLSTM block, repeated).

mLSTM block: up-projection to ``2 * inner`` (a cell branch and a gate
branch ``z``); a causal depthwise convolution of width 4 and SiLU on the
cell branch; per head, block-diagonal projections to queries and keys
(from the convolved branch, keys scaled by ``1/sqrt(head width)``) and
values (from the branch before the convolution); scalar input and forget
gates per head from the convolved branch (float32 weights, forget bias
3).  The matrix memory is computed in the paper's parallel form with its
max-stabiliser:

    log D[t, j] = sum_{r=j+1..t} log sigmoid(f_r) + i_j   (j <= t)
    h_t = sum_j (q_t . k_j) D'[t, j] v_j / max(|sum_j (q_t . k_j) D'[t, j]|,
                                                exp(-m_t))

with ``m_t = max_j log D[t, j]`` and ``D' = exp(log D - m_t)``.  The head
outputs are concatenated, RMS-normed over the inner width, gated by
``SiLU(z)`` and projected down.  The program computes the same memory in
the chunkwise form.

sLSTM block: input projection to the four gates z, i, f, o (forget bias
3), plus the per-head recurrent projection of the previous hidden state;
as configured, the four per-head outputs are laid out head-major and then
split into the gate chunks.  Exponential input gate with the paper's
log-space stabiliser ``m``, normaliser floored at ``exp(-m)``, stepped
sequentially over the prompt from a zero state; then RMSNorm and a GEGLU
feed-forward block (tanh-approximated GELU, width ``int(4/3 * d)``).

The family serves mLSTM and sLSTM blocks without a feed-forward block of
their own, and gives their useful FLOPs (``bench/flops.py``): each
block's projections at every position, and the mLSTM memory over the
causal pairs in its parallel form.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import weights as W
from bench.reference.ops import (gelu_tanh, log_sigmoid, mm, rms_norm,
                                 sigmoid, silu)


def _served(m):
    for kind, mlp in zip(m["block_pattern"], m["mlp_pattern"]):
        if kind not in ("mlstm", "slstm") or mlp != "none":
            raise ValueError(f"the xlstm reference serves mLSTM and sLSTM "
                             f"blocks without a feed-forward block, not "
                             f"{kind!r} with {mlp!r}")


def matmul_params(m: dict) -> int:
    """Weights applied by matrix products to each position, all layers."""
    _served(m)
    d = m["d_model"]
    total = 0
    for kind in m["block_pattern"]:
        if kind == "mlstm":
            inner = m["xlstm_expand"] * d
            nh = m["xlstm_num_heads"]
            # up-projection, per-head q/k/v blocks, the two gates, down
            total += (d * 2 * inner + 3 * inner * (inner // nh)
                      + 2 * inner * nh + inner * d)
        else:
            nh = m["xlstm_num_heads"]
            dffn = int(d * 4 / 3)
            # gate input projection, per-head recurrence, GEGLU block
            total += 4 * d * d + 4 * d * (d // nh) + 3 * d * dffn
    return total * (m["num_layers"] // len(m["block_pattern"]))


def mixing_flops(m: dict, s: int) -> float:
    """The mLSTM memory over the causal pairs of one prompt, in its
    parallel form; the sLSTM's recurrence is elementwise."""
    pairs = s * (s + 1) / 2
    n_sb = m["num_layers"] // len(m["block_pattern"])
    total = 0.0
    for kind in m["block_pattern"]:
        if kind == "mlstm":
            inner = m["xlstm_expand"] * m["d_model"]
            total += 2 * 2 * pairs * inner
    return total * n_sb


def _dims(m):
    inner = m["xlstm_expand"] * m["d_model"]
    nh = m["xlstm_num_heads"]
    return inner, nh, inner // nh


def _mlstm_shapes(m):
    d, ck = m["d_model"], m["xlstm_conv_dim"]
    inner, nh, hd = _dims(m)
    p = lambda idx: ((4, 0), (9, idx))
    return ((p(0), (d, 2 * inner)), (p(1), (ck, inner)),
            (p(2), (nh, hd, hd)), (p(3), (nh, hd, hd)), (p(4), (nh, hd, hd)),
            (p(5), (inner, nh)), (p(6), (inner, nh)), (p(7), (inner, d)))


def _slstm_shapes(m):
    d, nh = m["d_model"], m["xlstm_num_heads"]
    hd, dffn = d // nh, int(d * 4 / 3)
    p = lambda idx: ((4, 0), (7, idx))
    return ((p(0), (d, 4 * d)), (p(1), (nh, hd, 4 * hd)),
            (p(2), (d, dffn)), (p(3), (d, dffn)), (p(4), (dffn, d)))


@partial(jax.jit, static_argnums=(2, 3))
def _mlstm_layer(h, raw, mkey, quant):
    m = dict(mkey)
    eps, ck = m["norm_eps"], m["xlstm_conv_dim"]
    inner, nh, hd = _dims(m)
    r_in, r_conv, r_q, r_k, r_v, r_i, r_f, r_out = raw
    w_in = W.scaled(r_in, r_in.shape[0])
    w_conv = W.scaled(r_conv, ck)
    wq, wk, wv = (W.scaled(x, hd) for x in (r_q, r_k, r_v))
    w_i = W.scaled(r_i, inner, served=False)
    w_f = W.scaled(r_f, inner, served=False)
    w_out = W.scaled(r_out, inner)
    b, s, _ = h.shape

    x = rms_norm(h, eps)
    xz = mm("bsd,di->bsi", x, w_in, quant)
    xm, z = xz[..., :inner], xz[..., inner:]
    xp = jnp.pad(xm, ((0, 0), (ck - 1, 0), (0, 0)))
    conv = sum(xp[:, c:c + s] * w_conv[c] for c in range(ck))
    xc = silu(conv)
    xch, xmh = xc.reshape(b, s, nh, hd), xm.reshape(b, s, nh, hd)
    q = mm("bshd,hde->bhse", xch, wq, quant)
    k = mm("bshd,hde->bhse", xch, wk, quant) * (hd ** -0.5)
    v = mm("bshd,hde->bhse", xmh, wv, quant)
    ig = mm("bsi,ih->bhs", xc, w_i, quant)
    fg = mm("bsi,ih->bhs", xc, w_f, quant) + 3.0

    cum = jnp.cumsum(log_sigmoid(fg), axis=-1)                 # (b, nh, s)
    logd = cum[..., :, None] - cum[..., None, :] + ig[..., None, :]
    causal = jnp.tril(jnp.ones((s, s), bool))
    logd = jnp.where(causal, logd, -jnp.inf)
    mt = jnp.max(logd, axis=-1)                                # (b, nh, s)
    c = mm("bhte,bhje->bhtj", q, k, quant) * jnp.exp(logd - mt[..., None])
    norm = jnp.maximum(jnp.abs(jnp.sum(c, axis=-1)), jnp.exp(-mt))
    hh = mm("bhtj,bhje->bhte", c / norm[..., None], v, quant)
    hh = hh.transpose(0, 2, 1, 3).reshape(b, s, inner)
    hh = rms_norm(hh, eps) * silu(z)
    return h + mm("bsi,id->bsd", hh, w_out, quant)


@partial(jax.jit, static_argnums=(2, 3))
def _slstm_layer(h, raw, mkey, quant):
    m = dict(mkey)
    eps, d, nh = m["norm_eps"], m["d_model"], m["xlstm_num_heads"]
    hd = d // nh
    r_in, r_r, r_g, r_u, r_d = raw
    w_in = W.scaled(r_in, d)
    rec = W.scaled(r_r, hd)
    w_g, w_u = W.scaled(r_g, d), W.scaled(r_u, d)
    w_d = W.scaled(r_d, r_d.shape[0])
    bias = jnp.concatenate([jnp.zeros(2 * d), jnp.full(d, 3.0),
                            jnp.zeros(d)]).astype(jnp.float32)
    b, s, _ = h.shape

    x = rms_norm(h, eps)
    wx = mm("bsd,df->bsf", x, w_in, quant)

    def step(state, wx_t):
        c, n, mm_, hp = state
        r = mm("bhe,hef->bhf", hp.reshape(b, nh, hd), rec, quant)
        g = wx_t + r.reshape(b, 4 * d) + bias
        zg, ig, fg, og = jnp.split(g, 4, axis=-1)
        logf = log_sigmoid(fg)
        m_new = jnp.maximum(logf + mm_, ig)
        f_s = jnp.exp(logf + mm_ - m_new)
        i_s = jnp.exp(ig - m_new)
        c = f_s * c + i_s * jnp.tanh(zg)
        n = jnp.maximum(f_s * n + i_s, jnp.exp(-m_new))
        hn = sigmoid(og) * c / n
        return (c, n, m_new, hn), hn

    zero = jnp.zeros((b, d), jnp.float32)
    init = (zero, zero, jnp.full((b, d), -1e30, jnp.float32), zero)
    _, hs = jax.lax.scan(step, init, wx.transpose(1, 0, 2))
    y = rms_norm(hs.transpose(1, 0, 2), eps)
    f = gelu_tanh(mm("bsd,df->bsf", y, w_g, quant)) * mm("bsd,df->bsf", y,
                                                         w_u, quant)
    return h + mm("bsf,fd->bsd", f, w_d, quant)


@partial(jax.jit, static_argnums=(2, 3))
def _head(h_last, table, mkey, quant):
    m = dict(mkey)
    return mm("bd,vd->bv", rms_norm(h_last, m["norm_eps"]), table, quant)


def last_logits(m: dict, seed: int, prompts: np.ndarray, quant=None):
    """Last-position logits (B, V) of ``prompts`` (B, S) int32."""
    _served(m)
    mkey = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))
    pattern = m["block_pattern"]
    keys = W.root_keys(seed)
    (e,) = W.normals(keys[0], (((), (m["vocab_size"], m["d_model"])),))
    h = W.embed(e)[jnp.asarray(prompts)]
    del e
    kinds = {"mlstm": (_mlstm_shapes(m), _mlstm_layer),
             "slstm": (_slstm_shapes(m), _slstm_layer)}
    for i in range(m["num_layers"] // len(pattern)):
        for j, kind in enumerate(pattern):
            shapes, layer = kinds[kind]
            raw = W.normals(W.layer_key(keys, i, j), shapes)
            h = layer(h, raw, mkey, quant)
            del raw
    (o,) = W.normals(keys[1], (((), (m["d_model"], m["vocab_size"])),))
    table = W.scaled(o, m["d_model"]).T
    return _head(h[:, -1], table, mkey, quant)

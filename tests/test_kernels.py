"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracle, with
hypothesis shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # degrade to deterministic example sweeps
    from _hypothesis_fallback import given, settings, st

from repro.kernels import ops

SETTINGS = dict(max_examples=12, deadline=None)


def _cmp(a, b, name, atol=2e-2, rtol=2e-2):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               atol=atol, rtol=rtol, err_msg=name)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

@settings(**SETTINGS)
@given(
    b=st.integers(1, 3),
    sq=st.integers(1, 80),
    kvh=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2, 4]),
    hd=st.sampled_from([8, 16, 32]),
    causal=st.booleans(),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_flash_attention_sweep(b, sq, kvh, g, hd, causal, dtype):
    h = kvh * g
    key = jax.random.PRNGKey(b * 1000 + sq)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, sq, kvh, hd), dtype)
    v = jax.random.normal(ks[2], (b, sq, kvh, hd), dtype)
    ref = ops.flash_attention(q, k, v, causal=causal, impl="ref")
    pal = ops.flash_attention(q, k, v, causal=causal,
                              impl="pallas_interpret")
    xla = ops.flash_attention(q, k, v, causal=causal, impl="xla")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    _cmp(pal, ref, "pallas", atol=tol, rtol=tol)
    _cmp(xla, ref, "xla", atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [1, 7, 16, 64])
def test_flash_attention_window(window):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 48, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (2, 48, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (2, 48, 2, 16), jnp.float32)
    ref = ops.flash_attention(q, k, v, causal=True, window=window, impl="ref")
    pal = ops.flash_attention(q, k, v, causal=True, window=window,
                              impl="pallas_interpret")
    _cmp(pal, ref, f"window={window}", atol=3e-3, rtol=3e-3)


def test_flash_attention_block_sizes():
    """Result must not depend on the BlockSpec tiling."""
    from repro.kernels.flash_attention import flash_attention_bhsd
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (4, 100, 16), jnp.float32)
    k = jax.random.normal(ks[1], (2, 100, 16), jnp.float32)
    v = jax.random.normal(ks[2], (2, 100, 16), jnp.float32)
    outs = [flash_attention_bhsd(q, k, v, num_heads=4, num_kv_heads=2,
                                 block_q=bq, block_kv=bk, interpret=True)
            for bq, bk in ((16, 16), (32, 64), (128, 128), (8, 128))]
    for o in outs[1:]:
        _cmp(o, outs[0], "block invariance", atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# decode attention
# --------------------------------------------------------------------------

@settings(**SETTINGS)
@given(
    b=st.integers(1, 3),
    sc=st.integers(4, 96),
    kvh=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 4]),
    valid_frac=st.floats(0.1, 1.0),
)
def test_decode_attention_sweep(b, sc, kvh, g, valid_frac):
    h = kvh * g
    hd = 16
    key = jax.random.PRNGKey(sc)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, sc, kvh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, sc, kvh, hd), jnp.float32)
    valid = jnp.asarray(max(1, int(sc * valid_frac)), jnp.int32)
    ref = ops.decode_attention(q, k, v, valid, impl="ref")
    pal = ops.decode_attention(q, k, v, valid, impl="pallas_interpret")
    xla = ops.decode_attention(q, k, v, valid, impl="xla")
    _cmp(pal, ref, "pallas", atol=3e-3, rtol=3e-3)
    _cmp(xla, ref, "xla", atol=3e-3, rtol=3e-3)


# --------------------------------------------------------------------------
# ssm scan
# --------------------------------------------------------------------------

@settings(**SETTINGS)
@given(
    b=st.integers(1, 3),
    l=st.integers(1, 40),
    d=st.sampled_from([8, 32, 96]),
    stt=st.sampled_from([4, 16]),
)
def test_ssm_scan_sweep(b, l, d, stt):
    key = jax.random.PRNGKey(l * 7 + d)
    da = jax.nn.sigmoid(jax.random.normal(key, (b, l, d, stt)))
    dbx = jax.random.normal(jax.random.PRNGKey(1), (b, l, d, stt)) * 0.1
    ref = ops.ssm_scan(da, dbx, impl="ref")
    pal = ops.ssm_scan(da, dbx, impl="pallas_interpret")
    xla = ops.ssm_scan(da, dbx, impl="xla")
    _cmp(pal, ref, "pallas", atol=1e-4, rtol=1e-3)
    _cmp(xla, ref, "xla", atol=1e-4, rtol=1e-3)


def test_ssm_scan_channel_blocking():
    from repro.kernels.ssm_scan import ssm_chunk_scan
    key = jax.random.PRNGKey(3)
    da = jax.nn.sigmoid(jax.random.normal(key, (2, 16, 100, 8)))
    dbx = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 100, 8))
    outs = [ssm_chunk_scan(da, dbx, block_d=bd, interpret=True)
            for bd in (16, 50, 256)]
    for o in outs[1:]:
        _cmp(o, outs[0], "block_d invariance", atol=1e-5, rtol=1e-5)


def test_ssm_scan_lane_dense_layout_matches_ref_exactly():
    """The kernel flattens (D, ST) into one lane axis; the recurrence is
    the same elementwise f32 arithmetic as the reference, step by step."""
    from repro.kernels.ref import ssm_chunk_scan_ref
    from repro.kernels.ssm_scan import ssm_chunk_scan
    da = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(5),
                                          (2, 24, 40, 16)))
    dbx = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 40, 16))
    for bd in (8, 16, 40):
        np.testing.assert_array_equal(
            np.asarray(ssm_chunk_scan(da, dbx, block_d=bd, interpret=True)),
            np.asarray(ssm_chunk_scan_ref(da, dbx)))


# --------------------------------------------------------------------------
# mlstm chunk
# --------------------------------------------------------------------------

@settings(**SETTINGS)
@given(
    bh=st.integers(1, 4),
    l=st.integers(2, 48),
    hd=st.sampled_from([8, 16]),
    chunks=st.integers(1, 3),
)
def test_mlstm_chunk_sweep(bh, l, hd, chunks):
    """Chunkwise-parallel kernel == sequential per-timestep reference, with
    the carry threaded across several chunks."""
    key = jax.random.PRNGKey(bh * 100 + l)
    c = jnp.zeros((bh, hd, hd))
    n = jnp.zeros((bh, hd))
    m = jnp.full((bh,), -1e30)
    c_r, n_r, m_r = c, n, m
    for ci in range(chunks):
        ks = jax.random.split(jax.random.fold_in(key, ci), 5)
        q = jax.random.normal(ks[0], (bh, l, hd))
        k = jax.random.normal(ks[1], (bh, l, hd)) / np.sqrt(hd)
        v = jax.random.normal(ks[2], (bh, l, hd))
        i_raw = jax.random.normal(ks[3], (bh, l))
        f_raw = jax.random.normal(ks[4], (bh, l)) + 2.0
        h_p, c, n, m = ops.mlstm_chunk(q, k, v, i_raw, f_raw, c, n, m,
                                       impl="pallas_interpret")
        h_r, c_r, n_r, m_r = ops.mlstm_chunk(q, k, v, i_raw, f_raw,
                                             c_r, n_r, m_r, impl="ref")
        _cmp(h_p, h_r, f"h chunk{ci}", atol=2e-3, rtol=2e-2)
        _cmp(m, m_r, f"m chunk{ci}", atol=1e-4, rtol=1e-4)
    _cmp(c, c_r, "final C", atol=2e-3, rtol=2e-2)


def test_mlstm_xla_path_matches_ref():
    key = jax.random.PRNGKey(9)
    ks = jax.random.split(key, 5)
    bh, l, hd = 3, 24, 16
    q = jax.random.normal(ks[0], (bh, l, hd))
    k = jax.random.normal(ks[1], (bh, l, hd)) / 4.0
    v = jax.random.normal(ks[2], (bh, l, hd))
    i_raw = jax.random.normal(ks[3], (bh, l))
    f_raw = jax.random.normal(ks[4], (bh, l)) + 2.0
    c = jnp.zeros((bh, hd, hd)); n = jnp.zeros((bh, hd))
    m = jnp.full((bh,), -1e30)
    h_x, *_ = ops.mlstm_chunk(q, k, v, i_raw, f_raw, c, n, m, impl="xla")
    h_r, *_ = ops.mlstm_chunk(q, k, v, i_raw, f_raw, c, n, m, impl="ref")
    _cmp(h_x, h_r, "xla vs ref", atol=2e-3, rtol=2e-2)


# --------------------------------------------------------------------------
# fused prefill attention (qk-norm + RoPE + grouped causal attention)
# --------------------------------------------------------------------------

@pytest.mark.parametrize(
    "b,s,h,kvh,hd,qk_norm,bias,rope,window,row_positions", [
        (2, 96, 16, 8, 128, True, False, True, None, False),   # qwen3-0.6b
        (2, 48, 16, 16, 64, False, True, True, None, False),   # qwen1.5-0.5b
        (2, 48, 4, 2, 64, True, False, True, None, True),      # grouped, hd 64
        (2, 64, 4, 2, 128, False, False, False, None, False),  # rope off
        (2, 96, 4, 4, 64, False, True, True, 24, False),       # window < S
        (1, 96, 16, 8, 128, True, False, True, None, False),   # one row
    ])
def test_prefill_attention_matches_ref(b, s, h, kvh, hd, qk_norm, bias,
                                       rope, window, row_positions):
    """The fused kernel against qk-norm and RoPE as the model applies them
    (``models.common``) followed by ``ref.attention_ref``."""
    from repro.kernels.prefill_attention import prefill_attention
    from repro.kernels.ref import attention_ref
    from repro.models.attention import _rope_tables
    from repro.models.common import apply_rope, head_rms_norm
    ks = jax.random.split(jax.random.PRNGKey(s * h + hd), 8)
    q = jax.random.normal(ks[0], (b, s, h * hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, kvh * hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, kvh * hd), jnp.bfloat16)
    if bias:
        q = q + jax.random.normal(ks[3], (h * hd,), jnp.bfloat16)
        k = k + jax.random.normal(ks[4], (kvh * hd,), jnp.bfloat16)
        v = v + jax.random.normal(ks[5], (kvh * hd,), jnp.bfloat16)
    q_scale = k_scale = None
    if qk_norm:
        q_scale = 1 + 0.2 * jax.random.normal(ks[6], (hd,), jnp.bfloat16)
        k_scale = 1 + 0.2 * jax.random.normal(ks[7], (hd,), jnp.bfloat16)
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    if row_positions:
        positions = positions + 5 * jnp.arange(b, dtype=jnp.int32)[:, None]
    cos = sin = None
    if rope:
        cos, sin = _rope_tables(positions, s, hd, 1e6)
    out = prefill_attention(q, k, v, num_heads=h, num_kv_heads=kvh,
                            q_scale=q_scale, k_scale=k_scale, eps=1e-6,
                            cos=cos, sin=sin, window=window, interpret=True)

    def heads(x, n, scale):
        x = x.reshape(b, s, n, hd)
        if qk_norm:
            x = head_rms_norm(x, scale, 1e-6)
        if rope:
            x = apply_rope(x, positions, 1e6)
        return x.transpose(0, 2, 1, 3).reshape(b * n, s, hd)

    vh = v.reshape(b, s, kvh, hd).transpose(0, 2, 1, 3).reshape(-1, s, hd)
    ref = attention_ref(heads(q, h, q_scale), heads(k, kvh, k_scale), vh,
                        num_heads=h, num_kv_heads=kvh, window=window)
    ref = ref.reshape(b, h, s, hd).transpose(0, 2, 1, 3).reshape(b, s, -1)
    assert out.shape == q.shape and out.dtype == q.dtype
    _cmp(out, ref, "prefill_attention")

"""Per-architecture smoke tests (reduced configs, CPU): one train step and a
prefill→decode round trip; output shapes + finiteness + cache consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import (forward_train, init_cache, init_params,
                          serve_decode, serve_prefill)


def _batch(cfg, key, b=2, s=32):
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.encoder_decoder:
        batch["frames"] = jax.random.normal(
            key, (b, cfg.encoder_seq_len, cfg.d_model), jnp.bfloat16) * 0.02
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch, rng_key):
    cfg = get_config(arch, reduced=True)
    params = init_params(rng_key, cfg)
    batch = _batch(cfg, rng_key)
    loss = jax.jit(lambda p, b: forward_train(p, b, cfg))(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: loss={loss}"
    # random tokens ~ uniform: loss should be near ln(vocab)
    assert float(loss) < np.log(cfg.vocab_size) + 1.5


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_prefill_decode(arch, rng_key):
    cfg = get_config(arch, reduced=True)
    params = init_params(rng_key, cfg)
    b, s = 2, 32
    batch = _batch(cfg, rng_key, b, s)
    kw = ({"frames": batch["frames"]} if cfg.encoder_decoder else {})
    logits, cache = jax.jit(
        lambda p, t: serve_prefill(p, t, cfg, cache_len=s + 8, **kw)
    )(params, batch["tokens"])
    assert logits.shape == (b, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32))), arch
    assert int(cache.pos) == s
    step = jax.jit(lambda p, c, t: serve_decode(p, c, t, cfg))
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(3):
        logits, cache = step(params, cache, nxt)
        assert logits.shape == (b, cfg.vocab_size)
        assert np.all(np.isfinite(np.asarray(logits, np.float32))), arch
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    assert int(cache.pos) == s + 3


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "xlstm-1.3b",
                                  "jamba-v0.1-52b", "starcoder2-3b"])
def test_prefill_matches_incremental_decode(arch, rng_key):
    """Prefill of [t0..tn] must equal decoding t1..tn one-by-one after
    prefilling [t0..tk] — the cache/state carries the same information."""
    cfg = get_config(arch, reduced=True)
    params = init_params(rng_key, cfg)
    b, s = 1, 16
    tokens = jax.random.randint(rng_key, (b, s), 0, cfg.vocab_size)
    kw = {}
    if cfg.encoder_decoder:
        kw["frames"] = jnp.zeros((b, cfg.encoder_seq_len, cfg.d_model),
                                 jnp.bfloat16)
    # full prefill
    logits_full, _ = serve_prefill(params, tokens, cfg, cache_len=s, **kw)
    # prefill first half, decode the rest
    half = s // 2
    logits, cache = serve_prefill(params, tokens[:, :half], cfg,
                                  cache_len=s, **kw)
    for i in range(half, s):
        logits, cache = serve_decode(params, cache, tokens[:, i], cfg)
    np.testing.assert_allclose(
        np.asarray(logits, np.float32), np.asarray(logits_full, np.float32),
        rtol=0.15, atol=0.15,
        err_msg=f"{arch}: incremental decode diverges from prefill")


def test_sliding_window_decode_ring_buffer(rng_key):
    """Decoding past the window keeps only the last `window` tokens."""
    cfg = get_config("starcoder2-3b", reduced=True)
    assert cfg.sliding_window is not None
    params = init_params(rng_key, cfg)
    b = 1
    win = cfg.sliding_window
    tokens = jax.random.randint(rng_key, (b, win), 0, cfg.vocab_size)
    logits, cache = serve_prefill(params, tokens, cfg, cache_len=win)
    # the cache is full; decode more tokens than the window
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(win + 4):
        logits, cache = serve_decode(params, cache, nxt, cfg)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        assert np.all(np.isfinite(np.asarray(logits, np.float32)))


def test_moe_aux_loss_nonzero(rng_key):
    cfg = get_config("qwen3-moe-30b-a3b", reduced=True)
    params = init_params(rng_key, cfg)
    batch = _batch(cfg, rng_key)
    loss_with = forward_train(params, batch, cfg)
    assert np.isfinite(float(loss_with))


def test_whisper_uses_encoder(rng_key):
    """Changing the encoder frames must change decoder logits (cross-attn)."""
    cfg = get_config("whisper-medium", reduced=True)
    params = init_params(rng_key, cfg)
    tokens = jax.random.randint(rng_key, (1, 8), 0, cfg.vocab_size)
    f1 = jnp.zeros((1, cfg.encoder_seq_len, cfg.d_model), jnp.bfloat16)
    f2 = jax.random.normal(rng_key, f1.shape, jnp.bfloat16)
    l1, _ = serve_prefill(params, tokens, cfg, frames=f1)
    l2, _ = serve_prefill(params, tokens, cfg, frames=f2)
    assert not np.allclose(np.asarray(l1, np.float32),
                           np.asarray(l2, np.float32))


@pytest.mark.parametrize("arch,seq,mode,platform,path", [
    ("qwen3-0.6b", 96, "prefill", "tpu", "prefill_attention"),
    ("qwen1.5-0.5b", 48, "prefill", "tpu", "prefill_attention"),
    ("qwen3-0.6b", 96, "train", "tpu", "flash_attn"),
    ("qwen3-0.6b", 1024, "prefill", "tpu", "flash_attn"),    # above bound
    ("qwen3-0.6b", 96, "prefill", "cpu", "flash_attn"),
    ("qwen1.5-0.5b", 48, "prefill", "cpu", "flash_attn"),
])
def test_attn_forward_path_choice(arch, seq, mode, platform, path,
                                  monkeypatch):
    """The img-to-img stages' attention at full width, traced (nothing
    runs): the fused kernel serves prefill on a TPU; train, a prompt
    above the kernel's single block, and the CPU take ``flash_attn``."""
    from repro.models import attention
    monkeypatch.setattr(attention, "_backend", lambda: platform)
    cfg = get_config(arch)
    p = jax.eval_shape(lambda: attention.init_attn_params(
        jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((8, seq, cfg.d_model), jnp.bfloat16)
    positions = jnp.arange(seq, dtype=jnp.int32)[None]
    with attention.count_attn_paths() as counts:
        out, cache = jax.eval_shape(
            lambda x, p: attention.attn_forward(x, p, cfg,
                                                positions=positions,
                                                mode=mode), x, p)
    assert counts == {path: 1}
    assert attention.attn_path(cfg, mode, seq, platform) == path
    assert out.shape == x.shape
    assert (cache is None) == (mode == "train")


def test_stage_server_reports_its_attention_path():
    """``attn_path`` is unknown before a warm-up traces the step, then
    names the path; on the CPU that is ``flash_attn``."""
    from repro.serving import ModelStageServer
    stage = ModelStageServer("s", "qwen3-0.6b", seq_len=16)
    assert stage.attn_path is None
    stage.warmup(2)
    assert stage.attn_path == "flash_attn"
    stage.warmup(2)                    # cached: no trace, path kept
    assert stage.attn_path == "flash_attn"


def test_kernel_path_compiled_for_the_cpu_matches_flash(rng_key,
                                                        monkeypatch):
    """A step traced for a TPU but compiled for the CPU (a TPU process
    checking itself against its host) runs ``flash_attn`` in place of the
    kernel: logits and decode cache equal the CPU path's."""
    from repro.models import attention
    cfg = get_config("qwen3-0.6b", reduced=True)
    params = init_params(rng_key, cfg)
    tokens = jax.random.randint(rng_key, (2, 32), 0, cfg.vocab_size)
    want = jax.jit(lambda p, t: serve_prefill(p, t, cfg))(params, tokens)
    monkeypatch.setattr(attention, "_backend", lambda: "tpu")
    with attention.count_attn_paths() as counts:
        got = jax.jit(lambda p, t: serve_prefill(p, t, cfg))(params, tokens)
    assert counts == {"prefill_attention": 1}
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

"""Compile rehearsals for one TPU v5e chip, made without the chip.

The TPU compiler is installed with jaxlib, and compiles for a chip that is
described (a ``v5e:2x2`` topology) rather than attached.  Each test
compiles a Pallas kernel of ``repro.kernels`` at the width of the model
that would call it, or a full-width stage step of the served path, for
one device of that topology.  A kernel the chip's compiler refuses (a
block not aligned to the tiling, more VMEM than a kernel may use) fails
here, where interpret mode would pass it.  Nothing runs: these tests say
nothing about results or times.

The topology is described inside a module-scoped fixture, so only the
worker that runs this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def tpu_step(monkeypatch):
    """Trace model code as on the chip: it asks ``jax.default_backend()``
    for its attention path, which sees the CPU here."""
    from repro.models import attention
    monkeypatch.setattr(attention, "_backend", lambda: "tpu")


def _compiled_step(sharding, arch, batch, seq):
    """The stage step as ``ModelStageServer`` jits it, compiled for the
    described chip; returns it with its config and parameter shapes."""
    from repro.models import init_params, serve_prefill
    cfg = get_config(arch)
    params = jax.tree.map(
        lambda x: _spec(sharding, x.shape, x.dtype),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))

    def run(p, tokens):
        logits, _ = serve_prefill(p, tokens, cfg)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    compiled = jax.jit(run).lower(
        params, _spec(sharding, (batch, seq), jnp.int32)).compile()
    return compiled, cfg, params


def test_flash_attention_compiles_at_qwen3_width(one_chip):
    from repro.kernels.flash_attention import flash_attention_bhsd
    cfg = get_config("qwen3-0.6b")
    h, kvh, hd, s = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 2048
    assert (h, kvh, hd) == (16, 8, 128)
    fn = jax.jit(lambda q, k, v: flash_attention_bhsd(
        q, k, v, num_heads=h, num_kv_heads=kvh, interpret=False))
    _assert_kernel(fn.lower(_spec(one_chip, (h, s, hd), jnp.bfloat16),
                            _spec(one_chip, (kvh, s, hd), jnp.bfloat16),
                            _spec(one_chip, (kvh, s, hd), jnp.bfloat16))
                   .compile())


def test_decode_attention_compiles_at_qwen3_width(one_chip):
    from repro.kernels.decode_attention import decode_attention_packed
    cfg = get_config("qwen3-0.6b")
    h, kvh, hd, sc = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 4096
    fn = jax.jit(lambda q, k, v, n: decode_attention_packed(
        q, k, v, n, num_heads=h, num_kv_heads=kvh, interpret=False))
    _assert_kernel(fn.lower(_spec(one_chip, (kvh, h // kvh, hd),
                                  jnp.bfloat16),
                            _spec(one_chip, (kvh, sc, hd), jnp.bfloat16),
                            _spec(one_chip, (kvh, sc, hd), jnp.bfloat16),
                            _spec(one_chip, (), jnp.int32)).compile())


def test_ssm_scan_compiles_at_jamba_width(one_chip):
    from repro.kernels.ssm_scan import ssm_chunk_scan
    from repro.models.ssm import SSM_CHUNK
    cfg = get_config("jamba-v0.1-52b")
    inner, st = cfg.ssm_expand * cfg.d_model, cfg.ssm_state_dim
    assert (inner, st) == (8192, 16)
    x = _spec(one_chip, (1, SSM_CHUNK, inner, st))
    fn = jax.jit(lambda da, dbx: ssm_chunk_scan(da, dbx, interpret=False))
    _assert_kernel(fn.lower(x, x).compile())


def test_mlstm_chunk_compiles_at_xlstm_width(one_chip):
    from repro.kernels.mlstm_scan import mlstm_chunk_step
    from repro.models.xlstm import MLSTM_CHUNK
    cfg = get_config("xlstm-1.3b")
    nh = cfg.xlstm_num_heads
    hd = cfg.xlstm_expand * cfg.d_model // nh
    assert hd == 1024
    l = MLSTM_CHUNK
    seq = _spec(one_chip, (nh, l, hd))
    gate = _spec(one_chip, (nh, l))
    fn = jax.jit(lambda *a: mlstm_chunk_step(*a, interpret=False))
    _assert_kernel(fn.lower(seq, seq, seq, gate, gate,
                            _spec(one_chip, (nh, hd, hd)),
                            _spec(one_chip, (nh, hd)),
                            _spec(one_chip, (nh,))).compile())


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "qwen1.5-0.5b"])
def test_full_width_prefill_step_fits_one_chip(one_chip, tpu_step, arch):
    """The img-to-img stages' step, as ``ModelStageServer`` jits it, at
    the served batch and sequence length of ``chip_smoke.py``."""
    compiled, cfg, params = _compiled_step(one_chip, arch, 8, 128)
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(params))
    assert ma.argument_size_in_bytes >= param_bytes
    assert total < V5E_HBM_BYTES, total


@pytest.mark.parametrize("arch,seq", [("qwen3-0.6b", 96),
                                      ("qwen1.5-0.5b", 48)])
def test_served_prefill_step_runs_the_fused_attention_kernel(
        one_chip, tpu_step, arch, seq):
    """The step of each img-to-img stage at the benchmark's prompt length
    calls the fused prefill attention kernel, and XLA no longer copies the
    layer's ``wq``/``wk``/``wv`` slice into a head-major layout for it."""
    compiled, cfg, _ = _compiled_step(one_chip, arch, 8, seq)
    text = compiled.as_text()
    kernels = [l for l in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in l]
    assert kernels and all("prefill_attention" in l for l in kernels)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    for width in (cfg.num_heads * hd, cfg.num_kv_heads * hd):
        weight_copy = re.compile(
            rf"= bf16\[(1,)?{d},{width}\]\{{[^}}]*\}} copy\(")
        assert not weight_copy.search(text), (arch, width)

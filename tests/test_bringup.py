"""The chip smoke run and the pieces it rests on, checked on the CPU:
``chip_smoke.py``'s body at reduced width, its refusal of a CPU platform,
the width choice of ``ModelStageServer`` across pickling, the
``device_kind`` table, the compile-cache helper and the processes
backend's refusal of JAX stages on a TPU."""
import os
import pickle
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.camelot import DEVICE_KINDS, device_for_kind
from repro.configs import get_config
from repro.core.types import TPU_V5E_DEV
from repro.launch import compile_cache
from repro.serving import ModelStageServer, PipelineEngine
from repro.serving import engine as engine_mod
from repro.serving.workers import CpuStageServer

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
        yield chip_smoke
    finally:
        sys.path.remove(str(REPO))


def test_smoke_body_at_reduced_width_completes_every_query(chip_smoke):
    s = chip_smoke.run_smoke(TPU_V5E_DEV, reduced=True, seq_len=16,
                             queries=16, qps=200.0, steps=2)
    assert s["completed"] == 16
    assert s["failed"] == 0 and s["last_error"] is None


def test_smoke_main_refuses_cpu_platform(chip_smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out


def test_smoke_script_fails_without_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_smoke_check_rejects_a_served_token_off_the_argmax(chip_smoke,
                                                          monkeypatch):
    stage = ModelStageServer("s", "qwen3-0.6b", seq_len=8)
    real = stage.process
    monkeypatch.setattr(stage, "process",
                        lambda t: (real(t) + 1) % stage.cfg.vocab_size)
    with pytest.raises(chip_smoke.SmokeFailure, match="argmax"):
        chip_smoke.check_stage(stage, 2, np.random.default_rng(0), 1)


@pytest.mark.parametrize("reduced", [True, False])
def test_model_stage_server_pickles_with_its_width(reduced, monkeypatch):
    if not reduced:
        # build the full-width server from shapes only: the test checks
        # what crosses the pickle boundary, not 1.2 GB of weights
        init = engine_mod.init_params
        monkeypatch.setattr(engine_mod, "init_params",
                            lambda key, cfg: jax.eval_shape(
                                lambda: init(key, cfg)))
    srv = ModelStageServer("s", "qwen3-0.6b", seq_len=8, seed=3,
                           reduced=reduced)
    back = pickle.loads(pickle.dumps(srv))
    assert back.reduced is reduced
    assert back.cfg == get_config("qwen3-0.6b", reduced=reduced)
    assert (back.name, back.seq_len, back._seed) == ("s", 8, 3)
    if reduced:
        for a, b in zip(jax.tree.leaves(srv.params),
                        jax.tree.leaves(back.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_device_kind_table_maps_v5e_and_raises_on_unknown_kind():
    assert DEVICE_KINDS["TPU v5 lite"] is TPU_V5E_DEV
    assert device_for_kind("TPU v5 lite") is TPU_V5E_DEV
    with pytest.raises(ValueError, match="no device model"):
        device_for_kind("cpu")


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_follows_env_var(monkeypatch, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/cache")
    assert compile_cache.enable_compile_cache() == "/some/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.enable_compile_cache() == got


def test_processes_backend_refuses_jax_stage_on_tpu(monkeypatch):
    stages = [ModelStageServer("a", "qwen3-0.6b", seq_len=8)]
    monkeypatch.setattr(engine_mod.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="backend='threads'"):
        PipelineEngine(stages, backend="processes")
    # numpy stages keep their worker processes; threads keep JAX stages
    PipelineEngine([CpuStageServer("c")], backend="processes").close()
    PipelineEngine(stages, backend="threads").close()

"""Sharding rules: every (arch × mode) produces structurally-valid shardings;
a subprocess check lowers a reduced config on a faked 16-device mesh."""
import json
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import ShardingRules
from repro.models import abstract_cache, abstract_params


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("mode,batch,seq", [
    ("train", 16, 64), ("decode", 8, 64)])
def test_rules_cover_every_leaf(arch, mode, batch, seq):
    cfg = get_config(arch, reduced=True)
    mesh = make_host_mesh()          # 1 CPU device: (1, 1) mesh
    axis_names = set(mesh.axis_names)
    rules = ShardingRules(cfg, mesh, mode, batch, seq)
    params = abstract_params(cfg)
    sh = rules.params_shardings(params)
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec"))
    assert len(flat_p) == len(flat_s)
    for leaf, s in zip(flat_p, flat_s):
        spec = s.spec
        assert len(spec) <= leaf.ndim, (leaf.shape, spec)
        # structural validity: every named entry references a real mesh
        # axis, no mesh axis is consumed twice by one spec, and a sharded
        # dimension divides evenly by the PRODUCT of its axis sizes (the
        # host mesh is (1,1), so the dividing coverage with real axis
        # sizes lives in the 16-fake-device subprocess test below)
        used = []
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            shard_n = 1
            for ax in names:
                assert ax in axis_names, (leaf.shape, spec, ax)
                assert ax not in used, f"axis {ax} used twice in {spec}"
                used.append(ax)
                shard_n *= mesh.shape[ax]
            assert leaf.shape[dim] % shard_n == 0, (leaf.shape, spec)
    if mode == "decode":
        cache = abstract_cache(cfg, batch, seq)
        csh = rules.cache_shardings(cache)
        assert len(jax.tree.leaves(cache)) == len(
            jax.tree.leaves(csh, is_leaf=lambda x: hasattr(x, "spec")))
    acts = rules.activation_rules()  # must build without error
    assert isinstance(acts, dict) and acts, "activation rules must be" \
        " a non-empty mapping"


def test_pure_dp_for_attention_free_train():
    cfg = get_config("xlstm-1.3b", reduced=True)
    mesh = make_host_mesh()
    r = ShardingRules(cfg, mesh, "train", 16, 64)
    assert r.pure_dp and not r.tp_enabled
    cfg2 = get_config("qwen3-0.6b", reduced=True)
    r2 = ShardingRules(cfg2, mesh, "train", 16, 64)
    assert not r2.pure_dp and r2.tp_enabled


@pytest.mark.slow
def test_dryrun_subprocess_reduced_mesh():
    """End-to-end dry-run path on 16 fake devices (fast reduced config)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp, json
from repro.configs import get_config
from repro.launch.sharding import ShardingRules
from repro.models import abstract_params, forward_train, set_sharding_rules
mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_config("qwen3-0.6b", reduced=True)
rules = ShardingRules(cfg, mesh, "train", 8, 64)
set_sharding_rules(rules.activation_rules())
params = abstract_params(cfg)
psh = rules.params_shardings(params)
batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
         "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
bsh = rules.batch_shardings(batch)
total_param_bytes = sum(l.size * l.dtype.itemsize
                        for l in jax.tree.leaves(params))
with mesh:
    lowered = jax.jit(lambda p, b: forward_train(p, b, cfg),
                      in_shardings=(psh, bsh)).lower(params, batch)
    compiled = lowered.compile()
ma = compiled.memory_analysis()
print(json.dumps({"ok": True, "temp": ma.temp_size_in_bytes,
                  "arg_bytes": ma.argument_size_in_bytes,
                  "out_bytes": ma.output_size_in_bytes,
                  "total_param_bytes": total_param_bytes,
                  "n_devices": len(jax.devices())}))
"""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"]
    assert rec["n_devices"] == 16, "XLA_FLAGS fake-device count not applied"
    # the compile must report real per-device numbers, and sharding must
    # leave each device with LESS than the full (replicated) parameter set
    assert rec["temp"] >= 0
    assert rec["out_bytes"] > 0
    assert 0 < rec["arg_bytes"] < rec["total_param_bytes"], \
        f"per-device arguments {rec['arg_bytes']} not sharded below " \
        f"replicated {rec['total_param_bytes']}"


@pytest.mark.slow
def test_int8_decode_lowering_subprocess():
    """The quantized-serving lowering path (§Perf pair 3) compiles and its
    resident arguments shrink vs bf16."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
os.environ["REPRO_QUANTIZE_DECODE"] = "1"
import jax, jax.numpy as jnp, json
from repro.configs import get_config, register
from repro.configs.base import InputShape
import repro.configs.base as cb
import repro.launch.dryrun as dr
# monkeypatch a small shape + host mesh for speed
cb.INPUT_SHAPES["tiny_decode"] = InputShape("tiny_decode", 256, 8, "decode")
dr.INPUT_SHAPES = cb.INPUT_SHAPES
import repro.launch.mesh as lm
lm.make_production_mesh = lambda multi_pod=False: jax.make_mesh(
    (4, 4), ("data", "model"),
    axis_types=(jax.sharding.AxisType.Auto,) * 2)
dr.make_production_mesh = lm.make_production_mesh
rec = dr.run_combo("qwen3-0.6b", "tiny_decode")
print(json.dumps({"status": rec["status"],
                  "args": rec["memory_per_device"]["argument_bytes"]}))
"""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["status"] == "ok"

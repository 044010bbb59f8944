"""The harness body at the laptop-scale widths on the CPU: a cell and a
reference family added as files alone run; a stage's sizes, nested
sections included, are checked against the program; the check passes the
program and fails its control and each fault the served path can have;
``run.py`` refuses a machine without the chip."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import reference
from bench.harness import Cell, _check_stage_config, run_cell
from bench.tests.tiny import ROOT, _reduced_model, make_root

SEED = 2**33 + 7     # past 32 bits, as a run's seed may be


def run_tiny(tmp_path, base="img-to-img", trace=False, seconds=2.0,
             seed=SEED, family=None):
    root, cell = make_root(tmp_path, base, family)
    return run_root(tmp_path, root, cell, trace, seconds, seed)


def run_root(tmp_path, root, cell, trace=False, seconds=2.0, seed=SEED):
    return run_cell(cell, seed, seconds, trace, t_start=time.perf_counter(),
                    device_kind="TPU v5 lite", root=root, reduced=True,
                    trace_dir=tmp_path / "trace", log=lambda msg: None)


def _bench_files():
    return {p: p.read_bytes() for p in (ROOT / "bench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.mark.parametrize("base", ["img-to-img", "text-to-img"])
def test_cell_added_as_files_runs(tmp_path, base):
    before = _bench_files()
    res = run_tiny(tmp_path, base)
    assert _bench_files() == before
    assert res["correct"], res["compared"]
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"served_qps", "setup_s",
                                   "completed_count"}
    assert res["metrics"]["completed_count"]["value"] == res["attempted"]
    assert res["attempted"] == 80
    assert list(res)[-1] == "compared"
    for name in ("lost", "misrouted"):
        assert res["compared"][name] == {"value": 0.0, "limit": 0}
    json.dumps(res)


def test_cell_reports_latency_under_a_split_name(tmp_path):
    """A new cell reports its median latency through an end-to-end entry of
    its own, ``p50_ms.<cell>``, which ``metrics/p50_ms.py`` reads: no
    entry the benchmark already has is edited."""
    root, cell = make_root(tmp_path)
    path = root / "BENCHMARK.json"
    bm = json.loads(path.read_text())
    name = f"p50_ms.{cell}"
    bm["end_to_end"].append({"name": name, "unit": "ms", "better": "lower",
                             "bound": 0.13, "source": "host_clock",
                             "workloads": [cell]})
    path.write_text(json.dumps(bm, indent=1))
    res = run_root(tmp_path, root, cell)
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {name, "served_qps", "setup_s",
                                   "completed_count"}
    assert res["metrics"][name]["unit"] == "ms"
    # a few stage calls of the tiny models, well inside the 2 s window
    assert 0 < res["metrics"][name]["value"] < 2000


def test_family_added_as_files_runs(tmp_path):
    """A copy of the transformer reference under another name, written into
    the checkout alone, serves the tiny configuration that names it: the
    run is correct and the family's logits are the transformer's."""
    before = _bench_files()
    res = run_tiny(tmp_path, family="transformer_copy")
    assert _bench_files() == before
    assert res["correct"], res["compared"]
    bench = tmp_path / "checkout" / "bench"
    config = json.loads((bench / "configs" / "img-to-img-tiny.json")
                        .read_text())
    for st in config["stages"]:
        assert st["reference"] == "transformer_copy"
        prompts = np.random.default_rng(3).integers(
            0, st["model"]["vocab_size"], (4, st["seq_len"])).astype(np.int32)
        own = reference.last_logits(st, SEED, prompts, bench=bench)
        base = reference.last_logits({**st, "reference": "transformer"},
                                     SEED, prompts)
        np.testing.assert_array_equal(own, base)


@pytest.mark.parametrize("family", [None, "no_such_family"])
def test_missing_family_fails_at_cell_load(tmp_path, family):
    root, cell = make_root(tmp_path)
    path = root / "bench" / "configs" / "img-to-img-tiny.json"
    config = json.loads(path.read_text())
    stage = config["stages"][1]
    if family is None:
        del stage["reference"]
    else:
        stage["reference"] = family
    path.write_text(json.dumps(config))
    with pytest.raises(ValueError) as err:
        Cell(cell, root)
    assert stage["name"] in str(err.value)
    assert (family or '"reference"') in str(err.value)


def _moe_stage():
    """The zoo's sparse-expert model at its laptop-scale widths, as a
    configuration file would state it, with its ``moe`` section."""
    from repro.configs import get_config
    arch = "qwen3-moe-30b-a3b"
    cfg = get_config(arch, reduced=True)
    want = _reduced_model(arch, ("num_layers", "d_model", "num_heads",
                                 "num_kv_heads", "head_dim", "d_ff",
                                 "vocab_size", "block_pattern",
                                 "mlp_pattern", "qk_norm"))
    want["moe"] = dataclasses.asdict(cfg.moe)
    return SimpleNamespace(name="experts", cfg=cfg), want


def test_nested_section_is_checked():
    stage, want = _moe_stage()
    assert want["moe"]["num_experts"] == stage.cfg.moe.num_experts
    _check_stage_config(stage, want)


@pytest.mark.parametrize("key", ["num_experts", "top_k", "no_such_key"])
def test_nested_section_mismatch_names_its_path(key):
    stage, want = _moe_stage()
    want["moe"][key] = want["moe"].get(key, 0) + 1
    with pytest.raises(ValueError, match=rf"stage experts: .*moe\.{key}"):
        _check_stage_config(stage, want)


def test_traced_run_on_cpu_keeps_its_verdict(tmp_path):
    res = run_tiny(tmp_path, trace=True, seconds=2.0)
    assert res["correct"], res["compared"]
    # no per-layer metric names the tiny cell, and the CPU has no device
    # trace to read
    assert res["metrics"] == {}


def _patch_process(monkeypatch, fault):
    from repro.serving import engine
    original = engine.ModelStageServer.process
    last = {}

    def broken(self, tokens):
        out = original(self, tokens)
        n = out.shape[0]
        if fault == "altered":
            return (out + 1) % self.cfg.vocab_size
        if fault == "half_batch":
            return out.at[n // 2:].set(0)
        if fault == "stale":
            prev = last.get(self.name, out)
            last[self.name] = out
            return prev
        raise ValueError(fault)
    monkeypatch.setattr(engine.ModelStageServer, "process", broken)


@pytest.mark.parametrize("fault", ["altered", "half_batch", "stale",
                                   "handoff"])
def test_faults_fail_the_check(tmp_path, monkeypatch, fault):
    if fault == "handoff":
        from repro.serving import engine
        original = engine._fanin_combine

        def dropped(stages, node, inputs):
            return original(stages, node, inputs) * 0
        monkeypatch.setattr(engine, "_fanin_combine", dropped)
    else:
        _patch_process(monkeypatch, fault)
    res = run_tiny(tmp_path)
    assert not res["correct"]
    bad = {k for k, c in res["compared"].items()
           if not (c["value"] is not None and c["value"] <= c["limit"])}
    assert bad, res["compared"]


def test_control_fails_the_check(tmp_path, monkeypatch):
    """The float8 reference in the program's place: one precision below
    the configuration's bfloat16."""
    from bench.control import serve_control
    from repro.serving import engine
    monkeypatch.setattr(engine.ModelStageServer, "process",
                        engine.ModelStageServer.process)
    root, _ = make_root(tmp_path / "models")
    serve_control(json.loads((root / "bench" / "configs"
                              / "img-to-img-tiny.json").read_text()))
    res = run_tiny(tmp_path)
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for k, c in res["compared"].items()
               if k.startswith("gap.")), res["compared"]


def _run_py(cwd, env_extra):
    env = {**os.environ, **env_extra}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "img-to-img.poisson-knee80", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_refuses_a_machine_without_tpu():
    proc = _run_py(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_run_fails_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = _run_py(tmp_path, {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""

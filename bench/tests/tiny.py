"""A copy of the benchmark in a temporary directory, with one more
configuration at the laptop-scale widths, its own traffic mix, cell and
metric, and optionally a reference family of its own: every file new, no
file of the benchmark edited."""
from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Optional

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"

# Widest-gap limits at these widths, from CPU readings over six seeds of
# 64 prompts (program: bf16 against the float32 reference; control: the
# reference in float8): Qwen stages, program 0 to 0.013, control 0.068 to
# 0.16; xLSTM, program 0.22 to 0.52, control 3.05 to 4.36.
TINY_GAP_LIMITS = {"qwen3-0.6b": 0.03, "qwen1.5-0.5b": 0.03,
                   "xlstm-1.3b": 1.2}


def _reduced_model(arch: str, keys) -> dict:
    cfg = get_config(arch, reduced=True)
    out = {}
    for k in keys:
        v = cfg.resolved_head_dim if k == "head_dim" else getattr(cfg, k)
        out[k] = list(v) if isinstance(v, tuple) else v
    return out


def config_file(name: str) -> Path:
    """A configuration of the benchmark, or one kept for the tests alone
    (``data/``: ``text-to-img``, which no cell runs yet)."""
    own = ROOT / "bench" / "configs" / f"{name}.json"
    return own if own.exists() else DATA / f"{name}.json"


def make_root(tmp: Path, base: str = "img-to-img",
              family: Optional[str] = None):
    """``tmp/checkout`` holding ``BENCHMARK.json`` and ``bench/`` as
    committed, plus the tiny configuration of ``base``'s service and its
    cell.  With ``family``, the checkout also holds a copy of the
    ``transformer`` reference under that name, and the tiny configuration's
    transformer stages name it.  Returns the root and the cell's name."""
    cell = f"{base}-tiny.poisson-tiny"
    root = tmp / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())

    config = json.loads(config_file(base).read_text())
    config["name"] = f"{base}-tiny"
    for st in config["stages"]:
        st["model"] = _reduced_model(st["arch"], st["model"])
        if family and st["reference"] == "transformer":
            st["reference"] = family
    if family:
        shutil.copy(ROOT / "bench" / "reference" / "transformer.py",
                    root / "bench" / "reference" / f"{family}.py")
    config["limits"] = {"lost": 0, "misrouted": 0}
    for st in config["stages"]:
        config["limits"][f"gap.{st['name']}"] = TINY_GAP_LIMITS[st["arch"]]
    (root / "bench" / "configs" / f"{base}-tiny.json").write_text(
        json.dumps(config, indent=1))
    (root / "bench" / "traffic" / "poisson-tiny.json").write_text(
        json.dumps({"arrival": "poisson", "warmup_s": 0.5}))
    (root / "bench" / "workloads" / f"{cell}.json").write_text(
        json.dumps({"rate_qps": 40}))
    (root / "bench" / "metrics" / "completed_count.py").write_text(
        '"""Queries completed in the run."""\n\n\n'
        'def read(record):\n    return record["completed"]\n')

    bm["configs"].append({"name": f"{base}-tiny", "source": "test",
                          "file": f"bench/configs/{base}-tiny.json",
                          "reduced": [], "why": "CPU test"})
    bm["workloads"].append({"name": cell, "config": f"{base}-tiny",
                            "traffic": "poisson-tiny", "chips": 1,
                            "why": "CPU test"})
    bm["end_to_end"].append({"name": "completed_count", "unit": "queries",
                             "better": "higher", "bound": 0.01,
                             "source": "host_clock",
                             "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm, indent=1))
    return root, cell

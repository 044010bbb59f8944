"""Plain float32 references of the benchmark's models, one family a file.

Each stage of a configuration names its family (``"reference"``), and
``bench/reference/<family>.py`` serves it.  A family module provides

* ``last_logits(model, seed, prompts, quant=None)``: the last-position
  logits of ``prompts`` under the stage ``model`` (a configuration file's
  ``model`` entry) whose weights come from ``seed``;
* ``matmul_params(model)`` and ``mixing_flops(model, seq_len)``: the
  terms of the stage's useful FLOPs (``bench/flops.py``) for the block
  kinds it serves.

It raises for a block or feed-forward kind it does not serve.  The family
is found by its name in the run's own ``bench/`` directory, so a new
architecture joins by adding its file.

The references import nothing of the program and take nothing it made:
each draws the stage's weights again from its seed (``weights.py``) and
computes in float32 at ``Precision.HIGHEST``.  ``quant="fp8"`` runs the
same pass with every product's operands rounded to float8, the control of
the check.
"""
from __future__ import annotations

import functools
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]


@functools.lru_cache(maxsize=None)
def _load(path: Path):
    """One module object per family file, so that its jitted functions
    are traced once in a process."""
    from bench.harness import load_module   # here: the harness imports us
    return load_module(path)


def family(stage: dict, bench: Path = BENCH):
    """The module of the reference family that ``stage`` (a configuration
    file's stage entry) names, from ``bench/reference/<family>.py``."""
    name = stage.get("reference")
    if name is None:
        raise ValueError(f"stage {stage['name']}: no \"reference\" family "
                         "named in the configuration file")
    path = Path(bench).resolve() / "reference" / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"stage {stage['name']}: reference family "
                         f"{name!r} has no module {path}")
    return _load(path)


def last_logits(stage: dict, seed: int, prompts: np.ndarray, quant=None,
                bench: Path = BENCH) -> np.ndarray:
    """Last-position logits, float32 (B, V), of ``prompts`` (B, S) under
    ``stage``'s model whose weights come from ``seed``."""
    impl = family(stage, bench)
    return np.asarray(impl.last_logits(stage["model"], seed, prompts,
                                       quant=quant), np.float32)

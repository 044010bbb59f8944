"""What decides ``correct``: the served path's answers against the plain
reference, and the window's accounting.

Three numbers, each against its limit in the configuration file:

* ``lost``: queries sent that neither completed nor failed, plus extra
  completions of one query.  Limit 0.
* ``misrouted``: sampled queries whose prompt at some stage is not what
  that stage should have been given: the query's own tokens at the entry,
  and at each later stage the previous stage's served token repeated over
  the stage's ``seq_len`` (the hand-off), both as the stage call saw them.
  Limit 0.
* ``gap.<stage>``: for a sample of the completed queries drawn from the
  seed, the widest gap by which the token a stage served lies below the
  best logit of the float32 reference at that stage's prompt.  The
  reference, the family the stage names in ``bench/reference``, runs once
  over the sampled prompts of each stage.

Each stage call's input and output were kept by reference during the
window (``harness.Recorder``); rows of a call are matched to queries by
their tokens, and a later stage's call to the earlier call whose served
tokens its prompt repeats.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import reference


def served_paths(queries, calls: List[List[tuple]], vocab: List[int]):
    """For each query, its served token at every stage as the recorded calls
    show them, or ``None`` where a stage's call for it cannot be found with
    the prompt it should have had."""
    first = {}
    for c, (inp, _) in enumerate(calls[0]):
        for r in range(inp.shape[0]):
            first.setdefault(inp[r].tobytes(), (c, r))
    later = []
    for k in range(1, len(calls)):
        idx: Dict[bytes, list] = {}
        for c, (inp, _) in enumerate(calls[k]):
            idx.setdefault(inp[:, 0].tobytes(), []).append(c)
        later.append(idx)

    paths = []
    for q in queries:
        hit = first.get(np.asarray(q.tokens, np.int32).tobytes())
        if hit is None:
            paths.append(None)
            continue
        c, r = hit
        out = calls[0][c][1]
        tokens = [int(out[r])]
        for k in range(1, len(calls)):
            inp_want_col = (out % vocab[k]).astype(np.int32)
            found = None
            for c2 in later[k - 1].get(inp_want_col.tobytes(), []):
                inp = calls[k][c2][0]
                if np.array_equal(inp, np.broadcast_to(
                        inp_want_col[:, None], inp.shape)):
                    found = c2
                    break
            if found is None:
                tokens = None
                break
            out = calls[k][found][1]
            tokens.append(int(out[r]))
        paths.append(tokens)
    return paths


def stage_prompts(config: dict, prompts0: np.ndarray,
                  served: np.ndarray) -> List[np.ndarray]:
    """Each stage's prompts for queries whose entry prompts are
    ``prompts0`` (n, S0) and whose served tokens are ``served`` (n,
    stages): a later stage repeats the previous stage's token."""
    out = [prompts0]
    for k, st in enumerate(config["stages"][1:], start=1):
        tok = served[:, k - 1] % st["model"]["vocab_size"]
        out.append(np.repeat(tok[:, None], st["seq_len"], axis=1)
                   .astype(np.int32))
    return out


def widest_gap(logits: np.ndarray, tokens: np.ndarray) -> float:
    """max over rows of (best logit - logit of the row's token)."""
    picked = logits[np.arange(len(tokens)), tokens]
    return float(np.max(logits.max(axis=1) - picked))


def check(config: dict, queries, calls: List[List[tuple]],
          stage_seeds: List[int], *, completed: int, failed: int,
          sample: int, rng: np.random.Generator, bench: Path) -> dict:
    """The verdict of one run: ``{"correct": bool, "compared": {name:
    {"value", "limit"}}}``.  ``bench`` is the run's own ``bench/``
    directory, where the stages' reference families are found."""
    limits = config["limits"]
    vocab = [st["model"]["vocab_size"] for st in config["stages"]]
    done = [q for q in queries if q.done is not None]
    values: Dict[str, float] = {
        "lost": float(len(queries) - len(done) - failed
                      + abs(completed - len(done)))}

    pick = rng.choice(len(done), size=min(sample, len(done)), replace=False)
    picked = [done[i] for i in sorted(pick)]
    paths = served_paths(picked, calls, vocab)
    values["misrouted"] = float(sum(p is None for p in paths))
    ok = [(q, p) for q, p in zip(picked, paths) if p is not None]
    if ok:
        prompts0 = np.stack([np.asarray(q.tokens, np.int32) for q, _ in ok])
        served = np.array([p for _, p in ok], np.int64)
        for k, (st, prompts) in enumerate(zip(
                config["stages"], stage_prompts(config, prompts0, served))):
            logits = reference.last_logits(st, stage_seeds[k], prompts,
                                           bench=bench)
            values[f"gap.{st['name']}"] = widest_gap(logits, served[:, k])

    compared = {}
    correct = True
    for name, limit in limits.items():
        value: Optional[float] = values.get(name)
        compared[name] = {"value": value, "limit": limit}
        if value is None or limit is None or not value <= limit:
            correct = False
    return {"correct": correct, "compared": compared}

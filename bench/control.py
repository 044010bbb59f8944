"""The check's control, read on the chip at a cell's own size.

The control is the plain reference put in the program's place and
computed one precision below the configuration's bfloat16: every product
with float8 (e4m3) operands.  For each seed it draws the stage weights
and the window's prompts as a run of that seed does, takes the same
number of queries as a run compares, and reads at each stage the widest
gap by which the token that float8 puts first lies below the float32
reference's best logit.  A later stage's prompts repeat the previous
stage's reference token, as the hand-off would.  The benchmark's own runs
do not run it; its readings set the upper end of each ``gap.<stage>``
limit.

    python3 bench/control.py --config img-to-img --seeds 11,12,13

With ``--cell`` it instead serves one run of that cell through the
harness with the control in the program's place (each stage call answers
with the float8 reference's first token), so that the harness's own
check reads it and ``correct`` has to come out false:

    python3 bench/control.py --cell img-to-img.poisson-knee80 --seeds 11 \
        --seconds 1
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import check, reference  # noqa: E402
from bench.harness import (BENCH, SAMPLE_QUERIES, derived_seed,  # noqa: E402
                           device_info, load_json)


def control_gaps(config: dict, seed: int, n: int = SAMPLE_QUERIES) -> dict:
    stages = config["stages"]
    rng = np.random.default_rng(derived_seed(seed, "prompts", "window"))
    prompts = rng.integers(0, stages[0]["model"]["vocab_size"],
                           (n, stages[0]["seq_len"])).astype(np.int32)
    out = {}
    for k, st in enumerate(stages):
        s = derived_seed(seed, "weights", k)
        ref = reference.last_logits(st, s, prompts)
        ctl = reference.last_logits(st, s, prompts, quant="fp8")
        out[f"gap.{st['name']}"] = check.widest_gap(ref, ctl.argmax(axis=1))
        if k + 1 < len(stages):
            nxt = stages[k + 1]
            tok = ref.argmax(axis=1) % nxt["model"]["vocab_size"]
            prompts = np.repeat(tok[:, None], nxt["seq_len"],
                                axis=1).astype(np.int32)
    return out


def serve_control(config: dict) -> None:
    """Put the control in the program's place: every stage call answers
    with the token that the float8 reference puts first."""
    import jax.numpy as jnp
    from repro.serving import engine
    stages = {st["name"]: st for st in config["stages"]}

    def control(self, tokens):
        logits = reference.last_logits(stages[self.name], self._seed,
                                       np.asarray(tokens), quant="fp8")
        return jnp.asarray(logits.argmax(axis=1).astype(np.int32))
    engine.ModelStageServer.process = control


def run_cell_with_control(cell_name: str, seed: int, seconds: float,
                          dev: dict) -> dict:
    from bench.harness import Cell, run_cell
    serve_control(Cell(cell_name, ROOT).config)
    t0 = time.perf_counter()
    res = run_cell(cell_name, seed, seconds, False, t_start=t0,
                   device_kind=dev["kind"], root=ROOT,
                   log=lambda msg: print(msg, file=sys.stderr, flush=True))
    return {"cell": cell_name, "seed": seed, "control_served": True,
            "correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "compared": res["compared"],
            "seconds": time.perf_counter() - t0, "device": dev}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    group = ap.add_mutually_exclusive_group(required=True)
    group.add_argument("--config")
    group.add_argument("--cell")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=1.0,
                    help="window of a --cell run")
    args = ap.parse_args()
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"control: JAX found no TPU (platform {dev['platform']!r})",
              file=sys.stderr)
        return 1
    if args.cell:
        for seed in (int(s) for s in args.seeds.split(",")):
            print(json.dumps(run_cell_with_control(args.cell, seed,
                                                   args.seconds, dev)),
                  flush=True)
        return 0
    config = load_json(BENCH / "configs" / f"{args.config}.json")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        gaps = control_gaps(config, seed)
        print(json.dumps({"config": args.config, "seed": seed,
                          "control": gaps,
                          "seconds": time.perf_counter() - t0,
                          "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Bring-up smoke run of the served path on one TPU chip.

Drives the public facade once, at full published width: the paper's
``img-to-img`` service (Table I: ``qwen3-0.6b`` -> ``qwen1.5-0.5b``) is
profiled and solved with the ``max-peak`` policy for the chips JAX sees,
then served by ``CamelotSession.serve`` on the threads backend from two
``ModelStageServer``s built from the full configurations, replaying a
seeded open-loop trace.  Weights are random, made from the seed.

What it checks:

  * the solve is feasible;
  * each stage's logits are finite and of the expected shape, the token
    it serves is a top logit, and on a small input the chip's logits
    agree with the same weights run on the host CPU;
  * every query sent completes, and none fails.

It prints what it measured on lines labelled ``[smoke]``.  These are one
smoke run's numbers, not a benchmark.  The last line of standard output
is one JSON object, ``{"ok": true, "device": {...}}``, printed only when
every check passed.  Without a TPU it exits non-zero and prints no such
line.

    python3 chip_smoke.py
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.camelot import CamelotSession, ClusterSpec, device_for_kind  # noqa: E402
from repro.core.types import DeviceSpec  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import serve_prefill  # noqa: E402
from repro.serving import ModelStageServer  # noqa: E402
from repro.sim.workloads import workload_specs  # noqa: E402

SERVICE = "img-to-img"
# relative L2 error allowed between the chip's logits and the host CPU's
# for the same bf16 weights and tokens; set before the first chip run
# from bf16 rounding (2^-8 per op) accumulated over the layer stack
REFERENCE_RTOL = 0.1


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _logits_fn(cfg):
    return jax.jit(lambda params, tokens: serve_prefill(params, tokens,
                                                        cfg)[0])


def check_stage(stage: ModelStageServer, batch: int,
                rng: np.random.Generator, steps: int) -> dict:
    """Warm the stage up, time ``steps`` steps at the served batch, and
    check its outputs.  Returns what it measured."""
    cfg = stage.cfg
    t0 = time.perf_counter()
    stage.warmup(batch)
    warm_s = time.perf_counter() - t0
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (batch, stage.seq_len), np.int32))
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        out = stage.process(tokens)            # blocks until ready
        times.append(time.perf_counter() - t0)
    logits = np.asarray(_logits_fn(cfg)(stage.params, tokens), np.float32)
    out = np.asarray(out)
    if logits.shape != (batch, cfg.vocab_size):
        raise SmokeFailure(f"{stage.name}: logits shape {logits.shape}, "
                           f"expected {(batch, cfg.vocab_size)}")
    if not np.isfinite(logits).all():
        raise SmokeFailure(f"{stage.name}: non-finite logits")
    if out.shape != (batch,) or out.min() < 0 or out.max() >= cfg.vocab_size:
        raise SmokeFailure(f"{stage.name}: served tokens {out!r} are not "
                           f"{batch} ids below {cfg.vocab_size}")
    # the served token is a top logit (within bf16 rounding of the max)
    picked = logits[np.arange(batch), out]
    slack = 1e-2 * np.abs(logits).max(axis=1) + 1e-6
    if (picked < logits.max(axis=1) - slack).any():
        raise SmokeFailure(f"{stage.name}: served tokens are not the "
                           "argmax of the stage's logits")
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(stage.params))
    devices = {str(d) for x in jax.tree.leaves(stage.params)
               for d in x.devices()}
    return {"param_bytes": int(param_bytes), "devices": sorted(devices),
            "warmup_s": warm_s, "median_step_s": float(np.median(times))}


def check_against_host(stage: ModelStageServer, rng: np.random.Generator,
                       seq_len: int = 16) -> float:
    """Relative L2 error of the stage's last-token logits on its default
    device against the same weights run on the host CPU, for one short
    prompt.  Raises when it exceeds ``REFERENCE_RTOL``."""
    cfg = stage.cfg
    tokens = rng.integers(0, cfg.vocab_size, (1, seq_len), np.int32)
    fn = _logits_fn(cfg)
    got = np.asarray(fn(stage.params, jnp.asarray(tokens)), np.float32)
    cpu = jax.devices("cpu")[0]
    want = np.asarray(fn(jax.device_put(stage.params, cpu),
                         jax.device_put(tokens, cpu)), np.float32)
    err = float(np.linalg.norm(got - want)
                / max(np.linalg.norm(want), 1e-30))
    if not err <= REFERENCE_RTOL:
        raise SmokeFailure(f"{stage.name}: logits differ from the host CPU "
                           f"reference by {err:.3g} (limit "
                           f"{REFERENCE_RTOL})")
    return err


def run_smoke(device: DeviceSpec, *, reduced: bool, seq_len: int = 128,
              queries: int = 64, qps: float = 50.0, batch: int = 8,
              steps: int = 10, seed: int = 0) -> dict:
    """Spec -> profile -> solve -> serve of the img-to-img service on the
    chips JAX sees, priced as ``device``.  ``reduced`` picks the laptop
    twins of the two models in place of their published widths.  Raises
    ``SmokeFailure`` when a check does not hold; returns the serve
    summary."""
    spec = workload_specs(device=device)[SERVICE]
    sess = CamelotSession(
        spec, ClusterSpec(devices=jax.device_count(), device=device),
        batch=batch, seed=seed)
    sess.profile()
    res = sess.solve(policy="max-peak")
    if not res.feasible:
        raise SmokeFailure(f"max-peak solve of {SERVICE} on "
                           f"{jax.device_count()} x {device.name} is "
                           "infeasible")
    log(f"solve: {SERVICE} max-peak on {jax.device_count()} x "
        f"{device.name}: objective {res.objective} qps, stages "
        f"{[(s.n_instances, s.quota, s.batch) for s in res.allocation.stages]}")

    rng = np.random.default_rng(seed)
    width = "reduced" if reduced else "full width"
    stages, resident = [], 0
    for i, node in enumerate(sess.graph.nodes):
        t0 = time.perf_counter()
        stage = ModelStageServer(node.name, node.arch, seq_len=seq_len,
                                 seed=seed + i, reduced=reduced)
        init_s = time.perf_counter() - t0
        m = check_stage(stage, batch, rng, steps)
        err = check_against_host(stage, rng)
        log(f"stage {node.name} ({node.arch}, {width}, {stage.cfg.num_layers}"
            f" layers, d_model {stage.cfg.d_model}): params "
            f"{m['param_bytes']} B on {','.join(m['devices'])}; init "
            f"{init_s:.3f} s; warm-up+compile {m['warmup_s']:.3f} s; "
            f"median step {m['median_step_s'] * 1e3:.3f} ms at batch "
            f"{batch} x seq {seq_len}; logits vs host CPU rel err {err:.3g}")
        stages.append(stage)
        resident += m["param_bytes"]
    log(f"params resident: {resident} B")

    eng = sess.serve(stages=stages)
    trace = sess.make_trace(queries, qps=qps, seed=seed)
    t0 = time.perf_counter()
    summary = eng.run_trace(trace).summary()
    wall = time.perf_counter() - t0
    log(f"serve (threads backend): sent {queries} at {qps} qps; completed "
        f"{summary['completed']}, failed {summary['failed']}; p99 "
        f"{summary['p99'] * 1e3:.3f} ms, mean {summary['mean'] * 1e3:.3f} "
        f"ms; wall {wall:.3f} s")
    if summary["failed"]:
        raise SmokeFailure(f"{summary['failed']} queries failed; last "
                           f"error: {summary['last_error']}")
    if summary["completed"] != queries:
        raise SmokeFailure(f"completed {summary['completed']} of "
                           f"{queries} queries")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    return summary


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this smoke run needs the chip", file=sys.stderr)
        return 1
    log(f"smoke run, not a benchmark: jax {jax.__version__}, device_kind "
        f"{dev.device_kind!r}, {jax.device_count()} device(s)")
    device = device_for_kind(dev.device_kind)
    log(f"compile cache: {enable_compile_cache()}")
    try:
        run_smoke(device, reduced=False)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

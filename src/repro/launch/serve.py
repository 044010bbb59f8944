"""Serving launcher: Camelot-managed microservice pipeline on the host.

Builds a pipeline of model-zoo stages, profiles them live, runs the Camelot
allocator, then serves a batched request trace with the chosen communication
mechanism.  The allocator prices the accelerator JAX reports, through
``repro.camelot.device_for_kind``; a device with no model there is an error.

  PYTHONPATH=src python -m repro.launch.serve --stages qwen3-0.6b qwen1.5-0.5b
"""
from __future__ import annotations

import argparse

import jax

from repro.camelot import device_for_kind
from repro.core import (CamelotAllocator, PipelinePredictor, SAConfig,
                        profile_from_engine)
from repro.core.types import Pipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import ModelStageServer, PipelineEngine, make_trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", nargs="+",
                    default=["qwen3-0.6b", "qwen1.5-0.5b"])
    ap.add_argument("--queries", type=int, default=24)
    ap.add_argument("--qps", type=float, default=30.0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--qos", type=float, default=1.0)
    ap.add_argument("--devices", type=int, default=2)
    ap.add_argument("--comm", choices=("device", "host"), default="device")
    args = ap.parse_args()
    device = device_for_kind(jax.devices()[0].device_kind)
    enable_compile_cache()

    servers = [ModelStageServer(f"stage{i}", arch, seq_len=16, seed=i)
               for i, arch in enumerate(args.stages)]
    profiles = []
    for sv in servers:
        timings = sv.profile_stage_timings(batches=(1, 2, 4), repeats=2)
        profiles.append(profile_from_engine(
            sv.name, timings, weights_bytes=1e9, act_bytes_per_query=2e7,
            device=device, host_bytes_per_query=2e6))
    pipeline = Pipeline("serve", profiles, qos_target=args.qos)

    pred = PipelinePredictor.from_profiles(profiles, device)
    alloc = CamelotAllocator(pipeline, pred, device, args.devices,
                             sa=SAConfig(iterations=1200, seed=0))
    res = alloc.solve_max_load(args.batch)
    print(f"camelot allocation (predicted {res.objective:.0f} qps): "
          f"{[(s.n_instances, s.quota) for s in res.allocation.stages]}")

    eng = PipelineEngine(servers, comm_mechanism=args.comm,
                         qos_target=args.qos, batch_size=args.batch,
                         batch_timeout=0.05)
    trace = make_trace(args.queries, qps=args.qps, seq_len=16,
                       vocab=servers[0].cfg.vocab_size)
    stats = eng.run_trace(trace)
    s = stats.summary()
    print(f"served {s['completed']} queries: p99 {s['p99'] * 1e3:.1f} ms "
          f"(target {args.qos * 1e3:.0f} ms), comm share "
          f"{s['comm_frac'] * 100:.2f}% [{args.comm}]")


if __name__ == "__main__":
    main()

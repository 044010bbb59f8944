"""One traced run of one benchmark cell, read down to the served path's
own spans and counters.

    python3 bench/spans.py --workload img-to-img.poisson-100qps --seed 7 \
        --seconds 24

Runs the cell as ``run.py --trace 1`` does (``harness.run_cell``, the
same warm-up, window, traced sub-window and check) and keeps the window's
``ServeStats``.  Standard error gets the harness's log and then one line
splitting the mean latency by layer, one line per stage (step calls, mean
``camelot.step`` span, mean device time of the executions linked to it),
the linked share of the step executions, the longest idle gaps of the
chip by the spans that cover them, and the readings.  The last line of standard
output is one JSON object: ``run.py``'s result plus ``serve`` (the
window's counters), ``spans`` (``span_reduce.reduce_spans`` of the
trace), ``readings`` (``span_reduce.readings``) and ``decomposition``.
Without a TPU it exits 1, as ``run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

COUNTERS = ("batch_wait_s", "batched_queries", "ready_wait_s", "dispatches",
            "complete_lag_s", "folds", "handoff_s", "compute_time",
            "comm_time")


def traced_run(cell: str, seed: int, seconds: float, *, device_kind: str,
               root: Path = ROOT, reduced: bool = False,
               trace_dir: Optional[Path] = None, log=print) -> dict:
    """``run_cell(..., trace=True)`` with the window's counters and the
    reduction of its trace's spans added to the result."""
    from bench import harness
    from bench.span_reduce import decomposition, readings, reduce_trace

    windows = []

    class KeptService(harness.Service):
        """The harness's service, keeping what each replay served."""

        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            engine = self.engine
            replay = engine.run_trace

            def run_trace(queries):
                before = harness._edge_transfers(engine)
                stats = replay(queries)
                windows.append((stats, harness._edge_transfers(engine)
                                - before))
                return stats
            engine.run_trace = run_trace

    trace_dir = trace_dir or root / "bench_results" / f"spans-{cell}"
    harness.Service = KeptService
    try:
        result = harness.run_cell(cell, seed, seconds, True,
                                  t_start=T_START, device_kind=device_kind,
                                  root=root, reduced=reduced,
                                  trace_dir=trace_dir, log=log)
    finally:
        harness.Service = KeptService.__bases__[0]
    stats, transfers = windows[-1]                 # the warm-up came first
    serve = {k: getattr(stats, k) for k in COUNTERS}
    serve.update(transfers=transfers, batches=stats.batches,
                 mean=stats.qos.mean())
    files = sorted(trace_dir.glob("**/*.xplane.pb"))
    spans = reduce_trace(files[-1]) if files else None
    result.update(serve=serve, spans=spans, readings=readings(serve, spans),
                  decomposition=decomposition(serve))
    return result


def report(result: dict, log) -> None:
    """The latency decomposition, the per-stage lines and the link count."""
    d = result["decomposition"]
    if d is not None:
        log("[spans] mean latency {mean:.4f} ms = batch wait {batch_wait:.4f}"
            " + ready waits {ready_wait:.4f} + stage calls {worker:.4f} + "
            "completion lag {complete_lag:.4f} + hand-off {handoff:.4f} "
            "(sum {sum:.4f} ms)".format(**d))
    sp = result["spans"]
    if sp is None:
        log("[spans] no trace")
        return
    for name, st in sorted(sp["stages"].items()):
        dev = (f"{1e3 * st['device_s'] / st['linked']:.4f} ms"
               if st["linked"] else "not linked")
        log(f"[spans] stage {name}: {st['calls']} calls, step span "
            f"{1e3 * st['span_s'] / st['calls']:.4f} ms, device {dev} "
            f"({st['linked']} linked; programs {st['programs']})")
    log(f"[spans] {sp['linked']} of {sp['executions']} step executions "
        f"linked to a camelot.step span")
    log("[spans] longest idle gaps: " + ", ".join(
        f"{name} {1e3 * g:.3f} ms" for name, g in sp["gaps"]))
    log(f"[spans] readings: {json.dumps(result['readings'])}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    import jax
    from bench.harness import Cell, device_info

    cell = Cell(args.workload, ROOT)
    dev = device_info()
    if dev["platform"] != "tpu" or dev["count"] < int(cell.entry["chips"]):
        print(f"spans.py: cell {args.workload} needs {cell.entry['chips']} "
              f"TPU chip(s); JAX found {dev['count']} {dev['platform']!r} "
              "device(s)", file=sys.stderr)
        return 1
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    result = traced_run(args.workload, args.seed, args.seconds,
                        device_kind=dev["kind"], log=log)
    report(result, log)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The benchmark's body: one run of one cell, from the files that name it.

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs``), a
traffic mix (``bench/traffic/<traffic>.json``, whose ``arrival`` names a
generator in ``bench/arrivals``) and its own file ``bench/workloads/
<cell>.json`` with the rate it offers.  Each stage of a configuration
names its reference family, ``bench/reference/<family>.py``, which gives
the plain reference and the useful FLOPs of its block kinds.  Each metric
is a reader in ``bench/metrics/<name>.py``.  Adding a cell, a mix, a
metric or a model family adds files and edits none.

One run builds the service through the program's public entry (spec,
profile, ``solve("max-peak")``, ``serve``), replays a short warm-up of the
cell's own traffic, then offers the window's queries open loop and drains
until every query sent has completed or failed.  Latencies run from each
query's scheduled arrival.  After the window the run checks what the
served path produced against the plain reference in ``bench/reference``
(``bench/check.py``).

A metric reader's ``read(record)`` returns a number, or ``None`` where it
finds nothing to read.  The record holds: ``seconds`` (the window),
``setup_s``, ``batch``, ``arrivals`` and ``done`` (per query sent, seconds
from the window's opening, NaN where it never completed), ``completed``,
``failed`` and ``batches`` (``ServeStats``), ``comm_time_s`` and
``transfers`` (the hand-off's host time and ``EdgeChannel`` transfers in
the window), ``stages`` (each stage's ``seq_len``, reference family,
model sizes and ``flops``, its useful FLOPs a query, ``bench/flops.py``),
``peaks`` (``peaks.json`` for the chip), and in a traced run ``trace``
(``trace_reduce.reduce_trace``).  A metric named ``<base>.<cells>``, one
reading split by cells (a per-layer one by the end-to-end metric it moves
there, an end-to-end one by the cells that report it), has no reader of
its own and is read by ``<base>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import hashlib
import importlib.util
import json
import shutil
import threading
import time
from pathlib import Path
from typing import List, Optional

import numpy as np

from bench.flops import stage_flops

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# reference sample: queries compared per run, each giving one served token
# per stage
SAMPLE_QUERIES = 128


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file of the benchmark by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(bench: Path, name: str) -> Path:
    """``metrics/<name>.py``, or for ``<base>.<cells>`` without a reader
    of its own, ``metrics/<base>.py``."""
    own = bench / "metrics" / f"{name}.py"
    return own if own.exists() else bench / "metrics" / \
        f"{name.split('.')[0]}.py"


def derived_seed(seed: int, *parts) -> int:
    """A 31-bit seed for one use of the run's seed.  ``jax.random.PRNGKey``
    keeps only the low 32 bits of a larger integer, and a run's seed may
    pass 2**31, so every use hashes the whole seed."""
    h = hashlib.sha256(":".join(str(p) for p in (seed,) + parts).encode())
    return int.from_bytes(h.digest()[:4], "little") & 0x7FFFFFFF


def peaks_for(kind: str, bench: Path = BENCH) -> dict:
    """The published peaks of one chip, by JAX's ``device_kind``.  A kind
    missing from ``peaks.json`` is an error, never a default."""
    table = load_json(bench / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in peaks.json; "
                       f"known: {sorted(table)}")
    return table[kind]


class Cell:
    """Everything one cell names, read from its files."""

    def __init__(self, name: str, root: Path = ROOT):
        bench = root / "bench"
        bm = load_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in bm["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        self.name = name
        self.root = root
        self.bench = bench
        self.entry = cells[name]
        configs = {c["name"]: c for c in bm["configs"]}
        self.config = load_json(root / configs[self.entry["config"]]["file"])
        # each stage as a run's record holds it; counting its useful FLOPs
        # finds its reference family and raises where the family is missing
        # or does not serve the stage's block kinds
        self.stages = []
        for st in self.config["stages"]:
            flops = stage_flops(st, bench)
            self.stages.append({"name": st["name"], "seq_len": st["seq_len"],
                                "reference": st["reference"],
                                "model": st["model"], "flops": flops})
        self.traffic = load_json(bench / "traffic"
                                 / f"{self.entry['traffic']}.json")
        self.spec = load_json(bench / "workloads" / f"{name}.json")
        self.rate_qps = float(self.spec["rate_qps"])
        self.end_to_end = [m["name"] for m in bm["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m["name"] for m in bm["per_layer"]
                          if name in m.get("workloads", [name])]
        self.units = {m["name"]: m["unit"]
                      for m in bm["end_to_end"] + bm["per_layer"]}

    def arrivals(self, seconds: float, seed: int, use: str) -> np.ndarray:
        """Arrival offsets of one replay, drawn from the seed."""
        gen = load_module(self.bench / "arrivals"
                          / f"{self.traffic['arrival']}.py")
        rng = np.random.default_rng(derived_seed(seed, "arrivals", use))
        return gen.arrival_times(self.rate_qps, seconds, rng)


# --------------------------------------------------------------------------
# The service under test
# --------------------------------------------------------------------------

def _check_stage_config(stage, want: dict) -> None:
    """The configuration file holds the model as it is run: every size it
    states must be the program's.  A section (a dict, such as ``moe``) is
    compared key by key with the program's dataclass of that name."""
    _check_sizes(stage.name, stage.cfg, want, "")


def _check_sizes(stage_name: str, cfg, want: dict, prefix: str) -> None:
    for key, value in want.items():
        path = prefix + key
        if not hasattr(cfg, key):
            raise ValueError(f"stage {stage_name}: the program's config has "
                             f"no {path}")
        got = cfg.resolved_head_dim if path == "head_dim" else getattr(cfg,
                                                                       key)
        if isinstance(value, dict) and dataclasses.is_dataclass(got):
            _check_sizes(stage_name, got, value, path + ".")
            continue
        if isinstance(got, tuple):
            got = list(got)
        if got != value:
            raise ValueError(f"stage {stage_name}: {path} is {got!r} in the "
                             f"program, {value!r} in the configuration file")


class Recorder:
    """Keeps a host copy of each stage call's input and output, taken on
    the worker thread once the call has returned, so the check can read
    what the served path produced.  Copies, not the device arrays, so
    that a window does not keep thousands of small buffers alive on the
    chip while it is timed."""

    def __init__(self):
        self.calls: List[tuple] = []
        self.on = True

    def wrap(self, process):
        def recorded(tokens):
            out = process(tokens)
            if self.on:
                # list.append is atomic
                self.calls.append((np.asarray(tokens), np.asarray(out)))
            return out
        return recorded


class Service:
    """The configuration built through the program's public entry."""

    def __init__(self, config: dict, seed: int, device_kind: str, *,
                 reduced: bool = False):
        from repro.camelot import CamelotSession, ClusterSpec, device_for_kind
        from repro.serving import ModelStageServer
        from repro.sim.workloads import workload_specs

        device = device_for_kind(device_kind)
        spec = workload_specs(device=device)[config["service"]]
        # the allocation belongs to the deployment, not to the traffic: one
        # fixed profiling seed gives every run the same solve
        self.session = CamelotSession(
            spec, ClusterSpec(devices=1, device=device),
            batch=config["batch"], seed=0)
        self.session.profile()
        self.solve = self.session.solve(policy="max-peak")
        if not self.solve.feasible:
            raise RuntimeError(f"max-peak solve of {config['service']} on "
                               f"one {device.name} is infeasible")
        nodes = self.session.graph.nodes
        if [n.name for n in nodes] != [s["name"] for s in config["stages"]]:
            raise ValueError("configuration stages do not match the "
                             "service graph's nodes")
        self.stage_seeds = [derived_seed(seed, "weights", i)
                            for i in range(len(nodes))]
        self.stages, self.recorders = [], []
        for st, node, s in zip(config["stages"], nodes, self.stage_seeds):
            if node.arch != st["arch"]:
                raise ValueError(f"stage {st['name']}: arch {node.arch} in "
                                 f"the service, {st['arch']} in the file")
            stage = ModelStageServer(st["name"], st["arch"],
                                     seq_len=st["seq_len"], seed=s,
                                     reduced=reduced)
            _check_stage_config(stage, st["model"])
            rec = Recorder()
            stage.process = rec.wrap(stage.process)
            self.stages.append(stage)
            self.recorders.append(rec)
        self.engine = self.session.serve(stages=self.stages)

    def queries(self, times: np.ndarray, seed: int, use: str):
        """Queries arriving at ``times`` with prompts drawn from the seed."""
        from repro.serving import Query
        rng = np.random.default_rng(derived_seed(seed, "prompts", use))
        entry = self.stages[0]
        tokens = rng.integers(0, entry.cfg.vocab_size,
                              (len(times), entry.seq_len)).astype(np.int32)
        return [Query(qid=i, arrival=float(times[i]), tokens=tokens[i])
                for i in range(len(times))]

    def free(self) -> None:
        """Delete the program's device state (the parameters) so the
        reference runs in the memory they held."""
        import jax
        for stage in self.stages:
            for leaf in jax.tree.leaves(stage.params):
                leaf.delete()
            stage.params = None
        self.engine = None
        self.session = None
        gc.collect()


# --------------------------------------------------------------------------
# Compilations inside the window
# --------------------------------------------------------------------------

class CompileCounter:
    """Counts JAX's trace, lower and compile events while ``on``."""

    def __init__(self):
        import jax.monitoring as mon
        self.on = False
        self.count = 0
        self.names: List[str] = []

        def listener(event, duration, **kw):
            if self.on and event.startswith("/jax/core/compile"):
                self.count += 1
                self.names.append(event)
        self._listener = listener
        mon.register_event_duration_secs_listener(listener)

    def close(self) -> None:
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._listener)


class GcWatch:
    """Collections of the cyclic garbage collector while ``on``: a full
    collection stops every Python thread of the server while it runs."""

    def __init__(self):
        self.on = False
        self.pauses: List[tuple] = []          # (generation, seconds)
        self._t0 = 0.0

        def callback(phase, info):
            if not self.on:
                return
            if phase == "start":
                self._t0 = time.perf_counter()
            else:
                self.pauses.append((info["generation"],
                                    time.perf_counter() - self._t0))
        self._callback = callback
        gc.callbacks.append(callback)

    def close(self) -> None:
        gc.callbacks.remove(self._callback)

    def summary(self) -> str:
        full = [p for g, p in self.pauses if g == 2]
        return (f"{len(self.pauses)} collections, {len(full)} full; "
                f"longest {max((p for _, p in self.pauses), default=0) * 1e3:.3f} ms, "
                f"total {sum(p for _, p in self.pauses) * 1e3:.3f} ms")


def freeze_setup_objects() -> None:
    """Move every object set-up left alive out of the collector's reach
    (``gc.freeze``), as a Python server does once it has warmed up: a full
    collection in the window then scans only what the window allocates,
    instead of stopping every thread for tens of milliseconds over the
    imports, the model objects and the warm-up's leftovers."""
    gc.collect()
    gc.freeze()


# --------------------------------------------------------------------------
# Profiler sub-window
# --------------------------------------------------------------------------

def trace_bounds(seconds: float) -> tuple:
    """(start, length) of the traced part of the window: a few seconds in
    its middle, so the reading of the trace stays short."""
    length = min(4.0, 0.5 * seconds)
    return 0.25 * seconds, length


class TraceWindow:
    """Starts the profiler at an offset into the window and stops it after
    a fixed length, from a thread of its own."""

    def __init__(self, out_dir: Path, offset: float, length: float):
        import jax
        self.out_dir = out_dir
        shutil.rmtree(out_dir, ignore_errors=True)
        self.opts = jax.profiler.ProfileOptions()
        self.opts.python_tracer_level = 0
        self.opts.host_tracer_level = 2
        self.offset, self.length = offset, length
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self) -> None:
        self._t0 = time.perf_counter()
        self._thread.start()

    def _run(self) -> None:
        import jax
        try:
            time.sleep(max(0.0, self.offset
                           - (time.perf_counter() - self._t0)))
            jax.profiler.start_trace(str(self.out_dir),
                                     profiler_options=self.opts)
            time.sleep(self.length)
            jax.profiler.stop_trace()
        except BaseException as e:         # reported by join()
            self.error = e

    def join(self) -> Optional[Path]:
        self._thread.join()
        if self.error is not None:
            raise self.error
        files = glob.glob(str(self.out_dir / "**" / "*.xplane.pb"),
                          recursive=True)
        return Path(files[0]) if files else None


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------

def device_info():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def _edge_transfers(engine) -> int:
    return sum(c.transfers for c in engine.channels.values())


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, device_kind: str, root: Path = ROOT,
             reduced: bool = False, trace_dir: Optional[Path] = None,
             log=print) -> dict:
    """One run of one cell.  Returns the result object that ``run.py``
    prints.  ``device_kind`` prices the solve and names the peaks;
    ``reduced`` serves the models' laptop-scale twins (CPU tests only)."""
    from repro.launch.compile_cache import enable_compile_cache

    outer_log = log

    def log(msg: str) -> None:
        outer_log(f"{msg} [+{time.perf_counter() - t_start:.3f} s]")

    cell = Cell(cell_name, root)
    config = cell.config
    log(f"[bench] cell {cell_name}: config {config['name']}, traffic "
        f"{cell.entry['traffic']}, {cell.rate_qps} qps, {seconds} s window, "
        f"seed {seed}, trace {int(trace)}")
    log(f"[bench] compile cache: {enable_compile_cache()}")
    counter = CompileCounter()
    gc_watch = GcWatch()

    service = Service(config, seed, device_kind, reduced=reduced)
    alloc = service.solve.allocation
    log(f"[bench] solve: {[(s.n_instances, s.quota, s.batch) for s in alloc.stages]}")

    # warm-up: a short replay of the cell's own traffic, on a seed the
    # window does not use, compiles every shape the window drives
    warm_s = float(cell.traffic.get("warmup_s", 1.0))
    warm = service.queries(cell.arrivals(warm_s, seed, "warmup"), seed,
                           "warmup")
    wstats = service.engine.run_trace(warm)
    if wstats.failed or wstats.qos.count() != len(warm):
        raise RuntimeError(f"warm-up replay: {wstats.qos.count()} of "
                           f"{len(warm)} completed, {wstats.failed} failed; "
                           f"last error {wstats.last_error}")
    for rec in service.recorders:
        rec.calls.clear()
    queries = service.queries(cell.arrivals(seconds, seed, "window"), seed,
                              "window")
    transfers0 = _edge_transfers(service.engine)

    tracer = None
    if trace:
        offset, length = trace_bounds(seconds)
        tracer = TraceWindow(trace_dir or (root / "bench_results" / "trace"),
                             offset, length)
    freeze_setup_objects()
    counter.on = gc_watch.on = True
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    log(f"[bench] set-up {setup_s:.3f} s; window opens")
    if tracer is not None:
        tracer.start()
    stats = service.engine.run_trace(queries)
    t_drained = time.perf_counter()
    counter.on = gc_watch.on = False
    counter.close()
    gc_watch.close()
    trace_file = tracer.join() if tracer is not None else None
    log(f"[bench] compilations inside the window: {counter.count}"
        + (f" ({sorted(set(counter.names))})" if counter.count else ""))
    log(f"[bench] garbage collection inside the window: {gc_watch.summary()}")
    transfers1 = _edge_transfers(service.engine)
    mem_peak = memory_peak_bytes()

    record = {
        "seconds": float(seconds),
        "setup_s": setup_s,
        "drain_s": t_drained - t_window,
        "batch": int(config["batch"]),
        "arrivals": np.array([q.arrival for q in queries]),
        "done": np.array([np.nan if q.done is None else q.done
                          for q in queries]),
        "completed": int(stats.qos.count()),
        "failed": int(stats.failed),
        "batches": int(stats.batches),
        "comm_time_s": float(stats.comm_time),
        "transfers": int(transfers1 - transfers0),
        "stages": cell.stages,
        "peaks": peaks_for(device_kind, cell.bench),
        "trace": None,
    }
    attempted = len(queries)
    n_done = int(np.isfinite(record["done"]).sum())
    log(f"[bench] window: sent {attempted}, completed {stats.qos.count()} "
        f"({n_done} with a completion time), failed {stats.failed}, "
        f"batches {stats.batches}, drained {record['drain_s']:.3f} s after "
        f"the window opened; last error {stats.last_error}")

    lat = record["done"] - record["arrivals"]
    lat = np.where(np.isfinite(lat), lat, np.inf)
    log(f"[bench] latency over all {attempted} sent: p50 "
        f"{np.percentile(lat, 50) * 1e3:.4f} ms, p99 "
        f"{np.percentile(lat, 99) * 1e3:.4f} ms, max "
        f"{lat.max() * 1e3:.4f} ms (query arriving "
        f"{record['arrivals'][np.argmax(lat)]:.3f} s into the window)")
    finished = np.sort(record["done"][np.isfinite(record["done"])])
    if len(finished) > 1:
        pause = np.diff(finished)
        i = int(np.argmax(pause))
        log(f"[bench] longest pause between completions: "
            f"{pause[i] * 1e3:.3f} ms, ending {finished[i + 1]:.3f} s into "
            f"the window")

    if trace_file is not None:
        from bench.trace_reduce import reduce_trace
        t0 = time.perf_counter()
        record["trace"] = reduce_trace(trace_file)
        log(f"[bench] trace {trace_file.name}: {trace_file.stat().st_size} "
            f"B, reduced in {time.perf_counter() - t0:.3f} s")

    # the check reads what the window's calls produced, then frees the
    # program's state before the reference runs
    from bench import check
    calls = [rec.calls for rec in service.recorders]
    stage_seeds = service.stage_seeds
    service.free()
    del service
    t0 = time.perf_counter()
    verdict = check.check(config, queries, calls, stage_seeds,
                          completed=record["completed"],
                          failed=record["failed"], sample=SAMPLE_QUERIES,
                          rng=np.random.default_rng(
                              derived_seed(seed, "sample")),
                          bench=cell.bench)
    log(f"[bench] check took {time.perf_counter() - t0:.3f} s")

    metrics = {}
    names = cell.per_layer if trace else cell.end_to_end
    for name in names:
        reader = load_module(metric_reader(cell.bench, name))
        value = reader.read(record)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": cell.units[name]}
    device = {**device_info(), "memory_peak_bytes": mem_peak}
    result = {"correct": verdict["correct"], "attempted": attempted,
              "failed": int(stats.failed), "metrics": metrics,
              "device": device}
    if trace and record["trace"] is not None:
        tr = record["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["top_ops"],
                               "idle_gaps": tr["gaps"]}
    result["compared"] = verdict["compared"]
    return result

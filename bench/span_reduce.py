"""Reduce the served path's own spans in a profiler trace (``.xplane.pb``)
to the numbers of its layers.

The served path (``repro/serving/engine.py``) writes ``camelot.*`` spans
through ``jax.profiler.TraceAnnotation``, on the host planes of the same
trace ``trace_reduce`` reads, each with ``tenant``, ``stage`` and ``bid``
where it has them:

* ``camelot.worker``: one stage call on a worker thread, with the post of
  its completion; ``camelot.step`` inside it, from the dispatch of the
  stage's jitted ``run`` to its result being ready;
* the driver's phases that did work: ``camelot.admit``, ``camelot.form``,
  ``camelot.dispatch``, ``camelot.complete`` and, inside it,
  ``camelot.handoff``;
* the driver's waits for a completion, named by what set their timeout:
  ``camelot.wait_arrival``, ``camelot.wait_batch``, ``camelot.wait_retry``,
  ``camelot.wait_completion``.

The trace's own stats tie each execution of a step program on the chip to
the ``camelot.step`` span that enqueued it, through flows (type and id:
``_pt`` and ``_p`` on the producing event, ``_ct`` and ``_c`` on the
consuming one): the ``PJRT_Loaded
Executable_Execute linkage`` event inside the span leads to the
``PJRT_LoadedExecutable_Execute`` event on the runtime's thread; the
``tpu::System::Execute`` event inside that leads to the ``tpu::System::
Execute=>IssueSequencedEvent`` event that holds the ``DoEnqueueProgram``
event, on the same thread or, when the program's inputs are still on
their way to the chip, on the thread that enqueues it once they arrive;
that event's ``run_id`` is the ``run_id`` of the program's execution on
the ``XLA Modules`` line of the chip.  The link also names each step
program by the stage it serves.

``reduce_spans`` works on plain tuples, as ``trace_reduce.reduce_planes``
does; ``readings`` turns its result and the engine's ``ServeStats``
counters into the served path's layer numbers.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench.trace_reduce import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,
                                STEP_MODULE, TOP, merge)

SPAN = "camelot."
STEP = "camelot.step"
WORKER = "camelot.worker"
WAIT = "camelot.wait_"
# the flows from a step span to the enqueue of its program: the first
# producer is found inside the span, the second inside the first's
# consumer, and the enqueue inside the second's consumer
PRODUCERS = ("PJRT_LoadedExecutable_Execute linkage", "tpu::System::Execute")
CONSUMERS = ("PJRT_LoadedExecutable_Execute",
             "tpu::System::Execute=>IssueSequencedEvent")
ENQUEUE = "DoEnqueueProgram"
WITH_STATS = PRODUCERS + CONSUMERS + (ENQUEUE,)

Interval = Tuple[float, float]


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def reduce_spans(planes) -> dict:
    """The reduction over already-read planes: each plane a ``(name,
    [(line name, [(name, start_ns, duration_ns, stats), ...]), ...])``
    pair, ``stats`` a dict (empty where not read).  Lines stay a list:
    host threads may share a name.

    Returns ``window_s`` (first to last event, host or device, as
    ``trace_reduce`` reckons it), ``idle_s`` (no operation on the chip)
    and ``idle_host_s`` (of that, time in which the host did served-path
    work: a ``camelot.worker`` span open, or a driver span other than a
    wait), each averaged over the chips; ``executions`` of the step
    programs and how many are ``linked`` to a ``camelot.step`` span;
    ``step_wait_s``, the linked spans' time less their executions' device
    time; ``stages``, per stage the step spans' ``calls`` and seconds and
    the linked executions' device seconds and programs; ``worker_self_s``
    and ``workers``, the time of ``camelot.worker`` spans outside their
    ``camelot.step``; ``waits``, seconds per wait span name; ``gaps``, the
    ten longest stretches with no operation on a chip, each labelled by
    the span name whose spans cover most of it; and ``clock_offset_s``,
    by how much the chip's clock reads ahead of the host's.

    The chip's events do not share the host's clock exactly: an execution
    can start on the chip no earlier than its enqueue on the host, and end
    no later than its ``camelot.step`` span.  Over the linked executions
    these bound the offset from above and below; the chip's operations
    are moved by the middle of the two bounds before they are set against
    the host's spans (``idle_host_s``, ``gaps``), and by none where
    nothing is linked.  ``clock_offset_bounds_s`` gives the bounds."""
    t_lo, t_hi = float("inf"), float("-inf")
    devices = []
    steps: List[tuple] = []            # (start, end, stage, line id)
    # per line, (start, flow) of each producer kind and (start, run_id) of
    # each enqueue; per flow, (start, end, line) of its consumer.  A flow
    # is its type and id (``_pt``/``_ct``, ``_p``/``_c``): ids of
    # different types collide.
    produced = [defaultdict(list) for _ in PRODUCERS]
    consumed: Dict[tuple, tuple] = {}
    enqueues: Dict[int, List[tuple]] = defaultdict(list)
    host_work: List[Interval] = []
    named: List[tuple] = []            # (start, end, name) of every span
    workers: Dict[int, List[Interval]] = defaultdict(list)   # by line
    waits: Dict[str, float] = defaultdict(float)
    line_id = 0
    for pname, lines in planes:
        is_dev = bool(DEVICE_PLANE.match(pname))
        if not (is_dev or pname.startswith("/host:")):
            continue
        for _, events in lines:
            line_id += 1
            for n, s, d, st in events:
                t_lo, t_hi = min(t_lo, s), max(t_hi, s + d)
                if is_dev:
                    continue
                if n in PRODUCERS and "_p" in st:
                    produced[PRODUCERS.index(n)][line_id].append(
                        (s, (st.get("_pt"), st["_p"])))
                elif n in CONSUMERS and "_c" in st:
                    consumed[(st.get("_ct"), st["_c"])] = (s, s + d,
                                                           line_id)
                elif n == ENQUEUE and "run_id" in st:
                    enqueues[line_id].append((s, st["run_id"]))
                elif n.startswith(SPAN):
                    named.append((s, s + d, n))
                    if n == STEP:
                        steps.append((s, s + d, str(st.get("stage", "?")),
                                      line_id))
                    elif n.startswith(WAIT):
                        waits[n] += d * 1e-9
                    else:
                        host_work.append((s, s + d))
                        if n == WORKER:
                            workers[line_id].append((s, s + d))
        if is_dev:
            devices.append(dict(lines))

    # run_id -> (device start, duration, program) of each step execution
    runs: Dict[int, tuple] = {}
    busy: List[List[Interval]] = []
    n_exec = 0
    for lines in devices:
        for n, s, d, st in lines.get(MODULES_LINE, []):
            if STEP_MODULE.match(n):
                n_exec += 1
                if "run_id" in st:
                    runs[st["run_id"]] = (s, d, n)
        busy.append(merge([(s, s + d) for _, s, d, _ in
                           lines.get(OPS_LINE, [])]))
    for by_line in produced + [enqueues]:
        for evs in by_line.values():
            evs.sort()

    stages: Dict[str, dict] = {}
    step_wait, linked = 0.0, 0
    upper, lower = float("inf"), float("-inf")       # clock offset bounds
    step_ivals: Dict[int, List[Interval]] = defaultdict(list)  # by line
    for s, e, stage, line in steps:
        step_ivals[line].append((s, e))
        agg = stages.setdefault(stage, {"calls": 0, "span_s": 0.0,
                                        "linked": 0, "device_s": 0.0,
                                        "programs": set()})
        agg["calls"] += 1
        agg["span_s"] += (e - s) * 1e-9
        hit = _linked_run((s, e, line), produced, consumed, enqueues, runs)
        if hit is None:
            continue
        (ds, dd, prog), enq = hit
        linked += 1
        agg["linked"] += 1
        agg["device_s"] += dd * 1e-9
        agg["programs"].add(prog)
        step_wait += (e - s - dd) * 1e-9
        upper = min(upper, ds - enq)
        lower = max(lower, ds + dd - e)

    window = (t_hi - t_lo) if t_hi > t_lo else 0.0
    offset = (upper + lower) / 2 if linked else 0.0
    host = merge(host_work)
    idle = idle_host = 0.0
    gaps = []
    for merged in busy:
        merged = [(s - offset, e - offset) for s, e in merged]
        free = _complement(merged, t_lo, t_hi)
        idle += sum(e - s for s, e in free)
        idle_host += overlap(free, host)
        gaps += [(s1 - e0, e0, s1) for (_, e0), (s1, _) in zip(merged,
                                                               merged[1:])]
    gaps.sort(reverse=True)
    n_dev = max(len(busy), 1)
    # a worker's step runs on its own thread, inside its span
    worker_self = sum(sum(e - s for s, e in ws)
                      - overlap(sorted(ws), merge(step_ivals[line]))
                      for line, ws in workers.items())
    return {
        "window_s": window * 1e-9,
        "idle_s": idle * 1e-9 / n_dev,
        "idle_host_s": idle_host * 1e-9 / n_dev,
        "executions": n_exec,
        "linked": linked,
        "step_wait_s": step_wait,
        "stages": {k: {**v, "programs": sorted(v["programs"])}
                   for k, v in stages.items()},
        "workers": sum(len(ws) for ws in workers.values()),
        "worker_self_s": worker_self * 1e-9,
        "waits": dict(waits),
        "gaps": [[_span_label(named, s, e), g * 1e-9]
                 for g, s, e in gaps[:TOP]],
        "clock_offset_s": offset * 1e-9,
        "clock_offset_bounds_s": ([lower * 1e-9, upper * 1e-9] if linked
                                  else None),
    }


def _inside(evs: List[tuple], s: float, e: float):
    """The (start, value) pairs of a sorted list that start in [s, e)."""
    i = bisect.bisect_left(evs, (s,))
    while i < len(evs) and evs[i][0] < e:
        yield evs[i]
        i += 1


def _linked_run(span, produced, consumed, enqueues, runs):
    """The step execution enqueued from inside ``span`` (start, end,
    line), with the start of its enqueue, or None: follow each producer
    kind in turn to its consumer, then the enqueue inside the last."""
    frontier = [span]
    for by_line in produced:
        frontier = [consumed[flow] for s, e, line in frontier
                    for _, flow in _inside(by_line.get(line, []), s, e)
                    if flow in consumed]
    for s, e, line in frontier:
        for start, run_id in _inside(enqueues.get(line, []), s, e):
            if run_id in runs:
                return runs[run_id], start
    return None


def _span_label(named: List[tuple], s: float, e: float) -> str:
    """The span name whose spans cover most of [s, e)."""
    cover: Dict[str, float] = defaultdict(float)
    for hs, he, n in named:
        if hs < e and he > s:
            cover[n] += min(he, e) - max(hs, s)
    return max(cover, key=cover.get) if cover else "no camelot span"


def _complement(merged: List[Interval], lo: float,
                hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def read_planes(path: Path):
    """Planes of a trace file as plain tuples (see ``reduce_spans``): the
    chips' and the host's, with the stats of the events the reduction
    reads them from."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        if not (is_dev or plane.name.startswith("/host:")):
            continue
        lines = []
        for line in plane.lines:
            modules = is_dev and line.name == MODULES_LINE
            lines.append((line.name, [
                (e.name, e.start_ns, e.duration_ns,
                 dict(e.stats) if modules or e.name.startswith(SPAN)
                 or e.name in WITH_STATS else {})
                for e in line.events]))
        out.append((plane.name, lines))
    return out


def reduce_trace(path: Path) -> dict:
    return reduce_spans(read_planes(path))


def readings(serve: dict, spans: Optional[dict]) -> Dict[str, Optional[float]]:
    """The served path's layer numbers from one window's ``ServeStats``
    counters (``serve``: the counters, ``transfers`` of the edge channels,
    ``batches``) and, in a traced run, ``reduce_spans`` of its trace; None
    where there is nothing to read."""
    def per(total, count, scale):
        return scale * serve[total] / serve[count] if serve.get(count) \
            else None

    out = {
        "batch_wait_ms": per("batch_wait_s", "batched_queries", 1e3),
        "ready_wait_ms": per("ready_wait_s", "dispatches", 1e3),
        "complete_lag_ms": per("complete_lag_s", "folds", 1e3),
        "handoff_total_us": per("handoff_s", "transfers", 1e6),
        "step_wait_ms": None,
        "idle_host_pct": None,
    }
    if spans and spans["linked"]:
        out["step_wait_ms"] = 1e3 * spans["step_wait_s"] / spans["linked"]
    if spans and spans["window_s"] and spans["executions"]:
        out["idle_host_pct"] = 100.0 * spans["idle_host_s"] / \
            spans["window_s"]
    return out


def decomposition(serve: dict) -> Optional[Dict[str, float]]:
    """A query's mean latency split by layer, in ms: its batch's wait to
    form, then per batch (each passes every stage of the chain once) the
    ready waits, the stage calls, the completions' lag and the hand-offs.
    ``sum`` is their total and ``mean`` the measured mean latency."""
    if not serve.get("batched_queries") or not serve.get("batches"):
        return None
    b = serve["batches"]
    parts = {
        "batch_wait": serve["batch_wait_s"] / serve["batched_queries"],
        "ready_wait": serve["ready_wait_s"] / b,
        "worker": serve["compute_time"] / b,
        "complete_lag": serve["complete_lag_s"] / b,
        "handoff": serve["handoff_s"] / b,
    }
    out = {k: 1e3 * v for k, v in parts.items()}
    out["sum"] = sum(out.values())
    out["mean"] = 1e3 * serve["mean"]
    return out

"""The reduction from the served path's spans to its layer numbers."""
import json
from pathlib import Path

import pytest

from bench.span_reduce import (decomposition, overlap, readings,
                               reduce_spans, reduce_trace)
from bench.tests.tiny import make_root

RECORDED = Path(__file__).parent / "data" / "spans.xplane.pb"
MS = 1_000_000


def _planes(host_lines, modules, ops):
    return [("/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", ops)]),
            ("/host:CPU", host_lines),
            ("#Chip0 Misc", [("x", [("camelot.worker", 0, 99 * MS, {})])])]


def test_overlap_of_interval_lists():
    assert overlap([(0, 4), (6, 10)], [(3, 7), (9, 20)]) == 1 + 1 + 1
    assert overlap([(0, 1)], []) == 0


def test_step_span_linked_to_the_execution_it_enqueued():
    """Two worker threads share a name.  Each step span holds a linkage
    event that leads (flow type 14) to an execute event on the runtime's
    thread; the chip-side execute inside it leads (flow type 7) to the
    event that issues the program, whose enqueue carries the ``run_id`` of
    the execution on the chip.  The first program is issued on the same
    thread, the second, its inputs still in flight, on another; flow ids
    of the two types collide.  A third execution has no span."""
    host = [
        ("python3", [("camelot.step", 0, 5 * MS, {"stage": "face"}),
                     ("PJRT_LoadedExecutable_Execute linkage", MS // 10, 10,
                      {"_pt": 14, "_p": 41})]),
        ("python3", [("camelot.step", 6 * MS, 4 * MS, {"stage": "enhance"}),
                     ("PJRT_LoadedExecutable_Execute linkage", 6 * MS + 10,
                      10, {"_pt": 14, "_p": 42})]),
        ("", [("PJRT_LoadedExecutable_Execute", MS // 5, MS // 2,
               {"_ct": 14, "_c": 41}),
              ("tpu::System::Execute", MS // 5 + 10, 100,
               {"_pt": 7, "_p": 42}),
              ("tpu::System::Execute=>IssueSequencedEvent", MS // 5 + 20,
               50, {"_ct": 7, "_c": 42}),
              ("DoEnqueueProgram", MS // 5 + 30, 10, {"run_id": 7}),
              ("PJRT_LoadedExecutable_Execute", 6 * MS + 20, 100,
               {"_ct": 14, "_c": 42}),
              ("tpu::System::Execute", 6 * MS + 30, 50,
               {"_pt": 7, "_p": 41})]),
        ("pjrt-tpu-tasks", [("tpu::System::Execute=>IssueSequencedEvent",
                             6 * MS + 500, 50, {"_ct": 7, "_c": 41}),
                            ("DoEnqueueProgram", 6 * MS + 510, 10,
                             {"run_id": 8})]),
    ]
    modules = [("jit_run(1)", MS, 3 * MS, {"run_id": 7}),
               ("jit_run(2)", 7 * MS, 2 * MS, {"run_id": 8}),
               ("jit_run(1)", 11 * MS, 1 * MS, {"run_id": 9}),
               ("jit_other(3)", 12 * MS, MS, {"run_id": 10})]
    out = reduce_spans(_planes(host, modules, []))
    assert out["executions"] == 3
    assert out["linked"] == 2
    # (5 - 3) + (4 - 2) ms
    assert out["step_wait_s"] == pytest.approx(4e-3)
    assert out["stages"]["face"] == {
        "calls": 1, "span_s": pytest.approx(5e-3), "linked": 1,
        "device_s": pytest.approx(3e-3), "programs": ["jit_run(1)"]}
    assert out["stages"]["enhance"]["programs"] == ["jit_run(2)"]
    # the chip's clock: no more than 1 - 0.20003 ms ahead (face starts
    # after its enqueue), no more than 1 ms behind (each ends 1 ms before
    # its span)
    assert out["clock_offset_bounds_s"] == [pytest.approx(-1e-3),
                                            pytest.approx(0.79997e-3)]
    assert out["clock_offset_s"] == pytest.approx(-0.100015e-3)


def test_idle_split_by_what_the_host_did():
    """Ops run 0-4 and 10-12 ms; the window ends at 13 ms.  A worker span
    is open 3-6 ms, its step 3-4 ms; the driver waits 6-10 ms and
    completes 11-13 ms: of the 7 idle ms, 4-6 and 12-13 are the host's.
    Another thread's step (4-6 ms) leaves the worker's self time alone."""
    host = [("python3", [("camelot.worker", 3 * MS, 3 * MS,
                          {"tenant": 0, "stage": "a", "bid": 0,
                           "attempt": 0}),
                         ("camelot.step", 3 * MS, MS, {"stage": "a"})]),
            ("python3", [("camelot.step", 4 * MS, 2 * MS, {"stage": "a"})]),
            ("python3", [("camelot.wait_batch", 6 * MS, 4 * MS, {}),
                         ("camelot.complete", 11 * MS, 2 * MS,
                          {"tenant": 0, "stage": "a", "bid": 0})])]
    ops = [("%fusion.1 = f32[8] fusion()", 0, 4 * MS, {}),
           ("%fusion.1 = f32[8] fusion()", 10 * MS, 2 * MS, {})]
    modules = [("jit_run(1)", 0, 4 * MS, {"run_id": 1})]
    out = reduce_spans(_planes(host, modules, ops))
    assert out["window_s"] == pytest.approx(13e-3)
    assert out["idle_s"] == pytest.approx(7e-3)
    assert out["idle_host_s"] == pytest.approx(3e-3)
    assert out["waits"] == {"camelot.wait_batch": pytest.approx(4e-3)}
    # the one gap between ops, 4-10 ms: the wait covers 4 ms of it, the
    # worker 2
    assert out["gaps"] == [["camelot.wait_batch", pytest.approx(6e-3)]]
    assert out["workers"] == 1
    assert out["worker_self_s"] == pytest.approx(2e-3)
    r = readings({"batched_queries": 0, "dispatches": 0, "folds": 0,
                  "transfers": 0}, out)
    assert r["idle_host_pct"] == pytest.approx(100 * 3 / 13)
    assert r["step_wait_ms"] is None
    assert r["batch_wait_ms"] is None


def test_readings_and_decomposition_from_counters():
    serve = {"batch_wait_s": 3.0, "batched_queries": 100,
             "ready_wait_s": 0.5, "dispatches": 50, "complete_lag_s": 0.25,
             "folds": 50, "handoff_s": 0.002, "transfers": 25,
             "compute_time": 0.25, "batches": 25, "mean": 0.0501}
    r = readings(serve, None)
    assert r == {"batch_wait_ms": pytest.approx(30.0),
                 "ready_wait_ms": pytest.approx(10.0),
                 "complete_lag_ms": pytest.approx(5.0),
                 "handoff_total_us": pytest.approx(80.0),
                 "step_wait_ms": None, "idle_host_pct": None}
    d = decomposition(serve)
    assert d["sum"] == pytest.approx(30 + 20 + 10 + 10 + 0.08)
    assert d["mean"] == pytest.approx(50.1)


def test_recorded_tpu_trace_with_spans():
    """A trace recorded on one v5e (jax 0.9.0): a reduced-width two-stage
    engine (``face``: qwen3-0.6b, 16 tokens; ``enhance``: qwen1.5-0.5b, 8
    tokens; two instances a stage, batches of 4, 10 ms batch timeout)
    serving 8 queries at 200 qps.  The programs' HLO, which no reduction
    reads, is cut from the ``/host:metadata`` plane.  The engine warms
    each stage up outside a step span, so two executions stay unlinked."""
    out = reduce_trace(RECORDED)
    assert out["executions"] == 8
    assert out["linked"] == 6
    assert set(out["stages"]) == {"face", "enhance"}
    programs = [p for st in out["stages"].values() for p in st["programs"]]
    assert len(programs) == len(set(programs)) == 2
    for st in out["stages"].values():
        assert 0 < st["device_s"] < st["span_s"]
    assert out["step_wait_s"] > 0
    assert 0 < out["idle_host_s"] <= out["idle_s"] < out["window_s"]
    lower, upper = out["clock_offset_bounds_s"]
    assert lower <= out["clock_offset_s"] <= upper


def test_traced_cpu_run_prints_the_six_readings(tmp_path):
    """One traced run of a laptop-width cell through ``spans.py``: the
    counters read on the CPU; the device readings need the chip."""
    from bench.spans import report, traced_run
    root, cell = make_root(tmp_path)
    res = traced_run(cell, 2**33 + 7, 2.0, device_kind="TPU v5 lite",
                     root=root, reduced=True, trace_dir=tmp_path / "trace",
                     log=lambda msg: None)
    assert res["correct"], res["compared"]
    lines = []
    report(res, lines.append)
    (printed,) = [ln for ln in lines if ln.startswith("[spans] readings: ")]
    got = json.loads(printed.split(": ", 1)[1])
    assert set(got) == {"batch_wait_ms", "ready_wait_ms", "complete_lag_ms",
                        "handoff_total_us", "step_wait_ms", "idle_host_pct"}
    for name in ("batch_wait_ms", "ready_wait_ms", "complete_lag_ms",
                 "handoff_total_us"):
        assert got[name] is not None and got[name] >= 0, name
    assert got["step_wait_ms"] is None and got["idle_host_pct"] is None
    assert res["serve"]["batched_queries"] == res["attempted"]
    assert res["spans"]["workers"] > 0
    assert any(ln.startswith("[spans] mean latency") for ln in lines)

"""The served path's own spans and counters (``repro.serving.engine``):
the ``ServeStats`` waits on scripted traces, the per-query parts adding up
to the mean latency, the ``camelot.*`` spans in a profiler trace, and both
backends filling the counters."""
import glob
import types
import time

import numpy as np
import pytest

from repro.core.exec import default_allocation
from repro.core.types import Allocation, Placement, StageAlloc
from repro.serving import ModelStageServer, PipelineEngine, Query, make_trace
from repro.serving.workers import CpuStageServer


class SleepStage:
    """A stage whose call takes a known time and releases the GIL."""

    def __init__(self, name, service_time, vocab=16, seq_len=8):
        self.name = name
        self.service_time = service_time
        self.seq_len = seq_len
        self.cfg = types.SimpleNamespace(vocab_size=vocab)

    def warmup(self, batch):
        pass

    def process(self, tokens):
        time.sleep(self.service_time)
        return np.zeros((tokens.shape[0],), np.int32)


class FailOnce(SleepStage):
    """Raises on its first call, then sleeps like ``SleepStage``."""

    failed = False

    def process(self, tokens):
        if not self.failed:
            self.failed = True
            raise RuntimeError("injected stage fault")
        return super().process(tokens)


def _queries(arrivals):
    return [Query(qid=i, arrival=a, tokens=np.zeros(8, np.int32))
            for i, a in enumerate(arrivals)]


def _served(delays, batch, timeout, arrivals):
    """Serve ``arrivals`` on a chain of ``SleepStage``s, one instance
    each, after a first replay of the same trace has compiled the
    hand-off's glue."""
    stages = [SleepStage(f"s{i}", d) for i, d in enumerate(delays)]
    eng = PipelineEngine(stages, batch_size=batch, batch_timeout=timeout,
                         qos_target=10.0,
                         allocation=default_allocation(len(stages), batch))
    eng.run_trace(_queries(arrivals))
    return eng.run_trace(_queries(arrivals))


def test_counters_on_a_scripted_trace():
    """One instance per stage, batches of 2, a 10 ms batch timeout, stage
    calls of 50 and 20 ms.  Queries 0 and 1 arrive together and fill
    batch A at once; query 2 forms batch B alone at its 10 ms deadline and
    waits for stage 0 until A leaves it at 50 ms.  B then reaches stage 1
    after A has left it."""
    stats = _served([0.05, 0.02], batch=2, timeout=0.01,
                    arrivals=[0.0, 0.0, 0.0])
    assert stats.qos.count() == 3 and stats.failed == 0
    assert stats.batched_queries == 3
    assert stats.dispatches == stats.folds == 4
    # batch A formed on arrival, batch B at its deadline: 0 + 0 + 10 ms
    assert 0.010 <= stats.batch_wait_s <= 0.010 + 0.015
    # B waited at stage 0 from its formation (10 ms) to A's end (50 ms)
    assert 0.040 - 0.005 <= stats.ready_wait_s <= 0.040 + 0.015
    assert stats.compute_time >= 2 * 0.05 + 2 * 0.02
    assert 0.0 < stats.complete_lag_s < 0.02
    assert 0.0 < stats.handoff_s < 0.02
    s = stats.summary()
    for key in ("batch_wait_s", "batched_queries", "ready_wait_s",
                "dispatches", "complete_lag_s", "folds", "handoff_s"):
        assert s[key] == getattr(stats, key)


def test_parts_add_up_to_the_mean_latency():
    """Full batches, so each query's latency is its batch's path: the
    wait to form, then per stage the ready wait, the call, the lag of its
    completion and the hand-off to the next stage."""
    stats = _served([0.06, 0.03], batch=2, timeout=0.05,
                    arrivals=[0.0] * 4 + [0.2] * 4)
    assert stats.qos.count() == 8 and stats.batches == 4
    per_query = stats.batch_wait_s / stats.batched_queries
    per_batch = (stats.ready_wait_s + stats.compute_time
                 + stats.complete_lag_s + stats.handoff_s) / stats.batches
    assert stats.ready_wait_s > 0.05        # the second batch of each burst
    assert per_query + per_batch == pytest.approx(stats.qos.mean(),
                                                  rel=0.05)


def _spans(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("camelot."):
                    out.setdefault(e.name, []).append(dict(e.stats))
    return out


def test_profiled_run_writes_every_span_with_its_arguments(tmp_path):
    """A reduced-width two-stage model engine, and a stub stage that fails
    once and retries after a backoff, both served under the profiler."""
    import jax
    models = [ModelStageServer("face", "qwen3-0.6b", seq_len=16, seed=1),
              ModelStageServer("enhance", "qwen1.5-0.5b", seq_len=8,
                               seed=2)]
    eng = PipelineEngine(models, batch_size=4, batch_timeout=0.01,
                         qos_target=10.0)
    trace = make_trace(6, qps=200.0, seq_len=16,
                       vocab=models[0].cfg.vocab_size, seed=3)
    flaky = PipelineEngine([FailOnce("flaky", 0.005)], batch_size=1,
                           batch_timeout=0.01, qos_target=10.0,
                           max_retries=1, retry_backoff=0.02)
    eng.run_trace(make_trace(4, qps=200.0, seq_len=16,
                             vocab=models[0].cfg.vocab_size, seed=4))
    jax.profiler.start_trace(str(tmp_path))
    try:
        stats = eng.run_trace(trace)
        retried = flaky.run_trace(_queries([0.0]))
    finally:
        jax.profiler.stop_trace()
    assert stats.qos.count() == 6 and retried.retries == 1
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = _spans(path)
    batch_args = {"tenant", "stage", "bid"}
    expect = {"camelot.worker": batch_args | {"attempt"},
              "camelot.step": {"stage"},
              "camelot.admit": {"tenant"},
              "camelot.form": batch_args,
              "camelot.dispatch": batch_args,
              "camelot.complete": batch_args,
              "camelot.handoff": batch_args,
              "camelot.wait_arrival": set(),
              "camelot.wait_batch": set(),
              "camelot.wait_retry": set(),
              "camelot.wait_completion": set()}
    assert set(spans) == set(expect)
    for name, args in expect.items():
        assert all(set(a) == args for a in spans[name]), (name, spans[name])
    # the model stages' step spans name their stage; the retried call is
    # the worker span with attempt 1
    assert {a["stage"] for a in spans["camelot.step"]} == {"face", "enhance"}
    assert {a["stage"] for a in spans["camelot.handoff"]} >= {"face"}
    assert any(a["attempt"] == 1 and a["stage"] == "flaky"
               for a in spans["camelot.worker"])


def _spread(n_stages, batch):
    return Allocation(
        stages=[StageAlloc(n_instances=1, quota=1.0, batch=batch)
                for _ in range(n_stages)],
        placement=Placement(per_stage=[[(i, 1.0)]
                                       for i in range(n_stages)]))


@pytest.mark.parametrize("backend", ["threads", "processes"])
def test_both_backends_fill_the_counters(backend):
    stages = [CpuStageServer(f"s{i}", seq_len=8, vocab=64, spin=80)
              for i in range(3)]
    trace = make_trace(12, qps=400.0, seq_len=8, vocab=64, seed=5)
    with PipelineEngine(stages, batch_size=4, batch_timeout=0.01,
                        qos_target=30.0, backend=backend,
                        allocation=_spread(3, 4)) as eng:
        stats = eng.run_trace(trace)
    assert stats.qos.count() == 12 and stats.failed == 0
    assert stats.batched_queries == 12
    assert stats.dispatches == stats.folds == 3 * stats.batches
    assert stats.batch_wait_s > 0
    assert stats.ready_wait_s >= 0
    assert stats.complete_lag_s > 0
    assert stats.handoff_s > 0

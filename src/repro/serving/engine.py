"""Live mini serving engine: runs REAL JAX models as microservice graphs.

This is the reduced-scale twin of the simulator, and since the
unified-execution refactor it is built on the SAME scheduling core
(``repro.core.exec.ExecCore``) the simulator uses: the engine consumes an
``Allocation`` + ``Placement`` from the allocator and runs N_i concurrent
instances per node — a thread pool around the jitted calls, which works
because ``block_until_ready`` releases the GIL — with QoS-aware dynamic
batching and per-edge communication-mechanism selection
(``CommModel.crossover_bytes``, paper Fig. 11): ``DeviceHandoff`` passes the
stage-output ``jax.Array`` by reference (global-memory mechanism, §VI-B);
``HostStagedChannel`` forces the device→host→device round trip (§VI-A).

Topology is a ``ServiceGraph`` (``graph=`` argument; default: the linear
chain over the given stage servers).  Fan-out sends one payload per
out-edge through that edge's channel; fan-in waits on the core's join
barrier and feeds the consumer a deterministic, branch-order-independent
combination of the predecessor outputs; with several exit nodes a query
completes only when every exit has produced it.

Two execution backends share this driver (``backend=`` knob):

  * ``"threads"`` (default, the pre-process-plane behaviour, bit-pinned):
    stage instances dispatch onto one shared ``ThreadPoolExecutor`` —
    fine for jitted calls that release the GIL;
  * ``"processes"``: stage instances run in a persistent worker-process
    pool (``repro.serving.workers``, one worker pinned per placed
    device, spawned once and reused across traces), and inter-stage
    payloads travel over ``repro.serving.transport`` — shared-memory
    hand-off above the ``CommModel`` crossover (the paper's
    global-memory mechanism, written once and mapped zero-copy), pickle
    queue below it (host-staged).  The scheduling state machine stays
    here in the driver; only ``process()`` execution and payload
    transport cross the process boundary, and a crashed worker process
    is detected, restarted, and its in-flight batches replayed within
    the retry budget (``WorkerSupervisor``).

Both drivers write ``camelot.*`` spans into the JAX profiler's trace
(``jax.profiler.TraceAnnotation``; next to free while no trace runs):
one per phase of the driver loop that did work (``admit``, ``form``,
``dispatch``, ``complete``, ``handoff``), one per wait for a completion
named by what set its timeout (``wait_arrival``, ``wait_batch``,
``wait_retry``, ``wait_completion``), one per worker task (``worker``)
and, inside ``ModelStageServer.process``, one per step (``step``).  Each
carries the tenant, the node's name and the batch id where it has them.
``ServeStats`` sums the waits those spans show: batch formation, ready
queues, completion pick-up and the hand-off.

Retry backoff is driver-scheduled on BOTH backends: a failing batch is
requeued with a timed wake (``retry_backoff × 2^attempt``) instead of
sleeping inside a worker slot, so a backing-off batch never idles an
otherwise-free instance.

It validates Camelot's mechanisms end-to-end and produces the real step
timings that calibrate the simulator's profiles (``profile_stage_timings``
→ ``repro.core.predictor.profile_from_engine``).  ``apply_allocation``
makes ``CamelotRuntime.reallocate`` applicable to a *running* engine:
allocations swap between batches while in-flight work drains.
"""
from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from heapq import heappop, heappush
from itertools import count
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs import ModelConfig, get_config
from repro.core.comm import (GLOBAL_MEMORY, HOST_STAGED, CommModel,
                             EdgeChannel)
from repro.core.exec import (BatchingPolicy, ExecCore, ReadyBatch,
                             StageInstance, default_allocation)
from repro.core.qos import QoSTracker
from repro.core.types import RTX_2080TI, Allocation, ServiceGraph
from repro.models import init_params, serve_prefill
from repro.models.attention import count_attn_paths
from repro.serving.transport import SHM, PayloadRef
from repro.serving.workers import WorkerPool, WorkerSupervisor, stage_blob


@dataclass
class Query:
    qid: int
    arrival: float
    tokens: np.ndarray                  # (S,) int32
    done: Optional[float] = None


class ModelStageServer:
    """One microservice stage: a model-zoo model served via prefill scoring.

    ``reduced=True`` (the default) serves the laptop-scale twin of ``arch``
    (``get_config(arch, reduced=True)``); ``reduced=False`` serves its
    published configuration.  The stage consumes a token batch (or the
    previous stage's hidden-state batch re-tokenised via argmax — the
    pipeline contract used by the Camelot-suite live twins) and emits
    next-token ids.  ``process`` is
    thread-safe: the engine may run several instances of one stage
    concurrently against the same (immutable) params + jitted callable.
    """

    def __init__(self, name: str, arch: str, seq_len: int = 32, seed: int = 0,
                 reduced: bool = True):
        self.name = name
        self._arch = arch
        self._seed = seed
        self.reduced = reduced
        self.cfg: ModelConfig = get_config(arch, reduced=reduced)
        self.seq_len = seq_len
        self.params = init_params(jax.random.PRNGKey(seed), self.cfg)
        # the attention path of the step (``attention.attn_path``), known
        # once a warm-up has traced it; None for a model without attention
        self.attn_path: Optional[str] = None
        cfg = self.cfg

        def run(params, tokens):
            frames = None
            if cfg.encoder_decoder:
                frames = jnp.zeros(
                    (tokens.shape[0], cfg.encoder_seq_len, cfg.d_model),
                    jnp.bfloat16)
                logits, _ = serve_prefill(params, tokens, cfg,
                                          frames=frames)
            else:
                logits, _ = serve_prefill(params, tokens, cfg)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        self._run = jax.jit(run)

    def __reduce__(self):
        """Rebuild from construction arguments across process boundaries:
        params re-init deterministically from the seed, so a worker-side
        replica computes exactly what the driver-side original would —
        jitted callables and locks never cross the boundary."""
        return (ModelStageServer,
                (self.name, self._arch, self.seq_len, self._seed,
                 self.reduced))

    def warmup(self, batch: int):
        """Compile and run the step at ``batch``.  A warm-up that traces
        the step records the attention path it took in ``attn_path``."""
        t = jnp.zeros((batch, self.seq_len), jnp.int32)
        with count_attn_paths() as paths:
            self._run(self.params, t).block_until_ready()
        if paths:
            self.attn_path = "+".join(sorted(paths))

    def process(self, tokens: jax.Array) -> jax.Array:
        # from the dispatch of the step program to its result being ready:
        # the program's device time plus what it waited for the device
        with TraceAnnotation("camelot.step", stage=self.name):
            out = self._run(self.params, tokens)
            out.block_until_ready()
        return out

    def profile_stage_timings(self, batches: Sequence[int] = (1, 2, 4, 8),
                              repeats: int = 3) -> List[tuple]:
        """Measured (batch, seconds) pairs — the live profiling feed for
        repro.core.predictor.profile_from_engine."""
        out = []
        for b in batches:
            self.warmup(b)
            ts = []
            for _ in range(repeats):
                t = jnp.zeros((b, self.seq_len), jnp.int32)
                t0 = time.perf_counter()
                self._run(self.params, t).block_until_ready()
                ts.append(time.perf_counter() - t0)
            out.append((b, float(np.median(ts))))
        return out


@dataclass
class ServeStats:
    """What one ``run_traces`` call served, per tenant.  The waits are
    sums in seconds, each with the count it is summed over beside it:

    * ``batch_wait_s`` / ``batched_queries``: from a query's scheduled
      arrival to the formation of its entry batch;
    * ``ready_wait_s`` / ``dispatches``: from a batch being ready at a
      stage (formed, or handed off to it) to its dispatch to an instance;
    * ``complete_lag_s`` / ``folds``: from a worker posting a stage call's
      completion to the driver starting to fold it in;
    * ``handoff_s``: the driver's whole hand-off to the successors of a
      finished stage (channel send, join delivery, fan-in combine)."""
    qos: QoSTracker
    comm_time: float = 0.0
    compute_time: float = 0.0
    batch_wait_s: float = 0.0
    batched_queries: int = 0
    ready_wait_s: float = 0.0
    dispatches: int = 0
    complete_lag_s: float = 0.0
    folds: int = 0
    handoff_s: float = 0.0
    batches: int = 0
    failed: int = 0                    # queries lost (worker exceptions
                                       # past the retry budget, deadline
                                       # abandonment)
    retries: int = 0                   # worker-side retry attempts
    last_error: Optional[str] = None   # "Type: message" of the latest
                                       # stage exception

    def summary(self) -> dict:
        return {
            "p99": self.qos.tail_latency(),
            "mean": self.qos.mean(),
            "completed": self.qos.count(),
            "comm_time": self.comm_time,
            "compute_time": self.compute_time,
            "comm_frac": self.comm_time
                         / max(self.comm_time + self.compute_time, 1e-12),
            "batch_wait_s": self.batch_wait_s,
            "batched_queries": self.batched_queries,
            "ready_wait_s": self.ready_wait_s,
            "dispatches": self.dispatches,
            "complete_lag_s": self.complete_lag_s,
            "folds": self.folds,
            "handoff_s": self.handoff_s,
            "failed": self.failed,
            "retries": self.retries,
            "last_error": self.last_error,
        }


class _EdgeChannels(dict):
    """Per-edge live channels, addressable by ``(src, dst)`` or by position
    in the graph's edge list (``channels[0]`` is the first edge — for a
    chain, the stage-0 -> stage-1 hop, as before the DAG refactor)."""

    def __init__(self, graph: ServiceGraph, comm: CommModel,
                 force: Optional[str]):
        super().__init__()
        self._order = [(e.src, e.dst) for e in graph.edges]
        for key in self._order:
            self[key] = EdgeChannel(comm, force=force)

    def __getitem__(self, key):
        if isinstance(key, int):
            key = self._order[key]
        return dict.__getitem__(self, key)


class PipelineEngine:
    """Executes a service graph of stage servers over a query trace, driven
    by the shared ``ExecCore``.

    Since the fault-tolerance refactor this is the ONE-TENANT DELEGATION
    into ``MultiTenantEngine`` — the exact counterpart of
    ``PipelineSimulator`` delegating to ``MultiTenantSimulator``: with a
    single tenant the multi-tenant driver loop's admission, batching,
    dispatch and completion flow are the historical single-service ones,
    so the delegation preserves the existing contract (pinned by
    tests/test_api.py and tests/test_serving.py).  The constructor surface
    is unchanged; ``alloc``/``batch_size``/``swaps`` read through to the
    inner engine's single tenant.

    ``graph`` gives the topology (node i is served by ``stages[i]``);
    omitted, the stages form the linear chain of the paper.
    ``allocation`` (an ``Allocation`` with a ``Placement``) decides how many
    concurrent instances each node runs and on which (logical) device; when
    omitted, a trivial 1-instance-per-node allocation is built.
    ``comm_mechanism``: "auto" routes each edge payload via the crossover
    rule; "device"/"host" pin the mechanism for A/B comparisons.
    ``max_retries``/``retry_backoff``/``deadline`` are the fault knobs,
    ``backend``/``start_method``/``shm_slots``/``shm_slot_bytes``/
    ``supervise_timeout`` the execution-backend knobs — see
    ``MultiTenantEngine``.
    """

    def __init__(self, stages: Sequence, comm_mechanism: str = "auto",
                 qos_target: float = 2.0, batch_size: int = 4,
                 batch_timeout: float = 0.2,
                 allocation: Optional[Allocation] = None,
                 comm_model: Optional[CommModel] = None,
                 graph: Optional[ServiceGraph] = None,
                 max_retries: int = 0, retry_backoff: float = 0.0,
                 deadline: Optional[float] = None,
                 backend: str = "threads", start_method: str = "spawn",
                 shm_slots: int = 32, shm_slot_bytes: int = 1 << 20,
                 supervise_timeout: float = 5.0):
        assert comm_mechanism in ("auto", "device", "host")
        self.stages = list(stages)
        if graph is None:
            graph = ServiceGraph.chain(
                "engine", [None] * len(self.stages), qos_target=qos_target)
        assert graph.n_nodes == len(self.stages), \
            "graph nodes and stage servers must correspond 1:1"
        self.graph = graph
        self.comm_mechanism = comm_mechanism
        self.qos_target = qos_target
        self.batch_timeout = batch_timeout
        self.comm_model = comm_model or CommModel(RTX_2080TI)
        if allocation is None:
            allocation = default_allocation(len(self.stages), batch_size)
        assert allocation.placement is not None, "allocation must be placed"
        assert len(allocation.stages) == len(self.stages)
        self._inner = MultiTenantEngine(
            [self.stages], [graph], [allocation],
            comm_mechanism=comm_mechanism, batch_timeout=batch_timeout,
            comm_model=self.comm_model, qos_targets=[qos_target],
            max_retries=max_retries, retry_backoff=retry_backoff,
            deadline=deadline, backend=backend, start_method=start_method,
            shm_slots=shm_slots, shm_slot_bytes=shm_slot_bytes,
            supervise_timeout=supervise_timeout)
        self.channels = self._inner.tenants[0].channels

    @property
    def backend(self) -> str:
        return self._inner.backend

    @property
    def worker_restarts(self) -> int:
        return self._inner.worker_restarts

    def close(self) -> None:
        """Release the worker-process pool (processes backend); no-op for
        threads."""
        self._inner.close()

    def __enter__(self) -> "PipelineEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # read-through views over the inner engine's single tenant, so the
    # historical attribute surface (tests, benchmarks, runtimes) survives
    # the delegation
    @property
    def alloc(self) -> Allocation:
        return self._inner.tenants[0].alloc

    @property
    def batch_size(self) -> int:
        return self._inner.tenants[0].batch_size

    @property
    def swaps(self) -> int:
        return self._inner.swaps

    # ---- live re-allocation -------------------------------------------

    def apply_allocation(self, allocation: Allocation) -> None:
        """Queue an Allocation(+Placement) swap.  A running trace applies it
        between batches — in-flight batches drain on the old instances, the
        next dispatch uses the new pool.  Safe to call from another thread
        (e.g. a CamelotRuntime reallocating against live load)."""
        assert allocation.placement is not None, "allocation must be placed"
        assert len(allocation.stages) == len(self.stages)
        self._inner.apply_allocations([allocation])

    # ---- trace replay --------------------------------------------------

    def run_trace(self, queries: List[Query]) -> ServeStats:
        """Replay: queries arrive per their timestamps; the core forms
        batches on size/timeout and dispatches them to free stage instances;
        each dispatch runs on a worker thread (the jitted call releases the
        GIL); wall-clock latencies are recorded."""
        return self._inner.run_traces([queries])[0]


def make_trace(n: int, qps: float, seq_len: int, vocab: int,
               seed: int = 0) -> List[Query]:
    rng = np.random.default_rng(seed)
    t = np.cumsum(rng.exponential(1.0 / qps, n))
    return [Query(qid=i, arrival=float(t[i]),
                  tokens=rng.integers(0, vocab, seq_len).astype(np.int32))
            for i in range(n)]


def _stack_tokens_np(tokens_list: List[np.ndarray],
                     batch_size: int) -> np.ndarray:
    """Pad a partial batch to the stage's fixed batch size (one compiled
    shape per stage), staying in host memory."""
    stacked = np.stack(tokens_list)
    if len(tokens_list) < batch_size:
        pad = np.zeros((batch_size - len(tokens_list),) + stacked.shape[1:],
                       stacked.dtype)
        stacked = np.concatenate([stacked, pad])
    return stacked


def _stack_tokens(tokens_list: List[np.ndarray], batch_size: int) -> jax.Array:
    """Device-resident variant of ``_stack_tokens_np`` — the threads
    backend hands stages jax arrays directly."""
    return jnp.asarray(_stack_tokens_np(tokens_list, batch_size))


def _fanin_combine(stages: Sequence, node: int,
                   inputs: Dict[int, jax.Array]) -> jax.Array:
    """Consumer input from the joined predecessor outputs: the branch
    token ids are summed in predecessor-id order (commutative, so the
    result is independent of branch completion order) and consumed as a
    token prefix — for a single predecessor this is the chain contract
    unchanged.  Shared by both engines."""
    nxt = stages[node]
    arrs = [inputs[p] for p in sorted(inputs)]
    handed = arrs[0]
    for a in arrs[1:]:
        handed = handed + a
    vocab = getattr(nxt, "vocab_size", None)
    if vocab is None:
        vocab = nxt.cfg.vocab_size
    return jnp.tile(handed[:, None] % vocab, (1, nxt.seq_len))


# --------------------------------------------------------------------------
# Multi-tenant live serving: N services sharing one worker pool
# --------------------------------------------------------------------------

@dataclass
class _TenantServe:
    """Per-tenant serving context of a MultiTenantEngine."""
    stages: List                       # one ModelStageServer per graph node
    graph: ServiceGraph
    alloc: Allocation
    channels: _EdgeChannels
    batch_size: int
    names: List[str] = field(init=False)   # per node, for spans

    def __post_init__(self):
        # the graph node's name, else the stage server's, else its index
        self.names = [getattr(node, "name", None)
                      or getattr(st, "name", None) or str(i)
                      for i, (node, st) in enumerate(zip(self.graph.nodes,
                                                         self.stages))]


class _RetryQueue:
    """Driver-side timed retry requeue (the non-blocking backoff fix).

    A failing batch no longer sleeps out its backoff inside a worker slot
    — the slot is released immediately and the batch re-enters its ready
    queue once ``retry_backoff × 2^attempt`` has elapsed, so an
    otherwise-free instance keeps serving other batches meanwhile."""

    def __init__(self):
        self.heap: List[Tuple[float, int, int, ReadyBatch, int]] = []
        self._seq = count()
        self._attempts: Dict[Tuple[int, int], int] = {}

    def schedule(self, wake: float, ti: int, rb: ReadyBatch,
                 attempt: int) -> None:
        heappush(self.heap, (wake, next(self._seq), ti, rb, attempt))

    def due(self, now: float) -> List[Tuple[int, ReadyBatch, int]]:
        out = []
        while self.heap and self.heap[0][0] <= now:
            _, _, ti, rb, attempt = heappop(self.heap)
            out.append((ti, rb, attempt))
        return out

    def next_wake(self) -> Optional[float]:
        return self.heap[0][0] if self.heap else None

    def __bool__(self) -> bool:
        return bool(self.heap)

    # a requeued batch re-enters core.ready; its attempt count rides here
    # until the dispatch that re-submits it
    def mark(self, ti: int, rb: ReadyBatch, attempt: int) -> None:
        self._attempts[(ti, id(rb))] = attempt

    def take(self, ti: int, rb: ReadyBatch) -> int:
        return self._attempts.pop((ti, id(rb)), 0)


@dataclass
class _InFlight:
    """Driver-side record of one batch executing in a worker process."""
    ti: int
    inst: StageInstance
    rb: ReadyBatch
    attempt: int
    device: int
    input_refs: List = field(default_factory=list)


class MultiTenantEngine:
    """Live twin of ``MultiTenantSimulator``: N tenant service graphs
    co-served from ONE shared worker pool.

    Each tenant gets its own ``ExecCore`` (admission, batching, ready
    queues against its slice of the joint ``Placement``) and its own
    per-edge channels, but every dispatch lands in one shared
    ``ThreadPoolExecutor`` sized by the TOTAL placed instance count — the
    live counterpart of the shared device pool: tenants contend for the
    same workers, and a joint allocation that over-packs one tenant slows
    the others, observably.  ``apply_allocations`` swaps all tenants'
    allocations between batches (``MultiTenantRuntime`` pushes the
    service-scoped slices of each joint re-solve here).

    Fault knobs:

    * ``max_retries`` — a worker whose stage raises retries the execution
      in place (bounded, with ``retry_backoff × 2^attempt`` sleeps
      between tries) before reporting failure;
    * on a reported failure the batch is *abandoned* (failed queries in
      ``ServeStats.failed``) and the trace DRAINS — a worker exception
      used to strand its batch in the core's join/exit tracking and hang
      ``run_traces`` waiting on completions that could never come;
    * ``deadline`` — queries still waiting past this many seconds after
      arrival are abandoned at admission (per-query deadline, counted
      failed), so a degraded pool sheds backlog instead of serving
      un-meetable requests.

    Backend knobs:

    * ``backend`` — ``"threads"`` (default; bit-pinned pre-process-plane
      behaviour) or ``"processes"`` (worker-process pool + shared-memory
      transport; requires picklable stage servers);
    * ``start_method`` — multiprocessing start method (``"spawn"`` is
      jax-safe; ``"fork"`` starts faster for numpy-only stages);
    * ``shm_slots``/``shm_slot_bytes`` — per-worker shared-memory ring
      geometry (a full ring backpressures onto the queue mechanism);
    * ``supervise_timeout`` — heartbeat silence after which a worker
      process that still holds tasks is declared hung and restarted
      (a process that DIED is restarted as soon as it is seen).
    """

    def __init__(self, tenant_stages: Sequence[Sequence],
                 graphs: Sequence[ServiceGraph],
                 allocations: Sequence[Allocation],
                 comm_mechanism: str = "auto", batch_timeout: float = 0.05,
                 comm_model: Optional[CommModel] = None,
                 qos_targets: Optional[Sequence[float]] = None,
                 max_retries: int = 0, retry_backoff: float = 0.0,
                 deadline: Optional[float] = None,
                 backend: str = "threads", start_method: str = "spawn",
                 shm_slots: int = 32, shm_slot_bytes: int = 1 << 20,
                 supervise_timeout: float = 5.0):
        assert comm_mechanism in ("auto", "device", "host")
        assert backend in ("threads", "processes"), \
            f"unknown backend {backend!r}"
        assert len(tenant_stages) == len(graphs) == len(allocations), \
            "need stages, graph and allocation per tenant"
        if backend == "processes" and jax.default_backend() == "tpu" and \
                any(isinstance(st, ModelStageServer)
                    for stages in tenant_stages for st in stages):
            raise ValueError(
                "backend='processes' cannot serve JAX model stages on a "
                "TPU: one process holds the chip, and this one already "
                "does, so worker processes could not reach it.  Use "
                "backend='threads'.")
        self.comm_model = comm_model or CommModel(RTX_2080TI)
        force = None if comm_mechanism == "auto" else comm_mechanism
        self.tenants: List[_TenantServe] = []
        for stages, g, alloc in zip(tenant_stages, graphs, allocations):
            assert alloc.placement is not None, "allocations must be placed"
            assert g.n_nodes == len(stages), \
                "graph nodes and stage servers must correspond 1:1"
            self.tenants.append(_TenantServe(
                stages=list(stages), graph=g, alloc=alloc,
                channels=_EdgeChannels(g, self.comm_model, force),
                batch_size=alloc.stages[0].batch))
        if qos_targets is None:
            qos_targets = [g.qos_target for g in graphs]
        assert len(qos_targets) == len(self.tenants)
        self.qos_targets = [float(t) for t in qos_targets]
        self.batch_timeout = batch_timeout
        self.max_retries = int(max_retries)
        self.retry_backoff = float(retry_backoff)
        self.deadline = deadline
        self._pending_allocs: Optional[List[Allocation]] = None
        self._alloc_lock = threading.Lock()
        self.swaps = 0
        # process-backend state: the pool is spawned lazily on the first
        # trace (workers warm up at spawn) and reused across traces
        self.backend = backend
        self.comm_mechanism = comm_mechanism
        self.start_method = start_method
        self.shm_slots = int(shm_slots)
        self.shm_slot_bytes = int(shm_slot_bytes)
        self.supervise_timeout = float(supervise_timeout)
        self.worker_restarts = 0
        self._pool = None
        self._supervisor = None

    def close(self) -> None:
        """Shut down the worker-process pool (processes backend); no-op
        for threads.  The engine stays usable — the next trace respawns."""
        if self._pool is not None:
            self._pool.close()
            self._pool = None
            self._supervisor = None

    def __enter__(self) -> "MultiTenantEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ---- live joint re-allocation -------------------------------------

    def apply_allocations(self, allocations: Sequence[Allocation]) -> None:
        """Queue a per-tenant allocation swap (one placed Allocation per
        tenant — the split of a joint re-solve).  A running trace applies
        it between batches; safe to call from another thread."""
        allocations = list(allocations)
        assert len(allocations) == len(self.tenants)
        for a, t in zip(allocations, self.tenants):
            assert a.placement is not None
            assert len(a.stages) == t.graph.n_nodes
        with self._alloc_lock:
            self._pending_allocs = allocations

    def _apply_pending(self, cores: List[ExecCore], ex) -> None:
        with self._alloc_lock:
            allocs = self._pending_allocs
            self._pending_allocs = None
        if allocs is None:
            return
        for t, core, alloc in zip(self.tenants, cores, allocs):
            t.alloc = alloc
            t.batch_size = alloc.stages[0].batch
            core.batching.batch_size = t.batch_size
            core.reset_instances(alloc.placement)
        total = sum(len(c.instances) for c in cores)
        if ex is not None and hasattr(ex, "_max_workers"):
            ex._max_workers = max(ex._max_workers, total)
        self.swaps += 1

    # ---- trace replay --------------------------------------------------

    def run_traces(self, traces: Sequence[List[Query]]) -> List[ServeStats]:
        """Replay one query trace per tenant on the shared pool; returns
        one ``ServeStats`` per tenant (each against its own QoS target)."""
        assert len(traces) == len(self.tenants)
        if self.backend == "processes":
            return self._run_traces_processes(traces)
        stats = [ServeStats(qos=QoSTracker(qt)) for qt in self.qos_targets]
        for t in self.tenants:
            for st in t.stages:
                st.warmup(t.batch_size)
        cores = [ExecCore(t.graph, t.alloc.placement,
                          BatchingPolicy(t.batch_size, self.batch_timeout),
                          comm=self.comm_model)
                 for t in self.tenants]
        completions: queue.Queue = queue.Queue()
        retry = _RetryQueue()
        in_flight = 0
        idx = [0] * len(self.tenants)
        lens = [len(tr) for tr in traces]
        start = time.perf_counter()
        total_inst = sum(len(c.instances) for c in cores)
        with ThreadPoolExecutor(max_workers=max(total_inst, 1)) as ex:

            def submit(ti: int, inst: StageInstance, rb: ReadyBatch,
                       attempt: int) -> None:
                nonlocal in_flight
                in_flight += 1
                ex.submit(self._worker, ti, inst, rb, completions, attempt)

            while any(i < n for i, n in zip(idx, lens)) or in_flight \
                    or retry or any(c.has_work() for c in cores):
                now = time.perf_counter() - start
                self._apply_pending(cores, ex)
                self._requeue_due(retry, cores, now)
                for ti in range(len(self.tenants)):
                    self._schedule(ti, cores[ti], traces[ti], idx, now,
                                   start, stats[ti], retry, _stack_tokens,
                                   submit)
                timeout, wait = self._next_wake(traces, idx, cores, retry,
                                                now)
                try:
                    with TraceAnnotation(wait):
                        ev = completions.get(timeout=timeout)
                except queue.Empty:
                    continue
                while True:
                    in_flight -= 1
                    self._complete(ev, cores, stats, start, retry)
                    try:
                        ev = completions.get_nowait()
                    except queue.Empty:
                        break
        return stats

    # ---- process backend ----------------------------------------------

    def _ensure_pool(self, cores: List[ExecCore], now: float) -> None:
        """Spawn the worker pool on first use (workers warm up in their
        own processes) and add workers for any newly placed device."""
        if self._pool is None:
            force = (None if self.comm_mechanism == "auto"
                     else self.comm_mechanism)
            self._pool = WorkerPool(
                stage_blob([t.stages for t in self.tenants]),
                [t.batch_size for t in self.tenants],
                self.comm_model.crossover_bytes(), force=force,
                shm_ok=self.comm_model.global_memory_enabled,
                start_method=self.start_method, slots=self.shm_slots,
                slot_bytes=self.shm_slot_bytes)
            self._supervisor = WorkerSupervisor(
                self._pool, heartbeat_timeout=self.supervise_timeout)
        devices = sorted({inst.device for core in cores
                          for inst in core.instances})
        for d in self._pool.ensure(devices):
            self._supervisor.track(d, now)

    def _run_traces_processes(self,
                              traces: Sequence[List[Query]]) \
            -> List[ServeStats]:
        """The multi-process twin of the threads driver loop.

        Scheduling (admission, deadlines, batching, dispatch, joins, QoS)
        is the SAME ``ExecCore`` flow; what differs is execution — batches
        run in worker processes keyed by placed device — and transport:
        stage outputs stay put in the producer's shared-memory arena and
        only a ``PayloadRef`` travels through the driver when the payload
        is above the comm crossover (queue pickling below it).  The driver
        is the single freer of arena slots: a producer's output slot is
        pinned once per consumer edge and freed when the last consuming
        batch reaches a terminal state, so retries and out-of-order joins
        can always re-map their inputs."""
        stats = [ServeStats(qos=QoSTracker(qt)) for qt in self.qos_targets]
        cores = [ExecCore(t.graph, t.alloc.placement,
                          BatchingPolicy(t.batch_size, self.batch_timeout),
                          comm=self.comm_model)
                 for t in self.tenants]
        self._ensure_pool(cores, 0.0)
        pool, sup = self._pool, self._supervisor
        retry = _RetryQueue()
        fid_gen = count()
        inflight: Dict[int, _InFlight] = {}
        # slot refcounts: ref.key() -> [consumers_left, ref]; a bid's live
        # refs are also indexed by (ti, bid) so abandonment can reclaim
        # slots whose consumers will never run
        pins: Dict[Tuple[str, int], List] = {}
        bid_refs: Dict[Tuple[int, int], Set[Tuple[str, int]]] = {}

        def unpin(refs: List[PayloadRef]) -> None:
            for ref in refs:
                ent = pins.get(ref.key())
                if ent is None:            # already reclaimed via its bid
                    continue
                ent[0] -= 1
                if ent[0] <= 0:
                    del pins[ref.key()]
                    pool.free(ref)

        def drop_bid(ti: int, bid: int) -> None:
            for key in bid_refs.pop((ti, bid), ()):
                ent = pins.pop(key, None)
                if ent is not None:
                    pool.free(ent[1])

        def fail_or_retry(fl: _InFlight, now: float) -> None:
            core = cores[fl.ti]
            if fl.rb.bid in core._abandoned:
                return
            if self._fail_or_retry(fl.ti, fl.rb, fl.attempt, core,
                                   stats[fl.ti], retry, now):
                return                     # replay re-maps the input refs
            unpin(fl.input_refs)
            drop_bid(fl.ti, fl.rb.bid)

        def submit(ti: int, inst: StageInstance, rb: ReadyBatch,
                   attempt: int) -> None:
            fid = next(fid_gen)
            refs = [v for v in (rb.inputs or {}).values()
                    if isinstance(v, PayloadRef)]
            inflight[fid] = _InFlight(ti, inst, rb, attempt, inst.device,
                                      refs)
            if rb.inputs is not None:
                task = (fid, ti, rb.stage, None, dict(rb.inputs), attempt)
            else:
                task = (fid, ti, rb.stage, rb.data, None, attempt)
            pool.submit(inst.device, task)

        idx = [0] * len(self.tenants)
        lens = [len(tr) for tr in traces]
        # workers (re-)tracked per run: supervisor heartbeats are
        # trace-relative times
        for d in pool.devices():
            sup.track(d, 0.0)
        start = time.perf_counter()
        while any(i < n for i, n in zip(idx, lens)) or inflight \
                or retry or any(c.has_work() for c in cores):
            now = time.perf_counter() - start
            self._apply_pending(cores, None)
            self._ensure_pool(cores, now)
            # worker supervision: a dead/hung worker process is replaced
            # and its in-flight batches replayed within the retry budget
            for d in sup.dead_workers(now):
                self.worker_restarts += 1
                for fid in sorted(sup.restart(d, now)):
                    fl = inflight.pop(fid, None)
                    if fl is None:
                        continue
                    cores[fl.ti].release(fl.inst, busy_for=0.0)
                    fail_or_retry(fl, now)
            self._requeue_due(retry, cores, now)
            for ti in range(len(self.tenants)):
                # host-resident stacking: workers are jax-free
                self._schedule(ti, cores[ti], traces[ti], idx, now, start,
                               stats[ti], retry, _stack_tokens_np, submit)
            timeout, wait = self._next_wake(traces, idx, cores, retry, now)
            with TraceAnnotation(wait):
                done = pool.poll(timeout)
            for ev in done:
                self._complete_proc(ev, cores, stats, start, retry,
                                    inflight, pins, bid_refs, unpin,
                                    drop_bid, fail_or_retry)
        return stats

    def _complete_proc(self, ev, cores: List[ExecCore],
                       stats: List[ServeStats], start: float,
                       retry: "_RetryQueue",
                       inflight: Dict[int, _InFlight],
                       pins: Dict, bid_refs: Dict,
                       unpin, drop_bid, fail_or_retry) -> None:
        """Fold one worker completion into the scheduling state — the
        process-backend mirror of ``_complete`` plus slot-lifecycle and
        mechanism accounting (each hand-off is recorded on its edge's
        ``EdgeChannel`` so per-edge stats read identically across
        backends)."""
        pool, sup = self._pool, self._supervisor
        wid, fid, payload, dt, err, mech, nbytes, t_comm, t_put = ev
        t_fold = time.perf_counter()
        now = t_fold - start
        sup.beat(wid, now)
        fl = inflight.pop(fid, None)
        if fl is None:
            # completion from a replaced worker generation — the batch was
            # already replayed or failed; reclaim an orphan shm payload
            if isinstance(payload, PayloadRef):
                pool.free(payload)
            return
        ti, rb = fl.ti, fl.rb
        t = self.tenants[ti]
        core = cores[ti]
        stats[ti].complete_lag_s += t_fold - t_put
        stats[ti].folds += 1
        with TraceAnnotation("camelot.complete", tenant=ti,
                             stage=t.names[rb.stage], bid=rb.bid):
            core.release(fl.inst, busy_for=dt)
            if err is not None:
                stats[ti].last_error = err
                fail_or_retry(fl, now)
                return
            if rb.bid in core._abandoned:      # a sibling branch failed
                if isinstance(payload, PayloadRef):
                    pool.free(payload)
                return
            stats[ti].compute_time += dt
            stats[ti].comm_time += t_comm
            # this batch is terminal for its inputs: release their slot pins
            unpin(fl.input_refs)
            u = rb.stage
            succs = core.succs[u]
            if succs:
                if isinstance(payload, PayloadRef):
                    pins[payload.key()] = [len(succs), payload]
                    bid_refs.setdefault((ti, rb.bid),
                                        set()).add(payload.key())
                mech_name = GLOBAL_MEMORY if mech == SHM else HOST_STAGED
                for v in succs:
                    with TraceAnnotation("camelot.handoff", tenant=ti,
                                         stage=t.names[u], bid=rb.bid):
                        t0 = time.perf_counter()
                        t.channels[(u, v)].record(mech_name, nbytes)
                        # joined batches keep raw inputs: the CONSUMER's
                        # worker resolves refs and runs the fan-in combine
                        # process-side
                        joined = core.deliver(u, v, rb.bid, rb.items, now,
                                              data=payload)
                        self._handed_off(joined, stats[ti], t0, start)
            else:
                if isinstance(payload, PayloadRef):
                    pool.free(payload)
                if core.complete_exit(rb.bid, u):
                    for q in rb.items:
                        q.done = now
                        stats[ti].qos.record(now - q.arrival)
                    stats[ti].batches += 1
                    drop_bid(ti, rb.bid)

    # ---- internals -----------------------------------------------------

    def _worker(self, ti: int, inst: StageInstance, rb: ReadyBatch,
                completions: queue.Queue, attempt: int = 0) -> None:
        """ONE stage execution attempt.  Retries are scheduled by the
        driver as timed requeues (``_RetryQueue``) — the pre-fix behaviour
        slept the backoff out right here, pinning the worker slot (and the
        stage instance holding it) idle for the whole backoff window."""
        t = self.tenants[ti]
        with TraceAnnotation("camelot.worker", tenant=ti,
                             stage=t.names[inst.stage], bid=rb.bid,
                             attempt=attempt):
            t0 = time.perf_counter()
            try:
                out, err = t.stages[inst.stage].process(rb.data), None
            except BaseException as e:
                out, err = None, e
            t_put = time.perf_counter()
            completions.put((ti, inst, rb, out, t_put - t0, err, attempt,
                             t_put))

    def _schedule(self, ti: int, core: ExecCore, trace: List[Query],
                  idx: List[int], now: float, start: float,
                  stats: ServeStats, retry: "_RetryQueue", stack,
                  submit) -> None:
        """One tenant's admission, deadline shedding, batch formation and
        dispatch at ``now`` — the step both backends' drivers share.
        ``stack`` builds an entry batch's input from its prompts;
        ``submit(ti, inst, rb, attempt)`` hands a dispatched batch to the
        backend."""
        t = self.tenants[ti]
        i, n = idx[ti], len(trace)
        if i < n and trace[i].arrival <= now:
            with TraceAnnotation("camelot.admit", tenant=ti):
                while i < n and trace[i].arrival <= now:
                    core.admit(trace[i], trace[i].arrival)
                    i += 1
            idx[ti] = i
        if self.deadline is not None and core.pending:
            # per-query deadline: abandon arrivals that have already
            # waited past it instead of batching them
            keep = [(a, q) for a, q in core.pending
                    if now - a <= self.deadline]
            n_drop = len(core.pending) - len(keep)
            if n_drop:
                core.pending = keep
                stats.failed += n_drop
        for rb in core.form_batches(now):
            with TraceAnnotation("camelot.form", tenant=ti,
                                 stage=t.names[rb.stage], bid=rb.bid):
                rb.data = stack([q.tokens for q in rb.items], t.batch_size)
            if rb.stage == core.entries[0]:    # once per admitted query
                stats.batch_wait_s += sum(now - q.arrival for q in rb.items)
                stats.batched_queries += len(rb.items)
        for inst, rb in core.dispatch(now):
            stats.ready_wait_s += time.perf_counter() - start - rb.ready_time
            stats.dispatches += 1
            with TraceAnnotation("camelot.dispatch", tenant=ti,
                                 stage=t.names[rb.stage], bid=rb.bid):
                submit(ti, inst, rb, retry.take(ti, rb))

    @staticmethod
    def _next_wake(traces: Sequence[List[Query]], idx: List[int],
                   cores: List[ExecCore], retry: "_RetryQueue",
                   now: float) -> Tuple[float, str]:
        """How long the driver may wait for a completion before the next
        event it must act on across ALL tenants (an arrival, a batch
        deadline, a retry's wake), and the name of the span that wait is
        recorded under: what set the timeout, or ``wait_completion`` when
        nothing is due and only in-flight work remains."""
        wake, name = None, "camelot.wait_completion"
        due = [(tr[i].arrival, "camelot.wait_arrival")
               for tr, i in zip(traces, idx) if i < len(tr)]
        due += [(d, "camelot.wait_batch")
                for d in (c.batch_deadline() for c in cores)
                if d is not None]
        rw = retry.next_wake()
        if rw is not None:
            due.append((rw, "camelot.wait_retry"))
        for at, why in due:
            if wake is None or at < wake:
                wake, name = at, why
        timeout = (wake - now) if wake is not None else 0.05
        return min(max(timeout, 0.0005), 0.05), name

    @staticmethod
    def _handed_off(joined: Optional[ReadyBatch], stats: ServeStats,
                    t0: float, start: float) -> None:
        """Count a hand-off that began at ``t0``.  A batch it made ready
        counts as ready from the hand-off's end, so that its ready wait
        and the hand-off do not overlap."""
        t1 = time.perf_counter()
        stats.handoff_s += t1 - t0
        if joined is not None:
            joined.ready_time = t1 - start

    def _fail_or_retry(self, ti: int, rb: ReadyBatch, attempt: int,
                       core: ExecCore, stats: ServeStats,
                       retry: "_RetryQueue", now: float) -> bool:
        """Shared failure policy for both backends: schedule a timed
        requeue while the retry budget lasts, else fail + abandon the
        batch.  Returns True when a retry was scheduled."""
        if rb.bid in core._abandoned:
            return False
        if attempt < self.max_retries:
            stats.retries += 1
            retry.schedule(now + self.retry_backoff * (2 ** attempt),
                           ti, rb, attempt + 1)
            return True
        # the retry budget is spent: record the batch as failed and
        # abandon it so its join/exit bookkeeping cannot strand
        # ``has_work`` — the pre-fix behaviour re-raised in the worker,
        # leaking the batch and deadlocking the driver loop on in_flight
        # work that no longer existed
        stats.failed += len(rb.items)
        core.abandon(rb.bid)
        return False

    def _requeue_due(self, retry: "_RetryQueue", cores: List[ExecCore],
                     now: float) -> None:
        """Re-enter backed-off batches whose wake time has passed into
        their stage's ready queue (their attempt count rides in the retry
        queue until dispatch re-submits them)."""
        for ti, rb, attempt in retry.due(now):
            if rb.bid in cores[ti]._abandoned:
                continue
            retry.mark(ti, rb, attempt)
            cores[ti].ready[rb.stage].append(rb)

    def _complete(self, ev, cores: List[ExecCore],
                  stats: List[ServeStats], start: float,
                  retry: "_RetryQueue") -> None:
        ti, inst, rb, out, dt, err, attempt, t_put = ev
        stats[ti].complete_lag_s += time.perf_counter() - t_put
        stats[ti].folds += 1
        t = self.tenants[ti]
        core = cores[ti]
        with TraceAnnotation("camelot.complete", tenant=ti,
                             stage=t.names[rb.stage], bid=rb.bid):
            core.release(inst, busy_for=dt)
            if err is not None:
                stats[ti].last_error = f"{type(err).__name__}: {err}"
                self._fail_or_retry(ti, rb, attempt, core, stats[ti], retry,
                                    time.perf_counter() - start)
                return
            stats[ti].compute_time += dt
            u = rb.stage
            now = time.perf_counter() - start
            succs = core.succs[u]
            if succs:
                for v in succs:
                    with TraceAnnotation("camelot.handoff", tenant=ti,
                                         stage=t.names[u], bid=rb.bid):
                        same = inst.device in core.consumer_devices(v)
                        t0 = time.perf_counter()
                        handed = t.channels[(u, v)].send(out,
                                                         same_device=same)
                        stats[ti].comm_time += time.perf_counter() - t0
                        joined = core.deliver(u, v, rb.bid, rb.items, now,
                                              data=handed)
                        if joined is not None:
                            joined.data = _fanin_combine(t.stages, v,
                                                         joined.inputs)
                        self._handed_off(joined, stats[ti], t0, start)
            elif core.complete_exit(rb.bid, u):
                for q in rb.items:
                    q.done = now
                    stats[ti].qos.record(now - q.arrival)
                stats[ti].batches += 1

"""Process workers for the live serving plane (paper §VI, multi-process).

The ``backend="processes"`` serving plane escapes the GIL: stage
``process()`` calls run in a pool of persistent OS processes — ONE worker
per placed device, the process-world realisation of the paper's
spatially-shared GPU — while the scheduling state machine (``ExecCore``)
stays in the driver.  Only execution and payload transport cross the
process boundary:

  * tasks (batch descriptors) travel driver -> worker over a per-worker
    task queue; completions come back over one shared queue;
  * stage outputs travel worker -> consumer-worker via the
    ``repro.serving.transport`` mechanisms: shared-memory hand-off above
    the comm crossover (written once, mapped zero-copy), pickle-over-queue
    below it — the same per-edge rule the ``CommModel`` prices.

This module is imported by spawned children, so it must stay light: numpy
and the transport layer only (no jax, no solver stack).  Stage servers
reach workers by pickle — anything picklable works; ``ModelStageServer``
reconstructs itself from (name, arch, seq_len, seed, reduced) via
``__reduce__``, and ``CpuStageServer`` below is the picklable CPU-bound
stage used by the serving benchmarks and tests.

Supervision: ``WorkerSupervisor`` wraps ``repro.core.runtime.HealthMonitor``
— completions are per-worker heartbeats; a worker whose PROCESS died
(``is_alive()`` false) or that holds tasks but has been heartbeat-silent
past the timeout is declared dead.  The pool restarts it (fresh process,
fresh output arena — the dead worker's old arena stays attached so
outstanding refs written before the crash remain readable) and the engine
replays its in-flight batches within the existing retry budget.
"""
from __future__ import annotations

import multiprocessing as mp
import pickle
import queue as _queue
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.serving.transport import (QUEUE, SHM, ArenaMap, PayloadRef,
                                     ShmArena)

__all__ = ["CpuStageServer", "WorkerPool", "WorkerSupervisor",
           "WorkerTask", "WorkerDone"]

#: task tuple: (fid, tenant, stage, data, inputs, attempt)
WorkerTask = Tuple[int, int, int, object, Optional[dict], int]
#: completion tuple:
#: (worker, fid, payload, compute_s, err, mechanism, nbytes, comm_s)
WorkerDone = Tuple[int, int, object, float, Optional[str], Optional[str],
                   int, float]


class CpuStageServer:
    """A picklable, deterministic, GIL-bound CPU microservice stage.

    ``process`` runs ``spin`` rounds of pure-Python integer arithmetic per
    query — work that HOLDS the GIL, so a thread pool of these stages
    serialises on one core while a process pool scales with the machine.
    This is the CPU-bound scenario of ``benchmarks/bench_serving.py``.

    The output is a deterministic function of the input tokens alone
    (no clocks, no RNG state), so thread- and process-backend runs of the
    same trace complete the same queries with identical payloads.
    """

    def __init__(self, name: str, seq_len: int = 16, vocab: int = 256,
                 spin: int = 400):
        self.name = name
        self.seq_len = int(seq_len)
        self.vocab_size = int(vocab)
        self.spin = int(spin)
        self.calls = 0

    def warmup(self, batch: int) -> None:
        self.process(np.zeros((batch, self.seq_len), np.int32))

    def process(self, tokens: np.ndarray) -> np.ndarray:
        tokens = np.asarray(tokens)
        self.calls += 1
        seeds = [int(r) for r in tokens.reshape(tokens.shape[0], -1)[:, 0]]
        out = np.empty((tokens.shape[0],), np.int32)
        for i, acc in enumerate(seeds):
            for _ in range(self.spin):          # GIL-bound by construction
                acc = (acc * 1103515245 + 12345) & 0x7FFFFFFF
            out[i] = acc % self.vocab_size
        return out


# --------------------------------------------------------------------------
# Worker process main loop
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class _WorkerConfig:
    """Everything a spawned worker needs, picklable."""
    arena_name: str
    slots: int
    slot_bytes: int
    crossover_bytes: float
    shm_ok: bool = True
    force: Optional[str] = None        # None | "device" | "host"
    batch_sizes: Tuple[int, ...] = ()  # per-tenant warmup batch


def _resolve(payload, amap: ArenaMap, cfg: _WorkerConfig):
    """Materialise a task payload: refs map zero-copy, arrays pass as-is."""
    if isinstance(payload, PayloadRef):
        return amap.attach(payload.arena, cfg.slots,
                           cfg.slot_bytes).get(payload)
    return payload


def _combine_np(stage, inputs: Dict[int, np.ndarray]) -> np.ndarray:
    """Consumer-side fan-in combine — the numpy mirror of the threads
    backend's ``_fanin_combine`` contract: branch outputs summed in
    predecessor order, consumed as a token prefix tiled to the consumer's
    sequence length.  A stage may override with its own ``combine``."""
    if hasattr(stage, "combine"):
        return stage.combine(inputs)
    arrs = [np.asarray(inputs[p]) for p in sorted(inputs)]
    handed = arrs[0]
    for a in arrs[1:]:
        handed = handed + a
    vocab = getattr(stage, "vocab_size", None)
    if vocab is None:
        vocab = stage.cfg.vocab_size
    return np.tile(handed[:, None] % vocab, (1, stage.seq_len))


def _pick_mechanism(cfg: _WorkerConfig, nbytes: int) -> str:
    """The executed per-edge rule: exactly ``select_mechanism``'s
    same-device branch (queue below the crossover, shm above), evaluated
    against the crossover constant the driver's ``CommModel`` supplied."""
    if cfg.force == "host" or not cfg.shm_ok:
        return QUEUE
    if cfg.force == "device":
        return SHM
    return QUEUE if nbytes < cfg.crossover_bytes else SHM


def _worker_main(wid: int, task_q, done_q, stages_blob: bytes,
                 cfg: _WorkerConfig) -> None:
    """Persistent worker loop: resolve payload -> combine -> process ->
    publish output via the selected mechanism -> report completion."""
    tenants = pickle.loads(stages_blob)
    arena = ShmArena(name=cfg.arena_name, slots=cfg.slots,
                     slot_bytes=cfg.slot_bytes, create=False)
    amap = ArenaMap()
    for ti, stages in enumerate(tenants):
        b = cfg.batch_sizes[ti] if ti < len(cfg.batch_sizes) else 1
        for st in stages:
            st.warmup(b)
    done_q.put((wid, -1, None, 0.0, None, None, 0, 0.0))   # ready beacon
    while True:
        task = task_q.get()
        if task is None:
            break
        fid, ti, stage, data, inputs, _attempt = task
        t0 = time.perf_counter()
        t_comm = 0.0
        try:
            tc0 = time.perf_counter()
            if inputs is not None:
                arrs = {p: np.asarray(_resolve(v, amap, cfg))
                        for p, v in inputs.items()}
                x = _combine_np(tenants[ti][stage], arrs)
            else:
                x = _resolve(data, amap, cfg)
            t_comm += time.perf_counter() - tc0
            out = np.asarray(tenants[ti][stage].process(x))
            dt = time.perf_counter() - t0
            tc0 = time.perf_counter()
            mech = _pick_mechanism(cfg, out.nbytes)
            payload: object = out
            if mech == SHM:
                ref = arena.try_put(out)
                if ref is None:            # ring full: backpressure fallback
                    mech = QUEUE
                else:
                    payload = ref
            t_comm += time.perf_counter() - tc0
            done_q.put((wid, fid, payload, dt, None, mech, int(out.nbytes),
                        t_comm))
        except BaseException as e:  # noqa: BLE001 — report, never die
            done_q.put((wid, fid, None, time.perf_counter() - t0,
                        f"{type(e).__name__}: {e}", None, 0, t_comm))
    arena.close()
    amap.close()


# --------------------------------------------------------------------------
# Driver-side pool
# --------------------------------------------------------------------------

@dataclass
class _Worker:
    device: int
    proc: mp.process.BaseProcess
    task_q: object
    arena: ShmArena                  # driver's attachment (freer side)
    pending: Set[int] = field(default_factory=set)
    gen: int = 0
    ready: bool = False


class WorkerPool:
    """Persistent process pool, one worker pinned per placed device.

    The driver submits ``WorkerTask``s to a device's worker and drains
    ``WorkerDone`` completions from one shared queue.  Spawned once per
    ``serve()``/first trace and reused across traces and allocation swaps
    (``ensure`` adds workers for newly placed devices on demand).
    """

    def __init__(self, stages_blob: bytes, batch_sizes: Sequence[int],
                 crossover_bytes: float, force: Optional[str] = None,
                 shm_ok: bool = True, start_method: str = "spawn",
                 slots: int = 32, slot_bytes: int = 1 << 20,
                 ready_timeout: float = 120.0):
        self._blob = stages_blob
        self._cfg_proto = _WorkerConfig(
            arena_name="", slots=int(slots), slot_bytes=int(slot_bytes),
            crossover_bytes=float(crossover_bytes), shm_ok=bool(shm_ok),
            force=force, batch_sizes=tuple(int(b) for b in batch_sizes))
        self._ctx = mp.get_context(start_method)
        self._done = self._ctx.Queue()
        self._workers: Dict[int, _Worker] = {}
        self._old_arenas: List[ShmArena] = []
        self._amap = ArenaMap()          # driver attachments for freeing
        self._ready_timeout = ready_timeout

    # ---- lifecycle ----------------------------------------------------

    def devices(self) -> List[int]:
        return sorted(self._workers)

    def ensure(self, devices: Sequence[int]) -> List[int]:
        """Spawn workers for any device not yet in the pool; returns the
        newly spawned device ids."""
        new = [int(d) for d in devices if int(d) not in self._workers]
        for d in new:
            self._spawn(d)
        if new:
            self.wait_ready()
        return new

    def _spawn(self, device: int, gen: int = 0) -> _Worker:
        arena = ShmArena(slots=self._cfg_proto.slots,
                         slot_bytes=self._cfg_proto.slot_bytes, create=True)
        self._amap.register(arena)
        cfg = _WorkerConfig(
            arena_name=arena.name, slots=self._cfg_proto.slots,
            slot_bytes=self._cfg_proto.slot_bytes,
            crossover_bytes=self._cfg_proto.crossover_bytes,
            shm_ok=self._cfg_proto.shm_ok, force=self._cfg_proto.force,
            batch_sizes=self._cfg_proto.batch_sizes)
        task_q = self._ctx.Queue()
        proc = self._ctx.Process(
            target=_worker_main, name=f"serve-worker-{device}",
            args=(device, task_q, self._done, self._blob, cfg), daemon=True)
        proc.start()
        w = _Worker(device=device, proc=proc, task_q=task_q, arena=arena,
                    gen=gen)
        self._workers[device] = w
        return w

    def wait_ready(self, timeout: Optional[float] = None) -> None:
        """Block until every worker has warmed up and posted its ready
        beacon (fid == -1).  Real completions arriving early are impossible
        — a worker beacons before its first task can have been submitted
        by callers that respect this barrier."""
        deadline = time.time() + (timeout or self._ready_timeout)
        while any(not w.ready for w in self._workers.values()):
            remaining = deadline - time.time()
            if remaining <= 0:
                raise TimeoutError("worker pool failed to come up")
            try:
                wid, fid, *_ = self._done.get(timeout=min(remaining, 0.5))
            except _queue.Empty:
                dead = [d for d, w in self._workers.items()
                        if not w.ready and not w.proc.is_alive()]
                if dead:
                    raise RuntimeError(
                        f"worker(s) {dead} died during startup")
                continue
            if fid == -1 and wid in self._workers:
                self._workers[wid].ready = True

    # ---- data plane ---------------------------------------------------

    def submit(self, device: int, task: WorkerTask) -> None:
        w = self._workers[device]
        w.pending.add(task[0])
        w.task_q.put(task)

    def poll(self, timeout: float) -> List[WorkerDone]:
        """Drain completions: block up to ``timeout`` for the first, then
        sweep everything immediately available (mirrors the threads
        driver's queue drain)."""
        out: List[WorkerDone] = []
        try:
            out.append(self._done.get(timeout=max(timeout, 1e-4)))
        except _queue.Empty:
            return out
        while True:
            try:
                out.append(self._done.get_nowait())
            except _queue.Empty:
                break
        cleaned = []
        for ev in out:
            wid, fid = ev[0], ev[1]
            if fid == -1:                       # late ready beacon
                if wid in self._workers:
                    self._workers[wid].ready = True
                continue
            w = self._workers.get(wid)
            if w is not None:
                w.pending.discard(fid)
            cleaned.append(ev)
        return cleaned

    def get_payload(self, ref: PayloadRef) -> np.ndarray:
        return self._amap.get(ref)

    def free(self, ref: PayloadRef) -> None:
        self._amap.free(ref)

    # ---- supervision hooks --------------------------------------------

    def alive(self, device: int) -> bool:
        w = self._workers.get(device)
        return w is not None and w.proc.is_alive()

    def pending(self, device: int) -> Set[int]:
        w = self._workers.get(device)
        return set(w.pending) if w is not None else set()

    def restart(self, device: int) -> Set[int]:
        """Replace a dead/hung worker with a fresh process and a FRESH
        output arena (a crash can leave half-claimed slots; outputs the
        old worker already published stay readable through the old arena,
        which is kept attached until ``close``).  Returns the in-flight
        fids the caller must replay or fail."""
        w = self._workers.pop(device)
        inflight = set(w.pending)
        if w.proc.is_alive():
            w.proc.kill()
        w.proc.join(timeout=5.0)
        w.task_q.close()
        self._old_arenas.append(w.arena)        # refs may still be pinned
        self._spawn(device, gen=w.gen + 1)
        self.wait_ready()
        return inflight

    def generation(self, device: int) -> int:
        w = self._workers.get(device)
        return w.gen if w is not None else -1

    # ---- teardown -----------------------------------------------------

    def close(self) -> None:
        for w in self._workers.values():
            try:
                w.task_q.put(None)
            except (ValueError, OSError):  # pragma: no cover
                pass
        for w in self._workers.values():
            w.proc.join(timeout=5.0)
            if w.proc.is_alive():
                w.proc.kill()
                w.proc.join(timeout=5.0)
        self._amap.close()
        for w in self._workers.values():
            w.arena.unlink()
        for a in self._old_arenas:
            a.unlink()
        self._workers.clear()
        self._old_arenas.clear()
        self._done.close()


class WorkerSupervisor:
    """HealthMonitor-driven worker supervision.

    Every completion is a heartbeat for its worker ("device" in monitor
    terms).  A worker is declared dead when its PROCESS is gone — the
    definitive signal — or when it still holds in-flight tasks but has
    been heartbeat-silent past the timeout (hung, e.g. stuck in native
    code).  The engine then restarts it and replays its in-flight batches
    within the retry budget; ``HealthMonitor.reset_device`` clears the
    stale heartbeat so the replacement starts a fresh liveness record."""

    def __init__(self, pool: WorkerPool, heartbeat_timeout: float = 5.0):
        from repro.core.runtime import HealthMonitor
        self.pool = pool
        self.monitor = HealthMonitor(pool.devices(),
                                     heartbeat_timeout=heartbeat_timeout)
        self.restarts = 0

    def track(self, device: int, now: float) -> None:
        """Start (or restart) the liveness record for a worker."""
        self.monitor.reset_device(device)
        self.monitor.observe(now, {device: now})

    def beat(self, device: int, now: float) -> None:
        self.monitor.observe(now, {device: now})

    def dead_workers(self, now: float) -> List[int]:
        out = []
        for d in self.pool.devices():
            if not self.pool.alive(d):
                out.append(d)
            elif self.pool.pending(d) and \
                    d in self.monitor.dead_devices(now):
                out.append(d)
        return out

    def restart(self, device: int, now: float) -> Set[int]:
        inflight = self.pool.restart(device)
        self.restarts += 1
        self.track(device, now)
        return inflight


def stage_blob(tenant_stages: Sequence[Sequence]) -> bytes:
    """Pickle the per-tenant stage servers for worker spawning, with an
    actionable error naming the offending stage when one can't cross the
    process boundary."""
    try:
        return pickle.dumps([list(s) for s in tenant_stages],
                            protocol=pickle.HIGHEST_PROTOCOL)
    except Exception as e:
        for ti, stages in enumerate(tenant_stages):
            for si, st in enumerate(stages):
                try:
                    pickle.dumps(st, protocol=pickle.HIGHEST_PROTOCOL)
                except Exception:
                    raise TypeError(
                        f"stage {si} of tenant {ti} "
                        f"({type(st).__name__}) is not picklable; the "
                        f"processes backend ships stage servers to worker "
                        f"processes by pickle — implement __reduce__ (see "
                        f"ModelStageServer) or use a picklable stage"
                    ) from e
        raise

"""JAX-jitted annealing kernel for the Camelot joint solver.

The vectorized annealer's hot loop is already flat array math over
``_PolicyTables`` lookups — this module ports the
(gather → constraint reduction → masked argmax → Metropolis accept)
inner loop to one jitted ``lax.scan``, so the whole walk runs as a
single compiled XLA program instead of ``steps`` Python-level rounds of
numpy dispatch.

Division of labour with the numpy paths:

  * the **kernel** (float32) scores candidates with Constraints 2–4,
    the aggregate form of Constraint 1, and the exact group-sparse
    Constraint 5 (per-QoS-group critical paths over the same padded
    membership tensors ``IncrementalEvaluator`` builds).  Per-device
    packability (integer FFD) is data-dependent recursion that does not
    jit — the kernel is deliberately *optimistic* about it;
  * the **exact numpy evaluator** then re-scores the kernel's incumbent
    pool (per-walker bests + final walker states) with the full
    ``_eval_many`` — real FFD, float64 — picks the best truly feasible
    state, and hands it to the deterministic greedy ``_polish``.

So the returned allocation is always exact-feasible; jitting only
accelerates the search.  ``run_anneal`` returns ``None`` when the
kernel does not apply (non-linear utility curves, graph past the
group-path cap) or leaves no exact-feasible survivor, and ``_anneal``
then takes the vectorized numpy walk — mode "jax" can never produce a
result the dense path would reject.  A failure of the kernel itself
raises: on an accelerator it runs on the device, and a fault there
must not pass for an ordinary fallback.

The jitted program is cached per static shape signature
(n, walkers, candidates, mutations, grid, group tensors); re-solves at
the same scale (diurnal tracking, Eq. 3 device ladders) reuse the
compiled kernel and pay tracing exactly once.
"""
from __future__ import annotations

import math
import time
from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.deployment import pack_instances
from repro.core.incremental import IncrementalEvaluator
from repro.core.types import Allocation, StageAlloc


@lru_cache(maxsize=8)
def _build_kernel(n: int, W: int, C: int, n_mut: int, g: int, Gq: int,
                  E: int, bw_on: bool, maxload: bool):
    """Compile-once builder: returns the jitted annealing program for one
    static problem shape.  Everything data-like (tables, seeds, caps,
    temperature ladder) stays a traced argument, so only genuinely new
    shapes re-trace."""
    K = W * C
    move_dn = jnp.array([1, -1, 0, 0, 1, -1], jnp.int32)
    move_dq = jnp.array([0, 0, 1, -1, 0, 0], jnp.int32)

    def kernel(key, NS0, QI0, temps, dur, bwt, tht, foots, gridv, norm,
               A, B, g_nodes, ge_src, ge_dst, ge_tc, ge_th, targets,
               max_inst, cap_quota, cap_inst, cap_bw, cap_mem, req):
        ari = jnp.arange(n)

        def score_rows(NS_c, QI_c):
            NSf = NS_c.astype(jnp.float32)
            PS = gridv[QI_c]                                 # (K, n)
            dur_r = dur[ari[None, :], QI_c]
            thpt_min = (NSf * tht[ari[None, :], QI_c]
                        / norm[None, :]).min(axis=1)
            quota = (NSf * PS).sum(axis=1)
            feas = quota <= cap_quota
            feas &= NS_c.sum(axis=1) <= cap_inst
            if bw_on:
                feas &= (NSf * bwt[ari[None, :], QI_c]).sum(axis=1) \
                    <= cap_bw
            feas &= (NSf * foots[None, :]).sum(axis=1) <= cap_mem
            # Constraint-5: per-group critical paths via the padded
            # membership tensors (padded slots carry zero membership)
            durg = dur_r[:, g_nodes]                         # (K, Gq, mn)
            lat_p = jnp.einsum("gpj,kgj->kgp", A, durg)
            if E:
                colo = PS[:, ge_src] + PS[:, ge_dst] <= 1.0 + 1e-6
                ec = jnp.where(colo, ge_tc[None], ge_th[None])
                lat_p = lat_p + jnp.einsum("gpj,kgj->kgp", B, ec)
            feas &= (lat_p.max(axis=2) <= targets[None, :]).all(axis=1)
            if maxload:
                return jnp.where(feas, thpt_min, -jnp.inf)
            s = jnp.where(feas, -quota, -jnp.inf)
            return jnp.where(thpt_min >= req, s, -jnp.inf)

        def body(carry, temp):
            key, NS, QI, cur, bNS, bQI, bS = carry
            key, k1, k2, k3, k4, k5, k6 = jax.random.split(key, 7)
            NS_c = jnp.repeat(NS, C, axis=0)                 # walker-major
            QI_c = jnp.repeat(QI, C, axis=0)
            # compound candidates: 1..n_mut stacked single moves per row
            muts = jax.random.randint(k1, (K,), 1, n_mut + 1)
            ik = jax.random.randint(k2, (n_mut, K), 0, n)
            mk = jax.random.randint(k3, (n_mut, K), 0, 6)
            ar_k = jnp.arange(K)
            for t in range(n_mut):                           # static unroll
                active = muts > t
                i, mv = ik[t], mk[t]
                cn = jnp.take_along_axis(NS_c, i[:, None], 1)[:, 0]
                cq = jnp.take_along_axis(QI_c, i[:, None], 1)[:, 0]
                tn = jnp.clip(cn + move_dn[mv], 1, max_inst)
                tq = cq + move_dq[mv]
                tq = jnp.where(mv >= 4, jnp.rint(
                    (cq + 1) * cn / tn).astype(jnp.int32) - 1, tq)
                tq = jnp.clip(tq, 0, g - 1)
                NS_c = NS_c.at[ar_k, i].set(jnp.where(active, tn, cn))
                QI_c = QI_c.at[ar_k, i].set(jnp.where(active, tq, cq))
            sw = score_rows(NS_c, QI_c).reshape(W, C)
            # annealed explore-vs-argmax pick, then per-walker Metropolis
            jmax = jnp.argmax(sw, axis=1)
            jr = jax.random.randint(k4, (W,), 0, C)
            explore = jax.random.uniform(k5, (W,)) < jnp.minimum(temp, 1.0)
            sr = jnp.take_along_axis(sw, jr[:, None], 1)[:, 0]
            jc = jnp.where(explore & jnp.isfinite(sr), jr, jmax)
            sj = jnp.take_along_axis(sw, jc[:, None], 1)[:, 0]
            cur_ok = jnp.isfinite(cur)
            cur_safe = jnp.where(cur_ok, cur, 0.0)
            gap = jnp.where(cur_ok, sj - cur_safe, jnp.inf)
            prob = jnp.exp(jnp.minimum(
                gap / jnp.maximum(temp * jnp.abs(cur_safe) + 1e-12,
                                  1e-12), 0.0))
            u = jax.random.uniform(k6, (W,))
            accept = jnp.isfinite(sj) & ((gap >= 0) | (u < prob))
            rows = jnp.arange(W) * C + jc
            NS = jnp.where(accept[:, None], NS_c[rows], NS)
            QI = jnp.where(accept[:, None], QI_c[rows], QI)
            cur = jnp.where(accept, sj, cur)
            # per-walker incumbents over the whole evaluated fan — the
            # pool the exact numpy evaluator re-scores afterwards
            sb = jnp.take_along_axis(sw, jmax[:, None], 1)[:, 0]
            rb = jnp.arange(W) * C + jmax
            upd = sb > bS
            bNS = jnp.where(upd[:, None], NS_c[rb], bNS)
            bQI = jnp.where(upd[:, None], QI_c[rb], bQI)
            bS = jnp.where(upd, sb, bS)
            return (key, NS, QI, cur, bNS, bQI, bS), sb.max()

        cur0 = score_rows(
            jnp.repeat(NS0, C, axis=0), jnp.repeat(QI0, C, axis=0)
        ).reshape(W, C)[:, 0]
        init = (key, NS0, QI0, cur0, NS0, QI0, cur0)
        (key, NS, QI, cur, bNS, bQI, bS), hist = \
            jax.lax.scan(body, init, temps)
        return NS, QI, bNS, bQI, bS, hist

    return jax.jit(kernel)


def run_anneal(alloc, batch: int, n_devices: int, objective: str,
               required_load: Optional[float] = None,
               warm: Optional[Allocation] = None):
    """Run one jitted annealing walk for ``alloc`` (a CamelotAllocator or
    subclass).  Returns a SolveResult with ``mode="jax"`` or ``None`` when
    the kernel does not apply — the caller then takes the numpy
    vectorized path."""
    if getattr(alloc, "_util_codes", None) is not None:
        # non-linear utility curves reshape the max-load objective; the
        # float32 kernel would rank incumbents by the UNtransformed min
        # and keep the wrong pool — the numpy path applies them exactly.
        # (Isolation floor/cap bounds are different: the kernel searches
        # optimistically without them, and the exact `_eval_many` re-eval
        # below enforces them on every surviving incumbent.)
        return None
    from repro.core.allocator import SolveResult           # avoid cycle

    t_start = time.perf_counter()
    sa = alloc.sa
    n = alloc.pipeline.n_stages
    tab = alloc._policy_tables(batch)
    g = len(tab.grid)
    max_inst = n_devices * alloc.device.max_instances
    # the kernel shares the group-sparse Constraint-5 tensors with the
    # incremental evaluator; graphs past the path cap fall back to numpy
    engine = IncrementalEvaluator(alloc, tab, n_devices)
    if not engine.usable:
        return None

    k = max(1, int(sa.population))
    w = int(np.clip(sa.walkers, 1, k))
    c = max(1, k // w)
    n_mut = max(1, int(sa.max_mutations))
    NS0, QI0 = alloc._seed_walkers(tab, n_devices, w, g, max_inst)
    n_warm = 0
    if warm is not None and len(warm.stages) == n:
        from repro.core.types import QUOTA_STEP
        wns = np.clip(np.array([s.n_instances for s in warm.stages],
                               np.int64), 1, max_inst)
        wqi = np.clip(np.rint(np.array(
            [s.quota for s in warm.stages]) / QUOTA_STEP).astype(
                np.int64) - 1, 0, g - 1)
        NS0 = np.vstack([NS0, wns[None]])
        QI0 = np.vstack([QI0, wqi[None]])
        n_warm = 1
    W = w + n_warm
    steps = max(1, -(-sa.iterations * n_mut // (w * c)))
    temps = sa.t0 * (sa.t_end / sa.t0) ** (
        np.arange(steps) / max(steps - 1, 1))

    norm = alloc._node_norm
    norm = np.ones(n) if norm is None else np.asarray(norm, np.float64)
    Gq = engine.Gq
    E = engine.E
    f32 = np.float32
    ge = engine._g_edges
    kern = _build_kernel(n, W, c, n_mut, g, Gq, E,
                         bool(sa.bandwidth_constraint),
                         objective == "max_load")
    out = kern(
        jax.random.PRNGKey(sa.seed & 0x7FFFFFFF),
        jnp.asarray(NS0, jnp.int32), jnp.asarray(QI0, jnp.int32),
        jnp.asarray(temps, f32),
        jnp.asarray(tab.dur, f32), jnp.asarray(tab.bw, f32),
        jnp.asarray(tab.thpt, f32), jnp.asarray(tab.foots, f32),
        jnp.asarray(tab.grid, f32), jnp.asarray(norm, f32),
        jnp.asarray(engine._A, f32), jnp.asarray(engine._B, f32),
        jnp.asarray(engine._g_nodes, jnp.int32),
        jnp.asarray(tab.edge_src[ge] if E else ge, jnp.int32),
        jnp.asarray(tab.edge_dst[ge] if E else ge, jnp.int32),
        jnp.asarray(tab.edge_t_colo[ge] if E else ge, f32),
        jnp.asarray(tab.edge_t_host[ge] if E else ge, f32),
        jnp.asarray(engine._targets, f32),
        jnp.int32(max_inst),
        # float32 aggregate sums drift ~1e-4 at thousand-node scale:
        # admit borderline rows here, let the exact re-eval decide
        f32(n_devices * 1.0 + 1e-3),
        jnp.int32(max_inst),
        f32(n_devices * alloc.device.mem_bandwidth * (1 + 1e-6)),
        f32(n_devices * alloc.device.mem_capacity * (1 + 1e-6)),
        f32(required_load if required_load is not None else 0.0))
    NS_f, QI_f, bNS, bQI, bS, hist = (np.asarray(x) for x in out)

    # exact numpy re-evaluation of the incumbent pool (real FFD, float64)
    pool_ns = np.concatenate([bNS, NS_f]).astype(np.int64)
    pool_qi = np.concatenate([bQI, QI_f]).astype(np.int64)
    ev = alloc._eval_many(pool_ns, pool_qi, tab, n_devices)

    def scores(ev):
        thpt, quota, lat, feas = ev
        if objective == "max_load":
            return np.where(feas, thpt, -np.inf)
        s = np.where(feas, -quota, -np.inf)
        if required_load is not None:
            s = np.where(thpt >= required_load, s, -np.inf)
        return s

    s = scores(ev)
    j = int(np.argmax(s))
    if not np.isfinite(s[j]):
        return None                  # no exact-feasible survivor: fallback
    best_ns, best_qi, best_score = pool_ns[j].copy(), pool_qi[j].copy(), \
        float(s[j])
    history = [float(x) for x in hist]
    best_ns, best_qi, best_score = alloc._polish(
        best_ns, best_qi, best_score, scores, tab, n_devices, max_inst, g,
        history, engine=engine)

    ps = tab.grid[best_qi]
    thpt, quota, lat, feas = alloc._eval_many(
        best_ns[None], best_qi[None], tab, n_devices)
    feasible = bool(feas[0])
    result = Allocation(
        stages=[StageAlloc(int(best_ns[i]), float(ps[i]), batch)
                for i in range(n)],
        predicted_min_throughput=float(thpt[0]) if feasible else 0.0,
        predicted_latency=float(lat[0]) if feasible else float("inf"))
    if feasible:
        result.placement = pack_instances(
            result, alloc.pipeline, alloc.predictor, alloc.device,
            n_devices)
        feasible = result.placement is not None
    if not feasible:
        return None
    return SolveResult(allocation=result, objective=best_score,
                       feasible=True,
                       solve_time=time.perf_counter() - t_start,
                       iterations=sa.iterations, history=history,
                       mode="jax", warm_started=bool(n_warm))

"""Datacenter-scale solver: incremental evaluation parity, hierarchical
pod decomposition, the jitted annealing kernel, and cache bounds."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # degrade to deterministic example sweeps
    from _hypothesis_fallback import given, settings, st

from repro.core import (RTX_2080TI, CamelotAllocator, HierarchicalSolver,
                        MultiTenantAllocator, PipelinePredictor, PodConfig,
                        SAConfig)
from repro.core.incremental import IncrementalEvaluator
from repro.core.types import TenantSet
from repro.sim import multitenant_suite, synthetic_predictor, \
    synthetic_tenant_set
from repro.sim.workloads import camelot_suite


def _tenant_fixture(name="3-tenant-mixed"):
    tenants = TenantSet(multitenant_suite()[name])
    pred = PipelinePredictor.from_graph(tenants.union_graph, RTX_2080TI,
                                        seed=0)
    return tenants, pred


# --------------------------------------------------------------------------
# incremental evaluator == dense evaluator (the tentpole's correctness bar)
# --------------------------------------------------------------------------

@settings(max_examples=8)
@given(seed=st.integers(0, 10_000), steps=st.integers(1, 6))
def test_incremental_eval_matches_dense_on_random_mutations(seed, steps):
    """Random walker states + randomized <= max_mutations mutation rows,
    eval'd by the incremental engine and the dense ``_eval_many``, must
    agree on all four outputs — including across commits (cache folding)
    and the periodic rebase."""
    tenants, pred = _tenant_fixture()
    sa = SAConfig(iterations=10, seed=seed, mode="incremental")
    alloc = MultiTenantAllocator(tenants, pred, RTX_2080TI, 4, sa=sa)
    batch = 4
    tab = alloc._policy_tables(batch)
    engine = IncrementalEvaluator(alloc, tab, 4)
    assert engine.usable, "suite graphs must support the sparse engine"

    rng = np.random.default_rng(seed)
    n, g = tenants.n_nodes, len(tab.grid)
    W, C = 5, 2                      # walkers x candidates-per-walker
    n_mut = max(1, sa.max_mutations)
    NS_w = rng.integers(1, 4, size=(W, n))
    QI_w = rng.integers(0, g, size=(W, n))
    engine.rebase(NS_w, QI_w)
    base = np.repeat(np.arange(W), C)          # the anneal's row layout
    for _ in range(steps):
        NS = NS_w[base].copy()
        QI = QI_w[base].copy()
        for r in range(W * C):
            for i in rng.integers(0, n, size=rng.integers(1, n_mut + 1)):
                if rng.random() < 0.5:
                    NS[r, i] = rng.integers(1, 4)
                else:
                    QI[r, i] = rng.integers(0, g)
        t_i, q_i, l_i, f_i = engine.eval(NS, QI, base)
        t_d, q_d, l_d, f_d = alloc._eval_many(NS, QI, tab, 4)
        np.testing.assert_allclose(t_i, t_d, rtol=1e-9)
        np.testing.assert_allclose(q_i, q_d, rtol=1e-9)
        np.testing.assert_allclose(l_i, l_d, rtol=1e-9)
        np.testing.assert_array_equal(f_i, f_d)
        # each accepted walker folds one of ITS OWN candidate rows back
        # in (the anneal's contract: commit(w, r) has base[r] == w)
        acc = np.flatnonzero(rng.random(W) < 0.5)
        if acc.size:
            picked = acc * C + rng.integers(0, C, size=acc.size)
            engine.commit(acc, picked)
            NS_w[acc] = NS[picked]
            QI_w[acc] = QI[picked]


def test_incremental_mode_end_to_end_parity():
    """A full incremental-mode anneal returns the exact vectorized-mode
    result (same objective, bit-identical allocation)."""
    tenants, pred = _tenant_fixture()
    res = {}
    for mode in ("vectorized", "incremental"):
        sa = SAConfig(iterations=400, seed=3, mode=mode)
        res[mode] = MultiTenantAllocator(tenants, pred, RTX_2080TI, 4,
                                         sa=sa).solve_max_load(4)
    assert res["incremental"].mode == "incremental"
    assert res["incremental"].objective == res["vectorized"].objective
    assert res["incremental"].allocation.to_dict() == \
        res["vectorized"].allocation.to_dict()


# --------------------------------------------------------------------------
# hierarchical solver
# --------------------------------------------------------------------------

def test_hierarchical_one_pod_is_flat_bit_for_bit():
    tenants, pred = _tenant_fixture()
    sa = SAConfig(iterations=400, seed=3, mode="incremental")
    flat = MultiTenantAllocator(tenants, pred, RTX_2080TI, 4,
                                sa=sa).solve_max_load(4)
    hier = HierarchicalSolver(tenants, pred, RTX_2080TI, 4, sa=sa,
                              pods=PodConfig(pod_size=4)).solve_max_load(4)
    assert hier.objective == flat.objective
    assert hier.allocation.to_dict() == flat.allocation.to_dict()
    assert hier.pods is not None and len(hier.pods) == 1


def test_hierarchical_multi_pod_feasible_and_partitioned():
    tenants = synthetic_tenant_set(8, seed=7)
    pred = synthetic_predictor(tenants)
    sa = SAConfig(iterations=300, seed=0, mode="incremental")
    res = HierarchicalSolver(tenants, pred, RTX_2080TI, 8, sa=sa,
                             pods=PodConfig(pod_size=4, repair_rounds=1)
                             ).solve_max_load(4)
    assert res.feasible
    assert res.mode == "hierarchical"
    assert len(res.pods) == 2
    # every tenant lands in exactly one pod; pods tile the device range
    seen = [t for p in res.pods for t in p["tenants"]]
    assert sorted(seen) == sorted(t.name for t in tenants.tenants)
    spans = sorted(tuple(p["devices"]) for p in res.pods)
    assert spans[0][0] == 0 and spans[-1][1] == 8
    assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))
    # round-trips through the SolveResult dict (session persistence)
    from repro.core.allocator import SolveResult
    back = SolveResult.from_dict(res.to_dict())
    assert back.pods == res.pods and back.mode == res.mode


# --------------------------------------------------------------------------
# jitted annealing kernel
# --------------------------------------------------------------------------

def test_jax_kernel_within_tolerance_on_every_suite_workload():
    for name, tenants in multitenant_suite().items():
        ts = TenantSet(tenants)
        pred = PipelinePredictor.from_graph(ts.union_graph, RTX_2080TI,
                                            seed=0)
        out = {}
        for mode in ("vectorized", "jax"):
            sa = SAConfig(iterations=400, seed=3, mode=mode)
            out[mode] = MultiTenantAllocator(ts, pred, RTX_2080TI, 4,
                                             sa=sa).solve_max_load(4)
        assert out["jax"].mode == "jax", name
        assert out["jax"].feasible == out["vectorized"].feasible, name
        ratio = out["jax"].objective / out["vectorized"].objective
        assert ratio >= 0.98, f"{name}: jax objective ratio {ratio:.4f}"


# --------------------------------------------------------------------------
# cache bounds (long-running runtimes must hold a fixed footprint)
# --------------------------------------------------------------------------

def test_allocator_caches_bounded_across_1k_solves():
    suite = camelot_suite()
    pipe = suite["img-to-img"]
    pred = PipelinePredictor.from_graph(pipe, RTX_2080TI, seed=0)
    sa = SAConfig(iterations=4, seed=0, mode="vectorized")
    alloc = CamelotAllocator(pipe, pred, RTX_2080TI, 2, sa=sa)
    for k in range(1000):
        alloc.solve_max_load(batch=2 + (k % 40))   # 40 distinct batches
        assert len(alloc._tables_cache) <= alloc.TABLES_CACHE_MAX
        assert len(alloc._ffd_memo) <= alloc.FFD_MEMO_MAX
    # table cache saturates at its cap, not at the distinct-batch count
    assert len(alloc._tables_cache) == alloc.TABLES_CACHE_MAX


def test_ffd_memo_fifo_eviction():
    suite = camelot_suite()
    pipe = suite["img-to-img"]
    pred = PipelinePredictor.from_graph(pipe, RTX_2080TI, seed=0)
    alloc = CamelotAllocator(pipe, pred, RTX_2080TI, 2)
    alloc.FFD_MEMO_MAX = 64          # instance override shadows the class
    for k in range(500):
        alloc._ffd_cached([k, 1], 2)
    assert len(alloc._ffd_memo) == 64
    # the newest keys survived (FIFO evicts oldest first)
    assert (2, (499, 1)) in alloc._ffd_memo
    assert (2, (0, 1)) not in alloc._ffd_memo

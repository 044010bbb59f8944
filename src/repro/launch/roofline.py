"""Roofline accounting: HLO collective parsing + analytic FLOPs/bytes.

Methodology (full discussion in EXPERIMENTS.md §Roofline):
  * collective bytes are parsed from the compiled HLO text.  jax scans lower
    to HLO while loops whose bodies appear ONCE in the module, so collectives
    inside the scanned superblock would be undercounted by ~num_superblocks.
    We recover trip counts from the loop-condition constants and multiply
    through the call graph (while/fusion/call nesting).
  * FLOPs / HBM bytes come from a closed-form model over the config — for the
    same reason (cost_analysis counts while bodies once).  The closed form is
    validated against cost_analysis on an unrolled smoke config in
    tests/test_roofline.py; the raw cost_analysis numbers are recorded
    alongside for transparency.
  * Convention: parsed collective bytes are per-device (the SPMD module is
    the per-device program); ``total_bytes`` in the report is per-device, and
    the collective term is per_device_bytes / ici_bandwidth.
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

from repro.configs.base import (ATTN, CROSS, MAMBA, MLSTM, SLSTM,
                                HardwareSpec, InputShape, ModelConfig,
                                active_param_count, param_count)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[\w\[\],{}\s]*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_CALL_RE = re.compile(r"(?:body|calls|to_apply|branch_computations)="
                      r"\{?%?([\w\.\-]+)")
_WHILE_RE = re.compile(
    r"while\(.*?condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    """computation name -> list of body lines."""
    comps: Dict[str, List[str]] = {}
    cur = None
    for line in hlo.splitlines():
        stripped = line.strip()
        if cur is None:
            m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\(.*)?\{?\s*$",
                         stripped)
            if stripped.endswith("{") and ("(" in stripped
                                           or stripped.startswith("ENTRY")):
                name = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)", stripped)
                if name:
                    cur = name.group(1)
                    comps[cur] = []
        else:
            if stripped == "}" or stripped.startswith("} "):
                cur = None
            else:
                comps[cur].append(stripped)
    return comps


def parse_collectives(hlo: str) -> dict:
    """Collective byte counts (per device) with while-trip-count roll-up."""
    comps = _split_computations(hlo)

    # per-computation direct collective bytes + op counts
    direct: Dict[str, Dict[str, float]] = {}
    edges: Dict[str, List[Tuple[str, int]]] = {}
    trip_of_body: Dict[str, int] = {}
    for name, lines in comps.items():
        d: Dict[str, float] = {}
        e: List[Tuple[str, int]] = []
        for ln in lines:
            cm = _COLL_RE.search(ln)
            if cm:
                kind = cm.group(2)
                nbytes = _shape_bytes(cm.group(1))
                if nbytes == 0:           # fall back: operand shapes
                    nbytes = _shape_bytes(ln.split("(", 1)[-1])
                d[kind] = d.get(kind, 0.0) + nbytes
                d[kind + "_count"] = d.get(kind + "_count", 0) + 1
            wm = _WHILE_RE.search(ln)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                trip = 1
                consts = [int(c) for c in
                          _CONST_RE.findall("\n".join(comps.get(cond, [])))]
                if consts:
                    trip = max(consts)
                trip_of_body[body] = trip
                e.append((body, trip))
                e.append((cond, 1))
            else:
                for callee in _CALL_RE.findall(ln):
                    e.append((callee, 1))
        direct[name] = d
        edges[name] = e

    # find entry (computation not called by anyone, or named main)
    called = {c for es in edges.values() for c, _ in es}
    entries = [n for n in comps if n not in called]
    roots = entries or [n for n in comps if "main" in n]

    # roll up multipliers through the call graph (memoised DFS)
    totals: Dict[str, float] = {}
    counts: Dict[str, float] = {}

    import functools

    @functools.lru_cache(maxsize=None)
    def rolled(name: str) -> Tuple[Tuple[Tuple[str, float], ...],]:
        acc: Dict[str, float] = dict(direct.get(name, {}))
        for callee, mult in edges.get(name, []):
            if callee == name or callee not in comps:
                continue
            sub = dict(rolled(callee)[0])
            for k, v in sub.items():
                acc[k] = acc.get(k, 0.0) + v * mult
        return (tuple(sorted(acc.items())),)

    agg: Dict[str, float] = {}
    for r in roots:
        for k, v in rolled(r)[0]:
            agg[k] = agg.get(k, 0.0) + v

    kinds = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")
    out = {k: float(agg.get(k, 0.0)) for k in kinds}
    out["counts"] = {k: int(agg.get(k + "_count", 0)) for k in kinds}
    out["total_bytes"] = float(sum(out[k] for k in kinds))
    out["while_trip_counts"] = {b: t for b, t in trip_of_body.items()}
    return out


# ==========================================================================
# Analytic FLOPs / HBM bytes (global, whole cluster)
# ==========================================================================

def _per_layer_matmul_params(cfg: ModelConfig) -> Tuple[float, float]:
    """(dense-active params per layer-pattern, moe-expert params active)."""
    total = 0.0
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    for kind, mlp in zip(cfg.block_pattern, cfg.mlp_pattern):
        if kind in (ATTN, CROSS):
            total += d * cfg.num_heads * hd + 2 * d * cfg.num_kv_heads * hd \
                + cfg.num_heads * hd * d
            if kind == CROSS:
                total += d * cfg.num_heads * hd + cfg.num_heads * hd * d
        elif kind == MAMBA:
            inner = cfg.ssm_expand * d
            total += d * 2 * inner + inner * d \
                + inner * (max(1, d // 16) + 2 * cfg.ssm_state_dim) \
                + max(1, d // 16) * inner
        elif kind == MLSTM:
            inner = cfg.xlstm_expand * d
            total += d * 2 * inner + inner * d \
                + 3 * inner * (inner // cfg.xlstm_num_heads)
        elif kind == SLSTM:
            nh = cfg.xlstm_num_heads
            total += 4 * d * d + 4 * d * (d // nh) + 2 * d * int(d * 4 / 3)
        if mlp == "dense":
            total += 3 * d * cfg.d_ff
        elif mlp == "moe":
            total += 3 * d * cfg.moe.d_expert * cfg.moe.top_k \
                + d * cfg.moe.num_experts
    return total / len(cfg.block_pattern), 0.0


def _attn_quadratic_flops(cfg: ModelConfig, b: int, s: int,
                          s_kv: int) -> float:
    """Per ATTN/CROSS layer: masked-full-KV scores + PV (the implementation
    computes the full rectangle; causal skipping is a §Perf item)."""
    hd = cfg.resolved_head_dim
    return 2.0 * 2.0 * b * s * s_kv * cfg.num_heads * hd


def _mixer_extra_flops(cfg: ModelConfig, b: int, s: int, mode: str) -> float:
    """Non-projection flops of SSM/xLSTM mixers per superblock pass."""
    d = cfg.d_model
    extra = 0.0
    for kind in cfg.block_pattern:
        if kind == MAMBA:
            inner = cfg.ssm_expand * d
            st = cfg.ssm_state_dim
            extra += 8.0 * b * s * inner * st        # scan + y=C·h
        elif kind == MLSTM:
            inner = cfg.xlstm_expand * d
            h = cfg.xlstm_num_heads
            hd = inner // h
            if mode == "decode":
                extra += 4.0 * b * h * hd * hd
            else:
                l = min(256, s)
                extra += 6.0 * b * h * s * l * hd \
                    + 4.0 * b * h * s * hd * hd / max(l, 1) * l  # carry upd
        elif kind == SLSTM:
            extra += 30.0 * b * s * d
    return extra / len(cfg.block_pattern)


def analytic_costs(cfg: ModelConfig, shp: InputShape,
                   weight_replicas: int = 1,
                   weight_bytes: float = 2.0) -> dict:
    """Global FLOPs / HBM bytes for one (arch, shape) combo.

    weight_replicas: how many independent copies of the weights the mesh
    holds (inference shards weights over the model axis only, so every
    data-parallel replica re-reads them — decode is usually bound by this).
    weight_bytes: bytes per weight (2 = bf16; 1 = int8-quantized serving).
    """
    b, s = shp.global_batch, shp.seq_len
    mode = shp.kind
    n_layers = cfg.num_layers
    d, v = cfg.d_model, cfg.vocab_size
    p_total = param_count(cfg)
    p_active = active_param_count(cfg)
    per_layer_mm, _ = _per_layer_matmul_params(cfg)

    from repro.models.transformer import decode_cache_len
    s_cache = decode_cache_len(cfg, s)

    if mode in ("train", "prefill"):
        toks = b * s
        linear = 2.0 * toks * (per_layer_mm * n_layers + d * v)
        attn_layers = sum(1 for k in cfg.block_pattern if k in (ATTN, CROSS))
        s_kv = min(s, cfg.sliding_window) if cfg.sliding_window else s
        quad = _attn_quadratic_flops(cfg, b, s, s_kv) * attn_layers \
            * cfg.num_superblocks
        mixer = _mixer_extra_flops(cfg, b, s, mode) * n_layers
        enc = 0.0
        if cfg.encoder_decoder:
            se = cfg.encoder_seq_len
            enc_params = cfg.num_encoder_layers * (
                4 * d * cfg.num_heads * cfg.resolved_head_dim // 2 * 2
                + 3 * d * cfg.d_ff)
            enc = 2.0 * b * se * enc_params \
                + _attn_quadratic_flops(cfg, b, se, se) \
                * cfg.num_encoder_layers
            # cross-attention PV against encoder keys
            quad += 2.0 * 2.0 * b * s * se * cfg.num_heads \
                * cfg.resolved_head_dim * attn_layers * cfg.num_superblocks \
                * (1 if CROSS in cfg.block_pattern else 0)
        fwd = linear + quad + mixer + enc
        if mode == "train":
            flops = 4.0 * fwd          # fwd + 2×bwd + remat re-fwd
            model_flops = 6.0 * p_active * toks
            # HBM: 3 weight passes + grads + fp32 adam m/v/p read+write
            wbytes = p_total * (3 * 2 + 2 + 24)
            act = n_layers * toks * d * 2 * 4
            logits_b = toks * v * 2 * 3
            hbm = wbytes + act + logits_b
        else:
            flops = fwd
            model_flops = 2.0 * p_active * toks
            cache_b = (n_layers * b * s_cache * cfg.num_kv_heads
                       * cfg.resolved_head_dim * 2 * 2
                       if any(k in (ATTN, CROSS) for k in cfg.block_pattern)
                       else 0)
            hbm = p_total * weight_bytes * weight_replicas \
                + n_layers * toks * d * 2 * 2 + cache_b + toks * v * 2
    else:  # decode: one token
        toks = b
        linear = 2.0 * toks * (per_layer_mm * n_layers + d * v)
        attn_layers = sum(1 for k in cfg.block_pattern if k in (ATTN, CROSS)) \
            * cfg.num_superblocks
        quad = 2.0 * 2.0 * b * cfg.num_heads * cfg.resolved_head_dim \
            * s_cache * attn_layers
        if cfg.encoder_decoder:
            quad += 2.0 * 2.0 * b * cfg.num_heads * cfg.resolved_head_dim \
                * cfg.encoder_seq_len * attn_layers
        mixer = _mixer_extra_flops(cfg, b, 1, "decode") * n_layers
        flops = linear + quad + mixer
        model_flops = 2.0 * p_active * toks
        # weights touched once per replica group; MoE: expected unique
        # experts across the batch
        wbytes = p_total * weight_bytes
        if cfg.moe is not None:
            e, k = cfg.moe.num_experts, cfg.moe.top_k
            n_moe = sum(1 for m in cfg.mlp_pattern if m == "moe") \
                * cfg.num_superblocks
            expert_p = 3 * d * cfg.moe.d_expert
            frac = min(1.0, b * k / e)
            wbytes = (p_total - e * expert_p * n_moe) * weight_bytes \
                + e * expert_p * n_moe * weight_bytes * frac
        wbytes *= weight_replicas
        cache_b = n_layers * b * s_cache * cfg.num_kv_heads \
            * cfg.resolved_head_dim * 2 * 2 \
            if any(k_ in (ATTN, CROSS) for k_ in cfg.block_pattern) else 0
        state_b = 0
        if MAMBA in cfg.block_pattern or MLSTM in cfg.block_pattern:
            inner = max(cfg.ssm_expand, cfg.xlstm_expand) * d
            per = inner * cfg.ssm_state_dim * 4 if MAMBA in cfg.block_pattern \
                else (inner // cfg.xlstm_num_heads) * inner * 4
            state_b = n_layers * b * per * 2
        hbm = wbytes + cache_b + state_b + toks * v * 2

    return {
        "flops": float(flops),
        "model_flops": float(model_flops),
        "hbm_bytes": float(hbm),
        "useful_ratio": float(model_flops / max(flops, 1.0)),
        "tokens": int(toks),
    }


def roofline_terms(analytic: dict, coll_bytes_per_dev: float, chips: int,
                   hw: HardwareSpec) -> dict:
    t_compute = analytic["flops"] / (chips * hw.peak_flops)
    t_memory = analytic["hbm_bytes"] / (chips * hw.hbm_bandwidth)
    t_coll = coll_bytes_per_dev / hw.ici_bandwidth
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    bound = max(t_compute, t_memory, t_coll)
    return {
        **terms,
        "dominant": dom.replace("_s", ""),
        "bound_s": bound,
        "mfu_upper_bound": t_compute / max(bound, 1e-30),
        "model_flops_ratio": analytic["useful_ratio"],
    }

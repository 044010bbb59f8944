import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import: jax locks the device
# count at first initialisation.  Do not set this flag globally — smoke tests
# and benchmarks are supposed to see the single real CPU device.

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes and extract the roofline inputs.

Per combo this produces a JSON artifact with:
  - memory_analysis (bytes per device: arguments/outputs/temps) — proves fit;
  - cost_analysis raw FLOPs/bytes (per-device, scan bodies counted once —
    see §Roofline methodology note in EXPERIMENTS.md);
  - collective bytes parsed from the compiled HLO, with while-loop trip
    counts recovered from loop-condition constants;
  - analytic FLOPs/bytes (closed-form over the config — the primary terms);
  - the three roofline terms and the dominant one.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, INPUT_SHAPES, TPU_V5E, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (analytic_costs, parse_collectives,
                                   roofline_terms)
from repro.launch.sharding import ShardingRules
from repro.models import (abstract_cache, abstract_params, decode_cache_len,
                          forward_train, serve_decode, serve_prefill,
                          set_sharding_rules)
from repro.models.common import set_shard_context
from repro.models.transformer import ModelCache
from repro.training.optimizer import AdamWConfig, init_adamw
from repro.training.train_step import make_train_step


def input_specs(arch: str, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of this combo."""
    cfg = get_config(arch)
    shp = INPUT_SHAPES[shape_name]
    b, s = shp.global_batch, shp.seq_len
    i32 = jnp.int32
    if shp.kind == "train":
        batch = {"tokens": jax.ShapeDtypeStruct((b, s), i32),
                 "labels": jax.ShapeDtypeStruct((b, s), i32)}
        if cfg.encoder_decoder:
            batch["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_seq_len, cfg.d_model), jnp.bfloat16)
        return batch
    if shp.kind == "prefill":
        out = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        if cfg.encoder_decoder:
            out["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_seq_len, cfg.d_model), jnp.bfloat16)
        return out
    # decode: one new token against a seq_len-deep cache
    return {"tokens": jax.ShapeDtypeStruct((b,), i32)}


def _lower_combo(arch: str, shape_name: str, mesh, remat: bool = True):
    cfg = get_config(arch)
    shp = INPUT_SHAPES[shape_name]
    b, s = shp.global_batch, shp.seq_len
    rules = ShardingRules(cfg, mesh, mode={"train": "train",
                                           "prefill": "prefill",
                                           "decode": "decode"}[shp.kind],
                          global_batch=b, seq_len=s)
    set_sharding_rules(rules.activation_rules())
    # shard-local dispatch layers (MoE scatter, sLSTM time scan) — only for
    # segment-level modes with a shardable batch
    if shp.kind in ("train", "prefill") and rules.batch_shardable:
        set_shard_context({
            "mesh": mesh, "dp": rules.dp,
            "tp": "model" if rules.tp_enabled else None,
            "tp_size": rules.tp_n if rules.tp_enabled else 0})
    else:
        set_shard_context(None)
    params_abs = abstract_params(cfg)
    params_sh = rules.params_shardings(params_abs)

    if shp.kind == "train":
        opt_abs = jax.eval_shape(init_adamw, params_abs)
        opt_sh = rules.opt_shardings(opt_abs, params_abs)
        batch_abs = input_specs(arch, shape_name)
        batch_sh = rules.batch_shardings(batch_abs)
        step = make_train_step(cfg, AdamWConfig(), remat=remat)
        with mesh:
            lowered = jax.jit(
                step, in_shardings=(params_sh, opt_sh, batch_sh),
                donate_argnums=(0, 1),   # params/opt update in place
            ).lower(params_abs, opt_abs, batch_abs)
        return lowered, cfg, shp

    if shp.kind == "prefill":
        ins = input_specs(arch, shape_name)
        tokens_abs = ins["tokens"]
        frames_abs = ins.get("frames")
        ins_sh = rules.batch_shardings(ins)

        def fn(params, tokens, frames=None):
            return serve_prefill(params, tokens, cfg, cache_len=s,
                                 frames=frames, remat=True)

        with mesh:
            if frames_abs is not None:
                lowered = jax.jit(fn, in_shardings=(
                    params_sh, ins_sh["tokens"], ins_sh["frames"]),
                ).lower(params_abs, tokens_abs, frames_abs)
            else:
                lowered = jax.jit(fn, in_shardings=(
                    params_sh, ins_sh["tokens"]),
                ).lower(params_abs, tokens_abs)
        return lowered, cfg, shp

    # decode
    cache_abs = abstract_cache(cfg, b, s)
    cache_sh_blocks = rules.cache_shardings(cache_abs)
    tokens_abs = input_specs(arch, shape_name)["tokens"]
    tokens_sh = rules.ns(rules.dp if rules.batch_shardable else None)

    quantize = os.environ.get("REPRO_QUANTIZE_DECODE") == "1"
    if quantize:
        # int8 weight serving (per-tensor scale; §Perf hillclimb #3): weight
        # matrices stored int8 in HBM, dequantised into the dot (fused) —
        # halves the per-step weight-read bound of batch decode
        def _q(x):
            if x.ndim >= 2 and x.dtype == jnp.bfloat16:
                return jax.ShapeDtypeStruct(x.shape, jnp.int8)
            return x
        params_q_abs = jax.tree.map(_q, params_abs)
        scales_abs = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((), jnp.float32)
            if x.dtype == jnp.int8 else None, params_q_abs,
            is_leaf=lambda x: hasattr(x, "dtype"))

        def dequant(pq, scales):
            return jax.tree.map(
                lambda x, sc: (x.astype(jnp.bfloat16) * sc.astype(jnp.bfloat16))
                if x.dtype == jnp.int8 else x, pq, scales,
                is_leaf=lambda x: hasattr(x, "dtype"))

        def fn(params_q, scales, cache, tokens):
            return serve_decode(dequant(params_q, scales), cache, tokens, cfg)

        scales_sh = jax.tree.map(lambda s_: rules.ns() if s_ is not None
                                 else None, scales_abs,
                                 is_leaf=lambda x: hasattr(x, "dtype"))
        with mesh:
            lowered = jax.jit(
                fn, in_shardings=(params_sh, scales_sh, cache_sh_blocks,
                                  tokens_sh),
                donate_argnums=(2,),
            ).lower(params_q_abs, scales_abs, cache_abs, tokens_abs)
        return lowered, cfg, shp

    def fn(params, cache, tokens):
        return serve_decode(params, cache, tokens, cfg)

    with mesh:
        lowered = jax.jit(
            fn, in_shardings=(params_sh, cache_sh_blocks, tokens_sh),
            donate_argnums=(1,),         # cache updates in place
        ).lower(params_abs, cache_abs, tokens_abs)
    return lowered, cfg, shp


def run_combo(arch: str, shape_name: str, multi_pod: bool = False,
              compile_: bool = True) -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    lowered, cfg, shp = _lower_combo(arch, shape_name, mesh)
    t_lower = time.time() - t0
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(n_chips),
        "mode": shp.kind,
        "t_lower_s": round(t_lower, 2),
        "status": "lowered",
    }
    if not compile_:
        return rec
    t0 = time.time()
    compiled = lowered.compile()
    rec["t_compile_s"] = round(time.time() - t0, 2)
    ma = compiled.memory_analysis()
    rec["memory_per_device"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "total_bytes": int(ma.argument_size_in_bytes
                           + ma.output_size_in_bytes
                           + ma.temp_size_in_bytes
                           - ma.alias_size_in_bytes),
    }
    ca = compiled.cost_analysis()
    rec["cost_analysis_raw"] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }
    coll = parse_collectives(compiled.as_text())
    rec["collectives"] = coll
    # inference shards weights over the model axis only -> every data-
    # parallel replica group re-reads its own weight copy each step
    from repro.launch.mesh import dp_size
    replicas = dp_size(mesh) if shp.kind in ("prefill", "decode") else 1
    wb = 1.0 if os.environ.get("REPRO_QUANTIZE_DECODE") == "1" \
        and shp.kind == "decode" else 2.0
    analytic = analytic_costs(cfg, shp, weight_replicas=replicas,
                              weight_bytes=wb)
    rec["analytic"] = analytic
    rec["weight_replicas"] = replicas
    rec["weight_bytes"] = wb
    rec["roofline"] = roofline_terms(
        analytic, coll["total_bytes"], n_chips, TPU_V5E)
    rec["status"] = "ok"
    rec["fits_hbm"] = rec["memory_per_device"]["total_bytes"] \
        <= TPU_V5E.hbm_capacity
    # XLA:CPU converts every bf16 weight to f32 before its dots (no native
    # bf16 matmul on the host backend), inflating temp_bytes by ~2× the
    # parameter bytes; on TPU the MXU consumes bf16 directly.  Record the
    # resident-state-only check alongside (see EXPERIMENTS.md §Dry-run).
    resident = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes)
    rec["fits_hbm_resident"] = bool(resident <= TPU_V5E.hbm_capacity)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    combos = ([(a, s) for a in ARCH_IDS for s in INPUT_SHAPES]
              if args.all else [(args.arch, args.shape)])
    os.makedirs(args.out, exist_ok=True)
    mesh_tag = "multipod" if args.multi_pod else "pod"
    for arch, shape in combos:
        tag = f"{arch}_{shape}_{mesh_tag}"
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path):
            print(f"[skip] {tag}")
            continue
        try:
            rec = run_combo(arch, shape, multi_pod=args.multi_pod,
                            compile_=not args.no_compile)
        except Exception as e:   # noqa: BLE001 — record the failure
            rec = {"arch": arch, "shape": shape, "mesh": mesh_tag,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        mem = rec.get("memory_per_device", {}).get("total_bytes", 0) / 1e9
        print(f"[{rec['status']}] {tag} mem/dev={mem:.2f}GB "
              f"coll={rec.get('collectives', {}).get('total_bytes', 0)/1e9:.2f}GB "
              f"dom={rec.get('roofline', {}).get('dominant', '-')}",
              flush=True)


if __name__ == "__main__":
    main()

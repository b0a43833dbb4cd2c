import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh, and derive the roofline terms from the compiled
artifact.  No real memory is allocated — all inputs are ShapeDtypeStructs.

The two lines above MUST stay the first statements in this file: jax locks
the device count on first backend initialization, and the production mesh
needs 512 placeholder devices.  (Everything else in the repo sees the real
single CPU device — this flag is set here and nowhere else.)

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all [--multi-pod]
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.compile_cache import enable_compile_cache
from repro.configs.base import (
    ALIASES,
    ARCH_IDS,
    INPUT_SHAPES,
    ArchConfig,
    InputShape,
    get_config,
)
from repro.launch import roofline as rf
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import batch_specs, decode_specs
from repro.launch.steps import (
    TrainHParams,
    make_optimizer,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
from repro.models.model import make_model
from repro.sharding import (
    DEFAULT_RULES,
    FSDP_RULES,
    tree_shardings,
)


def rules_for(cfg: ArchConfig, shape: InputShape, optimized: bool = False) -> dict:
    base = FSDP_RULES if cfg.sharding_rules == "fsdp" else DEFAULT_RULES
    rules = dict(base)
    if shape.name == "long_500k":
        # batch=1 cannot use the data axis; shard the KV/state sequence
        # dim over it instead (flash-decoding style).
        rules["batch"] = None
        rules["kv_seq"] = "data"
    elif shape.kind == "decode" and optimized:
        # §Perf iteration (llama3-405b decode_32k): GQA kv_heads rarely
        # divide model=16, leaving the KV cache replicated on the model
        # axis — shard its sequence dim there instead (kv_seq takes the
        # axis first; flash-decoding-style partial softmax combines).
        # Baseline: 410 GB/dev + 5.4 s collective; optimized: 40 GB/dev
        # (13 GB after donation aliasing) + 0.018 s.  See EXPERIMENTS.md.
        rules["kv_seq"] = "model"
    return rules


def skip_reason(cfg: ArchConfig, shape: InputShape) -> str | None:
    if shape.name == "long_500k" and not cfg.is_subquadratic:
        return (
            "long_500k requires sub-quadratic attention; "
            f"{cfg.name} is pure full-attention (DESIGN.md §Arch-applicability)"
        )
    return None


def lower_step(model, cfg, shape, mesh, rules):
    """Lower the workload's step function with explicit shardings."""
    replicated = NamedSharding(mesh, P())
    params_sds, axes = model.abstract()
    p_shard = tree_shardings(axes, mesh, rules, params_sds)

    if shape.kind in ("train", "prefill"):
        b_sds, b_axes = batch_specs(cfg, shape)
        b_shard = tree_shardings(b_axes, mesh, rules, b_sds)
        if shape.kind == "train":
            opt = make_optimizer(TrainHParams())
            opt_sds = jax.eval_shape(opt.init, params_sds)
            o_shard = optim.state_shardings(opt_sds, p_shard, replicated)
            step = make_train_step(model, opt)
            with mesh:
                lowered = jax.jit(
                    step,
                    in_shardings=(p_shard, o_shard, b_shard),
                    out_shardings=(p_shard, o_shard, replicated),
                    donate_argnums=(0, 1),
                ).lower(params_sds, opt_sds, b_sds)
        else:
            step = make_prefill_step(model)
            with mesh:
                lowered = jax.jit(
                    step,
                    in_shardings=(p_shard, b_shard),
                    out_shardings=(replicated, replicated),
                ).lower(params_sds, b_sds)
    else:  # decode
        box = {}

        def build_cache():
            cache, cache_axes = model.init_cache(
                shape.global_batch, shape.seq_len
            )
            box["axes"] = cache_axes
            return cache

        cache_sds = jax.eval_shape(build_cache)
        c_shard = tree_shardings(box["axes"], mesh, rules, cache_sds)
        tok_sds, tok_axes = decode_specs(cfg, shape)
        tok_shard = {
            "tokens": tree_shardings(
                tok_axes["tokens"], mesh, rules, tok_sds["tokens"]
            ),
            "pos": replicated,
        }
        step = make_serve_step(model)
        with mesh:
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, c_shard, tok_shard["tokens"],
                              tok_shard["pos"]),
                out_shardings=(tok_shard["tokens"], c_shard),
                donate_argnums=(1,),
            ).lower(params_sds, cache_sds, tok_sds["tokens"], tok_sds["pos"])
    return lowered


def dryrun_one(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    mesh=None,
    moe_impl: str = "sort",
    extra_rules: dict | None = None,
    roofline_pass: bool | None = None,
    cfg_overrides: dict | None = None,
    optimized: bool = False,
) -> dict:
    """Two-pass dry-run for one (arch, shape, mesh):

    Pass A — the PRODUCTION artifact (scan-over-layers, microbatching,
    remat): lower + compile proves the distribution config is coherent;
    memory_analysis() proves it fits.

    Pass B — an UNROLLED twin (python-loop layers, microbatches=1): XLA
    cost analysis counts a scan body once, so only the unrolled HLO yields
    honest roofline FLOPs/bytes/collective terms.  Single-pod only (the
    roofline table is single-pod per the assignment); multi-pod runs pass A
    only.
    """
    import dataclasses as _dc

    cfg = get_config(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    shape = INPUT_SHAPES[shape_name]
    result = {
        "arch": cfg.name,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
        "moe_impl": moe_impl,
        "cfg_overrides": cfg_overrides or {},
        "extra_rules": {k: str(v) for k, v in (extra_rules or {}).items()},
    }
    reason = skip_reason(cfg, shape)
    if reason:
        result["skipped"] = reason
        return result

    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rules = rules_for(cfg, shape, optimized=optimized)
    if extra_rules:
        rules.update(extra_rules)
    if roofline_pass is None:
        roofline_pass = not multi_pod

    # ---- pass A: production artifact ------------------------------------
    t0 = time.time()
    lowered = lower_step(make_model(cfg, moe_impl=moe_impl, mesh=mesh),
                         cfg, shape, mesh, rules)
    result["lower_s"] = round(time.time() - t0, 1)
    t0 = time.time()
    compiled = lowered.compile()
    result["compile_s"] = round(time.time() - t0, 1)
    mem = compiled.memory_analysis()
    result["memory"] = {
        "argument_gb": getattr(mem, "argument_size_in_bytes", 0) / 1e9,
        "output_gb": getattr(mem, "output_size_in_bytes", 0) / 1e9,
        "temp_gb": getattr(mem, "temp_size_in_bytes", 0) / 1e9,
        "peak_gb": (
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)
        )
        / 1e9,
        "fits_16gb": (
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)
        )
        < 16e9,
    }

    # ---- pass B: unrolled twin for honest roofline terms -----------------
    if roofline_pass:
        import dataclasses as dc

        t0 = time.time()
        model_flops = rf.model_flops_for(cfg, shape, shape.kind)
        if (
            cfg.num_layers >= 40
            and not cfg.layer_pattern
            and cfg.cross_attn_every == 0
        ):
            # deep uniform stacks (llama3-405b 126L, mamba2 48L): compiling
            # the fully-unrolled twin is prohibitively slow, and per-layer
            # cost is exactly linear in depth for a uniform stack.  Lower
            # two shallow unrolled twins and extrapolate the scalars.
            pts = []
            for L in (2, 4):
                cfg_l = dc.replace(cfg, microbatches=1, num_layers=L)
                lowered_l = lower_step(
                    make_model(cfg_l, moe_impl=moe_impl, unroll=True,
                               mesh=mesh),
                    cfg_l, shape, mesh, rules,
                )
                pts.append(rf.analyze(lowered_l.compile(), chips, model_flops))
            roof = rf.extrapolate_layers(pts[0], pts[1], (2, 4),
                                         cfg.num_layers)
            result["roofline_method"] = "layer-extrapolated (L=2,4)"
        else:
            cfg_b = dc.replace(cfg, microbatches=1)
            lowered_b = lower_step(
                make_model(cfg_b, moe_impl=moe_impl, unroll=True, mesh=mesh),
                cfg_b, shape, mesh, rules,
            )
            roof = rf.analyze(lowered_b.compile(), chips, model_flops)
            result["roofline_method"] = "unrolled"
        result["roofline_pass_s"] = round(time.time() - t0, 1)
        result["roofline"] = roof.to_dict()

    result["params_m"] = cfg.param_count() / 1e6
    result["active_params_m"] = cfg.active_param_count() / 1e6
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="input shape or 'all'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--moe-impl", default="sort",
                    choices=["sort", "dense", "a2a"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=val (perf iterations)")
    ap.add_argument("--rule", action="append", default=[],
                    help="sharding rule override key=axis|none")
    ap.add_argument("--tag", default="", help="suffix for output filenames")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the §Perf-tuned rule set")
    args = ap.parse_args()
    enable_compile_cache()

    def parse_val(v):
        if v.lower() in ("none", "null"):
            return None
        if v.lower() in ("true", "false"):
            return v.lower() == "true"
        try:
            return int(v)
        except ValueError:
            try:
                return float(v)
            except ValueError:
                return v

    cfg_overrides = {}
    for item in args.set:
        k, v = item.split("=", 1)
        cfg_overrides[k] = parse_val(v)
    extra_rules = {}
    for item in args.rule:
        k, v = item.split("=", 1)
        extra_rules[k] = parse_val(v)

    archs = ARCH_IDS if args.arch == "all" else [ALIASES.get(args.arch, args.arch)]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for multi_pod in meshes:
        mesh = make_production_mesh(multi_pod=multi_pod)
        for arch in archs:
            for shape in shapes:
                tag = f"{arch}_{shape}_{'2x16x16' if multi_pod else '16x16'}"
                if args.tag:
                    tag += "_" + args.tag
                path = os.path.join(args.out, tag + ".json")
                try:
                    res = dryrun_one(
                        arch, shape, multi_pod=multi_pod, mesh=mesh,
                        moe_impl=args.moe_impl,
                        cfg_overrides=cfg_overrides or None,
                        extra_rules=extra_rules or None,
                        optimized=args.optimized,
                    )
                    if "skipped" in res:
                        status = "SKIP"
                    else:
                        dom = res.get("roofline", {}).get("dominant", "-")
                        status = (
                            f"ok lower={res['lower_s']}s "
                            f"compile={res['compile_s']}s dom={dom} "
                            f"peak={res['memory']['peak_gb']:.2f}GB/dev"
                        )
                except Exception as e:  # noqa: BLE001
                    failures += 1
                    res = {
                        "arch": arch, "shape": shape,
                        "mesh": "2x16x16" if multi_pod else "16x16",
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc(),
                    }
                    status = f"FAIL {type(e).__name__}: {str(e)[:120]}"
                with open(path, "w") as f:
                    json.dump(res, f, indent=2, default=str)
                print(f"{tag:55s} {status}", flush=True)
    print(f"done; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()

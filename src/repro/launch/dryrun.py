"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

THIS FILE MUST SET XLA_FLAGS BEFORE ANY OTHER IMPORT (jax locks the device
count on first init); smoke tests and benches must NOT import this module.
"""
import os

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512"
                               ).strip()

# ruff: noqa: E402
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import hlo_cost
from repro.analysis import roofline as rl
from repro.configs.base import (ARCH_IDS, SHAPES, cell_supported, get_arch,
                                input_specs)
from repro.core import make_engine
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tfm
from repro.serve import kvcache
from repro.serve.serve_step import (make_decode_step, make_forward_step,
                                    make_prefill_step)
from repro.sharding import policy
from repro.train import optimizer as opt
from repro.train.train_step import make_train_step


def _named(mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch_id: str, shape_id: str, *, multi_pod: bool = False,
               policy_name: str = "fp32_strict", num_microbatches: int = 1,
               n_q_chunks: int | None = None, fsdp: bool | None = None,
               strategy: str | None = None, moe_dispatch: str | None = None,
               routed_experts: int = 0, return_text: bool = False):
    """Lower + compile one cell; returns the result record dict."""
    import dataclasses

    from repro.sharding import hints

    cfg = get_arch(arch_id)
    if moe_dispatch:
        cfg = dataclasses.replace(cfg, moe_dispatch=moe_dispatch)
    if routed_experts:
        cfg = dataclasses.replace(cfg, n_routed_experts=routed_experts)
    strategy = strategy or "tp"
    shape = SHAPES[shape_id]
    ok, reason = cell_supported(cfg, shape)
    if not ok:
        return {"arch": arch_id, "shape": shape_id,
                "mesh": "multi_pod" if multi_pod else "single_pod",
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    engine = make_engine("xla", policy_name)
    dtype = "fp32" if policy_name == "fp32_strict" else "bf16"
    if fsdp is None:
        fsdp = policy.needs_fsdp(cfg, mesh)
    if n_q_chunks is None:
        n_q_chunks = 16 if shape.seq_len >= 32768 else 8

    t0 = time.time()
    record = {"arch": arch_id, "shape": shape_id,
              "mesh": "multi_pod" if multi_pod else "single_pod",
              "chips": chips, "policy": policy_name, "fsdp": fsdp,
              "kind": shape.kind, "num_microbatches": num_microbatches,
              "strategy": strategy, "moe_dispatch": cfg.moe_dispatch}
    with jax.set_mesh(mesh), hints.strategy(strategy):
        pspecs = policy.param_pspecs(cfg, mesh, fsdp=fsdp,
                                     strategy=strategy)
        params_sh = _named(mesh, pspecs)
        param_structs = jax.eval_shape(
            lambda k: tfm.init_params(k, cfg),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        specs = input_specs(cfg, shape)
        batch_sh = _named(mesh, policy.batch_pspecs(specs, mesh,
                                                    strategy=strategy))

        if shape.kind == "train":
            ocfg = opt.AdamWConfig()
            opt_structs = jax.eval_shape(opt.adamw_init, param_structs)
            zsp = policy.zero1_pspecs(cfg, mesh, strategy=strategy)
            opt_sh = {"mu": _named(mesh, zsp),
                      "nu": _named(mesh, zsp),
                      "step": NamedSharding(mesh, P())}
            step = make_train_step(engine, cfg, ocfg,
                                   num_microbatches=num_microbatches,
                                   n_q_chunks=n_q_chunks,
                                   ce_chunk=min(512, shape.seq_len))
            jitted = jax.jit(step,
                             in_shardings=(params_sh, opt_sh, batch_sh),
                             out_shardings=(params_sh, opt_sh, None))
            lowered = jitted.lower(param_structs, opt_structs, specs)
        elif shape.kind == "prefill":
            if cfg.is_encoder:
                step = make_forward_step(engine, cfg, n_q_chunks=n_q_chunks)
                jitted = jax.jit(step, in_shardings=(params_sh, batch_sh))
                lowered = jitted.lower(param_structs, specs)
            else:
                step = make_prefill_step(engine, cfg, n_q_chunks=n_q_chunks)
                cache_sh = _named(mesh, kvcache.cache_pspecs(
                    cfg, mesh, shape.global_batch, shape.seq_len))
                jitted = jax.jit(step, in_shardings=(params_sh, batch_sh),
                                 out_shardings=(None, cache_sh))
                lowered = jitted.lower(param_structs, specs)
        else:  # decode
            cache_structs = kvcache.cache_struct(
                cfg, shape.global_batch, shape.seq_len,
                engine.precision.compute_dtype)
            cache_sh = _named(mesh, kvcache.cache_pspecs(
                cfg, mesh, shape.global_batch, shape.seq_len))
            step = make_decode_step(engine, cfg)
            jitted = jax.jit(
                step,
                in_shardings=(params_sh, cache_sh,
                              batch_sh["token"], batch_sh["pos"]),
                out_shardings=(None, cache_sh))
            lowered = jitted.lower(param_structs, cache_structs,
                                   specs["token"], specs["pos"])

        record["t_lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        record["t_compile_s"] = round(time.time() - t1, 1)

        # ---- cost & memory analysis ----
        # XLA's cost_analysis undercounts while bodies (counted once);
        # recorded for reference, the roofline uses the trip-count-aware
        # analyzer (analysis/hlo_cost.py).
        cost = compiled.cost_analysis()
        record["xla_cost"] = {"flops": float(cost.get("flops", 0.0)),
                              "bytes": float(cost.get("bytes accessed",
                                                      0.0))}
        try:
            mem = compiled.memory_analysis()
            record["memory_analysis"] = {
                k: int(getattr(mem, k)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(mem, k)}
        except Exception as e:  # pragma: no cover
            record["memory_analysis"] = {"error": str(e)}

        text = compiled.as_text()
        acc = hlo_cost.analyze(text)
        flops = acc["flops"]
        bytes_acc = acc["bytes"]
        colls = {k: float(v) for k, v in acc["collectives"].items()}
        record["hlo_ops"] = {
            k: text.count(f" {k}(") + text.count(f" {k}-start(")
            for k in ("all-gather", "all-reduce", "reduce-scatter",
                      "all-to-all", "collective-permute", "dot", "fusion",
                      "while")}
        record["hlo_chars"] = len(text)
        if not return_text:
            del text

        total, active = tfm.param_counts(cfg)
        mf = rl.model_flops_for(cfg, shape, total, active)
        roof = rl.Roofline(flops_per_chip=flops, bytes_per_chip=bytes_acc,
                           coll_bytes_per_chip=float(colls["total"]),
                           dtype=dtype, chips=chips, model_flops=mf)
        record["collectives"] = colls
        record["roofline"] = roof.to_dict()
        record["params_total"] = total
        record["params_active"] = active
        record["status"] = "ok"
    if return_text:
        return record, text
    return record


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--policy", default="fp32_strict",
                    choices=["fp32_strict", "mixed"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--n-q-chunks", type=int, default=None)
    ap.add_argument("--fsdp", default=None,
                    choices=[None, "on", "off"])
    ap.add_argument("--strategy", default=None, choices=[None, "tp", "fsdp"])
    ap.add_argument("--moe-dispatch", default=None,
                    choices=[None, "ep_scatter", "local"])
    ap.add_argument("--routed-experts", type=int, default=0,
                    help="override n_routed_experts (DESIGN.md §9 "
                         "ablation: the assignment line's 160 vs hf's 64)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    fsdp = None if args.fsdp is None else (args.fsdp == "on")

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = args.tag or args.policy
                name = (f"{arch}__{shape}__"
                        f"{'multi' if mp else 'single'}__{tag}.json")
                path = os.path.join(args.out, name)
                if os.path.exists(path) and not args.force:
                    print(f"[dryrun] skip (exists): {name}")
                    continue
                print(f"[dryrun] {arch} x {shape} x "
                      f"{'multi_pod(2,16,16)' if mp else 'single_pod(16,16)'}"
                      f" [{args.policy}]", flush=True)
                try:
                    rec = lower_cell(arch, shape, multi_pod=mp,
                                     policy_name=args.policy,
                                     num_microbatches=args.microbatches,
                                     n_q_chunks=args.n_q_chunks, fsdp=fsdp,
                                     strategy=args.strategy,
                                     moe_dispatch=args.moe_dispatch,
                                     routed_experts=args.routed_experts)
                except Exception as e:
                    failures += 1
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multi_pod" if mp else "single_pod",
                           "status": "error", "error": str(e)[:2000],
                           "traceback": traceback.format_exc()[-4000:]}
                    print(f"[dryrun]   ERROR: {str(e)[:300]}", flush=True)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    print(f"[dryrun]   ok: lower={rec['t_lower_s']}s "
                          f"compile={rec['t_compile_s']}s "
                          f"flops/chip={r['flops_per_chip']:.3e} "
                          f"dom={r['dominant']} "
                          f"useful={r['useful_ratio']:.2f}", flush=True)
    print(f"[dryrun] done, failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())

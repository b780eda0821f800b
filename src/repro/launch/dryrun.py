import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_DRYRUN_XLA_EXTRA", "") +
    " --xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512")).strip()

"""Multi-pod dry-run: lower + compile every (architecture × shape) cell on the
production mesh, record memory/cost analysis + collective schedule + roofline
terms.  No device allocation: inputs are ShapeDtypeStructs.

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--force]
  python -m repro.launch.dryrun --all --movement sync|zero1|zero1_bf16
"""
import argparse
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, cells, get_config, list_archs
from repro.configs.base import Cell
from repro.launch import hlo as hlo_mod
from repro.launch import jcost
from repro.launch import specs as specs_mod
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models.registry import build_model
from repro.optim import adamw
from repro.sharding import api as shard_api
from repro.sharding import rules
from repro.train import TrainConfig, make_train_step, plan_train

RESULTS_DIR = os.environ.get("REPRO_DRYRUN_DIR",
                             os.path.join(os.path.dirname(__file__),
                                          "..", "..", "..", "experiments",
                                          "dryrun"))


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _mem_summary(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return {}
        return {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
            "peak_bytes": int(getattr(ma, "peak_memory_in_bytes", 0) or
                              getattr(ma, "temp_size_in_bytes", 0)),
        }
    except Exception as ex:                                  # pragma: no cover
        return {"error": str(ex)}


def _cost_summary(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    keep = ("flops", "bytes accessed", "transcendentals", "optimal_seconds")
    return {k: float(v) for k, v in cost.items() if k in keep}


def _sharded_bytes(spec_tree, abs_tree, mesh) -> int:
    """Analytic per-device resident bytes for a spec'd pytree."""
    total = 0
    flat_s = jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, P))
    flat_a = jax.tree.leaves(abs_tree)
    for spec, leaf in zip(flat_s, flat_a):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        denom = 1
        for entry in tuple(spec):
            axes = entry if isinstance(entry, (tuple, list)) else (entry,)
            for a in axes:
                if a is not None:
                    denom *= mesh.shape[a]
        total += n * jnp.dtype(leaf.dtype).itemsize // max(denom, 1)
    return total


def _model_flops(cfg, shape) -> float:
    from repro.models.registry import count_flops_params
    n = count_flops_params(cfg, shape.kind)
    factor = 6.0 if shape.kind == "train" else 2.0
    return factor * n * shape.tokens_per_step


def _ideal_bytes(cfg, shape, meta) -> float:
    """Algorithmic-minimum per-device HBM traffic per step.

    train:   params fwd+bwd reads + grad write + moment read/write
    prefill: params read + cache write
    decode:  params read + full cache read + O(1) write
    """
    p = meta.get("param_bytes_per_device", 0)
    o = meta.get("opt_bytes_per_device", 0)
    c = meta.get("cache_bytes_per_device", 0)
    if shape.kind == "train":
        return 3.0 * p + 2.0 * o
    if shape.kind == "prefill":
        return p + c
    return p + c          # decode: read cache once; O(1 token) writes


MOVEMENTS = ("sync", "zero1", "zero1_bf16", "dp_only", "dp_only_zero1",
             "manual_dp", "manual_dp_bf16", "inplace", "inplace_sp",
             "inplace_q8", "tp8", "tp8_serve")


def build_lowerable(cfg, shape, mesh, movement: str = "sync"):
    """Returns (lowered, meta) for one cell under an active mesh.

    ``movement`` selects the tier-2 ROCKET mode / layout being measured:
      sync         — paper-faithful baseline (blocking all-reduce semantics)
      zero1        — moments sharded over data (reduce-scatter movement)
      zero1_bf16   — zero1 + bf16 gradient compression
      dp_only      — replicate params, model axis as extra DP (small archs)
    """
    dp_layout = movement.startswith(("dp_only", "manual_dp"))
    shard_api.set_layout("dp_only" if dp_layout else "tp")
    if shape.kind != "train" and movement != "sync" and cfg.fsdp:
        # serving holds no optimizer state: TP-only parameter sharding fits
        # and avoids per-step FSDP weight gathers (§Perf, decode cell)
        import dataclasses as _dc
        cfg = _dc.replace(cfg, fsdp=False)
    model = build_model(cfg)
    p_abs = specs_mod.params_specs(model)
    p_spec = rules.param_pspecs(cfg, p_abs)
    p_sh = _named(mesh, p_spec)
    meta = {"params": int(sum(int(np.prod(x.shape)) for x in jax.tree.leaves(p_abs))),
            "param_bytes_per_device": _sharded_bytes(p_spec, p_abs, mesh)}

    if shape.kind == "train":
        plan = plan_train(cfg, shape)
        if os.environ.get("REPRO_MICROBATCHES"):      # hillclimb override
            import dataclasses as _dc
            plan = _dc.replace(plan,
                               microbatches=int(os.environ["REPRO_MICROBATCHES"]))
        if plan.remat != cfg.remat:
            import dataclasses
            cfg = dataclasses.replace(cfg, remat=plan.remat)
            model = build_model(cfg)
        opt = adamw.AdamWConfig(
            grad_sync_dtype="bfloat16" if movement.endswith("bf16") else None)
        manual_axes = ("pod", "data", "model") \
            if movement.startswith("manual_dp") else ()
        tcfg = TrainConfig(microbatches=plan.microbatches,
                           accum_dtype=plan.accum_dtype, opt=opt,
                           manual_dp_axes=manual_axes)
        step = make_train_step(model, tcfg)
        opt_abs = jax.eval_shape(adamw.init, p_abs)
        opt_spec = rules.opt_pspecs(p_spec, opt_abs)
        if movement in ("zero1", "zero1_bf16", "dp_only_zero1"):
            opt_spec = {
                "m": rules.zero1_respec(opt_spec["m"], p_abs),
                "v": rules.zero1_respec(opt_spec["v"], p_abs),
                "step": P(),
            }
        opt_sh = _named(mesh, opt_spec)
        batch_abs = specs_mod.input_specs(cfg, shape)
        batch_sh = _named(mesh, rules.batch_pspecs(batch_abs))
        meta["plan"] = plan.describe()
        meta["opt_bytes_per_device"] = _sharded_bytes(opt_spec, opt_abs, mesh)
        jitted = jax.jit(step,
                         in_shardings=(p_sh, opt_sh, batch_sh),
                         out_shardings=(p_sh, opt_sh, None),
                         donate_argnums=(0, 1))
        return jitted.trace(p_abs, opt_abs, batch_abs), meta

    batch_sharded = shape.global_batch % max(rules.batch_axis_size(), 1) == 0 \
        and shape.global_batch >= rules.batch_axis_size()
    logits_sh = NamedSharding(mesh, rules.logits_pspec(cfg, batch_sharded))

    if shape.kind == "prefill":
        batch_abs = specs_mod.input_specs(cfg, shape)
        batch_sh = _named(mesh, rules.batch_pspecs(batch_abs))
        fn = functools.partial(model.prefill, max_len=shape.seq_len)
        out_abs = jax.eval_shape(fn, p_abs, batch_abs)
        cache_spec = rules.cache_pspecs(cfg, out_abs[1], shape.global_batch)
        cache_sh = _named(mesh, cache_spec)
        meta["cache_bytes_per_device"] = _sharded_bytes(
            cache_spec, out_abs[1], mesh)
        jitted = jax.jit(fn, in_shardings=(p_sh, batch_sh),
                         out_shardings=(logits_sh, cache_sh))
        return jitted.trace(p_abs, batch_abs), meta

    # decode
    if movement == "inplace_q8" and cfg.family in ("dense", "moe", "vlm"):
        from repro.models import attention as attn_mod
        cache_abs = jax.eval_shape(
            lambda: attn_mod.init_kv_cache_q8(
                cfg, shape.global_batch, shape.seq_len, cfg.num_layers))
    else:
        cache_abs = specs_mod.cache_specs(model, shape)
    cache_spec = rules.cache_pspecs(cfg, cache_abs, shape.global_batch)
    cache_sh = _named(mesh, cache_spec)
    tok_abs = specs_mod.input_specs(cfg, shape)["tokens"]
    tok_sh = NamedSharding(mesh, rules.batch_pspecs({"t": tok_abs})["t"])
    meta["cache_bytes_per_device"] = _sharded_bytes(cache_spec, cache_abs, mesh)
    decode_fn = model.decode_step
    if movement in ("inplace", "inplace_sp", "inplace_q8") and cfg.family in (
            "dense", "moe", "vlm"):
        from repro.models.transformer import lm_decode_step_inplace
        sp_axis = "model" if movement == "inplace_sp" else None
        sp_batch = tuple(a for a in ("pod", "data") if a in mesh.axis_names) \
            if batch_sharded else None
        decode_fn = functools.partial(lm_decode_step_inplace, cfg=cfg,
                                      sp_axis=sp_axis, sp_batch_axes=sp_batch)
        decode_fn = lambda p, c, t, _f=decode_fn: _f(p, c, t)
    jitted = jax.jit(decode_fn,
                     in_shardings=(p_sh, cache_sh, tok_sh),
                     out_shardings=(logits_sh, cache_sh),
                     donate_argnums=(1,))
    return jitted.trace(p_abs, cache_abs, tok_abs), meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             movement: str = "sync", save: bool = True,
             force: bool = False) -> dict:
    mesh_tag = "multipod" if multi_pod else "singlepod"
    tag = f"{arch}__{shape_name}__{mesh_tag}__{movement}"
    out_path = os.path.join(RESULTS_DIR, tag + ".json")
    if save and not force and os.path.exists(out_path):
        with open(out_path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    from repro.configs.base import cell_skip_reason
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
              "movement": movement, "status": "ok"}
    skip = cell_skip_reason(cfg, shape)
    if skip:
        record.update(status="skipped", reason=skip)
        _save(record, out_path, save)
        return record

    t0 = time.time()
    try:
        if movement.startswith("tp8"):
            # same 256 chips, lower TP degree: activation psums shrink with
            # the per-device activation slice (§Perf prefill exploration)
            mesh = make_mesh((32, 8), ("data", "model"))
        else:
            mesh = make_production_mesh(multi_pod=multi_pod)
        with shard_api.use_mesh(mesh):
            traced, meta = build_lowerable(cfg, shape, mesh, movement)
            jest = jcost.estimate_jaxpr(traced.jaxpr.jaxpr)
            lowered = traced.lower()
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            xla_cost = _cost_summary(compiled)
            mem = _mem_summary(compiled)
            coll = hlo_mod.collective_stats(compiled.as_text(),
                                            jest.depth_trips)
            # trip-count-exact logical cost (global) -> per-device share
            cost = {
                "flops": jest.flops / mesh.size,
                "bytes accessed": jest.bytes / mesh.size,
                "transcendentals": jest.transcendentals / mesh.size,
            }
            rl = hlo_mod.roofline_from_analysis(
                cost, coll, chips=mesh.size,
                model_flops=_model_flops(cfg, shape),
                ideal_bytes_per_device=_ideal_bytes(cfg, shape, meta))
            record.update(
                meta=meta, lower_s=round(t_lower, 1),
                compile_s=round(t_compile, 1),
                cost=cost, xla_cost=xla_cost, memory=mem,
                depth_trips={str(k): v for k, v in jest.depth_trips.items()},
                collectives={"bytes_by_op": coll.bytes_by_op,
                             "count_by_op": coll.count_by_op},
                roofline=rl.as_dict(),
            )
    except Exception as ex:
        record.update(status="error", error=f"{type(ex).__name__}: {ex}",
                      traceback=traceback.format_exc()[-4000:])
    _save(record, out_path, save)
    return record


def _save(record: dict, path: str, save: bool) -> None:
    if not save:
        return
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--movement", default="sync", choices=list(MOVEMENTS))
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    todo: list[Cell] = []
    if args.all:
        todo = cells([args.arch] if args.arch else None,
                     [args.shape] if args.shape else None)
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = cells([args.arch], [args.shape])

    n_ok = n_skip = n_err = 0
    for cell in todo:
        rec = run_cell(cell.arch, cell.shape, multi_pod=args.multi_pod,
                       movement=args.movement, force=args.force)
        status = rec["status"]
        n_ok += status == "ok"
        n_skip += status == "skipped"
        n_err += status == "error"
        if status == "ok":
            rl = rec["roofline"]
            print(f"[{status:7s}] {cell.arch:24s} {cell.shape:12s} "
                  f"compile={rec['compile_s']:6.1f}s dominant={rl['dominant']:10s} "
                  f"frac={rl['roofline_fraction']:.3f}", flush=True)
        elif status == "skipped":
            print(f"[{status:7s}] {cell.arch:24s} {cell.shape:12s}", flush=True)
        else:
            print(f"[{status:7s}] {cell.arch:24s} {cell.shape:12s} "
                  f"{rec['error'][:140]}", flush=True)
    print(f"done: ok={n_ok} skipped={n_skip} errors={n_err}", flush=True)
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

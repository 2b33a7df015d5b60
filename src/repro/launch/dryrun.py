import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# The two lines above MUST run before any other import pulls jax in: jax
# locks the device count at first init.  This module (and only this module)
# sees 512 placeholder devices — smoke tests and benches see the real one.

# Multi-pod dry-run: prove every (arch × input-shape × mesh) combination
# lowers, compiles, and fits, and extract the roofline inputs.
#
#     PYTHONPATH=src python -m repro.launch.dryrun \
#         --arch smollm-135m --shape train_4k --mesh pod --out experiments/dryrun
#
# Per combo this emits JSON with: memory analysis (bytes/device), HLO FLOPs &
# bytes (cost analysis), per-collective byte totals parsed from the compiled
# module, and the three roofline terms (launch/analysis.py).
# (No ``from __future__`` here: the XLA_FLAGS lines must stay first.)

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common import sharding as sh
from repro.common.compile_cache import enable_compile_cache
from repro.common.config import INPUT_SHAPES, TrainConfig
from repro.configs import get_config, shape_applicable
from repro.core.lookahead import init_lookahead_params
from repro.launch import analysis, steps
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tf
from repro.optim import adam


def _ns(mesh, spec):
    return NamedSharding(mesh, spec)


def _shard_tree(mesh, shapes, specs):
    return sh.with_sharding(shapes, specs, mesh)


def _batch_specs(mesh, batch_shapes: dict, global_batch: int,
                 dp_all: bool = False, seq_shard: bool = False):
    """Input batch shardings: batch over the data axes when divisible."""
    dp = tuple(mesh.axis_names) if dp_all else sh.batch_axes(mesh)
    seq = "model" if seq_shard else None
    dp_total = int(np.prod([mesh.shape[x] for x in dp]))
    bspec = dp if global_batch % dp_total == 0 else (
        ("data",) if global_batch % mesh.shape["data"] == 0 else None)

    def spec_for(name, s):
        if name == "mrope":
            return P(None, bspec, seq, *([None] * (len(s.shape) - 3)))
        if name == "frames":  # whisper encoder frames: keep unsharded seq
            return P(bspec, *([None] * (len(s.shape) - 1)))
        return P(bspec, seq, *([None] * (len(s.shape) - 2)))

    return {k: spec_for(k, v) for k, v in batch_shapes.items()
            if hasattr(v, "shape")}


def abstract_params(cfg, mesh, *, embed_replicated: bool = False):
    shapes = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
    specs = sh.param_specs(cfg, mesh, embed_replicated=embed_replicated)
    return _shard_tree(mesh, shapes, specs), specs


def abstract_lkv(cfg, mesh, param_shapes):
    lkv_shapes = jax.eval_shape(
        lambda: init_lookahead_params(
            jax.random.PRNGKey(0), cfg,
            jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
            ["layers"],
        )
    )
    specs = sh.lkv_specs(lkv_shapes)
    return _shard_tree(mesh, lkv_shapes, specs), specs


# --- §Perf variants: config transforms measured against the baselines -----

def _v_moe_sparse(cfg):
    assert cfg.moe is not None
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, dispatch="sparse"))


VARIANTS = {
    "": {},
    # sort-based top-k dispatch (phi/deepseek §Perf pair 1)
    "moe_sparse": {"cfg": _v_moe_sparse},
    # batch over (data, model) for TP-less archs (mamba2 §Perf pair 2):
    # the model axis otherwise idles while SSM compute replicates 16x.
    "dp_all": {"dp_all": True},
    # sequence parallelism for prefill (qwen2 §Perf pair 3): heads don't
    # divide the model axis, so shard the *sequence* over it — per-token ops
    # shard 16x further and XLA allgathers K/V per layer for attention.
    "seq_shard": {"seq_shard": True},
    # split-cache decode (§Perf decode iteration): frozen seq-sharded prompt
    # cache + replicated hot ring => no per-step cache resharding.
    "split_cache": {"hot_slots": 128},
}


def _variant_cfg(variant, cfg):
    fn = VARIANTS[variant].get("cfg")
    return fn(cfg) if fn else cfg


def build(arch: str, shape_name: str, mesh, variant: str = ""):
    """Returns (fn, args tuple of ShapeDtypeStructs, tokens_processed)."""
    cfg = _variant_cfg(variant, get_config(arch))
    shape = INPUT_SHAPES[shape_name]
    dp_all = VARIANTS[variant].get("dp_all", False)
    params_s, _ = abstract_params(cfg, mesh, embed_replicated=dp_all)

    if shape.kind == "train":
        tc = TrainConfig()
        fn = steps.make_train_step(cfg, tc)
        bs = steps.train_batch_shapes(cfg, shape)
        n_in, n_out = bs.pop("n_in"), bs.pop("n_out")
        bspecs = _batch_specs(mesh, bs, shape.global_batch, dp_all)
        batch_s = _shard_tree(mesh, bs, bspecs)
        tokens = shape.global_batch * shape.seq_len
        if cfg.technique_applies:
            lkv_s, _ = abstract_lkv(cfg, mesh, params_s)
            opt_s = jax.eval_shape(adam.init, lkv_s)
            opt_s = _shard_tree(
                mesh, opt_s,
                adam.AdamState(P(), sh.lkv_specs(lkv_s), sh.lkv_specs(lkv_s)),
            )
            return fn, (params_s, lkv_s, opt_s, batch_s), tokens
        opt_shapes = jax.eval_shape(adam.init, params_s)
        pspecs = sh.param_specs(cfg, mesh, embed_replicated=dp_all)
        opt_s = _shard_tree(mesh, opt_shapes,
                            adam.AdamState(P(), pspecs, pspecs))
        return fn, (params_s, opt_s, batch_s), tokens

    if shape.kind == "prefill":
        fn = steps.make_prefill_step(cfg, shape)
        bs = steps.prefill_batch_shapes(cfg, shape)
        bspecs = _batch_specs(mesh, bs, shape.global_batch, dp_all,
                              VARIANTS[variant].get("seq_shard", False))
        batch_s = _shard_tree(mesh, bs, bspecs)
        tokens = shape.global_batch * shape.seq_len
        if cfg.technique_applies:
            lkv_s, _ = abstract_lkv(cfg, mesh, params_s)
            return fn, (params_s, lkv_s, batch_s), tokens
        return fn, (params_s, batch_s), tokens

    # decode
    hot = VARIANTS[variant].get("hot_slots", 0)
    fn = steps.make_decode_step(cfg, mesh=mesh if hot else None)
    token_s, cache_shapes = steps.decode_batch_shapes(cfg, shape, hot)
    c_specs = sh.cache_specs(cfg, mesh, shape.global_batch,
                             shape.seq_len if cfg.uses_attention else 0,
                             hot_slots=hot)
    cache_s = _shard_tree(mesh, cache_shapes, c_specs)
    dp = sh.batch_axes(mesh)
    dp_total = int(np.prod([mesh.shape[x] for x in dp]))
    bspec = dp if shape.global_batch % dp_total == 0 else None
    token_s = jax.ShapeDtypeStruct(
        token_s.shape, token_s.dtype, sharding=_ns(mesh, P(bspec, None)))
    tokens = shape.global_batch  # one new token per sequence
    return fn, (params_s, token_s, cache_s), tokens


def run_one(arch: str, shape_name: str, mesh_kind: str, out_dir: str | None,
            variant: str = ""):
    applicable, reason = shape_applicable(arch, shape_name)
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
              "variant": variant}
    if not applicable:
        result["status"] = "skipped"
        result["reason"] = reason
        print(f"[dryrun] SKIP {arch} × {shape_name}: {reason}")
        _dump(result, out_dir)
        return result

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    chips = int(np.prod(list(mesh.shape.values())))
    cfg = _variant_cfg(variant, get_config(arch))
    shape = INPUT_SHAPES[shape_name]
    fn, args, tokens = build(arch, shape_name, mesh, variant)

    t0 = time.time()
    with mesh:
        lowered = jax.jit(fn).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = analysis.memory_analysis_dict(compiled)
    cost = analysis.cost_analysis_dict(compiled)  # reference only: XLA counts
    # while-loop bodies once (see analysis.py), so the roofline numerators
    # come from the scan-aware jaxpr counter + loop-multiplied collectives.
    jc = analysis.fn_cost(fn, *args)
    hlo = compiled.as_text()
    coll_raw = analysis.collective_bytes(hlo)
    coll = analysis.collective_bytes_with_loops(hlo, cfg.num_layers)

    mf = analysis.model_flops(cfg, shape.kind, tokens)
    eff_mesh = ({"data": chips, "model": 1}
                if VARIANTS[variant].get("dp_all") else dict(mesh.shape))
    comps = analysis.component_costs(
        cfg, shape.kind, shape.global_batch, shape.seq_len, eff_mesh,
        seq_sharded=VARIANTS[variant].get("seq_shard", False))
    pd = analysis.per_device_cost(comps, eff_mesh, shape.global_batch)
    # cross-check the component model against the exact jaxpr global flops
    comp_global = sum(c["flops"] for c in comps.values())
    jaxpr_check = comp_global / jc["flops"] if jc["flops"] else 0.0
    rl = analysis.roofline_terms(
        arch=arch, shape=shape_name, mesh=mesh_kind, chips=chips,
        hlo_flops_per_dev=pd["flops_per_dev"],
        hlo_bytes_per_dev=pd["bytes_per_dev"],
        coll_bytes_per_dev=float(coll["total"]),
        model_flops_global=mf,
        peak_bytes=mem.get("peak_memory_in_bytes"),
    )
    result.update({
        "status": "ok",
        "chips": chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem,
        "cost_xla": cost,
        "cost_jaxpr_global": jc,
        "components": {k: {kk: (vv if isinstance(vv, int) else float(vv))
                           for kk, vv in v.items()}
                       for k, v in comps.items()},
        "per_device": pd,
        "jaxpr_check_ratio": jaxpr_check,
        "collectives": coll,
        "collectives_raw": coll_raw,
        "roofline": rl.to_dict(),
        "params": cfg.num_params(),
        "active_params": cfg.active_params(),
    })
    print(f"[dryrun] OK {arch} × {shape_name} × {mesh_kind} "
          f"(lower {t_lower:.1f}s compile {t_compile:.1f}s)")
    print(f"  memory/device: {mem}")
    print(f"  flops/device: {pd['flops_per_dev']:.3e}  "
          f"hbm bytes/device: {pd['bytes_per_dev']:.3e}  "
          f"collective bytes/device: {coll['total']:.3e}  "
          f"jaxpr_check: {jaxpr_check:.2f}")
    print(f"  roofline: compute {rl.compute_s*1e3:.2f}ms  "
          f"memory {rl.memory_s*1e3:.2f}ms  collective {rl.collective_s*1e3:.2f}ms "
          f"-> {rl.bottleneck}-bound; useful-flop ratio {rl.useful_flop_ratio:.3f}")
    _dump(result, out_dir)
    return result


def _dump(result, out_dir):
    if not out_dir:
        return
    os.makedirs(out_dir, exist_ok=True)
    v = f"_{result['variant']}" if result.get("variant") else ""
    name = f"{result['arch']}_{result['shape']}_{result['mesh']}{v}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(result, f, indent=2, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--variant", default="", choices=list(VARIANTS))
    args = ap.parse_args()
    enable_compile_cache()
    res = run_one(args.arch, args.shape, args.mesh, args.out, args.variant)
    sys.exit(0 if res.get("status") in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()

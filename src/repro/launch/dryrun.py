"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh from ShapeDtypeStruct inputs only (no allocation), and
record memory_analysis / cost_analysis / collective schedule for the
roofline analysis (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k
  python -m repro.launch.dryrun --all --spawn          # every cell, isolated
  python -m repro.launch.dryrun --all --multi-pod      # 2x16x16 pass
"""
import argparse
import json
import os
import subprocess
import sys
import time
import traceback
from typing import Dict

if __name__ == "__main__":        # must precede the jax import below
    # The dry-run describes a pod on CPU host devices: it never takes a
    # TPU, and jax locks the device count at first init.
    # REPRO_DRYRUN_DEVICES overrides the count for mini CI runs.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ.get("REPRO_DRYRUN_DEVICES", "512"))

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs import SHAPES, get_config, list_archs, shape_supported
from ..distributed.sharding import (ShardingRecipe, cache_specs, make_recipe,
                                    param_specs, use_recipe)
from ..models import build, input_specs, param_shapes
from ..optim import make_optimizer
from ..roofline.analysis import (DRYRUN_DEVICE_KIND,
                                 collective_bytes_from_hlo, roofline_terms)
from .mesh import make_mini_mesh, make_production_mesh
from .steps import make_serve_step, make_train_step

DEFAULT_OUT = "experiments/dryrun"


# --------------------------------------------------------------- variants ---
# §Perf hillclimb variants: name -> fn(cfg, spec, recipe) -> (cfg, recipe).
def _baseline(cfg, spec, recipe):
    return cfg, recipe


def _no_seq_parallel(cfg, spec, recipe):
    """Prefill without sequence sharding (activations batch-sharded only)."""
    import dataclasses
    sites = {k: P(recipe.dp, None, None) for k in ("residual",)}
    sites["act_ff"] = P(recipe.dp, None, recipe.tp)
    sites["logits"] = P(recipe.dp, None, recipe.tp)
    sites["moe_disp"] = P(recipe.tp, None, None)
    return cfg, dataclasses.replace(recipe, seq=None, sites=sites)


def _no_remat(cfg, spec, recipe):
    import dataclasses
    return dataclasses.replace(cfg, remat=False), recipe


def _fp32_params(cfg, spec, recipe):
    import dataclasses
    return dataclasses.replace(cfg, dtype="float32"), recipe


DEDUP_NUM_VARIANTS = 6       # resident model variants (paper Tab. 1)
DEDUP_BLOCK = (256, 256)     # storage block (DESIGN.md §2)


def _pool_params(params_sds, cfg, ratio: float):
    """Replace every >=1 MiB 2-D-blockable weight with (pool, block_map):
    the pool holds the distinct blocks of DEDUP_NUM_VARIANTS variants at
    the given distinct fraction; the map belongs to the served variant.

    Returns (pooled ShapeDtypeStructs, unpool_fn).
    """
    import numpy as np
    from ..core.blocks import make_grid
    bh, bw = DEDUP_BLOCK

    flat, treedef = jax.tree_util.tree_flatten_with_path(params_sds)
    pooled = {}
    plans = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        size = int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        if len(leaf.shape) >= 2 and size >= (1 << 20):
            shape2d = (int(np.prod(leaf.shape[:-1])), int(leaf.shape[-1]))
            grid = make_grid(shape2d, (bh, bw))
            n_blocks = grid.num_blocks
            n_distinct = max(1, int(n_blocks * DEDUP_NUM_VARIANTS * ratio))
            n_distinct = -(-n_distinct // 512) * 512   # shardable on any mesh
            pooled[key + "#pool"] = jax.ShapeDtypeStruct(
                (n_distinct, bh, bw), leaf.dtype)
            pooled[key + "#map"] = jax.ShapeDtypeStruct(
                (n_blocks,), jnp.int32)
            plans[key] = (leaf.shape, shape2d, grid)
        else:
            pooled[key] = leaf

    def unpool(pooled_vals):
        out = []
        for path, leaf in flat:
            key = "/".join(str(getattr(p, "key", p)) for p in path)
            if key in plans:
                shape, shape2d, grid = plans[key]
                pool = pooled_vals[key + "#pool"]
                bmap = pooled_vals[key + "#map"]
                blocks = jnp.take(pool, bmap, axis=0)
                gh, gw = grid.grid
                w = (blocks.reshape(gh, gw, bh, bw)
                           .transpose(0, 2, 1, 3)
                           .reshape(gh * bh, gw * bw))
                w = w[: shape2d[0], : shape2d[1]].reshape(shape)
                out.append(w)
            else:
                out.append(pooled_vals[key])
        return jax.tree_util.tree_unflatten(treedef, out)

    return pooled, unpool


def _unrolled(cfg, spec, recipe):
    """Accounting mode: unroll layer scans so cost_analysis counts every
    layer (XLA counts while-loop bodies once; see EXPERIMENTS.md §Dry-run
    methodology).  Semantically identical program, bigger HLO."""
    import dataclasses
    return dataclasses.replace(cfg, scan_unroll=True), recipe


def _nsp_unrolled(cfg, spec, recipe):
    cfg, recipe = _no_seq_parallel(cfg, spec, recipe)
    return _unrolled(cfg, spec, recipe)


def _train_sp_unrolled(cfg, spec, recipe):
    """Sequence-parallel training activations: the scan carry (the per-
    layer residual stream kept live by remat) shards over `model`,
    dividing the dominant activation temp by the TP width."""
    import dataclasses
    sites = {
        "residual": P(recipe.dp, recipe.tp, None),
        "act_ff":   P(recipe.dp, recipe.tp, None),
        "logits":   P(recipe.dp, recipe.tp, None),
        "moe_disp": P(recipe.tp, None, None),
    }
    recipe = dataclasses.replace(recipe, seq=recipe.tp, sites=sites)
    return dataclasses.replace(cfg, scan_unroll=True), recipe


VARIANTS = {
    "baseline": _baseline,
    "unrolled": _unrolled,
    "no_seq_parallel": _no_seq_parallel,
    "nsp_unrolled": _nsp_unrolled,
    "train_sp_unrolled": _train_sp_unrolled,
    "no_remat": _no_remat,
    "fp32_params": _fp32_params,
    # dedup_serving handled specially in lower_cell (wraps the step and
    # re-shapes the weight inputs into pool+map form); list for CLI.
    "dedup_serving": _unrolled,
    "dedup_serving_dense_ref": _unrolled,
    # sharded page-pool serving (serving/shard_pool.py at pod scale):
    # the block maps shard with the pool instead of replicating, so the
    # lowering also schedules the map-distribution collectives.
    "dedup_serving_sharded": _unrolled,
}


# ---------------------------------------------------------------- helpers ---
def _tree_bytes(tree) -> int:
    import math
    return sum(math.prod(l.shape) * jnp.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tree))


def _shard_sds(tree, spec_tree, mesh):
    from ..distributed.sharding import sanitize_spec

    def f(sds, spec):
        spec = sanitize_spec(spec, sds.shape, mesh)
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                    sharding=NamedSharding(mesh, spec))
    return jax.tree.map(f, tree, spec_tree,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _batch_specs(batch_sds, recipe: ShardingRecipe, cfg) -> Dict:
    dp = recipe.dp

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in tree.items()}
        leaf = path.split("/")[-1]
        nd = len(tree.shape)
        if leaf in ("tokens", "labels"):
            if nd == 2 and tree.shape[1] > 1 and not cfg.encdec:
                return P(dp, recipe.seq)
            return P(dp, None)
        if leaf == "frames":
            return P(dp, recipe.seq, None)
        if leaf == "image_embeds":
            return P(dp, None, None)
        return P(*([None] * nd))

    out = {}
    for k, v in batch_sds.items():
        if k == "cache":
            out[k] = cache_specs(v, recipe)
        else:
            out[k] = walk(v, k)
    return out


def model_flops_estimate(cfg, spec) -> float:
    n_act = cfg.active_param_count()
    B, S = spec.global_batch, spec.seq_len
    if spec.kind == "train":
        return 6.0 * n_act * B * S
    if spec.kind == "prefill":
        return 2.0 * n_act * B * S
    return 2.0 * n_act * B           # decode: one token per sequence


# ------------------------------------------------------------------- cell ---
def lower_cell(arch: str, shape: str, multi_pod: bool = False,
               variant: str = "baseline", mini: bool = False,
               keep_hlo: bool = False) -> Dict:
    cfg = get_config(arch)
    spec = SHAPES[shape]
    meta = {"arch": arch, "shape": shape, "kind": spec.kind,
            "multi_pod": multi_pod, "variant": variant,
            "params_total": cfg.param_count(),
            "params_active": cfg.active_param_count(),
            "model_flops": model_flops_estimate(cfg, spec),
            "device_kind": DRYRUN_DEVICE_KIND}
    ok, reason = shape_supported(cfg, shape)
    if not ok:
        return {"meta": meta, "status": "skipped", "reason": reason}

    mesh = (make_mini_mesh(multi_pod=multi_pod) if mini
            else make_production_mesh(multi_pod=multi_pod))
    meta["mesh"] = "x".join(str(s) for s in mesh.devices.shape)
    meta["devices"] = mesh.devices.size
    recipe = make_recipe(spec.kind, multi_pod)
    cfg, recipe = VARIANTS[variant](cfg, spec, recipe)

    api = build(cfg)
    record: Dict = {"meta": meta, "status": "ok"}
    t0 = time.perf_counter()
    with jax.set_mesh(mesh), use_recipe(recipe):
        params_sds = param_shapes(cfg, spec)
        pspecs = param_specs(params_sds, recipe)
        params_in = _shard_sds(params_sds, pspecs, mesh)
        meta["param_bytes_global"] = _tree_bytes(params_sds)

        batch_sds = input_specs(cfg, spec)
        bspecs = _batch_specs(batch_sds, recipe, cfg)
        batch_in = _shard_sds(batch_sds, bspecs, mesh)
        if "cache" in batch_sds:
            meta["cache_bytes_global"] = _tree_bytes(batch_sds["cache"])

        if spec.kind == "train":
            opt = make_optimizer(cfg.optimizer)
            opt_sds = jax.eval_shape(opt.init, params_sds)
            ospecs = opt.state_specs(params_sds, pspecs)
            opt_in = _shard_sds(opt_sds, ospecs, mesh)
            meta["opt_bytes_global"] = _tree_bytes(opt_sds)
            step = make_train_step(api, opt)
            jfn = jax.jit(step, donate_argnums=(0, 1))
            lowered = jfn.lower(params_in, opt_in, batch_in)
        elif spec.kind == "prefill":
            def prefill_step(params, batch):
                return api.prefill(params, batch, None)
            jfn = jax.jit(prefill_step)
            lowered = jfn.lower(params_in, batch_in)
        elif variant.startswith("dedup_serving"):
            # The paper's technique as a pod-scale serving feature:
            # DEDUP_NUM_VARIANTS model variants resident as one distinct-
            # block pool + per-variant block maps.  "dedup_serving" uses
            # cfg.dedup_ratio (measured cross-variant distinct fraction);
            # "..._dense_ref" is the no-dedup reference (6 full copies).
            from ..distributed.sharding import param_spec
            ratio = 1.0 if variant == "dedup_serving_dense_ref" \
                else cfg.dedup_ratio
            pooled_sds, unpool = _pool_params(params_sds, cfg, ratio)
            axes = (("pod", "data", "model") if multi_pod
                    else ("data", "model"))
            # "_sharded": the remapped block maps partition with the pool
            # (serving/shard_pool.py's per-shard remaps at pod scale)
            # instead of replicating — the lowering then also schedules
            # the map-distribution collectives.
            map_spec = P(axes) if variant.endswith("_sharded") else P()
            pspecs2 = {}
            for k, s in pooled_sds.items():
                if k.endswith("#pool"):
                    pspecs2[k] = P(axes, None, None)
                elif k.endswith("#map"):
                    pspecs2[k] = map_spec
                else:
                    pspecs2[k] = param_spec(k, len(s.shape), recipe)
            params_in = _shard_sds(pooled_sds, pspecs2, mesh)
            meta["param_bytes_global"] = _tree_bytes(pooled_sds)
            meta["dedup_ratio"] = ratio
            meta["dedup_variants"] = DEDUP_NUM_VARIANTS

            def dedup_step(pooled, batch):
                params = unpool(pooled)
                return api.decode(params, batch["cache"], batch["tokens"])

            jfn = jax.jit(dedup_step, donate_argnums=(1,))
            lowered = jfn.lower(params_in, batch_in)
        else:
            step = make_serve_step(api)
            jfn = jax.jit(step, donate_argnums=(1,))
            lowered = jfn.lower(params_in, batch_in)
        record["lower_seconds"] = time.perf_counter() - t0

        t1 = time.perf_counter()
        compiled = lowered.compile()
        record["compile_seconds"] = time.perf_counter() - t1

    try:
        mem = compiled.memory_analysis()
        record["memory_analysis"] = {
            k: int(getattr(mem, k)) for k in dir(mem)
            if k.endswith("_in_bytes") and not k.startswith("host_")}
    except Exception as e:                       # pragma: no cover
        record["memory_analysis"] = {"error": str(e)}
    try:
        cost = compiled.cost_analysis()
        record["cost_analysis"] = {
            k: float(v) for k, v in cost.items()
            if k in ("flops", "transcendentals", "bytes accessed")
            or k.startswith("bytes accessed")}
    except Exception as e:                       # pragma: no cover
        record["cost_analysis"] = {"error": str(e)}

    hlo = compiled.as_text()
    record["collectives"] = collective_bytes_from_hlo(hlo)
    record["hlo_bytes"] = len(hlo)
    if keep_hlo:
        record["hlo_head"] = hlo[:20000]
    cost = record.get("cost_analysis", {})
    record["roofline"] = roofline_terms(
        float(cost.get("flops", 0.0)),
        float(cost.get("bytes accessed", 0.0)),
        float(record["collectives"].get("weighted_total", 0.0)),
        meta["device_kind"])
    # cost_analysis is the per-device SPMD program -> compare against the
    # per-device share of MODEL_FLOPS = 6·N·D (or 2·N·D for inference).
    record["roofline"]["useful_flops_ratio"] = (
        meta["model_flops"] / meta["devices"] / float(cost["flops"])
        if cost.get("flops") else None)
    return record


def cell_path(out_dir: str, arch: str, shape: str, multi_pod: bool,
              variant: str) -> str:
    mesh = "multi" if multi_pod else "single"
    v = "" if variant == "baseline" else f"__{variant}"
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh}{v}.json")


def run_cell_and_save(arch, shape, multi_pod, variant, out_dir,
                      mini=False) -> Dict:
    os.makedirs(out_dir, exist_ok=True)
    path = cell_path(out_dir, arch, shape, multi_pod, variant)
    try:
        rec = lower_cell(arch, shape, multi_pod, variant, mini=mini)
    except Exception as e:
        rec = {"meta": {"arch": arch, "shape": shape,
                        "multi_pod": multi_pod, "variant": variant},
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--spawn", action="store_true",
                    help="one subprocess per cell (isolates XLA state)")
    ap.add_argument("--mini", action="store_true",
                    help="mini mesh (set REPRO_DRYRUN_DEVICES=8)")
    args = ap.parse_args()

    archs = list_archs() if (args.all or args.arch in (None, "all")) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape in (None, "all")) \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    cells = [(a, s, mp) for a in archs for s in shapes for mp in meshes]
    for arch, shape, mp in cells:
        path = cell_path(args.out, arch, shape, mp, args.variant)
        if args.skip_existing and os.path.exists(path):
            print(f"[skip existing] {path}")
            continue
        label = f"{arch} x {shape} ({'multi' if mp else 'single'}-pod, " \
                f"{args.variant})"
        if args.spawn:
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape,
                   "--variant", args.variant, "--out", args.out]
            if mp:
                cmd.append("--multi-pod")
            if args.mini:
                cmd.append("--mini")
            t0 = time.perf_counter()
            r = subprocess.run(cmd, capture_output=True, text=True)
            status = "ok" if r.returncode == 0 else "proc-error"
            if r.returncode != 0:
                with open(cell_path(args.out, arch, shape, mp,
                                    args.variant), "w") as f:
                    json.dump({"meta": {"arch": arch, "shape": shape,
                                        "multi_pod": mp},
                               "status": "error",
                               "error": r.stderr[-4000:]}, f, indent=1)
            print(f"[{status}] {label} ({time.perf_counter()-t0:.1f}s)")
        else:
            t0 = time.perf_counter()
            rec = run_cell_and_save(arch, shape, mp, args.variant, args.out,
                                    mini=args.mini)
            rl = rec.get("roofline", {})
            print(f"[{rec['status']}] {label} ({time.perf_counter()-t0:.1f}s) "
                  f"dominant={rl.get('dominant')} "
                  f"compute={rl.get('compute_s', 0):.2e}s "
                  f"memory={rl.get('memory_s', 0):.2e}s "
                  f"collective={rl.get('collective_s', 0):.2e}s "
                  + ("" if rec["status"] != "error"
                     else rec.get("error", "")[:200]))


if __name__ == "__main__":
    main()

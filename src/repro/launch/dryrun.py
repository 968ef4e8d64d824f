import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax import (jax locks the device
count at first init); do not set that flag globally — smoke tests and
benchmarks should see one device.

Per cell this produces (and caches to ``experiments/dryrun/*.json``):
  * compile success + wall time,
  * ``cost_analysis`` flops / bytes (per-chip, post-SPMD),
  * per-kind collective bytes parsed from the per-device HLO,
  * ``memory_analysis`` (argument/output/temp/peak bytes per device),
  * the three roofline terms + dominant bottleneck + MODEL_FLOPS ratio.

Usage:
    python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh both
    python -m repro.launch.dryrun --all            # every cell, both meshes
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCH_IDS, SHAPES, cells, get_config
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.launch.runtime import enable_compile_cache
from repro.models import zoo
from repro.optim import adamw
from repro.roofline import analysis as roof
from repro.roofline import hlo_cost
from repro.train import train_step as ts

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _sds_with_sharding(struct_tree, pspec_tree, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        struct_tree, pspec_tree,
        is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, P)))


def lower_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
               mesh=None, shape=None, cfg=None):
    """Build and lower the step function for one cell.  Returns (lowered,
    mesh, n_chips).  ``mesh``/``shape``/``cfg`` overrides support in-test
    mini dry-runs on small host meshes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = cfg or get_config(arch_id)
    shape = shape or SHAPES[shape_name]
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    is_train = shape.kind == "train"

    # Weight sharding: train = full TP x FSDP; serve = TP with *residual*
    # FSDP (only otherwise-replicated tensors — e.g. llama4's 40-head attn
    # that 16 does not divide — borrow the data axis; TP-sharded tensors and
    # 2D expert weights stay RESIDENT, so decode gathers only the residual
    # set).  See EXPERIMENTS.md SPerf llama4 iterations 1-3.
    fsdp_mode = True if is_train else "residual"
    with shd.use_mesh(mesh, fsdp=fsdp_mode):
        aparams, pshard, aopt, oshard = ts.state_shardings(
            cfg, mesh, fsdp=fsdp_mode, with_opt=is_train)
        bspecs = ts.batch_pspecs(cfg, shape, mesh)
        specs = zoo.input_specs(cfg, shape)

        if is_train:
            opt_cfg = adamw.AdamWConfig()
            step = ts.make_train_step(cfg, opt_cfg)
            batch_sds = {
                k: jax.ShapeDtypeStruct(
                    v.shape, v.dtype, sharding=NamedSharding(mesh, bspecs[k]))
                for k, v in specs.items()}
            fn = jax.jit(step, donate_argnums=(0, 1))
            lowered = fn.lower(
                _with_shardings(aparams, pshard),
                _with_shardings_opt(aopt, oshard, mesh),
                batch_sds)
        elif shape.kind == "prefill":
            step = ts.make_prefill_step(cfg, cache_len=shape.seq_len)
            batch_sds = {
                k: jax.ShapeDtypeStruct(
                    v.shape, v.dtype, sharding=NamedSharding(mesh, bspecs[k]))
                for k, v in specs.items()}
            fn = jax.jit(step)
            lowered = fn.lower(_with_shardings(aparams, pshard), batch_sds)
        else:  # decode
            step = ts.make_serve_step(cfg)
            cache_sds = _sds_with_sharding(specs["caches"],
                                           bspecs["caches"], mesh)
            tok = jax.ShapeDtypeStruct(
                specs["token"].shape, specs["token"].dtype,
                sharding=NamedSharding(mesh, bspecs["token"]))
            pos = jax.ShapeDtypeStruct(
                specs["pos"].shape, specs["pos"].dtype,
                sharding=NamedSharding(mesh, bspecs["pos"]))
            fn = jax.jit(step, donate_argnums=(1,))
            lowered = fn.lower(_with_shardings(aparams, pshard),
                               cache_sds, tok, pos)
    return lowered, mesh, n_chips


def _with_shardings(struct_tree, shard_tree):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        struct_tree, shard_tree)


def _with_shardings_opt(aopt, oshard, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    def f(s, sh):
        if isinstance(sh, P):
            sh = NamedSharding(mesh, sh)
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

    return jax.tree.map(f, aopt, oshard,
                        is_leaf=lambda x: isinstance(
                            x, (jax.ShapeDtypeStruct, P, NamedSharding)))


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
             out_dir: str = OUT_DIR, force: bool = False) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"{mesh_name}__{arch_id}__{shape_name}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as fh:
            return json.load(fh)

    cfg = get_config(arch_id)
    shape = SHAPES[shape_name]
    rec = {"arch": arch_id, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "ok": False}
    t0 = time.time()
    try:
        lowered, mesh, n_chips = lower_cell(arch_id, shape_name,
                                            multi_pod=multi_pod)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        cost = compiled.cost_analysis() or {}
        try:
            mem = compiled.memory_analysis()
            mem_rec = {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            }
        except Exception as e:  # backend may not support it
            mem_rec = {"error": str(e)}
        hlo = compiled.as_text()
        # trip-count-aware analysis (cost_analysis misses while-loop bodies)
        mine = hlo_cost.analyze_module(hlo)
        coll = {k: mine[k] for k in
                ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                 "collective-permute", "ragged-all-to-all")}
        coll["count"] = mine["coll_count"]
        coll_bytes = mine["collective_bytes"]
        terms = roof.roofline_terms(
            {"flops": mine["flops"], "bytes accessed": mine["bytes"]},
            coll_bytes)
        mf = roof.model_flops(cfg, shape)
        hlo_flops_global = mine["flops"] * n_chips
        rec.update(
            ok=True,
            n_chips=n_chips,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            flops_per_chip=mine["flops"],
            bytes_per_chip=mine["bytes"],
            bytes_raw_per_chip=mine["bytes_raw"],
            collectives=coll,
            collective_bytes_per_chip=coll_bytes,
            memory=mem_rec,
            roofline=terms,
            model_flops_global=mf,
            useful_flops_ratio=(mf / hlo_flops_global
                                if hlo_flops_global else None),
            xla_cost={"flops": float(cost.get("flops", 0.0)),
                      "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
        )
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=6)
    rec["total_s"] = round(time.time() - t0, 2)
    with open(out_path, "w") as fh:
        json.dump(rec, fh, indent=1)
    status = "OK " if rec["ok"] else "FAIL"
    print(f"[{status}] {mesh_name} {arch_id} {shape_name} "
          f"({rec['total_s']}s)" + ("" if rec["ok"] else f" :: {rec.get('error')}"),
          flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out-dir", default=OUT_DIR)
    args = ap.parse_args()
    enable_compile_cache()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    targets = []
    arch_list = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    for aid in arch_list:
        for shape_name, _ in cells(aid):
            if args.shape and shape_name != args.shape:
                continue
            for mp in meshes:
                targets.append((aid, shape_name, mp))

    n_ok = 0
    for aid, shape_name, mp in targets:
        rec = run_cell(aid, shape_name, multi_pod=mp,
                       out_dir=args.out_dir, force=args.force)
        n_ok += bool(rec["ok"])
    print(f"\n{n_ok}/{len(targets)} cells compiled")
    if n_ok < len(targets):
        raise SystemExit(1)


if __name__ == "__main__":
    main()

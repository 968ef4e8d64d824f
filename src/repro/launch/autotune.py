import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""SmartConf-for-the-compiler: auto-tune a compile-time PerfConf with the
paper's controller, using dry-run compiles as the sensor.

``train.microbatch_tokens`` cannot be adjusted mid-step (it is baked into
the jitted program), but the SmartConf loop still applies offline: the
"plant" is ``lower().compile().memory_analysis()`` (peak bytes/device), the
configuration is the microbatch count, and the user goal is the HBM budget
(hard).  The controller's indirect form fits naturally: the deputy is the
*activation* share of peak memory (what microbatching actually divides),
with the transducer mapping desired activation bytes -> microbatch count.

    python -m repro.launch.autotune --arch llama4-maverick-400b-a17b \
        --budget-gb 15

This is the paper's §5 machinery verbatim (virtual goal from a lambda,
two poles, best-effort alert) driving a knob the paper's JVM systems never
had: an XLA compile parameter.  Result feeds EXPERIMENTS.md §Perf.
"""

import argparse
import json

from repro.core import ControllerModel, GoalSpec, SmartConfIndirect
from repro.core.smartconf import ConfRegistry
from repro.launch.runtime import enable_compile_cache


def measure(arch: str, shape_name: str, n_micro: int) -> dict:
    """One dry-run compile probe at the given microbatch count."""
    import jax
    from repro.configs import SHAPES, get_config
    from repro.distributed import sharding as shd
    from repro.launch import dryrun as dr
    from repro.launch.mesh import make_production_mesh
    from repro.models import zoo
    from repro.optim import adamw
    from repro.train import train_step as ts
    from jax.sharding import NamedSharding

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=False)
    with shd.use_mesh(mesh, fsdp=True):
        aparams, pshard, aopt, oshard = ts.state_shardings(
            cfg, mesh, fsdp=True, with_opt=True)
        bspecs = ts.batch_pspecs(cfg, shape, mesh)
        specs = zoo.input_specs(cfg, shape)
        step = ts.make_train_step(cfg, adamw.AdamWConfig(), n_micro=n_micro)
        batch_sds = {
            k: jax.ShapeDtypeStruct(
                v.shape, v.dtype, sharding=NamedSharding(mesh, bspecs[k]))
            for k, v in specs.items()}
        lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
            dr._with_shardings(aparams, pshard),
            dr._with_shardings_opt(aopt, oshard, mesh),
            batch_sds)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    # On this backend peak==arguments (aliased); the activation working set
    # lives in temp_size.  True per-device requirement = args + temp.
    args_b = getattr(mem, "argument_size_in_bytes", 0)
    temp_b = getattr(mem, "temp_size_in_bytes", 0)
    return {"n_micro": n_micro, "peak_bytes": int(args_b + temp_b),
            "static_bytes": int(args_b), "temp_bytes": int(temp_b)}


def autotune(arch: str, shape_name: str, budget_bytes: float,
             *, max_iters: int = 5) -> list[dict]:
    from repro.configs import SHAPES
    batch = SHAPES[shape_name].global_batch

    # Seed probe: peak = static + activations(n_micro=1)
    history = [measure(arch, shape_name, 1)]
    static = history[0]["static_bytes"]
    act0 = max(history[0]["peak_bytes"] - static, 1)

    # Controller on the deputy "activation (temp) bytes"; the transducer is
    # INCREMENTAL — n_new = n * temp_now / temp_desired — so the controller
    # keeps integrating even where temp has a microbatch-independent floor
    # (paper: model error is disturbance, the loop corrects it).
    state = {"n": 1, "temp": float(act0)}

    def transduce(desired_temp: float) -> float:
        return state["n"] * state["temp"] / max(desired_temp, 1.0)

    model = ControllerModel(alpha=1.0, delta=1.3, lam=0.08,
                            conf_min=0.0, conf_max=float(act0), integer=False)
    registry = ConfRegistry()
    sc = SmartConfIndirect(
        "train.microbatch_tokens", metric="hbm_peak_bytes",
        goal=GoalSpec(budget_bytes, hard=True), initial=float(act0),
        model=model, registry=registry, transducer=transduce)
    from repro.optim.accum import quantize_microbatches
    for it in range(max_iters):
        rec = history[-1]
        state["n"] = rec["n_micro"]
        state["temp"] = float(max(rec["peak_bytes"] - static, 1))
        sc.set_perf(float(rec["peak_bytes"]), state["temp"])
        n_new = quantize_microbatches(batch, max(1.0, float(sc.get_conf())))
        if n_new == rec["n_micro"] and rec["peak_bytes"] > budget_bytes:
            # quantization rounded back down while still over budget:
            # actuate to the next feasible divisor (integer actuator floor)
            from repro.optim.accum import divisors
            bigger = [d for d in divisors(batch) if d > rec["n_micro"]]
            if not bigger:
                print("goal unreachable at max feasible microbatching "
                      "(controller best-effort alert)", flush=True)
                break
            n_new = bigger[0]
        elif n_new == rec["n_micro"]:
            break
        history.append(measure(arch, shape_name, n_new))
        if history[-1]["peak_bytes"] <= budget_bytes:
            break
    return history


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama4-maverick-400b-a17b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--budget-gb", type=float, default=64.0)
    ap.add_argument("--out", default="experiments/autotune_microbatch.json")
    args = ap.parse_args()
    enable_compile_cache()

    history = autotune(args.arch, args.shape, args.budget_gb * 1e9)
    for rec in history:
        ok = "OK " if rec["peak_bytes"] <= args.budget_gb * 1e9 else "OVER"
        print(f"[{ok}] n_micro={rec['n_micro']:3d} "
              f"peak={rec['peak_bytes']/1e9:.2f}GB "
              f"(budget {args.budget_gb}GB)", flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump({"arch": args.arch, "shape": args.shape,
                   "budget_gb": args.budget_gb, "history": history}, fh,
                  indent=1)


if __name__ == "__main__":
    main()

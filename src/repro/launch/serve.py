"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the continuous-batching engine with SmartConf-governed admission and
KV budgets against a synthetic request trace: a reduced config by default,
the published widths with ``--full-size``.  The HBM budget is the device's
own memory limit less an activation margin where the device reports one
(a TPU), and the weights plus ``--budget-headroom-mb`` where it does not
(the CPU backend).
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import reduced
from repro.launch.runtime import device_hbm_budget, enable_compile_cache
from repro.models import zoo
from repro.serve import Request, ServeEngine, ServeOptions


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--budget-headroom-mb", type=float, default=2.0,
                    help="HBM budget above the weights on a device that "
                         "reports no memory limit (the CPU backend)")
    ap.add_argument("--prefill-mode", default="auto",
                    choices=["auto", "bucketed", "packed", "one_shot"],
                    help="packed = unified ticks: ONE token-packed ragged "
                         "stream per tick carrying prefill chunks AND "
                         "every running slot's decode token as a length-1 "
                         "segment (one fused dispatch; the "
                         "serve.prefill_chunk_tokens knob is the literal "
                         "per-tick token budget); bucketed = padded "
                         "power-of-two chunked prefill + a separate decode "
                         "dispatch (compile-count O(log len)); one_shot = "
                         "exact whole-prompt prefill per request (the "
                         "legacy baseline)")
    ap.add_argument("--kv-mode", default="auto",
                    choices=["auto", "paged", "dense"],
                    help="paged = block-table KV cache + paged decode "
                         "kernel (attention-only archs); dense = per-slot "
                         "[max_batch, cache_len] cache")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache over refcounted paged KV "
                         "blocks: requests whose prompts share a cached "
                         "prefix skip straight to the uncovered suffix "
                         "(copy-on-write at the block boundary); the "
                         "cache's share of the block budget is the "
                         "SmartConf-actuated serve.kv_cache_share knob. "
                         "Requires paged KV")
    ap.add_argument("--kv-cache-share", type=float, default=0.5,
                    help="initial fraction of the KV block budget the "
                         "prefix cache may hold (SmartConf adjusts it)")
    ap.add_argument("--prefix-groups", type=int, default=0,
                    help="with --trace: number of shared-prefix tenant "
                         "groups in the synthesized workload (0 = none)")
    ap.add_argument("--prefix-len", type=int, default=32,
                    help="with --trace: common preamble length (tokens) "
                         "for each prefix group")
    ap.add_argument("--spec-depth", type=int, default=0,
                    help="self-speculative decode draft depth k (0 = off): "
                         "each running slot's decode segment carries up to "
                         "k n-gram-drafted tokens verified in the same "
                         "fused dispatch; with SmartConf on, this is the "
                         "initial value of the serve.spec_depth knob. "
                         "Requires packed prefill mode")
    ap.add_argument("--spec-depth-max", type=int, default=8,
                    help="ceiling for the serve.spec_depth knob")
    ap.add_argument("--accept-rate-goal", type=float, default=0.5,
                    help="sc_spec setpoint: windowed draft accept rate the "
                         "depth controller holds the engine above")
    ap.add_argument("--no-spec-adaptive", action="store_true",
                    help="pin serve.spec_depth at --spec-depth instead of "
                         "letting SmartConf actuate it")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve on a (data, model) host mesh, e.g. 2x4: "
                         "the packed tick's one dispatch runs tensor-"
                         "parallel over the model axis (attention heads "
                         "and the KV block store shard on the Kv head "
                         "dim), token-identical to single-device.  Needs "
                         "packed prefill and data*model visible devices "
                         "(XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N on CPU); REPRO_SERVE_MESH sets the same "
                         "knob from the environment")
    ap.add_argument("--replicas", type=int, default=1,
                    help="with --trace: run N data-parallel engine "
                         "replicas behind one ReplicaRouter (weighted-"
                         "least-loaded dispatch; with --ttft-slo-s the "
                         "per-replica route.replica_weights are SmartConf-"
                         "actuated on each replica's TTFT-p99)")
    ap.add_argument("--full-size", action="store_true")
    # open-loop trace mode (serve/README.md): arrivals at trace rate on a
    # virtual clock, tier gating + SLO accounting + optional fault injection
    ap.add_argument("--trace", default=None,
                    choices=["poisson", "bursty", "diurnal"],
                    help="replay an open-loop arrival trace instead of the "
                         "one-shot synthetic batch")
    ap.add_argument("--rate-rps", type=float, default=10.0,
                    help="mean arrival rate for --trace (requests/s)")
    ap.add_argument("--horizon-s", type=float, default=10.0,
                    help="trace horizon in virtual seconds")
    ap.add_argument("--ttft-slo-s", type=float, default=None,
                    help="TTFT p99 SLO: enables per-request goodput "
                         "accounting and the serve.admit_tier_max brownout "
                         "controller")
    ap.add_argument("--chaos", action="store_true",
                    help="inject faults during --trace: slow ticks, a "
                         "mid-run KV budget cut, a NaN sensor window, one "
                         "worker preemption")
    ap.add_argument("--telemetry-dir", default=None,
                    help="enable the flight recorder and write trace.json "
                         "(Chrome trace-event / Perfetto), metrics.json, "
                         "audit.jsonl (controller decisions) and "
                         "flight.json (sensor-ring dumps) into this "
                         "directory (see serve/README.md)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = reduced(cfg)
    params = zoo.init_params(cfg, jax.random.key(0))
    budget = device_hbm_budget()
    if budget is None:
        weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(params))
        budget = int(weights + args.budget_headroom_mb * 1e6)
    if args.replicas > 1 and args.trace is None:
        raise SystemExit("--replicas N needs --trace: the ReplicaRouter "
                         "serves an open-loop arrival stream")
    if args.trace is not None:
        _run_trace(cfg, params, budget, args)
        return
    tel = None
    if args.telemetry_dir:
        from repro.core.telemetry import Telemetry
        tel = Telemetry(enabled=True)
    eng = ServeEngine(cfg, params, options=ServeOptions(
        max_batch=args.max_batch, cache_len=args.cache_len,
        hbm_budget_bytes=budget, prefill_mode=args.prefill_mode,
        kv_mode=args.kv_mode, prefix_cache=args.prefix_cache,
        kv_cache_share=args.kv_cache_share, telemetry=tel,
        spec_depth=args.spec_depth, spec_depth_max=args.spec_depth_max,
        spec_adaptive=not args.no_spec_adaptive,
        accept_rate_goal=args.accept_rate_goal, mesh=args.mesh))
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, int(rng.integers(8, 48)))
        eng.submit(Request(i, prompt.astype(np.int32), args.max_new_tokens))
    ticks = 0
    while len(eng.finished) < args.requests and ticks < 2000:
        eng.tick()
        ticks += 1
    kv = "paged" if eng.paged else "dense"
    print(f"{cfg.name}: {len(eng.finished)}/{args.requests} done in {ticks} "
          f"ticks; HBM violations {eng.accountant.violations}; "
          f"peak {eng.accountant.peak_bytes/1e6:.1f}/{budget/1e6:.1f} MB; "
          f"TTFT {eng.ttft.mean()*1e3:.0f}ms; prefill[{eng.prefill_impl}] "
          f"{eng.prefill_calls} calls / {eng.model_programs} programs, "
          f"{eng.model_dispatches/max(1, ticks):.2f} dispatches/tick, "
          f"pad_fraction {eng.pad_fraction:.2f}; "
          f"kv[{kv}] {eng.pool.used_blocks} blocks used, "
          f"{eng.preemptions} preemptions"
          + (f"; mesh {args.mesh}: {eng.tp_shards}-way TP ticks, "
             f"{eng.kv_shard_bytes()/1e6:.1f} MB KV per shard"
             if eng.mesh is not None else "")
          + (f"; prefix cache {eng._prefix_cache.blocks_held} blocks held, "
             f"hit rate {eng._prefix_cache.hit_rate:.2f}, "
             f"{eng.prefix_hit_tokens_total} prefill tokens reclaimed, "
             f"{eng.cow_copied_blocks} COW copies"
             if eng._prefix_cache is not None else "")
          + (f"; spec depth {eng.spec_depth}, "
             f"{eng.spec_accepted}/{eng.spec_proposed} drafts accepted"
             if eng.spec_enabled else ""))
    if tel is not None:
        paths = tel.write(args.telemetry_dir)
        print(f"telemetry: {paths['trace']} (open in https://ui.perfetto.dev), "
              f"{paths['metrics']}, {paths['audit']}, {paths['flight']}")
    eng.close()


def _run_trace(cfg, params, budget: int, args) -> None:
    from repro.serve import (ChaosMonkey, ChaosSpec, OpenLoopDriver,
                             ReplicaRouter, SLOSpec, ServeEngine,
                             TraceConfig, VirtualClock, as_requests,
                             synthesize_trace)

    vc = VirtualClock()
    slo = SLOSpec(ttft_s=args.ttft_slo_s) if args.ttft_slo_s else None
    tel = None
    if args.telemetry_dir:
        from repro.core.telemetry import Telemetry
        tel = Telemetry(enabled=True, clock=vc)  # virtual-time timestamps
    opts = ServeOptions(
        max_batch=args.max_batch, cache_len=args.cache_len,
        hbm_budget_bytes=budget, prefill_mode=args.prefill_mode,
        kv_mode=args.kv_mode, prefix_cache=args.prefix_cache,
        kv_cache_share=args.kv_cache_share, slo=slo, telemetry=tel,
        spec_depth=args.spec_depth, spec_depth_max=args.spec_depth_max,
        spec_adaptive=not args.no_spec_adaptive,
        accept_rate_goal=args.accept_rate_goal, mesh=args.mesh)
    if args.replicas > 1:
        # telemetry (and its decision audit) attaches to the router, which
        # owns the fleet-level control loop; each replica keeps its own
        # engine-level controllers
        engines = [ServeEngine(
            cfg, params,
            options=opts if i == 0 else dataclasses.replace(
                opts, telemetry=None), clock=vc)
            for i in range(args.replicas)]
        eng = ReplicaRouter(engines, clock=vc, slo=slo,
                            adaptive=slo is not None, telemetry=tel)
    else:
        eng = ServeEngine(cfg, params, options=opts, clock=vc)
    trace = synthesize_trace(TraceConfig(
        process=args.trace, rate_rps=args.rate_rps,
        horizon_s=args.horizon_s, seed=args.seed,
        prefix_groups=args.prefix_groups, prefix_len=args.prefix_len))
    chaos = None
    if args.chaos:
        # with replicas, the engine-level faults (budget cut, preemption,
        # sensor window) all land on replica 0 — the router must route
        # around them
        target = eng.engines[0] if args.replicas > 1 else eng
        chaos = ChaosMonkey(ChaosSpec(
            seed=args.seed, slow_tick_prob=0.04, slow_tick_s=0.15,
            budget_cut_tick=30, budget_cut_frac=0.6, budget_restore_tick=60,
            sensor_fault_tick=40, sensor_fault_ticks=10,
            preempt_tick=20, preempt_resume_ticks=3)).install(target)
    drv = OpenLoopDriver(
        eng, as_requests(trace, vocab=cfg.vocab_size, seed=args.seed),
        clock=vc, chaos=chaos)
    out = drv.run()
    slo_part = (f"goodput {out['goodput_tps']:.1f} tok/s under SLO "
                f"(throughput {out['throughput_tps']:.1f}); "
                if slo else "")
    print(f"{cfg.name}: open-loop {args.trace} trace, "
          f"{out['submitted']} arrivals over {args.horizon_s:.0f}s "
          f"(virtual elapsed {out['elapsed_s']:.1f}s, {out['ticks']} ticks); "
          f"{out['finished']} finished, {out['rejected']} rejected "
          f"{dict(out['reject_counts'])}; {slo_part}"
          f"{out['preemptions']} preemptions, "
          f"recompute {out['recompute_tokens']} tokens, "
          f"chaos events {len(chaos.events) if chaos else 0}, "
          f"unhandled {len(out['unhandled'])}"
          + (f"; prefix cache hit rate {eng._prefix_cache.hit_rate:.2f}, "
             f"{eng.prefix_hit_tokens_total} prefill tokens reclaimed"
             if getattr(eng, "_prefix_cache", None) is not None else "")
          + (f"; {args.replicas} replicas: weights "
             f"{[round(w, 2) for w in eng.weights]}, "
             f"{eng.reroutes} rerouted on replica loss"
             if args.replicas > 1 else ""))
    if tel is not None:
        paths = tel.write(args.telemetry_dir)
        print(f"telemetry: {paths['trace']} (open in https://ui.perfetto.dev), "
              f"{paths['metrics']}, {paths['audit']}, {paths['flight']}")
    eng.close()


if __name__ == "__main__":
    main()

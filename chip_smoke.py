#!/usr/bin/env python3
"""Serve yi-6b at its published widths on a TPU, end to end, and check what
it served against a plain forward pass.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # a 1x4 tensor-parallel serving mesh

Phases, each of which fails the run on its own:

1. devices: the first device must be a TPU (no fallback), and with
   ``--chips 4`` four of them must be visible.  No ``REPRO_*_IMPL`` override
   may be set, so every kernel runs compiled, never interpreted or as its
   reference.
2. init: yi-6b (32 layers, d_model 4096, 32/4 heads of 128, vocab 64000,
   bf16) with random weights from ``--seed``, built on device by one jitted
   program (``zoo.init_params``).
3. serve: one ``ServeEngine`` with its default serving options (packed
   ticks, paged KV) and ``max_batch`` 4, under the device's own HBM budget
   (``launch.runtime.device_hbm_budget``).  Eight seeded requests, prompts of
   128-992 tokens, 32 new tokens each, ticked until all finish; the run must
   contain unified (prefill + decode) ticks and decode-only ticks.
4. kernels: the compiled unified-tick and decode programs must contain a
   Pallas kernel (``tpu_custom_call``).
5. reference: each request's prompt + generated tokens go once through
   ``transformer.forward`` on the XLA path under
   ``jax.default_matmul_precision("highest")``.  At every generated
   position the served token's reference logit must lie within
   ``LOGIT_TOL`` of that position's largest reference logit.  Logits are
   compared, not tokens: with random weights the top logits are close, and a
   rounding difference flips an argmax between near-ties.

With ``--chips 4`` the same request set is served on a ``1x4`` mesh
(attention heads and the KV block store sharded over four chips, weights
replicated), judged by the same reference check, and the weights and KV
must be resident on all four devices.  Nothing else runs.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "yi-6b"
MAX_BATCH = 4
N_REQUESTS = 8
PROMPT_LEN = (128, 992)       # inclusive range of prompt lengths
NEW_TOKENS = 32
CACHE_LEN = 1024              # longest prompt + its new tokens
# Largest allowed distance, in logits, between a position's largest
# reference logit and the served token's reference logit.  The served path
# and the reference differ only by rounding: the engine keeps activations,
# K/V and logits in bf16, the reference carries f32 activations through the
# same bf16 weights.  With yi-6b's widths at 2 of its 32 layers, the largest
# gap a CPU run of this check saw over 256 positions was 0.019.  The logits
# of one position spread with a standard deviation of about 0.58, so the
# top of a 64000-token vocabulary sits about 2.5 above a typical token: a
# near-tie flipped by rounding stays inside the bound, a token served from
# a wrong context or by a broken kernel lands far outside it.
LOGIT_TOL = 0.25


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok, what) -> None:
    """Fail the run (``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def refuse_kernel_overrides(env=os.environ) -> None:
    forced = sorted(k for k in env
                    if k.startswith("REPRO_") and k.endswith("_IMPL"))
    if forced:
        raise SystemExit(f"chip_smoke: {', '.join(forced)} set; every kernel "
                         "must run compiled, so none may be overridden")


def make_requests(cfg, seed: int):
    import numpy as np
    from repro.serve import Request

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, N_REQUESTS)
    return [Request(i, rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32),
                    NEW_TOKENS) for i, n in enumerate(lens)]


class ProgramSpy:
    """Lowers a jitted engine step at its first call, so the program it ran
    can be compiled again for inspection.  Lowering there, inside the tick,
    traces under the engine's serving mesh as the real call does."""

    def __init__(self, fn):
        self.fn = fn
        self.lowered = None
        self.calls = 0

    def __call__(self, *args):
        if self.lowered is None:
            self.lowered = self.fn.lower(*args)
        self.calls += 1
        return self.fn(*args)

    def compiled_text(self) -> str:
        require(self.lowered is not None, "program was never called")
        return self.lowered.compile().as_text()


def serve(eng, requests, *, max_ticks: int = 2000) -> dict:
    """Submit every request, tick until all finish; count the tick kinds."""
    for req in requests:
        require(eng.submit(req),
                f"request {req.req_id} refused: {req.reject_reason}")
    packed = mixed = decode_only = 0
    t0 = time.perf_counter()
    for _ in range(max_ticks):
        if len(eng.finished) == len(requests):
            break
        st = eng.tick()
        packed += bool(st["prefill_tokens"])
        mixed += bool(st["prefill_tokens"] and st["decode_tokens"])
        decode_only += bool(st["decode_tokens"] and not st["prefill_tokens"])
    require(len(eng.finished) == len(requests),
            f"{len(eng.finished)}/{len(requests)} finished in {max_ticks} "
            "ticks")
    return {"ticks": eng.ticks_run, "packed_ticks": packed,
            "mixed_ticks": mixed, "decode_only_ticks": decode_only,
            "serve_s": time.perf_counter() - t0}


def reference_gaps(cfg, params, requests):
    """Per request, per generated position: the largest reference logit
    less the served token's reference logit (0 where they agree)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer

    length = CACHE_LEN   # one padded length, one compiled forward

    @jax.jit
    def logits_at(params, tokens, idx):
        # an f32 embedding makes every activation downstream f32; the
        # weights stay the bf16 values that were served
        params = dict(params, embed=params["embed"].astype(jnp.float32))
        x, _ = transformer.forward(cfg, params, {"tokens": tokens})
        xs = x[0, idx]                                         # [N, d]
        return jnp.einsum("nd,dv->nv", xs, params["head"]).astype(jnp.float32)

    out = {}
    with jax.default_matmul_precision("highest"):
        for req in requests:
            seq = np.concatenate([req.prompt, np.asarray(req.generated[:-1],
                                                         np.int32)])
            tokens = np.zeros((1, length), np.int32)
            tokens[0, :len(seq)] = seq
            # position p predicts token p + 1: the first generated token
            # comes from the prompt's last position
            idx = len(req.prompt) - 1 + np.arange(len(req.generated))
            ref = np.asarray(logits_at(params, jnp.asarray(tokens),
                                       jnp.asarray(idx, jnp.int32)))
            served = ref[np.arange(len(idx)), np.asarray(req.generated)]
            out[req.req_id] = (ref.max(axis=1) - served, ref.std(axis=1))
    return out


def run(cfg, *, seed: int, mesh, budget: int, compile_s: list) -> None:
    """Phases 2-5 on whatever devices JAX sees; raises on any failure."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.models import zoo
    from repro.serve import ServeEngine, ServeOptions

    # ---- 2. init
    t = time.perf_counter()
    params = zoo.init_params(
        cfg, jax.random.key(seed),
        sharding=None if mesh is None else NamedSharding(mesh,
                                                         PartitionSpec()))
    jax.block_until_ready(params)
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"init: {cfg.name} {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads}/{cfg.num_kv_heads} heads x "
        f"{cfg.resolved_head_dim}, vocab {cfg.vocab_size}, {cfg.dtype}; "
        f"{weights} bytes of weights in {time.perf_counter() - t:.1f} s; "
        f"device 0 peak {peak} bytes")

    # ---- 3. serve
    eng = ServeEngine(cfg, params, options=ServeOptions(
        max_batch=MAX_BATCH, cache_len=CACHE_LEN, hbm_budget_bytes=budget,
        mesh=None if mesh is None else "1x4"))
    require(eng.prefill_impl == "packed" and eng.paged,
            f"serving {eng.prefill_impl} ticks, paged={eng.paged}")
    spies = {"unified": ProgramSpy(eng._step_unified),
             "decode": ProgramSpy(eng._decode)}
    eng._step_unified, eng._decode = spies["unified"], spies["decode"]
    requests = make_requests(cfg, seed)
    n0 = len(compile_s)
    counts = serve(eng, requests)
    log(f"serve: {len(eng.finished)} requests in {counts['ticks']} ticks "
        f"({counts['packed_ticks']} packed, {counts['mixed_ticks']} of them "
        f"with decode riders; {counts['decode_only_ticks']} decode-only) in "
        f"{counts['serve_s']:.1f} s, of which {len(compile_s) - n0} compiles "
        f"took {sum(compile_s[n0:]):.1f} s; budget {budget} bytes, peak "
        f"ledger {eng.accountant.peak_bytes}, HBM violations "
        f"{eng.accountant.violations}, {eng.preemptions} preemptions")
    require(counts["packed_ticks"] and counts["decode_only_ticks"],
            f"both packed and decode-only ticks must run: {counts}")
    require(eng.accountant.violations == 0,
            f"{eng.accountant.violations} HBM budget violations")

    # ---- 4. kernels
    for name, spy in spies.items():
        n = spy.compiled_text().count("tpu_custom_call")
        log(f"kernels: {name} program ({spy.calls} calls) holds {n} "
            "tpu_custom_call")
        require(n > 0, f"{name} program runs no Pallas kernel")

    if mesh is not None:
        ids = {d.id for d in mesh.devices.flat}
        for kind, tree in (("weights", eng.params), ("kv", eng.caches)):
            for leaf in jax.tree.leaves(tree):
                require({d.id for d in leaf.sharding.device_set} == ids,
                        f"{kind} leaf {leaf.shape} on {leaf.sharding}")
        for path, a in jax.tree_util.tree_flatten_with_path(eng.caches)[0]:
            if getattr(path[-1], "key", None) in ("k", "v"):
                shard = a.addressable_shards[0].data.shape
                require(shard != a.shape,
                        f"KV plane {a.shape} is not sharded")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in mesh.devices.flat]
        log(f"mesh: weights replicated and KV sharded over devices "
            f"{sorted(ids)}; bytes in use per device {in_use}")

    # ---- 5. reference
    gaps = reference_gaps(cfg, eng.params, requests)
    worst = 0.0
    for req in requests:
        gap, std = gaps[req.req_id]
        worst = max(worst, float(gap.max()))
        log(f"request {req.req_id}: prompt {len(req.prompt)}, "
            f"{len(req.generated)} tokens, {int((gap == 0).sum())} of them "
            f"the reference argmax, largest gap {gap.max():.5f} logits "
            f"(logit std {std.mean():.4f})")
        require(gap.max() <= LOGIT_TOL,
                f"request {req.req_id}: gap {gap.max():.5f} > {LOGIT_TOL}")
    log(f"reference: every generated position within {LOGIT_TOL} logits "
        f"(largest gap {worst:.5f}); {len(compile_s)} compiles took "
        f"{sum(compile_s):.1f} s in all")
    eng.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    refuse_kernel_overrides()

    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.runtime import device_hbm_budget, enable_compile_cache

    # ---- 1. devices
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (first device is {dev.platform}"
                         f" {dev.device_kind}); nothing runs without one")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but "
                         f"{len(devices)} device(s) visible")
    cache_dir = enable_compile_cache()
    compile_s: list = []
    cache_hits: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache_hits.append(event)
        if event == "/jax/compilation_cache/cache_hits" else None)
    budget = device_hbm_budget(dev)
    if budget is None:
        raise SystemExit(f"chip_smoke: {dev.device_kind} reports no memory "
                         "limit to budget against")
    log(f"devices: {len(devices)} x {dev.device_kind} ({dev.platform}); "
        f"HBM budget {budget} bytes per device; compile cache {cache_dir}")

    mesh = make_host_mesh(data=1, model=4) if args.chips == 4 else None
    run(get_config(ARCH), seed=args.seed, mesh=mesh, budget=budget,
        compile_s=compile_s)
    log(f"compile cache: {len(cache_hits)} programs read from {cache_dir}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

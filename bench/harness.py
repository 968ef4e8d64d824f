"""One run of one cell: set-up, lead-in, the measured window, the metrics
and the correctness check.  ``run.py`` is the command; this module holds
the steps so that tests can drive them at a small size on the CPU."""

from __future__ import annotations

import collections
import dataclasses
import gc
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from . import cells, loadgen, trace as trace_mod
from .peaks import peaks_for

# device memory the serving budget leaves to what the engine's own ledger
# does not count (a tick's temporaries, the runtime's reservations)
ACTIVATION_MARGIN_BYTES = 1 << 30
# served tokens the correctness check aims to compare in each run
CHECK_TOKENS = 256


class NoChip(RuntimeError):
    """The devices JAX sees cannot run this cell."""


def refuse_overrides(env=os.environ) -> None:
    """Every kernel runs compiled and every serving path as configured: no
    ``REPRO_*`` switch may reroute the program."""
    forced = sorted(k for k in env if k.startswith("REPRO_"))
    if forced:
        raise NoChip(f"{', '.join(forced)} set; the benchmark runs the "
                     "program as configured, so none may be")


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: the first device is {devs[0].platform} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def enable_compile_cache(root=cells.ROOT) -> str:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else ``.jax_cache/`` at the root of the checkout (a fixed path,
    since the path is part of the cache key).  Every program is cached,
    however short its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Times of backend compiles and of programs read from the persistent
    cache, from ``jax.monitoring``."""

    def __init__(self, clock=time.perf_counter):
        import jax
        self.clock = clock
        self.compiles: list[tuple[float, float]] = []
        self.cache_hits: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((self.clock(), secs))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits.append(self.clock())

    def between(self, lo: float, hi: float) -> tuple[int, int]:
        return (sum(lo <= t <= hi for t, _ in self.compiles),
                sum(lo <= t <= hi for t in self.cache_hits))


def hbm_budget(device) -> int:
    stats = device.memory_stats() or {}
    if "bytes_limit" not in stats:
        raise NoChip(f"{device.device_kind} reports no memory limit")
    return int(stats["bytes_limit"]) - ACTIVATION_MARGIN_BYTES


def build_engine(cfg, weights, serve: dict, budget: int):
    from repro.serve import ServeEngine, ServeOptions
    eng = ServeEngine(cfg, weights, options=ServeOptions(
        max_batch=serve["max_batch"], cache_len=serve["cache_len"],
        block_tokens=serve["block_tokens"], hbm_budget_bytes=budget,
        latency_goal_s=serve.get("latency_goal_s"), prefill_mode="packed",
        kv_mode="paged", spec_depth=0, prefix_cache=False, mesh=None))
    if eng.prefill_impl != "packed" or not eng.paged:
        raise RuntimeError(f"engine serves {eng.prefill_impl} ticks, "
                           f"paged={eng.paged}")
    return eng


def packed_widths(cache_len: int) -> list[int]:
    """Every stream width the unified tick can issue: the power-of-two
    buckets from 16 up, capped at cache_len, and cache_len itself."""
    out, w = [], 16
    while w < cache_len:
        out.append(w)
        w *= 2
    return out + [cache_len]


def warm_up(eng, vocab: int, rng: np.random.Generator) -> list[int]:
    """Make every program the cell's traffic can run, before the window.

    1. ``max_batch`` requests that each reserve a whole cache row grow the
       block store to its full size (under an HBM goal it starts at one row
       and would otherwise grow, and recompile, inside the window).  Their
       prompts run one packed tick; then the engine's own preemption drain
       hands them back, leaving the store full and empty.
    2. One lone request per packed width, prefilled in one tick.
    3. One request decoded alone: the decode-only program.
    Only step 3 feeds the decode-latency sensor (a tick with no decode
    rider records nothing), so the chunk budget stays at its initial value
    while the widths run.  Returns the widths that ran."""
    from repro.serve import Request
    cl, mb = eng.cache_len, eng.max_batch
    rid = [-1]

    def req(n, new):
        rid[0] -= 1
        return Request(rid[0], rng.integers(0, vocab, n).astype(np.int32),
                       new)

    def serve(r):
        if not eng.submit(r):
            raise RuntimeError(f"warm-up request {r.req_id} refused")

    for _ in range(mb):
        serve(req(16, cl - 16))
    ran = [eng.tick()["prefill_issued_tokens"]]
    eng.preemption.trigger()
    eng.tick()
    eng.take_drained()
    eng.preemption.reset()
    eng.tick()
    if eng.pool.capacity != mb * eng.blocks_per_seq:
        raise RuntimeError(f"store holds {eng.pool.capacity} blocks, not "
                           f"{mb * eng.blocks_per_seq}")
    for w in packed_widths(cl):
        r = req(min(w, cl - 1), 1)
        serve(r)
        while r.done_t is None:
            st = eng.tick()
            if st["prefill_issued_tokens"]:
                ran.append(st["prefill_issued_tokens"] + st["decode_slots"])
    missing = sorted(set(packed_widths(cl)) - set(ran))
    if missing:
        raise RuntimeError(f"warm-up never ran packed widths {missing}")
    r = req(16, 2)
    serve(r)
    decoded = 0
    while r.done_t is None:
        decoded += bool(eng.tick()["decode_slots"])
    if not decoded:
        raise RuntimeError("warm-up never ran the decode-only program")
    return ran


class GcLog:
    """Count and seconds of the garbage collections until ``stop``."""

    def __init__(self, clock=time.perf_counter):
        self.clock, self.count, self.seconds = clock, 0, 0.0
        self._t = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = self.clock()
        elif self._t is not None:
            self.count += 1
            self.seconds += self.clock() - self._t

    def stop(self):
        gc.callbacks.remove(self._cb)


@dataclasses.dataclass
class RunData:
    """What the metric readers read (``bench/metrics/<name>.py``)."""
    cell: cells.Cell
    shape: object          # the family's shape (``cell.family``)
    peaks: object | None
    records: list          # ticks of the window
    gaps: list             # inter-token gaps (s) whose later token is in it
    setup_s: float
    reduction: object | None = None
    notes: dict = dataclasses.field(default_factory=dict)


def _span(enabled: bool):
    if not enabled:
        return loadgen.no_span
    import jax

    def span(name):
        return jax.profiler.TraceAnnotation(name)
    return span


def pick_sample(finished, t_open: float, seed: int,
                target: int = CHECK_TOKENS):
    """Requests to check, drawn from the seed: the longest request that
    finished in the window, then others that finished in it, in random
    order, until ``target`` served tokens; requests finished during the
    lead-in are drawn from only where the window's fall short."""
    rng = np.random.default_rng([seed, 2])
    inside = [r for t, r in finished if t >= t_open]
    before = [r for t, r in finished if t < t_open]
    pool = inside or before
    if not pool:
        return []
    longest = max(pool, key=lambda r: len(r.prompt) + len(r.generated))
    rest = [r for r in inside if r is not longest]
    order = [rest[i] for i in rng.permutation(len(rest))] + \
        [before[i] for i in rng.permutation(len(before))
         if before[i] is not longest]
    out, n = [longest], len(longest.generated)
    for r in order:
        if n >= target:
            break
        out.append(r)
        n += len(r.generated)
    return out


def verdict(result: dict, failed: int = 0) -> bool:
    """``correct``: some served tokens were compared, none lies further
    below the reference's best than the limit, and no request failed."""
    return (result["tokens"] >= 1 and failed == 0
            and result["widest_gap_logits"] <= result["limit"])


def check(family, shape, weights, sample, limit: float, length: int,
          rows: int, control: bool = False) -> dict:
    """Widest gap, in logits, between the reference's best token and the
    token served, over every served token of the sample (with ``control``,
    the token the lower-precision control puts first), with the mean gap
    and the share of tokens that are not the reference's best beside it."""
    gaps = [family.served_gaps(shape, weights, r.prompt, r.generated,
                               length=length, rows=rows, control=control)
            for r in sample]
    flat = np.concatenate(gaps) if gaps else np.zeros((0,))
    return {"widest_gap_logits": float(flat.max()) if len(flat) else 0.0,
            "mean_gap_logits": float(flat.mean()) if len(flat) else 0.0,
            "not_best_share": float((flat > 0).mean()) if len(flat) else 0.0,
            "tokens": int(len(flat)), "requests": len(sample),
            "limit": limit}


def run_cell(cell: cells.Cell, seed: int, seconds: float, traced: bool, *,
             t_process: float, require_tpu: bool = True, log=None,
             control: bool = False, tamper=None) -> dict:
    """Runs the cell once and returns the result line (a dict).  ``tamper``
    (tests only) wraps the engine after set-up, to break the timed path."""
    import jax

    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    devs = check_devices(cell.chips) if require_tpu else jax.devices()
    dev = devs[0]
    cache_dir = enable_compile_cache() if require_tpu else None
    clog = CompileLog()
    family = cell.family
    shape = family.shape_from_config(cell.config)
    serve = cell.config["serve"]
    cfg = family.program_config(cell.config, shape)
    peaks = peaks_for(dev.device_kind) if require_tpu else None
    log(f"devices: {len(devs)} x {dev.device_kind} ({dev.platform}); "
        f"compile cache {cache_dir}")

    t = time.perf_counter()
    weights = family.init_weights(shape, seed)
    jax.block_until_ready(weights)
    log(f"weights: {shape} in {time.perf_counter() - t:.2f} s")
    eng = build_engine(cfg, weights, serve, hbm_budget(dev))
    t = time.perf_counter()
    widths = warm_up(eng, shape.vocab, np.random.default_rng([seed, 3]))
    log(f"warm-up: packed widths {sorted(set(widths))} and the decode "
        f"program in {time.perf_counter() - t:.2f} s; store "
        f"{eng.pool.capacity} blocks")
    if tamper is not None:
        eng = tamper(eng)

    from repro.serve import Request
    span = _span(traced)
    source = loadgen.RequestSource(cell.mix, seed, shape.vocab,
                                   serve["max_batch"], Request)
    drv = loadgen.Driver(eng, source, cell.mix, clients=serve["max_batch"],
                         span=span)
    t = time.perf_counter()
    with span("bench.leadin"):
        lead_ticks = 0
        while True:
            drv.step()
            lead_ticks += 1
            if (len(drv.live) == drv.clients and all(
                    r.gen_count > 0 for r in drv.live.values())):
                break
    log(f"lead-in: {lead_ticks} ticks in {time.perf_counter() - t:.2f} s, "
        f"{len(drv.finished)} requests finished")

    # what set-up made lives as long as the process: the collector need not
    # walk it again inside the window
    gc.collect()
    gc.freeze()
    gc_log = GcLog()
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        jax.profiler.start_trace(tdir)
    t_open = time.perf_counter()
    setup_s = t_open - t_process
    n0 = len(drv.records)
    while time.perf_counter() - t_open < seconds:
        drv.step()
    jax.block_until_ready(eng.caches)
    t_close = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    recs = drv.records[n0:]
    recs[-1].t1 = max(recs[-1].t1, t_close)
    compiles, loads = clog.between(t_open, t_close)
    log(f"window: {len(recs)} ticks in {t_close - t_open:.2f} s; compiles "
        f"inside it: {compiles} compiled, {loads} read from the cache")
    gc_log.stop()
    widths = collections.Counter(r.width for r in recs)
    log("window ticks by packed width (None = decode-only): "
        f"{sorted(widths.items(), key=lambda kv: kv[0] or 0)}; chunk budget "
        f"{min(r.chunk_budget for r in recs)}-"
        f"{max(r.chunk_budget for r in recs)}; "
        f"{sum(r.emitted for r in recs)} tokens; "
        f"{len(drv.finished)} requests finished by the close")
    tick_s = np.array([r.t1 - r.t0 for r in recs])
    slow = [(round(float(tick_s[i]), 4), int(i), recs[i].width,
             *(recs[i].stats[k] for k in ("dispatches", "preemptions",
                                          "kv_capacity_blocks")))
            for i in np.argsort(tick_s)[::-1][:5]]
    log(f"window tick seconds: median {np.median(tick_s):.4f}; slowest "
        f"(seconds, tick, width, dispatches, preemptions, store blocks): "
        f"{slow}; garbage collections inside it: {gc_log.count} taking "
        f"{gc_log.seconds:.4f} s")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:cell.chips])
    attempted, failed = drv.submitted, drv.rejected

    run = RunData(cell=cell, shape=shape, peaks=peaks, records=recs,
                  gaps=loadgen.window_gaps(drv.gaps, t_open, t_close),
                  setup_s=setup_s)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    breakdown = None
    if traced:
        try:
            path = trace_mod.find_xplane(tdir)
            red = trace_mod.reduce_trace(path, trace_mod.host_window(path))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        run.reduction = red
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = {"device_ops": [[n, s] for n, s in red.top_ops],
                     "idle_gaps": [[n, s] for n, s in red.idle_gaps]}
    wanted = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = cells.load_metric(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for name, note in run.notes.items():
        log(f"{name}: {note}")

    # too few served tokens finished for the check: the requests still live
    # run on, untimed, until enough have
    t = time.perf_counter()
    drained = drv.drain(CHECK_TOKENS)
    if drained:
        log(f"drain: {drained} ticks in {time.perf_counter() - t:.2f} s "
            f"after the close, {len(drv.finished)} requests finished")
    finished = list(drv.finished)
    # the program's state is freed before the reference runs
    drv.eng = None
    eng.close()
    del eng
    gc.collect()
    sample = pick_sample(finished, t_open, seed)
    limit = float(cell.config["check"]["widest_gap_logits"])
    t = time.perf_counter()
    # one reference program per cell: every sequence padded to the cache,
    # the served tokens to the mix's longest output
    dims = (serve["cache_len"], int(cell.mix["output"]["hi"]))
    result = check(family, shape, weights, sample, limit, *dims)
    log(f"reference: {result['requests']} requests, {result['tokens']} "
        f"served tokens in {time.perf_counter() - t:.2f} s; mean gap "
        f"{result['mean_gap_logits']}, not the best for "
        f"{result['not_best_share']} of them")
    checks = {"widest_gap_logits": {"value": result["widest_gap_logits"],
                                    "limit": limit},
              "tokens_checked_min": {"value": result["tokens"], "limit": 1}}
    correct = verdict(result, failed)
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if control:
        ctrl = check(family, shape, weights, sample, limit, *dims,
                     control=True)
        result["correct"] = correct
        ctrl["correct"] = verdict(ctrl, failed)
        line["program_check"] = result
        line["control_check"] = ctrl
    line["checks"] = checks
    return line

"""Flight recorder: metrics/tracer/flight/audit units, engine integration,
determinism (byte-identical artifacts under VirtualClock), the TickStats
schema freeze, and the profiler's metrics emission."""

import contextlib
import json
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.telemetry import (Decision, DecisionLog, FlightRecorder,
                                  Histogram, MetricsRegistry, Telemetry,
                                  Tracer)
from repro.models import zoo
from repro.serve import (OpenLoopDriver, Request, SLOSpec, ServeEngine,
                         TICK_STATS_KEYS, TickCostModel, TraceConfig,
                         VirtualClock, as_requests, synthesize_trace)


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("yi-6b"))
    params, _ = zoo.init(cfg, jax.random.key(0))
    return cfg, params


def _req(rng, cfg, rid, plen=12, new=4, **kw):
    return Request(rid, rng.integers(1, cfg.vocab_size, plen)
                   .astype(np.int32), new, **kw)


# ------------------------------------------------------------- metrics unit

def test_histogram_quantiles_and_nonfinite_skip():
    h = Histogram("h", buckets=(0.1, 0.2, 0.4, 0.8))
    for v in (0.05, 0.05, 0.15, 0.3, 0.3, 0.3, 0.5, 0.7, 2.0):
        h.record(v)
    h.record(float("nan"))
    h.record(float("inf"))
    assert h.count == 9          # non-finite never poisons stats
    assert h.p50() == 0.4        # rank 4.5 lands in the (0.2, 0.4] bucket
    assert h.p99() == h._max == 2.0   # overflow bucket reads back max
    assert h.p50() <= h.p90() <= h.p99()
    snap = h.snapshot()
    assert snap["count"] == 9 and snap["min"] == 0.05
    assert sum(snap["counts"]) == 9
    assert Histogram("e", buckets=(1.0,)).p99() == 0.0   # empty -> 0


def test_metrics_registry_get_or_create_and_write(tmp_path):
    m = MetricsRegistry()
    m.counter("a").inc()
    m.counter("a").inc(2)
    m.gauge("g").set(3.5)
    m.histogram("h").record(0.01)
    assert m.counter("a").value == 3
    path = str(tmp_path / "metrics.json")
    m.write(path)
    snap = json.load(open(path))
    assert snap["counters"]["a"] == 3
    assert snap["gauges"]["g"] == 3.5
    assert snap["histograms"]["h"]["count"] == 1


# -------------------------------------------------------------- tracer unit

def test_tracer_tick_spans_and_schema():
    t = [0.0]
    trc = Tracer(clock=lambda: t[0])
    with trc.span("tick 0") as args:
        # each phase ends where the clock stood at its exit: the phases'
        # own times, not slices of the tick
        for name, end in (("admit", 0.001), ("pack", 0.004),
                          ("dispatch", 0.025)):
            with trc.span(name, {"tick": 0}):
                t[0] = end
        t[0] = 0.03
        args["tokens"] = 5
    with pytest.raises(RuntimeError):
        with trc.span("sample", {"tick": 1}):
            t[0] = 0.031
            raise RuntimeError("a span closes on an exception")
    with trc.span("finish", {"tick": 1}):
        pass                               # a frozen clock: zero-length
    trc.instant("chaos:slow_tick", tid=Tracer.TID_CHAOS)
    trc.async_begin("request", 7, args={"tier": 0})
    trc.async_end("request", 7)
    evs = trc.events
    spans = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert list(spans) == ["admit", "pack", "dispatch", "tick 0", "sample",
                           "finish"]       # written as each span closes
    assert [(spans[n]["ts"], spans[n]["dur"]) for n in spans] == [
        (0, 1_000), (1_000, 3_000), (4_000, 21_000), (0, 30_000),
        (30_000, 1_000), (31_000, 0)]
    assert spans["tick 0"]["args"] == {"tokens": 5}
    assert spans["pack"]["args"] == {"tick": 0}
    for e in evs:                          # trace-event required fields
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] != "M":
            assert isinstance(e["ts"], int)
        if e["ph"] == "X":
            assert isinstance(e["dur"], int) and e["dur"] >= 0
        if e["ph"] in ("b", "e"):
            assert "id" in e and "cat" in e
    doc = trc.to_json()
    assert doc["traceEvents"] and doc["otherData"]["dropped_events"] == 0
    json.dumps(doc)                        # strictly serializable


def test_tracer_bounded_drops_counted():
    trc = Tracer(clock=lambda: 0.0, max_events=5)
    for i in range(10):
        trc.instant(f"e{i}")
    assert len(trc.events) == 5
    assert trc.dropped == 8                # 3 metadata events pre-fill the ring


# ----------------------------------------------------- flight recorder unit

def test_flight_recorder_ring_and_dedupe():
    fr = FlightRecorder(window=4, max_dumps=2)
    for tick in range(10):
        fr.record(tick, {"s": (float(tick), float(tick))})
    assert [r["tick"] for r in fr._ring] == [6, 7, 8, 9]
    assert fr.dump("storm", 9) is True
    assert fr.dump("storm", 10) is False       # same reason inside window
    assert fr.dump("storm", 9 + 4) is True     # window elapsed
    assert fr.dump("other", 20) is False       # max_dumps reached
    assert fr.dropped_dumps == 1
    snap = fr.snapshot()
    assert len(snap["dumps"]) == 2
    assert snap["dumps"][0]["ring"][-1]["tick"] == 9


def test_flight_recorder_sanitizes_nonfinite(tmp_path):
    fr = FlightRecorder()
    fr.record(0, {"ttft_p99_s": (float("nan"), float("inf"))})
    fr.dump("chaos:sensor_nan", 0)
    path = str(tmp_path / "flight.json")
    fr.write(path)
    snap = json.load(open(path))               # strict JSON round-trips
    assert snap["dumps"][0]["ring"][0]["ttft_p99_s"] == ["nan", "inf"]


# --------------------------------------------------------------- audit unit

def _decision(**kw):
    base = dict(tick=0, conf="c", metric="m", goal=1.0, sensor=0.5,
                deputy=None, sane=True, error=0.5, raw=2.0, applied=1.5,
                clamped=True, fallback=False)
    base.update(kw)
    return Decision(**base)


def test_decision_log_query_bound_and_jsonl(tmp_path):
    log = DecisionLog(max_records=3)
    for i in range(5):
        log.tick = i
        log.append(_decision(tick=log.tick, fallback=i >= 3))
    assert len(log.records) == 3 and log.dropped == 2
    assert [d.tick for d in log.query(fallback=True)] == [3, 4]
    log.append(_decision(tick=9, sensor=float("nan")))
    path = str(tmp_path / "audit.jsonl")
    log.write_jsonl(path)
    lines = [json.loads(x) for x in open(path)]
    assert len(lines) == 3
    assert lines[-1]["sensor"] == "nan"        # strict-JSON sanitized


def test_smartconf_audit_records_fallback_and_clamp():
    from repro.core import ControllerModel, GoalSpec
    from repro.core.smartconf import ConfRegistry, Guardrails, SmartConf
    log = DecisionLog()
    sc = SmartConf(
        "t.knob", metric="lat", goal=GoalSpec(1.0, hard=True), initial=4.0,
        model=ControllerModel(alpha=1.0, delta=1.3, lam=0.1, conf_max=100.0),
        guardrails=Guardrails(perf_lo=0.0, perf_hi=10.0, fault_tolerance=2,
                              max_step=0.5),
        registry=ConfRegistry())
    sc.attach_audit(log)
    log.tick = 1
    sc.set_perf(5.0)
    v1 = sc.get_conf()
    d = log.records[-1]
    assert (d.conf, d.metric, d.tick) == ("t.knob", "lat", 1)
    assert d.sane and not d.fallback
    assert d.applied == v1
    # slew guard: a large error makes |raw - applied| exceed max_step
    if d.clamped:
        assert abs(d.raw - d.applied) > 0.0
    # NaN window: fault_tolerance=2 consecutive insane readings pin the conf
    log.tick = 2
    sc.set_perf(float("nan"))
    sc.get_conf()
    assert not log.records[-1].sane
    log.tick = 3
    sc.set_perf(float("nan"))
    pinned = sc.get_conf()
    d = log.records[-1]
    assert d.fallback and not d.sane
    assert d.applied == pinned
    assert log.query(fallback=True, tick=3)


def test_smartconf_indirect_audit_carries_deputy():
    from repro.core import ControllerModel, GoalSpec
    from repro.core.smartconf import ConfRegistry, SmartConfIndirect
    log = DecisionLog()
    sci = SmartConfIndirect(
        "t.ind", metric="hbm", goal=GoalSpec(100.0, hard=True), initial=8.0,
        model=ControllerModel(alpha=1.0, delta=1.3, lam=0.1, conf_max=1e6),
        registry=ConfRegistry())
    sci.attach_audit(log)
    log.tick = 4
    sci.set_perf(50.0, 7.0)
    sci.get_conf()
    d = log.records[-1]
    assert d.deputy == 7.0 and d.sensor == 50.0 and d.tick == 4


# -------------------------------------------------------- engine integration

def test_disabled_telemetry_is_absent_from_engine(small_model):
    cfg, params = small_model
    for tel in (None, Telemetry(enabled=False), Telemetry.disabled()):
        eng = ServeEngine(cfg, params, max_batch=2, cache_len=64,
                          enable_smartconf=False, telemetry=tel)
        assert eng._tel is None            # disabled path IS the baseline path
        eng.tick()
        eng.close()


def test_repro_telemetry_env_force_enables(small_model, monkeypatch):
    cfg, params = small_model
    monkeypatch.setenv("REPRO_TELEMETRY", "1")
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64,
                      enable_smartconf=False)
    assert eng._tel is not None and eng._tel.enabled
    eng.close()
    monkeypatch.setenv("REPRO_TELEMETRY", "0")
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64,
                      enable_smartconf=False)
    assert eng._tel is None
    eng.close()


def test_tick_stats_schema_frozen(small_model):
    cfg, params = small_model
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64,
                      enable_smartconf=False)
    # TICK_STATS_KEYS is the documented contract: keys AND their order.
    # Growing it is fine (append + update the tuple); renames/removals
    # break downstream consumers of tick()'s return value.
    assert tuple(eng._stats(0)) == TICK_STATS_KEYS
    assert TICK_STATS_KEYS[-2:] == ("host_s", "attn_kv_blocks")
    stats = eng.tick()
    assert tuple(stats) == TICK_STATS_KEYS
    assert stats["tick"] == 0 and eng.ticks_run == 1
    eng.close()


def test_engine_emits_spans_counters_and_readings(small_model, rng):
    cfg, params = small_model
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    tel = Telemetry(enabled=True)
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64,
                      block_tokens=16, enable_smartconf=True,
                      hbm_budget_bytes=weights + 2_000_000,
                      slo=SLOSpec(ttft_s=5.0, window=8), telemetry=tel)
    assert eng.submit(_req(rng, cfg, 0))
    assert not eng.submit(_req(rng, cfg, 1, plen=0))           # typed reject
    ticks = 0
    while len(eng.finished) < 1 and ticks < 50:
        eng.tick()
        ticks += 1
    assert len(eng.finished) == 1
    names = {e["name"] for e in tel.tracer.events}
    assert "tick 0" in names
    assert {"control", "admit", "schedule", "finish"} <= names
    assert "dispatch" in names             # at least one dispatching tick
    snap = tel.metrics.snapshot()
    assert snap["counters"]["serve.ticks"] == ticks
    assert snap["counters"]["serve.reject.empty_prompt"] == 1
    assert snap["histograms"]["serve.ttft_s"]["count"] == 1
    # every tick recorded its sensor stream into the flight ring
    assert tel.flight._ring and "ttft_p99_s" in tel.flight._ring[-1]
    # smartconf engine wrote audit decisions for the serve confs
    confs = {d.conf for d in tel.audit.records}
    assert {"serve.admit_tier_max", "serve.kv_block_budget",
            "serve.max_queue_tokens"} <= confs
    # request lifetime closed out as an async end (finish or rejection)
    ends = [e for e in tel.tracer.events if e["ph"] == "e"]
    assert {e["id"] for e in ends} == {0, 1}
    eng.close()


def test_chaos_note_marks_trace_and_dumps_flight(small_model):
    cfg, params = small_model
    tel = Telemetry(enabled=True)
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64,
                      enable_smartconf=False, telemetry=tel)
    eng.note_chaos("sensor_nan:ttft_p99_s")
    eng.note_chaos("sensor_nan:decode_p99_s")   # same family: deduped
    marks = [e for e in tel.tracer.events
             if e["ph"] == "i" and e["name"].startswith("chaos:")]
    assert len(marks) == 2 and marks[0]["tid"] == Tracer.TID_CHAOS
    assert tel.metrics.counter("chaos.sensor_nan").value == 2
    assert [d["reason"] for d in tel.flight.dumps] == ["chaos:sensor_nan"]
    eng.close()


# ------------------------------------------------------------- determinism

def _driven_run(cfg, params, tmp_dir):
    vc = VirtualClock()
    tel = Telemetry(enabled=True, clock=vc)
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64,
                      block_tokens=16, enable_smartconf=True,
                      slo=SLOSpec(ttft_s=0.5, window=8), num_tiers=2,
                      clock=vc, telemetry=tel)
    trace = synthesize_trace(TraceConfig(
        process="poisson", rate_rps=20.0, horizon_s=2.0, seed=11,
        prompt_lo=4, prompt_hi=16, new_lo=2, new_hi=6))
    drv = OpenLoopDriver(
        eng, as_requests(trace, vocab=cfg.vocab_size, seed=3), clock=vc,
        cost=TickCostModel(base_s=0.02, prefill_token_s=1e-3,
                           decode_token_s=8e-3))
    out = drv.run()
    assert out["unhandled"] == []
    paths = tel.write(tmp_dir)
    eng.close()
    return paths


def test_telemetry_deterministic_under_virtual_clock(small_model, tmp_path):
    cfg, params = small_model
    paths_a = _driven_run(cfg, params, str(tmp_path / "a"))
    paths_b = _driven_run(cfg, params, str(tmp_path / "b"))
    audit_a = open(paths_a["audit"], "rb").read()
    assert audit_a and audit_a == open(paths_b["audit"], "rb").read()
    assert open(paths_a["trace"], "rb").read() == \
        open(paths_b["trace"], "rb").read()
    assert open(paths_a["flight"], "rb").read() == \
        open(paths_b["flight"], "rb").read()
    # virtual timestamps: the span sequence is identical, and every complete
    # event in the written artifact satisfies the trace-event schema; the
    # clock is frozen within a tick, so every measured span is zero-length
    doc = json.load(open(paths_a["trace"]))
    for e in doc["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] == 0 and e["ts"] >= 0
    assert doc["otherData"]["dropped_events"] == 0
    # the audit log is replayable: decisions arrive in tick order
    ticks = [json.loads(x)["tick"] for x in open(paths_a["audit"])]
    assert ticks == sorted(ticks)


# ------------------------------------------------------------ profiler ties

def test_write_sysfile_never_leaves_tmp_on_failure(tmp_path):
    from repro.core.profiler import write_sysfile
    sys_dir = str(tmp_path)
    write_sysfile(sys_dir, "ok.conf", {"x": 1})
    with pytest.raises(TypeError):
        write_sysfile(sys_dir, "bad.conf", {"x": object()})  # not serializable
    leftovers = [f for f in os.listdir(sys_dir) if f.startswith(".")]
    assert leftovers == [], f"tmp files leaked: {leftovers}"
    assert sorted(os.listdir(sys_dir)) == ["ok.conf.smartconf.sys"]


def test_profile_buffer_emits_flush_metrics(tmp_path):
    from repro.core.profiler import ProfileBuffer
    m = MetricsRegistry()
    buf = ProfileBuffer(str(tmp_path), "t.knob", flush_every=4, metrics=m)
    for i in range(9):
        buf.record(float(i % 3), float(i))
    buf.flush()
    assert len(buf.samples) == 9
    assert m.counter("profiler.t.knob.samples").value == 9
    assert m.counter("profiler.t.knob.flushes").value == 3   # 4 + 4 + 1


# ------------------------------------------------------ engine phase spans

def _paged_engine(cfg, params, **kw):
    return ServeEngine(cfg, params, max_batch=2, cache_len=64,
                       block_tokens=16, kv_mode="paged",
                       prefill_mode="packed", enable_smartconf=False, **kw)


def _serve(eng, rng, cfg, first_id):
    for i in range(2):
        assert eng.submit(_req(rng, cfg, first_id + i, plen=12, new=4))
    stats = []
    while len(eng.finished) < first_id + 2:
        stats.append(eng.tick())
    return stats


def _host_spans(trace_dir):
    """(name, start_ns, end_ns, args) of every serve.* span, by start."""
    from jax.profiler import ProfileData
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
               for f in fs if f.endswith(".xplane.pb")]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith("serve."))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def test_phase_spans_nest_in_the_profiler_trace(small_model, rng, tmp_path):
    """A few ticks traced with jax.profiler: each serve.tick holds its
    phases in order, serve.fetch lies inside serve.finish, serve.wait
    inside the tick, and serve.dispatch names its program and width."""
    cfg, params = small_model
    eng = _paged_engine(cfg, params)
    _serve(eng, rng, cfg, 0)                   # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        stats = _serve(eng, rng, cfg, 2)
    eng.close()
    spans = _host_spans(str(tmp_path))
    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert [t[3]["step_num"] for t in ticks] == [s["tick"] for s in stats]
    phases = []
    for _, a, b, args in ticks:
        inside = [s for s in spans if a <= s[1] and s[2] <= b
                  and s[0] != "serve.tick"]
        names = [s[0] for s in inside]
        phases.append(names)
        assert names[:3] == ["serve.control", "serve.admit",
                             "serve.schedule"]
        assert names[3:6] == ["serve.pack", "serve.dispatch", "serve.wait"]
        assert names[-1] == "serve.finish" or names[-2:] == [
            "serve.finish", "serve.fetch"]
        for n, fa, fb, _ in inside:
            if n == "serve.fetch":              # the token read, in finish
                (fin,) = [s for s in inside if s[0] == "serve.finish"]
                assert fin[1] <= fa and fb <= fin[2]
        # the host's part leaves out the waits, and only the waits
        waited = sum(fb - fa for n, fa, fb, _ in inside
                     if n in ("serve.wait", "serve.fetch"))
        assert waited < b - a
    dispatches = [s[3] for s in spans if s[0] == "serve.dispatch"]
    assert dispatches[0] == {"program": "unified", "width": 32}   # 2 x 12
    assert {"program": "decode", "width": "None"} in dispatches
    assert {d["program"] for d in dispatches} == {"unified", "decode"}
    assert sum("serve.fetch" in p for p in phases) == 1
    assert all(0.0 < s["host_s"] for s in stats)


def test_host_s_leaves_out_the_device_waits(small_model, rng):
    """host_s is the tick's time less its wait and fetch spans, on the
    engine's clock: a clock that moves 1 s per reading inside a wait span
    and not elsewhere gives host_s 0."""
    cfg, params = small_model
    t = [0.0]
    eng = _paged_engine(cfg, params, clock=lambda: t[0])
    span = eng._span

    @contextlib.contextmanager
    def waiting_span(phase, **args):
        with span(phase, **args) as note:
            if phase in ("wait", "fetch"):
                t[0] += 1.0
            yield note

    eng._span = waiting_span
    for s in _serve(eng, rng, cfg, 0):
        assert s["host_s"] == 0.0
    eng.close()


def test_programs_keep_the_names_the_benchmark_reads(small_model, rng):
    """The device trace names a program after its jitted function: the
    benchmark finds the unified tick as jit_step_unified_fn and the
    decode-only tick as jit_decode_fn."""
    cfg, params = small_model
    eng = _paged_engine(cfg, params)
    modules = {}

    def spy(attr):
        fn = getattr(eng, attr)

        def call(*args):
            modules.setdefault(attr, fn.lower(*args).compile().as_text())
            return fn(*args)
        setattr(eng, attr, call)

    spy("_step_unified")
    spy("_decode")
    _serve(eng, rng, cfg, 0)
    eng.close()
    heads = {k: v.split("\n", 1)[0] for k, v in modules.items()}
    assert heads["_step_unified"].startswith("HloModule jit_step_unified_fn,")
    assert heads["_decode"].startswith("HloModule jit_decode_fn,")

"""The engine's phase spans in a profiler trace (``.xplane.pb``).

The program writes ``TraceAnnotation`` spans on the trace's host plane, on
the time base of the device planes: ``serve.tick`` around each tick (a step
annotation carrying ``step_num``) and, inside it, ``serve.control``,
``serve.admit``, ``serve.schedule``, ``serve.pack``, ``serve.dispatch``
(with ``program`` and ``width``), ``serve.wait``, ``serve.sample``,
``serve.finish`` (holding ``serve.fetch``, the token read) and
``serve.drain``.  The benchmark's own spans (``bench.tick`` around each
``eng.tick()``, ``bench.driver`` between ticks) enclose them.

``reduce_spans`` reads, over a window: the seconds and count of each
``serve.*`` span; each tick's host time, its ``serve.tick`` less the
``serve.wait`` and ``serve.fetch`` spans inside it; the device's idle time
split by the innermost host span open at each moment; and the longest idle
gaps, each named after the deepest host span that overlaps it (the one
covering most of the gap among spans of that depth).  On a trace without
``serve.*`` spans the gaps are named as ``trace.reduce_trace`` names them.
"""

from __future__ import annotations

import collections
import dataclasses

from . import trace

SERVE = "serve."
WAITS = ("serve.wait", "serve.fetch")


@dataclasses.dataclass
class Spans:
    window_s: float
    seconds: dict        # serve.* span name -> seconds inside the window
    calls: dict          # serve.* span name -> spans overlapping the window
    tick_host_s: dict    # serve.tick step_num -> tick less its waits (s)
    idle_by_span: dict   # innermost host span -> device idle seconds in it
    idle_gaps: list      # [(deepest host span, seconds)], longest first

    def tick_host_ms(self) -> float | None:
        """Mean host milliseconds per tick of the window."""
        host = list(self.tick_host_s.values())
        return 1e3 * sum(host) / len(host) if host else None


def _nest(events):
    """Depth of each span of one thread (the spans enclosing it; ``events``
    sorted by start, longest first), and the stretches of time in which
    each span is the innermost one open."""
    depth, inner, stack, t = [], [], [], None
    for name, a, b, _ in events:
        while stack and stack[-1][1] <= a:
            top, end = stack.pop()
            if end > t:
                inner.append((t, end, top))
                t = end
        if stack and a > t:
            inner.append((t, a, stack[-1][0]))
        t = a if t is None else max(t, a)
        depth.append(len(stack))
        stack.append((name, b))
    while stack:
        top, end = stack.pop()
        if end > t:
            inner.append((t, end, top))
            t = end
    return depth, inner


def _overlap(a, b, lo, hi):
    return min(b, hi) - max(a, lo)


def reduce_spans(path: str, window: tuple[float, float] | None = None,
                 top: int = 10) -> Spans:
    """The engine's spans over ``window`` (seconds on the trace's clock; by
    default from the first ``bench.tick`` to the last)."""
    data = trace.load(path)
    ops, host = [], []          # chip 0's operations; spans with their depth
    chip0 = None
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m and (chip0 is None or int(m.group(1)) < chip0):
            chip0 = int(m.group(1))
            ops = [ab for line in plane.lines if line.name == "XLA Ops"
                   for _, *ab in trace._events(line)]
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = sorted(((e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9, e)
                          for e in line.events if e.name in trace.BENCH_SPANS
                          or e.name.startswith(SERVE)),
                         key=lambda e: (e[1], -e[2]))
            if evs:
                host.append((evs, *_nest(evs)))
    if not ops:
        raise ValueError(f"{path}: no device operations in the trace")
    if window is None:
        ticks = [(a, b) for evs, _, _ in host for n, a, b, _ in evs
                 if n == "bench.tick"]
        if not ticks:
            raise ValueError(f"{path}: no bench.tick span")
        window = (min(a for a, _ in ticks), max(b for _, b in ticks))
    lo, hi = window

    seconds, calls = collections.Counter(), collections.Counter()
    tick_host: dict[int, float] = {}
    for evs, _, _ in host:
        ticks = []
        for name, a, b, e in evs:
            if not name.startswith(SERVE) or b <= lo or a >= hi:
                continue
            seconds[name] += _overlap(a, b, lo, hi)
            calls[name] += 1
            if name == "serve.tick" and lo <= a and b <= hi:
                ticks.append([dict(e.stats)["step_num"], a, b, b - a])
            elif name in WAITS:
                for tk in ticks[-1:]:
                    if tk[1] <= a and b <= tk[2]:
                        tk[3] -= b - a
        tick_host.update((int(n), s) for n, _, _, s in ticks)

    active = trace.merge((max(a, lo), min(b, hi)) for a, b in ops
                         if b > lo and a < hi)
    edges = [lo] + [x for ab in active for x in ab] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    by_span: collections.Counter = collections.Counter()
    inner = sorted(s for _, _, segs in host for s in segs)
    j = 0
    for a, b in idle:
        covered = 0.0
        while j < len(inner) and inner[j][1] <= a:
            j += 1
        k = j
        while k < len(inner) and inner[k][0] < b:
            ov = _overlap(inner[k][0], inner[k][1], a, b)
            if ov > 0:
                by_span[inner[k][2]] += ov
                covered += ov
            k += 1
        if b - a > covered:
            by_span["other"] += b - a - covered

    named = []
    for a, b in sorted(idle, key=lambda ab: ab[0] - ab[1])[:top]:
        cover: dict[int, collections.Counter] = collections.defaultdict(
            collections.Counter)
        for evs, depth, _ in host:
            for (name, ha, hb, _), d in zip(evs, depth):
                ov = _overlap(ha, hb, a, b)
                if ov > 0:
                    cover[d][name] += ov
        named.append((cover[max(cover)].most_common(1)[0][0] if cover
                      else "other", b - a))
    return Spans(window_s=hi - lo, seconds=dict(seconds), calls=dict(calls),
                 tick_host_s=tick_host, idle_by_span=dict(by_span),
                 idle_gaps=named)

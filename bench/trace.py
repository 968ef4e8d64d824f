"""Reduction of one profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Read by hand on a TPU v5 lite (JAX 0.9), the trace holds:

* a plane ``/device:TPU:<n>`` per chip, with a line ``XLA Modules`` (one
  event per program run, named ``jit_<function>(<fingerprint>)``: the
  engine's unified tick is ``jit_step_unified_fn``, its decode-only tick
  ``jit_decode_fn``) and a line ``XLA Ops`` (one event per operation,
  named by its HLO text ``%<op>.<n> = <type> ...``).  Container operations
  (``%while``, ``%conditional``, ``%call``) span the operations they run,
  so busy time is a union of intervals, never a sum.
* Pallas kernels appear in ``XLA Ops`` under the name of the function that
  wraps the ``pallas_call``: ``paged_segment_attention`` (the paged entry of
  ``kernels/segment_attention``, run by the unified tick),
  ``segment_attention`` (its flat-key entry), ``paged_decode_attention``
  (``kernels/paged_attention``, run by the decode-only tick) and
  ``decode_attention``.  Their kernel bodies are all named ``_kernel`` or
  ``_paged_kernel``; the wrapper's name is what the trace shows.
* a plane ``/host:CPU`` whose main thread's line carries the benchmark's own
  ``TraceAnnotation`` spans (``bench.tick``, ``bench.driver``,
  ``bench.leadin``).  Host and device events share one time base.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
CONTAINERS = ("while", "conditional", "call")
BENCH_SPANS = ("bench.tick", "bench.driver", "bench.leadin")

PROGRAMS = {"unified": "jit_step_unified_fn", "decode": "jit_decode_fn"}
KERNELS = {"segment_attention_paged": "paged_segment_attention",
           "segment_attention_flat": "segment_attention",
           "paged_decode_attention": "paged_decode_attention",
           "decode_attention": "decode_attention"}


def op_name(event_name: str) -> str:
    """``%paged_segment_attention.9 = bf16[...] ...`` -> the HLO op's name
    without its ordinal: ``paged_segment_attention``."""
    head = event_name.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"\.\d+$", "", head)


def program_name(event_name: str) -> str:
    """``jit_step_unified_fn(9554464522264377401)`` -> ``jit_step_unified_fn``."""
    return event_name.split("(", 1)[0]


def union_seconds(intervals) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def merge(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclasses.dataclass
class Reduction:
    window_s: float
    chips: int
    busy_s: float                     # mean over chips
    program_s: dict                   # program name -> device seconds
    program_calls: dict               # program name -> runs
    kernel_s: dict                    # op name -> device seconds
    kernel_calls: dict
    top_ops: list                     # [(op name, seconds)] leaf ops
    idle_gaps: list                   # [(host span, seconds)] longest first

    def kernel(self, key: str) -> tuple[float, int]:
        name = KERNELS[key]
        return self.kernel_s.get(name, 0.0), self.kernel_calls.get(name, 0)

    def program(self, key: str) -> tuple[float, int]:
        name = PROGRAMS[key]
        return self.program_s.get(name, 0.0), self.program_calls.get(name, 0)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under {trace_dir}")
    return paths[0]


def load(path: str):
    """The trace at ``path``: an ``.xplane.pb`` file, or one gzipped."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def reduce_trace(path: str, window: tuple[float, float] | None = None,
                 top: int = 10) -> Reduction:
    """Busy and idle time of the device over ``window`` (seconds on the
    trace's clock; by default from the first to the last device event),
    device time per program and per operation, and the longest idle gaps
    named after the benchmark span the host was in."""
    data = load(path)
    ops_by_chip: dict[int, list] = collections.defaultdict(list)
    modules: list = []
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                ops_by_chip[int(m.group(1))].extend(_events(line))
            elif m and line.name == "XLA Modules":
                modules.extend(_events(line))
            elif plane.name == "/host:CPU":
                host.extend(ev for ev in _events(line)
                            if ev[0] in BENCH_SPANS)
    if not ops_by_chip:
        raise ValueError(f"{path}: no device operations in the trace")
    if window is None:
        starts = [a for evs in ops_by_chip.values() for _, a, _ in evs]
        ends = [b for evs in ops_by_chip.values() for _, _, b in evs]
        window = (min(starts), max(ends))
    lo, hi = window

    def clip(evs):
        return [(n, max(a, lo), min(b, hi)) for n, a, b in evs
                if b > lo and a < hi]

    busy, kernel_s, kernel_calls = [], collections.Counter(), \
        collections.Counter()
    leaf_s: collections.Counter = collections.Counter()
    chip0 = min(ops_by_chip)
    for chip, evs in ops_by_chip.items():
        evs = clip(evs)
        busy.append(union_seconds((a, b) for _, a, b in evs))
        if chip != chip0:
            continue
        for n, a, b in evs:
            name = op_name(n)
            if name in KERNELS.values():
                kernel_s[name] += b - a
                kernel_calls[name] += 1
            if name not in CONTAINERS:
                leaf_s[name] += b - a
    program_s, program_calls = collections.Counter(), collections.Counter()
    for n, a, b in clip(modules):
        program_s[program_name(n)] += b - a
        program_calls[program_name(n)] += 1
    # the longest idle gaps of the first chip, each named after the host
    # span that covers most of it ("other" where no benchmark span does)
    active = merge((a, b) for _, a, b in clip(ops_by_chip[chip0]))
    edges = [lo] + [x for ab in active for x in ab] + [hi]
    gaps = sorted(((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]),
                  key=lambda ab: ab[0] - ab[1])[:top]
    named = []
    for a, b in gaps:
        cover = collections.Counter()
        for n, ha, hb in host:
            ov = min(b, hb) - max(a, ha)
            if ov > 0:
                cover[n] += ov
        named.append((cover.most_common(1)[0][0] if cover else "other",
                      b - a))
    return Reduction(
        window_s=hi - lo, chips=len(ops_by_chip),
        busy_s=sum(busy) / len(busy),
        program_s=dict(program_s), program_calls=dict(program_calls),
        kernel_s=dict(kernel_s), kernel_calls=dict(kernel_calls),
        top_ops=leaf_s.most_common(top), idle_gaps=named)


def host_window(path: str, span: str = "bench.tick") -> tuple[float, float]:
    """First start and last end of a benchmark span on the trace's clock."""
    data = load(path)
    first = last = None
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for n, a, b in _events(line):
                if n == span:
                    first = a if first is None else min(first, a)
                    last = b if last is None else max(last, b)
    if first is None:
        raise ValueError(f"{path}: no {span} span")
    return first, last

"""CPU tests of the reading of the engine's phase spans: the span reduction
on two traces recorded on a TPU v5 lite (one from before the engine wrote
spans, one with them: 3 s of ``yi6b.chat``, 37 decode-only ticks) and the
``tick_host_ms`` reader."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import cells, harness, spans, trace
from bench.loadgen import TickRecord

DATA = Path(__file__).resolve().parent / "data"
BENCH_ONLY = str(DATA / "yi6b_chat.xplane.pb.gz")
WITH_SPANS = str(DATA / "yi6b_chat_spans.xplane.pb.gz")


def test_nesting_depth_and_innermost_stretches():
    evs = [("bench.tick", 0.0, 10.0, None), ("serve.tick", 1.0, 9.0, None),
           ("serve.pack", 2.0, 3.0, None), ("serve.finish", 5.0, 8.0, None),
           ("serve.fetch", 6.0, 7.0, None), ("bench.driver", 10.0, 12.0, None)]
    depth, inner = spans._nest(evs)
    assert depth == [0, 1, 2, 2, 3, 0]
    assert inner == [(0.0, 1.0, "bench.tick"), (1.0, 2.0, "serve.tick"),
                     (2.0, 3.0, "serve.pack"), (3.0, 5.0, "serve.tick"),
                     (5.0, 6.0, "serve.finish"), (6.0, 7.0, "serve.fetch"),
                     (7.0, 8.0, "serve.finish"), (8.0, 9.0, "serve.tick"),
                     (9.0, 10.0, "bench.tick"), (10.0, 12.0, "bench.driver")]


def test_without_engine_spans_gaps_are_named_as_before():
    """A trace of a program that writes no serve.* span: nothing to count,
    and the idle gaps carry the names bench/trace.py gives them."""
    window = trace.host_window(BENCH_ONLY)
    red = trace.reduce_trace(BENCH_ONLY, window)
    sp = spans.reduce_spans(BENCH_ONLY)
    assert sp.window_s == pytest.approx(red.window_s)
    assert sp.seconds == {} and sp.calls == {} and sp.tick_host_s == {}
    assert sp.tick_host_ms() is None
    assert sp.idle_gaps == red.idle_gaps
    assert sum(sp.idle_by_span.values()) == pytest.approx(
        red.window_s - red.busy_s)
    assert set(sp.idle_by_span) <= set(trace.BENCH_SPANS) | {"other"}


def _record(stats):
    return TickRecord(0.0, 0.1, stats, [], 0, 0, None, 16)


def test_tick_host_ms_reads_the_engine_counter_or_nothing():
    reader = cells.load_metric("tick_host_ms")
    cell = cells.find_cell("yi6b.chat")

    def run(records):
        return harness.RunData(cell, None, None, records, [], 0.0)

    assert reader.read(run([_record({"tick": 1}), _record({"tick": 2})])) \
        is None                            # a program without the counter
    assert reader.read(run([])) is None
    assert reader.read(run([_record({"host_s": 0.002}),
                            _record({"host_s": 0.004})])) == pytest.approx(3.0)


def test_tick_host_time_and_gap_names_on_a_recorded_chip_trace():
    """Three ticks' host time counted by hand from the raw spans (tick 24:
    serve.tick 81,220,095 ns less serve.wait 79,143,344 ns; tick 25:
    81,410,415 less 79,285,935; tick 26: 81,156,035 less 79,260,015; no
    serve.fetch), the whole window against the engine's own host_s counter
    of the same run (mean 1.8695 ms), and every idle gap that the
    benchmark's reduction puts under bench.tick named after an engine
    span."""
    three = spans.reduce_spans(WITH_SPANS, (0.8572, 1.10119))
    hand = {24: 2_076_751e-9, 25: 2_124_480e-9, 26: 1_896_020e-9}
    assert three.tick_host_s == pytest.approx(hand, abs=1e-9)
    assert three.tick_host_ms() == pytest.approx(2.032417, abs=1e-6)

    sp = spans.reduce_spans(WITH_SPANS)
    assert len(sp.tick_host_s) == sp.calls["serve.tick"] == 37
    assert sp.calls["serve.dispatch"] == sp.calls["serve.wait"] == 37
    assert "serve.fetch" not in sp.calls        # no request finished
    assert sp.tick_host_ms() == pytest.approx(1.8695, abs=0.05)
    window = trace.host_window(WITH_SPANS)
    red = trace.reduce_trace(WITH_SPANS, window)
    assert sp.window_s == pytest.approx(red.window_s)
    assert [s for _, s in sp.idle_gaps] == [s for _, s in red.idle_gaps]
    assert any(old == "bench.tick" for old, _ in red.idle_gaps)
    for (old, _), (new, _) in zip(red.idle_gaps, sp.idle_gaps):
        if old == "bench.tick":
            assert new.startswith(spans.SERVE)
    assert sum(sp.idle_by_span.values()) == pytest.approx(
        red.window_s - red.busy_s)
    assert sp.idle_by_span["serve.dispatch"] > sp.idle_by_span["bench.driver"]

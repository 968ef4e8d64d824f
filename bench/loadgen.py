"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
and a seed into requests, and drives the serving engine with them on the
real clock.

Lengths.  Prompt and output lengths follow bounded Pareto distributions on
``[lo, hi]`` with tail index ``alpha`` (the inverse-CDF arithmetic of the
program's ``serve/traffic._bounded_pareto``).  They are not drawn at
random: requests come in rounds of ``clients``, and round ``r`` takes the
distribution's quantiles at ``(i + f_r) / clients`` for ``i < clients``,
with offsets ``f_r`` from a fixed low-discrepancy sequence.  Prompt and
output strata are paired, and each round ordered, by permutations fixed
per round.  So every seed serves the same lengths in the same order, and
the same amount of work falls into the window; the seed draws the token
ids (and, elsewhere, the weights).

The loop is closed (``"loop": "closed"``, the only kind this generator
runs): ``clients`` users, each of whom sends its next request when its
previous one completes, with no think time.

The driver keeps one record per engine tick, on the host clock: when the
tick started and returned, the engine's tick stats, which live segments it
carried (for ``work.py``) and which tokens it emitted.  A token counts as
emitted when the tick that sampled it returns, because the engine waits on
the device there."""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable

import numpy as np

GOLDEN = 0.6180339887498949


def bounded_pareto_quantile(u, lo: int, hi: int, alpha: float) -> np.ndarray:
    """Inverse CDF of a Pareto truncated to ``[lo, hi]``, at quantiles u."""
    lo_f, hi_f = float(lo), float(max(hi, lo + 1))
    ratio = (lo_f / hi_f) ** alpha
    x = lo_f / (1.0 - np.asarray(u, np.float64) * (1.0 - ratio)) ** (1.0 / alpha)
    return np.clip(x.astype(np.int64), lo, hi)


def bounded_pareto_mean(lo: int, hi: int, alpha: float, n: int = 100000):
    return float(bounded_pareto_quantile((np.arange(n) + 0.5) / n, lo, hi,
                                         alpha).mean())


def round_lengths(mix: dict, r: int, size: int) -> list[tuple[int, int]]:
    """(prompt, output) lengths of round ``r``: the same for every seed."""
    u = (np.arange(size) + (r * GOLDEN) % 1.0) / size
    v = (np.arange(size) + (r * GOLDEN * GOLDEN) % 1.0) / size
    p, o = mix["prompt"], mix["output"]
    plen = bounded_pareto_quantile(u, p["lo"], p["hi"], p["alpha"])
    olen = bounded_pareto_quantile(v, o["lo"], o["hi"], o["alpha"])
    rng = np.random.default_rng([r, size])
    pair, order = rng.permutation(size), rng.permutation(size)
    return [(int(plen[i]), int(olen[pair[i]])) for i in order]


class RequestSource:
    """Requests of one run: the mix's lengths, the seed's token ids."""

    def __init__(self, mix: dict, seed: int, vocab: int, round_size: int,
                 make_request: Callable):
        self.mix, self.vocab, self.size = mix, vocab, round_size
        self.rng = np.random.default_rng(seed)
        self.make = make_request
        self.pending: collections.deque = collections.deque()
        self.rounds = 0
        self.issued = 0

    def next(self):
        if not self.pending:
            lens = round_lengths(self.mix, self.rounds, self.size)
            self.rounds += 1
            self.pending.extend(lens)
        plen, olen = self.pending.popleft()
        prompt = self.rng.integers(0, self.vocab, plen).astype(np.int32)
        req = self.make(self.issued, prompt, olen)
        self.issued += 1
        return req


@dataclasses.dataclass
class TickRecord:
    t0: float
    t1: float
    stats: dict
    segments: list          # [(start, n)] live segments carried
    sampled: int            # segments that sampled a token
    emitted: int            # output tokens this tick emitted
    width: int | None       # packed stream lanes; None for decode-only
    chunk_budget: int       # the live serve.prefill_chunk_tokens value


class Driver:
    """Drives one engine with one request source on the host clock."""

    def __init__(self, engine, source: RequestSource, mix: dict, *,
                 clients: int, clock: Callable[[], float] = time.perf_counter,
                 span: Callable | None = None):
        self.eng, self.src, self.mix = engine, source, mix
        self.clients = clients
        if mix.get("loop") != "closed":
            raise ValueError(f"mix {mix.get('name')!r}: loop "
                             f"{mix.get('loop')!r}; only closed loops run")
        self.clock = clock
        self.span = span or no_span
        self.admit = True
        self.live: dict[int, object] = {}       # req_id -> in-flight request
        self.finished: list = []
        self.records: list[TickRecord] = []
        self.last_emit: dict[int, float] = {}
        self.gaps: list[tuple[float, float]] = []  # (emit time, gap)
        self.submitted = 0
        self.rejected = 0

    # ---------------------------------------------------------- submission
    def _top_up(self) -> None:
        while self.admit and len(self.live) < self.clients:
            req = self.src.next()
            self.submitted += 1
            if self.eng.submit(req):
                self.live[req.req_id] = req
            else:
                self.rejected += 1

    # ---------------------------------------------------------------- tick
    def step(self) -> TickRecord:
        eng = self.eng
        with self.span("bench.driver"):
            self._top_up()
            before = {rid: (r.prefilled, r.gen_count)
                      for rid, r in self.live.items()}
            budget = int(getattr(eng, "prefill_chunk", 0))
        t0 = self.clock()
        with self.span("bench.tick"):
            st = eng.tick()
        t1 = self.clock()
        with self.span("bench.driver"):
            rec = self._account(st, before, t0, t1, budget)
        return rec

    def _account(self, st, before, t0, t1, budget) -> TickRecord:
        segments, sampled, emitted = [], 0, 0
        for rid, (pre0, gen0) in before.items():
            r = self.live[rid]
            if r.prefilled > pre0:                      # a prefill chunk
                segments.append((pre0, r.prefilled - pre0))
            elif r.gen_count > gen0 and gen0 > 0:       # a decode rider
                segments.append((len(r.prompt) + gen0 - 1, 1))
            new = (min(r.gen_count, r.max_new_tokens)
                   - min(gen0, r.max_new_tokens))
            if new > 0:
                sampled += 1
                emitted += new
                if rid in self.last_emit:
                    self.gaps.append((t1, t1 - self.last_emit[rid]))
                self.gaps.extend((t1, 0.0) for _ in range(new - 1))
                self.last_emit[rid] = t1
        for rid in [rid for rid, r in self.live.items()
                    if r.done_t is not None or r.reject_reason is not None]:
            req = self.live.pop(rid)
            if req.reject_reason is None:
                self.finished.append((t1, req))
            else:
                self.rejected += 1
        width = (st["prefill_issued_tokens"] + st["decode_slots"]
                 if st["prefill_issued_tokens"] else None)
        rec = TickRecord(t0, t1, st, segments, sampled, emitted, width,
                         budget)
        self.records.append(rec)
        return rec

    def finished_tokens(self) -> int:
        return sum(len(r.generated) for _, r in self.finished)

    def drain(self, tokens: int) -> int:
        """After the window: admits nothing more and ticks until the
        finished requests hold ``tokens`` served tokens or none is live.
        Returns the ticks it ran."""
        self.admit = False
        n = 0
        while self.live and self.finished_tokens() < tokens:
            self.step()
            n += 1
        return n


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def no_span(name):
    return _NoSpan()


# ----------------------------------------------------------- window numbers
def window_records(records, t_open: float) -> list[TickRecord]:
    return [r for r in records if r.t0 >= t_open]


def output_tok_s(recs) -> float | None:
    """Output tokens of the window's ticks over the time from the first
    such tick's start to the last one's end."""
    if not recs:
        return None
    span = recs[-1].t1 - recs[0].t0
    return sum(r.emitted for r in recs) / span if span > 0 else None


def percentile(values, q: float) -> float | None:
    """The q-th percentile by the nearest-rank rule (no interpolation)."""
    if not len(values):
        return None
    v = np.sort(np.asarray(values, np.float64))
    k = max(0, int(np.ceil(q / 100.0 * len(v))) - 1)
    return float(v[k])


def window_gaps(gaps, t_open: float, t_close: float) -> list[float]:
    """Every inter-token gap whose later token was emitted in the window."""
    return [g for t, g in gaps if t_open <= t <= t_close]

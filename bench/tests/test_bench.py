"""CPU tests of the benchmark's yardstick: discovery by name (an
architecture family among it), the traffic arithmetic, the work counts,
the peaks table, the trace reduction on a trace recorded on a TPU v5 lite,
the dense family against values pinned before it moved, and the
correctness check (its control and a broken timed path) driven end to end
at a small size."""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import cells, harness, loadgen, peaks, trace, work

ROOT = cells.ROOT
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))
DATA = Path(__file__).resolve().parent / "data"

DENSE = cells.load_family("dense")
YI = DENSE.shape_from_config(json.loads((ROOT / "bench/configs/yi-6b.json")
                                        .read_text()))


# ------------------------------------------------------------- the contract
def test_benchmark_json_names_files_and_layers():
    bench = cells.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(cells.NAME_RE.match(n) for n in names)
    assert len(names) == len(set(names))
    for c in bench["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert (ROOT / "bench/families" / f"{conf['family']}.py").exists()
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").exists()
    for w in bench["workloads"]:
        cell = cells.find_cell(w["name"])
        assert cell.chips == 1 and cell.per_layer and cell.end_to_end
        assert cell.config["serve"]["cache_len"] >= (
            cell.mix["prompt"]["hi"] + cell.mix["output"]["hi"])


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A cell added as new files plus BENCHMARK.json entries, with no
    existing file changed, is found by name."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = cells.load_benchmark()
    conf = json.loads((ROOT / "bench/configs/yi-6b.json").read_text())
    conf["name"] = "yi-6b.short"
    (root / "bench/configs/yi-6b.short.json").write_text(json.dumps(conf))
    (root / "bench/traffic/reason.json").write_text(json.dumps({
        "loop": "closed", "prompt": {"lo": 64, "hi": 512, "alpha": 1.2},
        "output": {"lo": 512, "hi": 1536, "alpha": 1.0}}))
    (root / "bench/metrics/ticks_in_window.py").write_text(
        "def read(run):\n    return float(len(run.records)) or None\n")
    bench["configs"].append({"name": "yi-6b.short", "source": conf["source"],
                             "file": "bench/configs/yi-6b.short.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "yi6b.reason", "config": "yi-6b.short",
                               "traffic": "reason", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "ticks_in_window", "unit": "ticks",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "output_tok_s",
                               "workloads": ["yi6b.reason"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.find_cell("yi6b.reason", root=root)
    assert cell.config["name"] == "yi-6b.short"
    assert cell.mix["output"]["lo"] == 512 and cell.mix["name"] == "reason"
    assert [m["name"] for m in cell.per_layer] == ["ticks_in_window"]
    reader = cells.load_metric("ticks_in_window", root=root)
    assert reader.read(harness.RunData(cell, YI, None, [1, 2], [], 0.0)) == 2
    with pytest.raises(KeyError):
        cells.find_cell("yi6b.nothing", root=root)
    with pytest.raises(ValueError):
        cells.load_mix("../chat", root=root)


@pytest.mark.parametrize("name", ["yi6b.chat", "sc2.complete"])
def test_cell_configs_state_published_widths(name):
    cell = cells.find_cell(name)
    s = cell.family.shape_from_config(cell.config)
    assert s.head_dim == 128 and s.dtype == "bfloat16"
    cfg = cell.family.program_config(cell.config, s)
    assert cfg.num_layers == s.layers
    assert cell.config["check"]["widest_gap_logits"] > 0


def test_program_config_refuses_a_width_that_differs():
    conf = json.loads((ROOT / "bench/configs/yi-6b.json").read_text())
    conf["intermediate_size"] = 11000
    family = cells.load_family(conf["family"])
    with pytest.raises(ValueError):
        family.program_config(conf, family.shape_from_config(conf))


# ------------------------------------------------------------------- peaks
def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


# -------------------------------------------------------------------- work
def test_work_matches_hand_counts_for_one_packed_tick():
    # a 16-lane yi-6b tick: a 9-token prefill chunk at positions 100..108
    # and 7 decode riders at position 300
    segs = [(100, 9)] + [(300, 1)] * 7
    keys = sum(100 + i + 1 for i in range(9)) + 7 * 301
    assert keys == 945 + 2107
    assert work.attention_flops(YI, segs) == 4 * 32 * 128 * keys
    per_layer = (4096 * 4096 + 2 * 4096 * 512 + 4096 * 4096
                 + 3 * 4096 * 11008)
    assert YI.matmul_flops_per_token() == 2 * 32 * per_layer
    assert work.head_flops(YI, 8) == 2 * 4096 * 64000 * 8
    assert work.step_flops(YI, segs, 8) == (
        16 * 2 * 32 * per_layer + 32 * 4 * 32 * 128 * keys
        + 2 * 4096 * 64000 * 8)
    q_o = 2 * 32 * 128 * 16
    kv = 2 * 4 * 128 * (109 + 7 * 301)
    assert work.attention_bytes(YI, segs) == 2 * (q_o + kv)
    t, bound = work.attention_min_seconds(YI, segs, 197e12, 819e9)
    assert bound == "memory"
    assert t == pytest.approx(2 * (q_o + kv) / 819e9)


# ---------------------------------------------------------------- families
PINS = json.loads((DATA / "dense_pins.json").read_text())


def _leaf_digests(tree) -> dict:
    import jax
    return {jax.tree_util.keystr(path): f"{x.dtype}{list(x.shape)} "
            + hashlib.sha256(np.asarray(x).tobytes()).hexdigest()
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("name", ["yi6b.chat", "sc2.complete"])
def test_dense_family_matches_values_pinned_before_the_move(name):
    """The same seed gives the same weight tree, bit for bit, and the same
    reference and fp8-control gaps as the dense code gave before it moved
    into ``bench/families/dense.py``."""
    pin = PINS["cells"][name]
    cell = tiny_cell(name)
    family = cell.family
    s = family.shape_from_config(cell.config)
    weights = family.init_weights(s, PINS["seed"])
    assert _leaf_digests(weights) == pin["leaves"]
    rng = np.random.default_rng(PINS["prompt_rng"])
    prompt = rng.integers(0, s.vocab, PINS["prompt_tokens"]).astype(np.int32)
    served = rng.integers(0, s.vocab, PINS["served_tokens"]).astype(np.int32)
    for key, control in (("reference_gaps", False), ("control_gaps", True)):
        gaps = family.served_gaps(s, weights, prompt, served,
                                  length=PINS["length"], rows=PINS["rows"],
                                  control=control)
        assert [float(g) for g in gaps] == pin[key]


# ----------------------------------------------------------------- traffic
def test_every_seed_serves_the_same_lengths_with_its_own_tokens():
    mix = cells.load_mix("chat")

    def requests(seed, n):
        src = loadgen.RequestSource(mix, seed, 1000, 8,
                                    lambda i, p, o: (p, o))
        return [src.next() for _ in range(n)]

    a, b = requests(3, 24), requests(2**33 + 7, 24)
    assert [(len(p), o) for p, o in a] == [(len(p), o) for p, o in b]
    assert not np.array_equal(a[0][0], b[0][0])
    assert all(np.array_equal(x[0], y[0])
               for x, y in zip(a, requests(3, 24)))
    # each round of 8 covers the distribution's strata once
    p, o = mix["prompt"], mix["output"]
    lens = sorted(len(x) for x, _ in a[:8])
    assert lens[0] == p["lo"] and lens[-1] > 4 * lens[3]
    assert all(p["lo"] <= len(x) <= p["hi"] and o["lo"] <= n <= o["hi"]
               for x, n in a)
    many = requests(5, 800)
    for side, got in (("prompt", [len(x) for x, _ in many]),
                      ("output", [n for _, n in many])):
        d = mix[side]
        assert np.mean(got) == pytest.approx(
            loadgen.bounded_pareto_mean(d["lo"], d["hi"], d["alpha"]),
            rel=0.05)


@pytest.mark.parametrize("mix,mean", [("chat", (69.5, 214.5)),
                                      ("complete", (1708, 19.1))])
def test_mixes_hold_the_published_statistics(mix, mean):
    """chat: LMSYS-Chat-1M's mean prompt and response; complete: the Azure
    2023 code trace's median prompt (1500) and output (13)."""
    m = cells.load_mix(mix)
    got = [loadgen.bounded_pareto_mean(m[k]["lo"], m[k]["hi"], m[k]["alpha"])
           for k in ("prompt", "output")]
    assert got == pytest.approx(list(mean), rel=0.01)
    if mix == "complete":
        u = (np.arange(10001) + 0.5) / 10001
        med = [np.median(loadgen.bounded_pareto_quantile(
            u, m[k]["lo"], m[k]["hi"], m[k]["alpha"])) for k in
            ("prompt", "output")]
        assert med[0] == pytest.approx(1500, rel=0.01) and med[1] == 13


def test_the_driver_runs_closed_loops_only():
    src = loadgen.RequestSource(cells.load_mix("chat"), 0, 100, 2, None)
    with pytest.raises(ValueError):
        loadgen.Driver(None, src, {"loop": "open", "name": "x"}, clients=2)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@dataclasses.dataclass
class FakeReq:
    req_id: int
    prompt: np.ndarray
    max_new_tokens: int
    prefilled: int = 0
    gen_count: int = 0
    done_t: float | None = None
    reject_reason: object = None
    generated: list = dataclasses.field(default_factory=list)


class FakeEngine:
    """Each tick: prefill up to 8 prompt tokens of the oldest prefilling
    request, decode one token for every running one; a tick lasts 0.5 s
    when it prefills and 0.1 s when it only decodes."""

    prefill_chunk = 8

    def __init__(self, clock):
        self.clock, self.reqs = clock, []

    def submit(self, r):
        self.reqs.append(r)
        return True

    def tick(self):
        live = [r for r in self.reqs if r.done_t is None]
        pre = [r for r in live if r.prefilled < len(r.prompt)]
        run = [r for r in live if r.prefilled >= len(r.prompt)]
        issued = 0
        for r in run:
            r.gen_count += 1
        if pre:
            r = pre[0]
            n = min(8, len(r.prompt) - r.prefilled)
            r.prefilled += n
            issued = 8
            if r.prefilled == len(r.prompt):
                r.gen_count = 1
        self.clock.t += 0.5 if pre else 0.1
        for r in live:
            if r.gen_count >= r.max_new_tokens:
                r.done_t = self.clock.t
                r.generated = list(range(r.max_new_tokens))
        return {"prefill_issued_tokens": issued, "decode_slots": len(run),
                "prefill_tokens": n if pre else 0}


def test_driver_gap_and_whole_tick_rate_arithmetic():
    clock = FakeClock()
    eng = FakeEngine(clock)
    mix = {"loop": "closed", "prompt": {"lo": 8, "hi": 8, "alpha": 1.0},
           "output": {"lo": 3, "hi": 3, "alpha": 1.0}}
    src = loadgen.RequestSource(mix, 0, 100, 2,
                                lambda i, p, o: FakeReq(i, p, o))
    drv = loadgen.Driver(eng, src, mix, clients=2, clock=clock)
    for _ in range(6):
        drv.step()
    # tick 1 (0.0-0.5): prefill A, A's token 1; tick 2 (0.5-1.0): prefill
    # B and B's token 1, A's token 2 (gap 0.5); tick 3 (1.0-1.1): A's 3rd
    # and B's 2nd (gaps 0.1), A done, C submitted; tick 4 (1.1-1.6):
    # prefill C and C's token 1, B's 3rd (gap 0.5), B done
    recs = drv.records
    assert [r.emitted for r in recs[:4]] == [1, 2, 2, 2]
    assert [round(r.t1, 6) for r in recs[:4]] == [0.5, 1.0, 1.1, 1.6]
    assert recs[0].segments == [(0, 8)]
    assert sorted(recs[1].segments) == [(0, 8), (8, 1)]
    gaps = sorted(round(g, 6) for _, g in drv.gaps[:4])
    assert gaps == [0.1, 0.1, 0.5, 0.5]
    window = loadgen.window_records(recs, 1.0)
    assert window[0].t0 == 1.0
    total = sum(r.emitted for r in window)
    assert loadgen.output_tok_s(window) == pytest.approx(
        total / (window[-1].t1 - 1.0))
    assert loadgen.percentile([1, 2, 3, 4], 50) == 2
    assert loadgen.percentile([5.0] * 19 + [100.0], 95) == 5.0
    assert loadgen.percentile(list(range(1, 101)), 95) == 95
    assert [f.req_id for _, f in drv.finished][:2] == [0, 1]


def test_the_drain_admits_nothing_and_stops_at_enough_tokens():
    clock = FakeClock()
    eng = FakeEngine(clock)
    mix = {"loop": "closed", "prompt": {"lo": 8, "hi": 8, "alpha": 1.0},
           "output": {"lo": 4, "hi": 4, "alpha": 1.0}}
    src = loadgen.RequestSource(mix, 0, 100, 3,
                                lambda i, p, o: FakeReq(i, p, o))
    drv = loadgen.Driver(eng, src, mix, clients=3, clock=clock)
    drv.step()
    submitted = drv.submitted
    assert submitted == 3 and not drv.finished
    ticks = drv.drain(8)
    assert drv.submitted == submitted and drv.finished_tokens() == 8
    assert len(drv.live) == 1 and ticks == len(drv.records) - 1
    drv.drain(100)                       # runs the rest out, then stops
    assert not drv.live and drv.finished_tokens() == 12


# ------------------------------------------------------------------- trace
TRACE = DATA / "yi6b_chat.xplane.pb.gz"


def test_trace_reduction_on_a_recorded_chip_trace():
    """A 6 s window of yi6b.chat on one TPU v5 lite: 30 decode-only ticks
    and one 16-lane unified tick."""
    path = str(TRACE)
    lo, hi = trace.host_window(path)
    red = trace.reduce_trace(path, (lo, hi))
    assert red.chips == 1 and red.window_s == pytest.approx(hi - lo)
    assert red.window_s == pytest.approx(5.98, abs=0.05)
    assert red.program_calls == {"jit_decode_fn": 30,
                                 "jit_step_unified_fn": 1}
    seg_s, seg_n = red.kernel("segment_attention_paged")
    dec_s, dec_n = red.kernel("paged_decode_attention")
    uni_s, _ = red.program("unified")
    dec_prog_s, _ = red.program("decode")
    # one kernel call per layer and program run
    assert seg_n == 32 and dec_n == 30 * 32
    assert 0 < seg_s < uni_s and 0 < dec_s < dec_prog_s
    assert uni_s + dec_prog_s <= red.busy_s * 1.0001 < red.window_s
    names = [n for n, _ in red.top_ops]
    assert names[:2] == ["paged_decode_attention", "paged_segment_attention"]
    assert "while" not in names
    spans = [n for n, _ in red.idle_gaps]
    assert len(spans) == 10 and set(spans) <= set(trace.BENCH_SPANS) | {"other"}
    assert [s for _, s in red.idle_gaps] == sorted(
        (s for _, s in red.idle_gaps), reverse=True)


def test_union_and_names():
    assert trace.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.merge([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.op_name("%paged_segment_attention.9 = bf16[32,128,128]"
                         "{2,1,0} custom-call(...)") == "paged_segment_attention"
    assert trace.program_name("jit_step_unified_fn(95544)") == \
        "jit_step_unified_fn"


# ----------------------------------------------------------- whole runs
def tiny_cell(name="yi6b.chat", root=ROOT) -> cells.Cell:
    """The cell's traffic and serving path at a size the CPU runs in
    seconds: two layers of width 64."""
    cell = cells.find_cell(name, root=root)
    conf = dict(cell.config, hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2,
                num_hidden_layers=2, vocab_size=512, head_dim=16)
    conf["program"] = dict(conf["program"], replace=dict(
        d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
        vocab_size=512))
    conf["serve"] = dict(conf["serve"], cache_len=512, max_batch=4)
    mix = dict(cell.mix, prompt={"lo": 8, "hi": 256, "alpha": 1.2},
               output={"lo": 4, "hi": 64, "alpha": 1.2})
    return dataclasses.replace(cell, config=conf, mix=mix)


def _run(cell, seed=2**33 + 5, **kw):
    # the CPU reports no memory limit: the tiny engine gets a budget
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "hbm_budget", lambda device: 10**9)
        return harness.run_cell(cell, seed, 1.5, False, t_process=0.0,
                                require_tpu=False, log=lambda m: None, **kw)


@pytest.fixture(scope="module")
def control_run():
    return _run(tiny_cell(), control=True)


def test_a_run_is_correct_and_its_control_reads_wider_gaps(control_run):
    line = control_run
    assert line["correct"] and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"output_tok_s", "itl_p95_ms", "setup_s"}
    prog, ctrl = line["program_check"], line["control_check"]
    assert prog["tokens"] >= 100 and ctrl["tokens"] == prog["tokens"]
    assert ctrl["widest_gap_logits"] > prog["widest_gap_logits"]
    assert ctrl["mean_gap_logits"] > prog["mean_gap_logits"]


def test_a_control_over_the_limit_is_not_correct(control_run):
    """The control's gaps go through the run's own verdict: with the limit
    between the program's widest gap and the control's, the program reads
    correct and the control does not."""
    prog, ctrl = control_run["program_check"], control_run["control_check"]
    assert prog["correct"] is True
    assert ctrl["correct"] == harness.verdict(ctrl)
    mid = (prog["widest_gap_logits"] + ctrl["widest_gap_logits"]) / 2
    assert harness.verdict(dict(prog, limit=mid))
    assert not harness.verdict(dict(ctrl, limit=mid))
    assert not harness.verdict(dict(prog, limit=mid), failed=1)
    assert not harness.verdict(dict(prog, limit=mid, tokens=0))


def _alter_tokens(eng):
    """Every sampled token is changed where the tick produces it."""
    step, dec = eng._step_unified, eng._decode
    vocab = eng.cfg.vocab_size

    def bad_step(*a):
        c, tok, gbuf = step(*a)
        return c, tok, (gbuf + 1) % vocab

    def bad_decode(*a):
        tok, c, gbuf = dec(*a)
        return tok, c, (gbuf + 1) % vocab

    eng._step_unified, eng._decode = bad_step, bad_decode
    return eng


def _drop_kv_state(eng):
    """Ticks run against an empty block table: no K/V is written or read,
    so the KV state stays as it was."""
    import jax.numpy as jnp
    eng._bt = lambda: jnp.full(eng._bt_np.shape, -1, jnp.int32)
    return eng


@pytest.mark.parametrize("fault", [_alter_tokens, _drop_kv_state])
def test_a_broken_timed_path_fails_the_check(fault):
    line = _run(tiny_cell(), tamper=fault)
    assert not line["correct"]
    gap = line["checks"]["widest_gap_logits"]
    assert gap["value"] > gap["limit"]


TOY_FAMILY = '''"""The dense family under another name, counting the calls the
harness makes of it."""
from pathlib import Path

from bench import cells

dense = cells.load_family("dense", Path(__file__).resolve().parents[2])
calls = {"init_weights": 0, "served_gaps": 0}
shape_from_config = dense.shape_from_config
program_config = dense.program_config


def init_weights(shape, seed):
    calls["init_weights"] += 1
    return dense.init_weights(shape, seed)


def served_gaps(shape, weights, prompt, served, **kw):
    calls["served_gaps"] += 1
    return dense.served_gaps(shape, weights, prompt, served, **kw)
'''


def test_a_new_family_is_new_files_only(tmp_path):
    """A family file, a configuration that names it and the entries in
    BENCHMARK.json make a cell that runs, with no existing file changed;
    the run's weights and reference come from the new family."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    (root / "bench/families/toy.py").write_text(TOY_FAMILY)
    conf = json.loads((ROOT / "bench/configs/yi-6b.json").read_text())
    conf.update(name="yi-6b.toy", family="toy")
    (root / "bench/configs/yi-6b.toy.json").write_text(json.dumps(conf))
    bench = cells.load_benchmark()
    bench["configs"].append({"name": "yi-6b.toy", "source": conf["source"],
                             "file": "bench/configs/yi-6b.toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.chat", "config": "yi-6b.toy",
                               "traffic": "chat", "chips": 1, "why": "t"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = tiny_cell("toy.chat", root=root)
    assert Path(cell.family.__file__) == root / "bench/families/toy.py"
    assert Path(cell.family.dense.__file__) == root / "bench/families/dense.py"
    line = _run(cell)
    assert line["correct"] and line["checks"]["tokens_checked_min"]["value"]
    assert cell.family.calls["init_weights"] == 1
    assert cell.family.calls["served_gaps"] >= 1
    assert all(p.read_bytes() == b for p, b in before.items())


def test_run_refuses_without_a_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    for extra in ({}, {"REPRO_SEGMENT_IMPL": "xla"}):
        p = subprocess.run(
            [sys.executable, str(ROOT / "bench/run.py"), "--workload",
             "yi6b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
            cwd=ROOT, env=dict(env, **extra), capture_output=True, text=True,
            timeout=120)
        assert p.returncode == 2, p.stderr[-2000:]
        assert p.stdout == ""

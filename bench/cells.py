"""Finds what a cell is made of by name: ``BENCHMARK.json`` names the cell,
its configuration and its traffic mix; each of those, and each metric, is a
file of its own under ``bench/``.  A new configuration, mix or metric is a
new file plus a ``BENCHMARK.json`` entry, and no existing file changes."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(name: str) -> str:
    if not NAME_RE.match(name):
        raise ValueError(f"{name!r} is not a benchmark name")
    return name


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict        # the configuration file, as run
    mix: dict           # the traffic mix file
    end_to_end: tuple   # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", (cell,))


def find_cell(name: str, root: Path = ROOT, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        mix=load_mix(w["traffic"], root),
        end_to_end=tuple(m for m in bench["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)))


def load_mix(name: str, root: Path = ROOT) -> dict:
    mix = json.loads((root / "bench" / "traffic" / f"{_checked(name)}.json")
                     .read_text())
    mix.setdefault("name", name)
    return mix


def load_metric(name: str, root: Path = ROOT):
    """The reader module of one metric: ``bench/metrics/<name>.py`` with
    ``read(run) -> float | None``."""
    path = root / "bench" / "metrics" / f"{_checked(name)}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

"""Finds what a cell is made of by name: ``BENCHMARK.json`` names the cell,
its configuration and its traffic mix; the configuration file names its
architecture's family.  Each of those, and each metric, is a file of its
own under ``bench/``.  A new configuration, mix or metric is a new file
plus a ``BENCHMARK.json`` entry, and a new architecture is a new family
file besides; no existing file changes."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType

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
    family: ModuleType  # bench/families/<config["family"]>.py
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
    config = json.loads((root / conf["file"]).read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        family=load_family(config["family"], root),
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


_families: dict[Path, ModuleType] = {}


def load_family(name: str, root: Path = ROOT) -> ModuleType:
    """The module of one architecture family, ``bench/families/<name>.py``,
    loaded once per file.  It provides:

    - ``shape_from_config(config)``: a frozen, hashable shape (a static jit
      argument) with ``d_model``, ``layers``, ``vocab``, ``heads``,
      ``kv_heads``, ``head_dim``, ``kv_bytes_per_token`` and
      ``matmul_flops_per_token()``, which ``bench/work.py`` and the metric
      readers read;
    - ``program_config(config, shape)``: the program's ``ArchConfig``,
      refused where a width differs from the file;
    - ``init_weights(shape, seed)``: the weight tree the engine serves;
    - ``served_gaps(shape, weights, prompt, served, *, length, rows,
      control)``: the plain reference's gap at each served token
      (``bench/reference.py``)."""
    path = (root / "bench" / "families" / f"{_checked(name)}.py").resolve()
    if path not in _families:
        mod_name = f"bench_family_{name.replace('.', '_').replace('-', '_')}"
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        # dataclasses look their module up while the file executes
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        _families[path] = mod
    return _families[path]

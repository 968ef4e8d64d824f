#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this process holds.

    python3 bench/run.py --workload yi6b.chat --seed 7 --seconds 10 --trace 0

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window (and ``busy_s``,
``window_s`` and a ``breakdown``).  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, last, ``checks`` (each number compared with its limit); the
last lines of standard error repeat the checks.  Without a TPU, with fewer
chips than the cell asks for, or with a ``REPRO_*`` variable set, it exits
with 2 and prints no result."""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the benchmark is imported as the package ``bench`` and the program from
# ``src``; this directory itself must not shadow modules of those names
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import cells, harness
    cell = cells.find_cell(args.workload)
    try:
        harness.refuse_overrides()
        line = harness.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

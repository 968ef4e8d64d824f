#!/usr/bin/env python3
"""Readings that the correctness limit of a cell is set from: for each seed,
one run of the cell as ``run.py`` makes it, with the served tokens judged
by the reference and, at the same positions, the lower-precision control.
All seeds run in one process.

    python3 bench/calibrate.py --workload yi6b.chat --seconds 51 --seeds 1 2 3

Prints one JSON line per seed, ``{"seed", "program", "control"}``, each with
the widest and mean gap, the share of tokens that are not the reference's
best, and ``correct``: the run's own verdict (``harness.verdict``) on the
served tokens and on the control's, against the cell's limit.  ``--out``
also appends the lines to a file."""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from bench import cells, harness
    harness.refuse_overrides()
    cell = cells.find_cell(args.workload)
    for seed in args.seeds:
        line = harness.run_cell(cell, seed, args.seconds, False,
                                t_process=time.perf_counter(), control=True)
        rec = {"workload": args.workload, "seed": seed,
               "program": line["program_check"],
               "control": line["control_check"],
               "metrics": line["metrics"]}
        for side in ("program", "control"):
            c = rec[side]
            print(f"seed {seed} {side}: correct {c['correct']} (widest gap "
                  f"{c['widest_gap_logits']}, limit {c['limit']}, "
                  f"{c['tokens']} tokens)", file=sys.stderr)
        print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Readings for a cell's correctness limit, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed this runs the cell as ``bench/run.py`` does (set-up, a
window of ``--seconds``, the reference over the checked requests) and also
runs the control: the reference with its weights rounded to float8 e4m3,
put in the program's place, read at each checked position as the gap of
the token the control puts first.  One JSON line per seed gives the
program's widest gap (``served``) and ``correct``, and the control's
(``control``) and the same decision taken on it (``control_correct``).
The limit in ``bench/checks/<cell>.json`` lies between the largest
``served`` and the smallest ``control`` of such runs.  The benchmark's own
runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import run

    bench, cell, spec, mix, check = run.cell_files(args.workload)
    for seed in args.seeds:
        res = run.run_cell(cell, spec, mix, check, bench, seed, args.seconds,
                           False, controls=True)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "served": res["checks"]["max_logit_gap"]["value"],
            "control": res["control_gap"], "correct": res["correct"],
            "control_correct": res["control_correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

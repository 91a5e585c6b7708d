"""Readings that set the limits of ``correct``: the program's numbers and
each control's, cell by cell, over many seeds in one process (set-up is
paid once; later seeds find every program compiled).

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds S]
        [--controls fp8_lrc,fp8_kv,high] [--faults altered_token,...]

Each seed is one run of the cell at its own size and load with a short
window; each control is read on the same sampled prompts and served tokens
(bench/check.py).  Each fault (bench/faults.py) is a run of its own with
the fault planted under the timed path.  One JSON line per seed and per
reading goes to standard output.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, faults, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--controls", default=",".join(check.CONTROLS))
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        rc, out, r = run.run_cell(ROOT, args.workload, seed, args.seconds)
        if rc:
            return rc
        print(json.dumps({"seed": seed, "reading": "program",
                          "correct": out["correct"], **r.numbers}),
              flush=True)
        for control in args.controls.split(","):
            if not control:
                continue
            cmp = check.compare_control(r.spec, r.weights, r.samples,
                                        r.seq_len, control)
            print(json.dumps({"seed": seed, "reading": control,
                              **cmp.numbers()}), flush=True)
        del r
        for fault in filter(None, args.faults.split(",")):
            rc, out, r = run.run_cell(ROOT, args.workload, seed, args.seconds,
                                      fault=faults.FAULTS[fault])
            if rc:
                return rc
            print(json.dumps({"seed": seed, "reading": fault,
                              "correct": out["correct"], **r.numbers}),
                  flush=True)
            del r
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The readings that set a cell's limit, in one process on the chip.

    python3 perf/control.py --workload <cell> --seconds 2 \\
        --seeds 11 12 ... --control-seeds 21 22 23

Runs the cell as ``run.py`` runs it (same plans, traffic, window and
check; a short window) once per ``--seeds`` entry, then once per
``--control-seeds`` entry with the configuration's ``control`` planner
arguments: the program's own lower-precision path,
which the check must refuse. One JSON line per run with every number
compared; a control run that crashes is reported as such and sets no
reading. The benchmark's own runs never run this.
"""

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    cell = run.load_cell(args.workload)
    if not run.prepare(cell.chips):
        return 2
    control = cell.config["control"]
    runs = ([("program", s, None) for s in args.seeds]
            + [("control", s, control) for s in args.control_seeds])
    for who, seed, overrides in runs:
        rec = {"who": who, "workload": cell.name, "seed": seed,
               "overrides": overrides}
        t = time.perf_counter()
        try:
            line = run.run_cell(cell, seed, args.seconds, overrides=overrides,
                                t_start=t)
            rec.update(correct=line["correct"], checks=line["checks"],
                       attempted=line["attempted"])
        except Exception as e:  # noqa: BLE001 — a crash is a reading too
            traceback.print_exc(file=sys.stderr)
            rec["crashed"] = f"{type(e).__name__}: {e}"[:600]
        rec["seconds"] = time.perf_counter() - t
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

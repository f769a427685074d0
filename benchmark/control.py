"""Read the comparison's two readings for a cell, on the chip, over
several seeds in one process:

    python benchmark/control.py --workload <cell> --seeds 12 --seconds 5

- the PROGRAM's numbers: the cell run as the command runs it (same
  set-up, a short window at the cell's own size and load), whose
  largest `sum_rel_err` over the seeds is the lower reading;
- the CONTROL's: the query's plain reference computed in the nearest
  precision below the one the configuration states for the cell's
  path, which `control` in limits/<cell>.json names: float32 where
  the sum runs in emulated f64 (Q6's keyless dense reduce), bfloat16
  where the configuration states f32 chunk partials (Q1's binned MXU
  group-by, on which the float32 reference reads like the program).
  It is cut to the query's limit, put in the program's place and
  compared the same way. Its smallest reading over the seeds is the
  upper reading, and it has to come out as not correct. The other
  precision's readings are printed beside it.

One JSON line per seed, then a summary line. The benchmark's own runs
never run this; tests/benchmark_tests keeps the control as a test at
a size a test run can hold.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.run import load_cell, run_cell  # noqa: E402

PRECISIONS = ("bfloat16", "float32")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_011)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    limits = load_cell(args.workload)["limits"]
    name, limit = limits["control"], limits["sum_rel_err"]
    program, control = [], []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        res = run_cell(args.workload, seed, args.seconds, False,
                       controls=PRECISIONS)
        row = {"seed": seed, "correct": res["correct"],
               "program": {k: v["value"] for k, v in res["compared"].items()},
               "controls": res["controls"], "metrics": res["metrics"]}
        print(json.dumps(row), flush=True)
        program.append(row["program"]["sum_rel_err"])
        control.append(res["controls"][name])
    print(json.dumps({
        "workload": args.workload, "seeds": args.seeds,
        "control": name, "limit": limit,
        "program_sum_rel_err_max": max(program),
        "program_sum_rel_err_min": min(program),
        "control_sum_rel_err_min": min(c["sum_rel_err"] for c in control),
        "control_sum_rel_err_max": max(c["sum_rel_err"] for c in control),
        "control_rows_wrong": [c["rows_wrong"] for c in control],
        "control_refused": [c["sum_rel_err"] > limit or c["rows_wrong"] > 0
                            for c in control]}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

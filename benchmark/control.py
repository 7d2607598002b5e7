"""The control of a cell's comparison: the plain reference put in the
program's place and computed in the nearest precision below the
configuration's (float32 for float64), on the cell's own data size.  It
has to come out as not correct.  Needs no chip; the benchmark's own
runs never run it.

    python benchmark/control.py --workload <cell> --seeds 1 2 3
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE]

import cells                           # noqa: E402
import compare as cmp                  # noqa: E402
import datagen                         # noqa: E402


def control(cell, root: str, stmt, bindings: dict) -> dict:
    """The numbers compared when the control answers in the program's
    place."""
    want = stmt.reference.compute(root, bindings)
    got = stmt.reference.compute(root, bindings, float_dtype="float32")
    limit = stmt.spec.get("limit")
    got = got if limit is None else got.slice(0, limit)
    return cmp.compare(got, want, stmt.spec,
                       float(cell.limits["float_rel_err"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = cells.Cell(args.workload)
    for seed in args.seeds:
        root = tempfile.mkdtemp(prefix="bench_control_")
        try:
            datagen.generate(cell.config["datagen"], root,
                             cell.tables, seed)
            for stmt in cell.statements:
                nums = control(cell, root, stmt, {})
                failed = [k for k, v in nums.items()
                          if v > cell.limits.get(k, 0)]
                print(json.dumps({"cell": cell.name, "seed": seed,
                                  "stmt": stmt.name, **nums,
                                  "fails": failed}), flush=True)
        finally:
            shutil.rmtree(root, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

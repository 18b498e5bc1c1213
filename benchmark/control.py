#!/usr/bin/env python3
"""The control of ``correct``: the plain reference put in the program's
place and computed in float32, the nearest precision below the float64
the configurations state.  It has to come out as not correct.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--sf 1.0]

For every seed: the cell's tables, then for every binding of the mix
the float64 reference and the float32 one, compared as ``run.py``
compares an answer.  Prints the two numbers a seed reads (the widest
relative gap of a double, the exact mismatches) and whether the
configuration's limits fail it.  Host only: numpy, no jax, no chip.
``run.py`` never runs this; ``tests/test_control.py`` does at SF0.01.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE]


def read_control(workload: str, seed: int, sf=None) -> dict:
    import numpy as np

    import compare
    import tpch_gen
    from run import load_cell, tables_needed
    _, _, config, traffic, queries = load_cell(workload)
    tables = tpch_gen.gen_tables(config["scale_factor"] if sf is None else sf,
                                 seed, tables_needed(queries))
    worst, bad, per_query = 0.0, 0, {}
    for name, q in queries.items():
        for b in traffic["bindings"][name]:
            c = compare.compare_tables(q.reference(tables, b, np.float32),
                                       q.reference(tables, b))
            worst = max(worst, c["max_rel_err"])
            bad += c["exact_mismatches"]
            per_query[name] = max(per_query.get(name, 0.0), c["max_rel_err"])
    g = config["guarantees"]
    checks = {"exact_mismatches": {"value": bad, "limit": g["exact_mismatches"]},
              "max_rel_err": {"value": worst, "limit": g["double_rtol"]}}
    return {"workload": workload, "seed": seed, "checks": checks,
            "max_rel_err_by_query": per_query,
            "correct": compare.verdict(checks)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--sf", type=float, default=None)
    args = ap.parse_args(argv)
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        r = read_control(args.workload, seed, args.sf)
        print(json.dumps(r), flush=True)
        failed_all &= not r["correct"]
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())

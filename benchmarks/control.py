"""control.py -- the control of ``correct``: the plain reference put in
the program's place, computed in float32, the nearest precision below
the exact decimals the configurations state and the step that would
tempt a later PR (``engine.floats=true`` with ``engine.precision=f32``
is one template line away).  It has to come out as NOT correct.

    python benchmarks/control.py --workload <cell> --seeds 1 2 3

Host-only: it needs the cell's generated population (it builds it with
the harness's host-only children if absent) and no chip.  Prints, per
seed, each compared number of the control beside its limit, and the
same numbers for the float64 reference against itself read twice (0).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def control_numbers(config: dict, mix: dict, seed: int, raw_dir: str,
                    dtype=np.float32, tables=None) -> dict:
    """The compared numbers when the reference in ``dtype`` stands in
    the program's place, per statement and at their worst.  A mix's
    writes are laid over the tables first, as in a pass."""
    from benchmarks import compare, generator, run
    from benchmarks.reference import rawdata
    tables = tables or run.reference_tables(config, raw_dir)
    exact, low = rawdata.Real(), rawdata.Real(dtype)
    sets = generator.variants(mix, seed)
    rows_wrong, gap, per_stmt = 0, 0.0, {}
    tables.overlay.clear()
    for stmt in generator.distinct(mix, sets):
        module = importlib.import_module(
            "benchmarks.reference." + stmt.template.replace("/", "."))
        if stmt.writes:
            for table, frame in module.apply(tables, stmt.params,
                                             exact).items():
                tables.write(table, frame)
            continue
        fn = module.reference
        ref = fn(tables, stmt.params, exact)
        got = fn(tables, stmt.params, low).reset_index(drop=True)
        got.columns = range(got.shape[1])
        ok, g, note = compare.compare_statement(
            got, compare.frame_kinds(ref), ref, stmt.order_by)
        per_stmt[stmt.label] = {"ok": ok, "rel_gap": g, "note": note}
        rows_wrong += 0 if ok else 1
        gap = max(gap, g)
    tables.overlay.clear()
    return {"numbers": {"failed_statements": 0, "rows_wrong": rows_wrong,
                        "repeats_differ": 0, "max_rel_gap": gap},
            "per_stmt": per_stmt}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--benchmark",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    from benchmarks import compare, generator, run
    spec = run.load_cell(args.benchmark, args.workload)
    config = spec["config"]
    try:
        root = run.build_warehouse(config)
    finally:
        run.kill_children()
    raw_dir = os.path.join(root, "raw")
    mix = generator.load_mix(spec["cell"]["traffic"])
    tables = run.reference_tables(config, raw_dir)
    all_failed = True
    for seed in args.seeds:
        out = control_numbers(config, mix, seed, raw_dir, tables=tables)
        ok, checks = compare.verdict(out["numbers"], config["limits"])
        all_failed = all_failed and not ok
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": "float32 reference",
                          "correct": ok, "checks": checks,
                          "per_statement": out["per_stmt"]}))
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())

"""NDS-H data generation driver.

Behavioral port of `nds-h/nds_h_gen_data.py`: emit the 8 TPC-H tables as
'|'-delimited chunk files under per-table directories, with dbgen's
chunking contract (`-C parallel -S step`, `nds-h/nds_h_gen_data.py:90-95`)
and the nation/region single-file special case (`:109-115`).

Two generation paths:
- ``--use_builtin`` (default): the hermetic numpy generator
  (`nds_tpu.datagen.tpch`) fanned out over a process pool — the
  replacement for the reference's Hadoop-MR GenTable driver
  (`nds-h/tpch-gen/.../GenTable.java:209-277`); each (table, chunk) is an
  independent task, so the same fan-out runs across hosts.
- external dbgen via ``--dbgen_path``: shells out to the TPC-licensed
  tool exactly like the reference (the tool stays external, SURVEY.md
  §2.4 licensing note).
"""

from __future__ import annotations

import argparse
import os
import subprocess
from concurrent.futures import ProcessPoolExecutor

from nds_tpu.datagen import scale_factor, tpch
from nds_tpu.io.csv_io import write_tbl
from nds_tpu.nds_h.schema import get_schemas

SOURCE_TABLES = ["customer", "lineitem", "nation", "orders", "part",
                 "partsupp", "region", "supplier"]
SINGLE_CHUNK_TABLES = {"nation", "region"}


def _gen_chunk(table: str, sf: float, parallel: int, step: int,
               out_dir: str) -> str:
    arrays = tpch.gen_table(table, sf, parallel, step)
    schemas = get_schemas()
    if table in SINGLE_CHUNK_TABLES or parallel == 1:
        path = os.path.join(out_dir, table, f"{table}.tbl")
    else:
        path = os.path.join(out_dir, table, f"{table}.tbl.{step}")
    write_tbl(arrays, schemas[table], path)
    return path


def generate_data_local(scale: float, parallel: int, data_dir: str,
                        overwrite: bool = False, table: str | None = None,
                        chunk_range: tuple[int, int] | None = None,
                        workers: int | None = None) -> list[str]:
    if os.path.isdir(data_dir) and os.listdir(data_dir) and not overwrite:
        raise SystemExit(
            f"data dir {data_dir!r} is not empty (pass --overwrite_output)")
    os.makedirs(data_dir, exist_ok=True)
    tables = [table] if table else SOURCE_TABLES
    lo, hi = chunk_range or (1, parallel)
    tasks = []
    for t in tables:
        if t in SINGLE_CHUNK_TABLES:
            if lo == 1:  # fixed tables generated once, by chunk 1's owner
                tasks.append((t, scale, 1, 1, data_dir))
            continue
        for step in range(lo, hi + 1):
            tasks.append((t, scale, parallel, step, data_dir))
    paths = []
    with ProcessPoolExecutor(max_workers=workers) as pool:
        for p in pool.map(_gen_chunk_star, tasks):
            paths.append(p)
    return paths


def _gen_chunk_star(args):
    return _gen_chunk(*args)


def generate_data_dbgen(scale: int, parallel: int, data_dir: str,
                        dbgen_path: str) -> None:
    """External-tool path: one dbgen process per chunk (the reference's
    per-mapper command, `GenTable.java:209-277`, without Hadoop)."""
    os.makedirs(data_dir, exist_ok=True)
    procs = []
    env = dict(os.environ, DSS_PATH=data_dir)
    for step in range(1, parallel + 1):
        cmd = [dbgen_path, "-s", str(scale), "-C", str(parallel),
               "-S", str(step), "-f"]
        procs.append(subprocess.Popen(cmd, env=env,
                                      cwd=os.path.dirname(dbgen_path)))
    rc = [p.wait() for p in procs]
    if any(rc):
        raise SystemExit(f"dbgen chunks failed: {rc}")


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="generate NDS-H raw data")
    p.add_argument("scale", type=scale_factor,
                   help="scale factor: 5, 0.01, sf5")
    p.add_argument("parallel", type=int, help="number of chunks")
    p.add_argument("data_dir", help="output directory")
    p.add_argument("--table", choices=SOURCE_TABLES)
    p.add_argument("--range", dest="chunk_range",
                   help="'first,last' 1-based chunk subrange to (re)generate")
    p.add_argument("--overwrite_output", action="store_true")
    p.add_argument("--dbgen_path",
                   help="use the external TPC dbgen binary instead of the "
                        "builtin generator")
    p.add_argument("--workers", type=int,
                   help="process-pool size (default: cpu count)")
    args = p.parse_args(argv)
    if args.dbgen_path:
        generate_data_dbgen(int(args.scale), args.parallel, args.data_dir,
                            args.dbgen_path)
        return
    rng = None
    if args.chunk_range:
        lo, hi = (int(x) for x in args.chunk_range.split(","))
        if not (1 <= lo <= hi <= args.parallel):
            raise SystemExit(f"invalid --range {args.chunk_range!r}")
        rng = (lo, hi)
    generate_data_local(args.scale, args.parallel, args.data_dir,
                        args.overwrite_output, args.table, rng,
                        args.workers)


if __name__ == "__main__":
    main()

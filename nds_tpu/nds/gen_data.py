"""NDS (TPC-DS) data generation driver.

Behavioral port of `nds/nds_gen_data.py:183-290`: emit the 25 source
tables as '|'-delimited chunk files under per-table directories with
dsdgen's chunking contract (`-parallel N -child S`,
`nds/nds_gen_data.py:211-222`), single-chunk handling for the fixed
dimensions, and ``--range`` incremental regeneration
(`nds/nds_gen_data.py:155-174`).

Two generation paths (same split as `nds_tpu/nds_h/gen_data.py`):
- builtin (default): the hermetic numpy generator
  (`nds_tpu.datagen.tpcds`) fanned out over a process pool — the
  Hadoop-MR GenTable replacement (`tpcds-gen/.../GenTable.java:188-279`);
- external dsdgen via ``--dsdgen_path`` (the TPC-licensed tool stays
  external, SURVEY.md §2.4; see also `nds_tpu.datagen.toolwrap`).

``--update N`` generates the Nth refresh dataset (the 12 s_* maintenance
tables plus the delete-date tables, `nds/nds_gen_data.py:119-127,259-266`)
used by the data-maintenance phase.
"""

from __future__ import annotations

import argparse
import os
from concurrent.futures import ProcessPoolExecutor

from nds_tpu.datagen import scale_factor, tpcds
from nds_tpu.io.csv_io import write_tbl
from nds_tpu.nds.schema import get_maintenance_schemas, get_schemas

# the reference's source_table_names includes the dsdgen metadata table
# dbgen_version (`nds/nds_gen_data.py:51`) which has no query schema —
# generated for layout parity, skipped by transcode/power like the
# reference does (absent from `nds/nds_schema.py:49-568`)
SOURCE_TABLES = sorted(get_schemas()) + ["dbgen_version"]
# fixed-cardinality dimensions generated as a single chunk
# (reference dsdgen emits these without a _N_M suffix)
SINGLE_CHUNK_TABLES = {
    "date_dim", "time_dim", "reason", "income_band", "ship_mode",
    "call_center", "warehouse", "web_site", "web_page", "store",
    "household_demographics", "customer_demographics", "promotion",
    "dbgen_version",
}


def _gen_chunk(table: str, sf: float, parallel: int, step: int,
               out_dir: str, use_decimal: bool = True) -> str:
    if table == "dbgen_version":
        path = os.path.join(out_dir, table, f"{table}.dat")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        import time
        with open(path, "w") as f:
            f.write(f"nds_tpu-builtin-1.0|"
                    f"{time.strftime('%Y-%m-%d')}|"
                    f"{time.strftime('%H:%M:%S')}|"
                    f"-scale {sf:g} -parallel {parallel}|\n")
        return path
    arrays = tpcds.gen_table(table, sf, parallel, step)
    schemas = get_schemas(use_decimal)
    if table in SINGLE_CHUNK_TABLES or parallel == 1:
        path = os.path.join(out_dir, table, f"{table}.dat")
    else:
        path = os.path.join(out_dir, table,
                            f"{table}_{step}_{parallel}.dat")
    write_tbl(arrays, schemas[table], path)
    return path


def _gen_chunk_star(args):
    return _gen_chunk(*args)


def generate_data_local(scale: float, parallel: int, data_dir: str,
                        overwrite: bool = False, table: str | None = None,
                        chunk_range: tuple[int, int] | None = None,
                        workers: int | None = None,
                        use_decimal: bool = True) -> list[str]:
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
                tasks.append((t, scale, 1, 1, data_dir, use_decimal))
            continue
        for step in range(lo, hi + 1):
            tasks.append((t, scale, parallel, step, data_dir, use_decimal))
    paths = []
    with ProcessPoolExecutor(max_workers=workers) as pool:
        for p in pool.map(_gen_chunk_star, tasks):
            paths.append(p)
    return paths


def generate_refresh_data(scale: float, update: int, data_dir: str,
                          overwrite: bool = False,
                          use_decimal: bool = True) -> list[str]:
    """The ``--update N`` path: refresh (s_*) staging tables + the
    delete/inventory_delete date-range tables, written under
    ``data_dir`` exactly like dsdgen update sets
    (`nds/nds_gen_data.py:119-127,183-244` with ``--update``)."""
    if os.path.isdir(data_dir) and os.listdir(data_dir) and not overwrite:
        raise SystemExit(
            f"data dir {data_dir!r} is not empty (pass --overwrite_output)")
    os.makedirs(data_dir, exist_ok=True)
    from nds_tpu.datagen import tpcds_refresh
    schemas = get_maintenance_schemas(use_decimal)
    paths = []
    for t, schema in schemas.items():
        arrays = tpcds_refresh.gen_refresh_table(t, scale, update)
        path = os.path.join(data_dir, t, f"{t}.dat")
        write_tbl(arrays, schema, path)
        paths.append(path)
    return paths


def generate_data_dsdgen(scale: int, parallel: int, data_dir: str,
                         dsdgen_path: str,
                         update: int | None = None) -> None:
    """External-tool path: one dsdgen process per chunk (the reference's
    per-mapper command, `GenTable.java:233-279`, without Hadoop)."""
    from nds_tpu.datagen.toolwrap import run_dsdgen
    run_dsdgen(dsdgen_path, scale, parallel, data_dir, update=update)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="generate NDS raw data")
    p.add_argument("scale", type=scale_factor,
                   help="scale factor: 1, 0.01, sf1")
    p.add_argument("parallel", type=int, help="number of chunks")
    p.add_argument("data_dir", help="output directory")
    p.add_argument("--table", choices=SOURCE_TABLES)
    p.add_argument("--range", dest="chunk_range",
                   help="'first,last' 1-based chunk subrange to (re)generate")
    p.add_argument("--update", type=int,
                   help="generate the Nth refresh dataset instead of the "
                        "base tables")
    p.add_argument("--overwrite_output", action="store_true")
    p.add_argument("--floats", action="store_true",
                   help="double columns instead of decimals")
    p.add_argument("--dsdgen_path",
                   help="use the external TPC dsdgen binary instead of "
                        "the builtin generator")
    p.add_argument("--workers", type=int,
                   help="process-pool size (default: cpu count)")
    args = p.parse_args(argv)
    use_decimal = not args.floats
    if args.dsdgen_path:
        generate_data_dsdgen(int(args.scale), args.parallel, args.data_dir,
                             args.dsdgen_path, args.update)
        return
    if args.update is not None:
        generate_refresh_data(args.scale, args.update, args.data_dir,
                              args.overwrite_output, use_decimal)
        return
    rng = None
    if args.chunk_range:
        lo, hi = (int(x) for x in args.chunk_range.split(","))
        if not (1 <= lo <= hi <= args.parallel):
            raise SystemExit(f"invalid --range {args.chunk_range!r}")
        rng = (lo, hi)
    generate_data_local(args.scale, args.parallel, args.data_dir,
                        args.overwrite_output, args.table, rng,
                        args.workers, use_decimal)


if __name__ == "__main__":
    main()

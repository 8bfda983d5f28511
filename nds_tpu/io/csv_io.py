"""Raw '|'-delimited text IO (dbgen/dsdgen .dat format) + Parquet.

The raw-data contract matches what the TPC tools emit and the reference
consumes (`nds/nds_transcode.py:56-66` reads '|'-CSV with an explicit
schema; `nds-h/nds_h_schema.py:50-61` adds an 'ignore' trailing column for
dbgen's trailing '|'). Here ``trailing_delimiter=True`` handles that in the
reader. Parquet read/write goes through pyarrow; string columns round-trip
as Arrow dictionary arrays so the sorted-code invariant is rebuilt on read.
"""

from __future__ import annotations

import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.csv as pacsv
import pyarrow.parquet as pq

from nds_tpu.engine.types import (
    DateType, DecimalType, FloatType, IntType, Schema, StringType,
)
from nds_tpu.io.host_table import HostColumn, HostTable, encode_strings
from nds_tpu.obs.trace import get_tracer

_EPOCH = np.datetime64("1970-01-01", "D")


def _arrow_read_type(dtype) -> pa.DataType:
    if isinstance(dtype, IntType):
        return pa.int64() if dtype.bits == 64 else pa.int32()
    if isinstance(dtype, FloatType):
        return pa.float64() if dtype.bits == 64 else pa.float32()
    if isinstance(dtype, DecimalType):
        return pa.decimal128(max(dtype.precision, 18), dtype.scale)
    if isinstance(dtype, DateType):
        return pa.date32()
    if isinstance(dtype, StringType):
        return pa.string()
    raise TypeError(f"unsupported dtype {dtype}")


def read_tbl(paths: list[str] | str, name: str, schema: Schema,
             trailing_delimiter: bool = True) -> HostTable:
    """Read one table from one or more '|'-delimited files."""
    from nds_tpu.io import integrity
    from nds_tpu.resilience import faults
    if isinstance(paths, str):
        paths = [paths]
    faults.fault_point("io.read", table=name, paths=paths)
    # digest verification (io.verify_digests / NDS_TPU_VERIFY_DIGESTS):
    # a flipped bit in a raw chunk fails HERE with CorruptArtifact —
    # deterministic, never retried — instead of loading wrong rows
    integrity.verify_paths(paths, name)
    names = schema.names + (["_trailing"] if trailing_delimiter else [])
    types = {f.name: _arrow_read_type(f.dtype) for f in schema}
    if trailing_delimiter:
        types["_trailing"] = pa.string()
    from nds_tpu.resilience import watchdog
    tables = []
    with _reading(name, paths):
        for p in paths:
            # per-chunk heartbeat: multi-chunk fact reads on a loaded
            # box must not look like a hang to the watchdog
            watchdog.beat("engine", phase="io.read", table=name)
            if os.path.getsize(p) == 0:
                continue  # zero-row chunks are legitimate (fixed tables)
            t = pacsv.read_csv(
                p,
                read_options=pacsv.ReadOptions(column_names=names),
                parse_options=pacsv.ParseOptions(delimiter="|"),
                convert_options=pacsv.ConvertOptions(column_types=types),
            )
            if trailing_delimiter:
                t = t.drop(["_trailing"])
            tables.append(t)
        whole = pa.concat_tables(tables) if tables else pa.table(
            {f.name: pa.array([], type=_arrow_read_type(f.dtype))
             for f in schema})
    return from_arrow(name, schema, whole)


def _reading(name: str, paths: list):
    """``load.read``: files to one Arrow table.  Every reader below
    ends in ``from_arrow``, which is ``load.build``."""
    return get_tracer().span("load.read", table=name, files=len(paths))


def from_arrow(name: str, schema: Schema, t: pa.Table) -> HostTable:
    """Arrow table -> HostTable, carrying arrow validity bitmaps over as
    engine null masks (True = valid). Null slots are filled with 0/"" in
    the value arrays so downstream numpy code never sees NaN."""
    # load.build: decimals to scaled int64, string dictionaries, dates,
    # null masks
    with get_tracer().span("load.build", table=name, rows=t.num_rows):
        return _from_arrow(name, schema, t)


def _from_arrow(name: str, schema: Schema, t: pa.Table) -> HostTable:
    cols: dict[str, HostColumn] = {}
    for f in schema:
        arr = t.column(f.name).combine_chunks()
        mask = None
        if arr.null_count:
            mask = arr.is_valid().to_numpy(zero_copy_only=False)
        if isinstance(f.dtype, StringType):
            # arrow-native dictionary encode, then remap codes so the
            # dictionary is sorted (code order == lexicographic order);
            # only the (small) dictionary is ever sorted, not the column
            if not pa.types.is_dictionary(arr.type):
                arr = arr.dictionary_encode()
            raw_codes = arr.indices.fill_null(0).to_numpy(
                zero_copy_only=False).astype(np.int32)
            # Arrow orders strings by their UTF-8 bytes, which is code
            # point order, numpy's: the codes are what an argsort of
            # the dictionary as a numpy unicode array gave, without
            # that array (256 B a value: 6.4 GB for lineitem's 25M
            # distinct comments at scale 5) and without its minutes
            order = pc.sort_indices(arr.dictionary).to_numpy()
            remap = np.empty(len(order), dtype=np.int32)
            remap[order] = np.arange(len(order), dtype=np.int32)
            codes = remap[raw_codes] if len(order) else raw_codes
            dictionary = np.asarray(arr.dictionary.take(order).to_numpy(
                zero_copy_only=False), dtype=object)
            cols[f.name] = HostColumn(f.dtype, codes, dictionary, mask)
        elif isinstance(f.dtype, DecimalType):
            s = f.dtype.scale
            if f.dtype.precision <= 15:
                # float64 is exact for <= 15 significant digits: vectorized
                as_f = arr.cast(pa.float64()).to_numpy(zero_copy_only=False)
                ints = np.round(np.nan_to_num(as_f) * 10**s).astype(np.int64)
            else:
                ints = np.array(
                    [0 if v is None else int(v.scaleb(s)) for v in arr.to_pylist()],
                    dtype=np.int64)
            cols[f.name] = HostColumn(f.dtype, ints, None, mask)
        elif isinstance(f.dtype, DateType):
            d = arr.cast(pa.int32()).fill_null(0)
            cols[f.name] = HostColumn(
                f.dtype, d.to_numpy(zero_copy_only=False), None, mask)
        elif isinstance(f.dtype, (IntType, FloatType)):
            cols[f.name] = HostColumn(
                f.dtype, arr.fill_null(0).to_numpy(zero_copy_only=False),
                None, mask)
        else:
            cols[f.name] = HostColumn(
                f.dtype, arr.to_numpy(zero_copy_only=False), None, mask)
    return HostTable(name, schema, cols)


def to_arrow(table: HostTable) -> pa.Table:
    arrays, names = [], []
    for f in table.schema:
        col = table.columns[f.name]
        names.append(f.name)
        # arrow mask convention: True = NULL (inverse of the engine's)
        amask = None if col.null_mask is None else ~col.null_mask
        if col.is_string:
            dict_arr = pa.DictionaryArray.from_arrays(
                pa.array(col.values, type=pa.int32(), mask=amask),
                pa.array(list(col.dictionary), type=pa.string()))
            arrays.append(dict_arr)
        elif isinstance(f.dtype, DecimalType):
            s = f.dtype.scale
            target = pa.decimal128(max(f.dtype.precision, 18), s)
            if f.dtype.precision <= 15:
                # exact: |value| < 10^15 so float64 round-trips the cents
                as_f = col.values.astype(np.float64) / 10**s
                arrays.append(
                    pa.array(as_f, mask=amask).cast(target, safe=False))
            else:
                from decimal import Decimal
                vals = [Decimal(int(v)).scaleb(-s) for v in col.values]
                if amask is not None:
                    vals = [None if m else v
                            for v, m in zip(vals, amask)]
                arrays.append(pa.array(vals, type=target))
        elif isinstance(f.dtype, DateType):
            arrays.append(pa.array(col.values, type=pa.int32(),
                                   mask=amask).cast(pa.date32()))
        else:
            arrays.append(pa.array(col.values, mask=amask))
    return pa.Table.from_arrays(arrays, names=names)


def write_parquet(table: HostTable, path: str, compression: str = "snappy",
                  row_group_rows: int = 1 << 20) -> None:
    write_arrow(to_arrow(table), path, "parquet", compression,
                row_group_rows)


def transcode_parquet(paths: list[str], name: str, schema: Schema,
                      out: str, compression: str = "snappy",
                      row_group_rows: int = 1 << 20) -> None:
    """Raw '|'-delimited chunk files -> ONE parquet file, a chunk at a
    time: each chunk is read, built and written as row groups of its
    own (its strings under the chunk's own sorted dictionary), so the
    host never holds more than a chunk. The reader unifies the
    dictionaries and sorts the whole one once (``from_arrow``)."""
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    writer = None
    try:
        for p in paths:
            chunk = to_arrow(read_tbl([p], name, schema))
            if writer is None:
                writer = pq.ParquetWriter(out, chunk.schema,
                                          compression=compression)
            writer.write_table(chunk, row_group_size=row_group_rows)
    finally:
        if writer is not None:
            writer.close()


def read_parquet(paths: list[str] | str, name: str, schema: Schema) -> HostTable:
    if isinstance(paths, str):
        paths = [paths]
    # ParquetFile, not pq.read_table: read_table wraps single files in a
    # dataset and INFERS hive partitioning from `col=value` path
    # segments (pyarrow >= 13). The transcode layout nests files under
    # `<table>/<part_col>=<band>/part-N.parquet` WITH the partition
    # column physically present in every file, so the inferred
    # dictionary<int32> partition field collides with the physical
    # int32 column and the schema merge fails (ArrowTypeError). Reading
    # the file directly skips path inference entirely — partition
    # columns come from the file bytes, which the writer guarantees.
    with _reading(name, paths):
        whole = pa.concat_tables([pq.ParquetFile(p).read() for p in paths],
                                 promote_options="permissive")
    return from_arrow(name, schema, whole)


# warehouse output formats beyond parquet (the reference's transcode
# writes parquet/orc/avro/json, `nds/nds_transcode.py:69-152`; avro via
# the built-in spec container codec in io/avro_io.py)
FORMAT_EXT = {"parquet": ".parquet", "orc": ".orc", "json": ".json",
              "avro": ".avro"}


def write_arrow(t: pa.Table, path: str, fmt: str = "parquet",
                compression: str = "snappy",
                row_group_rows: int = 1 << 20) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    if fmt == "parquet":
        pq.write_table(t, path, compression=compression,
                       row_group_size=row_group_rows)
    elif fmt == "orc":
        import pyarrow.orc as paorc
        cols = []
        for i, f in enumerate(t.schema):
            c = t.column(i)
            if pa.types.is_dictionary(f.type):
                c = c.cast(pa.string())
            cols.append(c)
        paorc.write_table(pa.Table.from_arrays(cols,
                                               names=t.column_names),
                          path, compression=compression)
    elif fmt == "json":
        # JSON-lines records, the layout pyarrow.json reads back; dates
        # as ISO strings, decimals as exact decimal strings
        import json as _json
        with open(path, "w") as f:
            for row in t.to_pylist():
                f.write(_json.dumps(row, default=str) + "\n")
    elif fmt == "avro":
        raise ValueError(
            "avro writes go through write_table (HostTable input); "
            "an arrow Table has no engine schema to map from")
    else:
        raise ValueError(f"unknown output format {fmt!r}")


def write_table(table: HostTable, path: str, fmt: str = "parquet",
                compression: str = "snappy") -> None:
    if fmt == "avro":
        from nds_tpu.io import avro_io
        if compression in (None, "none"):
            codec = "null"
        elif compression == "deflate":
            codec = "deflate"
        elif compression == "snappy":
            # the CLI-wide default targets parquet; no snappy codec in
            # this image, so substitute deflate AUDIBLY, never silently
            from nds_tpu.utils.report import TaskFailureCollector
            TaskFailureCollector.notify(
                "avro: no snappy codec in this environment, writing "
                "deflate instead")
            codec = "deflate"
        else:
            raise ValueError(
                f"unsupported avro compression {compression!r} "
                f"(available: none, deflate)")
        avro_io.write_avro(table, path, table.schema, codec=codec)
        return
    write_arrow(to_arrow(table), path, fmt, compression)


def read_paths_auto(paths: list[str], name: str, schema: Schema,
                    default_fmt: str) -> HostTable:
    """Read warehouse files whose formats may differ per file: snapshot
    manifests mix the load-time warehouse format with the parquet
    version files maintenance commits (io/snapshots.py). Buckets by
    extension, reads each bucket in its own format, and rebuilds one
    table (string dictionaries re-encode across buckets)."""
    ext_to_fmt = {ext: f for f, ext in FORMAT_EXT.items()}
    groups: dict[str, list[str]] = {}
    for p in paths:
        ext = os.path.splitext(p)[1]
        groups.setdefault(ext_to_fmt.get(ext, default_fmt),
                          []).append(p)
    if len(groups) == 1:
        fmt, ps = next(iter(groups.items()))
        return read_table_fmt(ps, name, schema, fmt)
    parts = [read_table_fmt(ps, name, schema, fmt)
             for fmt, ps in groups.items()]
    arrays: dict[str, np.ndarray] = {}
    for f in schema:
        cols = [t.columns[f.name] for t in parts]
        vals = np.concatenate([c.decode() if c.is_string else c.values
                               for c in cols])
        arrays[f.name] = vals
        if f.nullable:
            arrays[f.name + "#null"] = np.concatenate(
                [c.null_mask if c.null_mask is not None
                 else np.ones(len(c.values), dtype=bool) for c in cols])
    from nds_tpu.io.host_table import from_arrays
    return from_arrays(name, schema, arrays)


def read_table_fmt(paths: list[str] | str, name: str, schema: Schema,
                   fmt: str) -> HostTable:
    """Read a warehouse table written by ``write_table`` in any format.

    When digest verification is on (io/integrity.py), every file is
    re-hashed against its table's ``_manifest.json`` before parsing:
    corruption surfaces as a fail-fast CorruptArtifact naming the file
    and both digests, never as silently wrong query output."""
    from nds_tpu.io import integrity
    from nds_tpu.resilience import faults
    if isinstance(paths, str):
        paths = [paths]
    faults.fault_point("io.read", table=name, fmt=fmt, paths=paths)
    integrity.verify_paths(paths, name)
    if fmt == "parquet":
        return read_parquet(paths, name, schema)
    if fmt == "avro":
        from nds_tpu.io import avro_io
        return avro_io.read_avro(paths, name, schema)
    if isinstance(paths, str):
        paths = [paths]
    if fmt == "orc":
        import pyarrow.orc as paorc
        with _reading(name, paths):
            whole = pa.concat_tables([paorc.read_table(p) for p in paths],
                                     promote_options="permissive")
        return from_arrow(name, schema, whole)
    if fmt == "json":
        import pyarrow.json as pajson
        # dates and decimals are ISO/decimal STRINGS in the json lines
        # (json has no such types); read as string, cast after
        read_types, casts = {}, {}
        for f in schema:
            t = _arrow_read_type(f.dtype)
            if isinstance(f.dtype, (DateType, DecimalType)):
                read_types[f.name] = pa.string()
                casts[f.name] = t
            else:
                read_types[f.name] = t
        want = pa.schema(read_types)
        tables = []
        with _reading(name, paths):
            for p in paths:
                t = pajson.read_json(
                    p, parse_options=pajson.ParseOptions(
                        explicit_schema=want))
                cols = []
                for i, fld in enumerate(t.schema):
                    c = t.column(i)
                    if fld.name in casts:
                        c = c.cast(casts[fld.name])
                    cols.append(c)
                tables.append(pa.Table.from_arrays(
                    cols, names=t.column_names))
            whole = pa.concat_tables(tables,
                                     promote_options="permissive")
        return from_arrow(name, schema, whole)
    raise ValueError(f"unknown input format {fmt!r}")


def write_tbl(arrays: dict[str, np.ndarray], schema: Schema, path: str,
              trailing_delimiter: bool = True) -> None:
    """Write generator output in dbgen's .tbl text format (for parity with
    the reference raw-data layout, `nds-h/nds_h_gen_data.py:109-115`)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    n = len(next(iter(arrays.values())))
    cols = []
    for f in schema:
        arr = arrays[f.name]
        if isinstance(f.dtype, DecimalType):
            s = f.dtype.scale
            ints = arr.astype(np.int64)
            sign = np.where(ints < 0, "-", "")
            mag = np.abs(ints)
            vals = [f"{sign[i]}{mag[i] // 10**s}.{mag[i] % 10**s:0{s}d}"
                    for i in range(n)]
        elif isinstance(f.dtype, DateType):
            vals = [str(_EPOCH + int(v)) for v in arr]
        else:
            vals = [str(v) for v in arr]
        valid = arrays.get(f.name + "#null")
        if valid is not None:
            # dsdgen's NULL convention: an empty field
            vals = [v if ok else "" for v, ok in zip(vals, valid)]
        cols.append(vals)
    end = "|\n" if trailing_delimiter else "\n"
    with open(path, "w") as f:
        for row in zip(*cols):
            f.write("|".join(row) + end)

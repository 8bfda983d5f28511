"""Raw-file reader of the plain reference.

The reference reads the generator's '|'-delimited text files itself
(pyarrow.csv), never the transcoded warehouse and never a table the
engine has loaded: transcode, load and upload are then inside what
``correct`` covers.  It imports nothing of ``nds_tpu``; the column order
comes from a frozen ``schema.json`` beside each suite's statements.

A configuration with a refresh set (``"refresh": {"update": N}``) also
has the set's staging tables, read from ``<raw>/../refresh<N>`` with the
column order of ``refresh_schema.json``.  A write's reference
(``apply``) gives the whole new content of the tables it writes; the
harness lays that over the raw tables (``Tables.overlay``), so that the
reads of a pass see the pass's writes, and drops it after the pass.

Values as the statements see them: ``int`` -> int64, ``decN`` -> int64
scaled by 10**N (exact; predicates compare scaled integers), ``date`` ->
int64 days since 1970-01-01, ``str`` -> object.  NULLs (empty fields)
become NaN in a float64 column for numbers and None for strings.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.csv as pacsv
import pyarrow.parquet as pq

HERE = os.path.dirname(os.path.abspath(__file__))


def days(iso: str) -> int:
    return int(np.datetime64(iso, "D").astype(np.int64))


def plus_months(iso: str, n: int) -> int:
    m = np.datetime64(iso[:7], "M") + n
    return int(np.datetime64(f"{m}-{iso[8:]}", "D").astype(np.int64))


class Tables:
    """``T(table, columns)`` -> DataFrame of just those columns."""

    def __init__(self, suite: str, raw_dir: str,
                 refresh_dir: "str | None" = None):
        with open(os.path.join(HERE, suite, "schema.json")) as f:
            doc = json.load(f)
        self.schema = doc["tables"]
        self.raw_dir = raw_dir
        self.cache_dir = os.path.join(os.path.dirname(
            os.path.abspath(raw_dir)), "ref_cache")
        self._cache: dict = {}
        self.staging: dict = {}       # staging table -> its directory
        if refresh_dir is not None:
            with open(os.path.join(HERE, suite,
                                   "refresh_schema.json")) as f:
                staging = json.load(f)["tables"]
            self.schema = {**self.schema, **staging}
            self.staging = {t: refresh_dir for t in staging}
        self.overlay: dict = {}       # table -> whole content after writes

    def columns(self, table: str) -> list:
        return [n for n, _k in self.schema[table]]

    def _paths(self, table: str) -> list:
        tdir = os.path.join(self.staging.get(table, self.raw_dir), table)
        if os.path.isdir(tdir):
            return sorted(os.path.join(tdir, f) for f in os.listdir(tdir)
                          if not f.startswith(".") and not f.startswith("_"))
        raise FileNotFoundError(f"no raw files for table {table!r} "
                                f"under {os.path.dirname(tdir)}")

    def __call__(self, table: str, columns: list) -> pd.DataFrame:
        if table in self.overlay:
            return self.overlay[table][list(columns)].copy()
        missing = [c for c in columns if (table, c) not in self._cache]
        if missing:
            self._load(table, missing)
        return pd.DataFrame({c: self._cache[(table, c)] for c in columns})

    def write(self, table: str, frame: pd.DataFrame) -> None:
        """Lay a write's whole new content of ``table`` over it, held to
        the values ``T`` gives: every column of the schema in its order,
        a number column int64, or float64 where it holds a NULL."""
        if list(frame.columns) != self.columns(table):
            raise ValueError(f"the new content of {table} has columns "
                             f"{list(frame.columns)}, not the schema's")
        out = {}
        for c, kind in self.schema[table]:
            v = frame[c]
            if kind == "str":
                out[c] = v.astype(object).where(v.notna(), None)
            else:
                v = pd.to_numeric(v).to_numpy(np.float64)
                out[c] = v if np.isnan(v).any() else v.astype(np.int64)
        self.overlay[table] = pd.DataFrame(out)

    def rows(self, table: str) -> int:
        """The table's row count as the reads see it."""
        if table in self.overlay:
            return len(self.overlay[table])
        return len(self(table, self.columns(table)[:1]))

    def _load(self, table: str, columns: list) -> None:
        """Columns come from the reference's own column cache
        (<raw>/../ref_cache/<table>/<column>.parquet, a staging table's
        under ref_cache/<refresh dir's name>/), which it fills
        from the raw text on first use: every run is a new process, and
        parsing 6M-row text files again each time would make the
        reference longer than the window."""
        cdir = os.path.join(self.cache_dir, table)
        if table in self.staging:
            cdir = os.path.join(self.cache_dir, os.path.basename(
                os.path.normpath(self.staging[table])), table)
        os.makedirs(cdir, exist_ok=True)
        todo = [c for c in columns
                if not os.path.exists(os.path.join(cdir, c + ".parquet"))]
        if todo:
            parsed = self._parse(table, todo)
            for c in todo:
                tmp = os.path.join(cdir, f".{c}.{os.getpid()}.tmp")
                pq.write_table(parsed.select([c]), tmp)
                os.replace(tmp, os.path.join(cdir, c + ".parquet"))
        kinds = dict(self.schema[table])
        for c in columns:
            arr = pq.read_table(os.path.join(cdir, c + ".parquet")
                                ).column(c).combine_chunks()
            if kinds[c] == "str":
                self._cache[(table, c)] = arr.to_pandas()
            else:
                v = arr.to_numpy(zero_copy_only=False)
                self._cache[(table, c)] = (
                    v.astype(np.float64) if arr.null_count
                    else v.astype(np.int64))

    def _parse(self, table: str, columns: list) -> pa.Table:
        """Raw text -> arrow columns as the statements see them."""
        fields = self.schema[table]
        kinds = dict(fields)
        names = [n for n, _k in fields] + ["_trailing"]
        types = {}
        for c in columns:
            k = kinds[c]
            types[c] = (pa.int64() if k == "int" else
                        pa.float64() if k.startswith("dec") else
                        pa.date32() if k == "date" else pa.string())
        parts = []
        for p in self._paths(table):
            if os.path.getsize(p) == 0:
                continue
            parts.append(pacsv.read_csv(
                p, read_options=pacsv.ReadOptions(column_names=names),
                parse_options=pacsv.ParseOptions(delimiter="|"),
                convert_options=pacsv.ConvertOptions(
                    column_types=types, include_columns=list(columns),
                    strings_can_be_null=True)))
        t = pa.concat_tables(parts)
        out = {}
        for c in columns:
            k = kinds[c]
            arr = t.column(c).combine_chunks()
            if k == "date":
                arr = arr.cast(pa.int32()).cast(pa.int64())
            elif k.startswith("dec"):
                arr = pc.round(pc.multiply(arr, float(10 ** int(k[3:])))
                               ).cast(pa.int64())
            out[c] = arr
        return pa.table(out)


class Real:
    """The arithmetic of one reading of the reference: float64 is the
    reference, float32 the lower-precision control put in its place."""

    def __init__(self, dtype=np.float64):
        self.dtype = np.dtype(dtype)

    def money(self, scaled, scale: int = 2):
        """Scaled-integer decimal column -> real values in this
        reading's precision."""
        return (np.asarray(scaled).astype(self.dtype)
                / self.dtype.type(10 ** scale))

    def num(self, values):
        return np.asarray(values).astype(self.dtype)

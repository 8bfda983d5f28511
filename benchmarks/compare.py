"""The comparison that decides ``correct``.

Rows the timed session returned in the window are held against the plain
reference (benchmarks/reference/).  Three numbers are compared, each
with a limit of its own from the configuration file:

``rows_wrong``       statements whose row count, column count or any
                     exactly-typed value (integer, string, date, NULL)
                     differs from the reference, or whose rows do not
                     come in the statement's ORDER BY order.  Exact:
                     limit 0.
``repeats_differ``   executions in the window whose rows differ from
                     the first execution of the same text.  Limit 0.
``max_rel_gap``      the widest gap of a real-valued cell (decimal or
                     float) from the reference's, as a share of the
                     larger magnitude.  Limit between what sound runs
                     read and what the lower-precision control reads
                     (PERF.md section 2).

A mix that writes adds two (run.py): ``writes_wrong``, write executions
after which a table they write holds another row count, or other rows
(``table_digest``), than the reference's; ``tables_not_restored``.

Nothing of the program is imported: a result is taken apart by the
names of its column types.
"""

from __future__ import annotations

import hashlib
import math

import numpy as np
import pandas as pd

REAL = "real"
EXACT = "exact"


def result_frame(result) -> "tuple[pd.DataFrame, list]":
    """Engine ResultTable -> (frame, kinds).  Decimals become float64
    values, dates int days, NULLs None/NaN."""
    cols, kinds = {}, []
    for i, (arr, dt, valid) in enumerate(zip(result.cols, result.dtypes,
                                             result.valids)):
        tname = type(dt).__name__
        a = np.asarray(arr)
        if tname == "DecimalType":
            v = a.astype(np.float64) / float(10 ** dt.scale)
            kind = REAL
        elif tname == "FloatType" or a.dtype.kind == "f":
            v, kind = a.astype(np.float64), REAL
        elif tname == "DateType":
            v, kind = a.astype(np.int64), EXACT
        elif a.dtype.kind in "iub":
            v, kind = a.astype(np.int64), EXACT
        else:
            v, kind = a.astype(object), EXACT
        if valid is not None and not np.all(valid):
            v = v.astype(object if kind == EXACT else np.float64)
            v[~np.asarray(valid)] = None if kind == EXACT else np.nan
        cols[i] = v
        kinds.append(kind)
    return pd.DataFrame(cols), kinds


def digest(result) -> str:
    """Content digest of one result, for repeat executions."""
    h = hashlib.sha256()
    for arr, valid in zip(result.cols, result.valids):
        a = np.asarray(arr)
        h.update(str(a.dtype).encode())
        h.update(a.astype(str).tobytes() if a.dtype.kind in "OU"
                 else np.ascontiguousarray(a).tobytes())
        if valid is not None:
            h.update(np.ascontiguousarray(valid).tobytes())
    return h.hexdigest()


def _canon(v) -> str:
    """One exactly-typed value as text both sides agree on."""
    if v is None or (isinstance(v, float) and math.isnan(v)):
        return ""
    if isinstance(v, str):
        return v.rstrip()
    return str(int(v))


def _null(col: np.ndarray) -> np.ndarray:
    return np.array([v is None or (isinstance(v, float) and math.isnan(v))
                     for v in col], dtype=bool)


def out_of_order(got: pd.DataFrame, order_by) -> "int | None":
    """The first row that comes before its predecessor by the
    statement's ORDER BY, ``((column position, "asc" | "desc"), ...)``,
    judged on the returned values themselves; None where the rows are
    in order.  Rows that tie on every key may come in any order.  NULL
    sorts lowest (first ascending, last descending), as Spark's default
    and the suites' answer sets have it; strings by code point with
    trailing blanks dropped."""
    if len(got) < 2 or not order_by:
        return None
    decided = np.zeros(len(got) - 1, dtype=bool)
    wrong = np.zeros(len(got) - 1, dtype=bool)
    for pos, direction in order_by:
        col = got[pos].to_numpy(dtype=object)
        null = _null(col)
        filler = "" if any(isinstance(v, str) for v in col) else 0
        vals = np.array([filler if n else (v.rstrip() if isinstance(v, str)
                                           else v)
                         for v, n in zip(col, null)], dtype=object)
        a, b, na, nb = vals[:-1], vals[1:], null[:-1], null[1:]
        less = np.where(na | nb, na & ~nb, a < b).astype(bool)
        more = np.where(na | nb, nb & ~na, a > b).astype(bool)
        if direction == "desc":
            less, more = more, less
        elif direction != "asc":
            raise ValueError(f"order_by direction {direction!r}")
        wrong |= ~decided & more
        decided |= less | more
    bad = np.nonzero(wrong)[0]
    return int(bad[0]) + 1 if len(bad) else None


def compare_statement(got: pd.DataFrame, kinds: list, ref: pd.DataFrame,
                      order_by=()) -> "tuple[bool, float, str]":
    """(rows right?, widest relative gap, note).  The returned rows have
    to come in the statement's ORDER BY order (``out_of_order``); after
    that both sides are sorted by their exactly-typed columns, so that
    only a tie on the ORDER BY keys, which the two sides may break
    differently, is not a difference."""
    if got.shape != ref.shape:
        return False, 0.0, f"shape {got.shape} vs reference {ref.shape}"
    row = out_of_order(got, order_by)
    if row is not None:
        return (False, 0.0, f"row {row} comes before row {row - 1} by "
                f"ORDER BY {list(order_by)}: "
                f"{got.iloc[row - 1].tolist()} then {got.iloc[row].tolist()}")
    ref = ref.reset_index(drop=True)
    ref.columns = range(ref.shape[1])
    exact = [i for i, k in enumerate(kinds) if k == EXACT]
    real = [i for i, k in enumerate(kinds) if k == REAL]

    def ordered(df):
        keys = {i: np.array([_canon(v) for v in df[i].tolist()],
                            dtype=object) for i in exact}
        if not exact or len(df) < 2:
            return df, keys
        order = np.lexsort([keys[i] for i in reversed(exact)])
        return (df.iloc[order].reset_index(drop=True),
                {i: k[order] for i, k in keys.items()})

    (g, gk), (r, rk) = ordered(got), ordered(ref)
    for i in exact:
        bad = np.nonzero(gk[i] != rk[i])[0]
        if len(bad):
            j = int(bad[0])
            return (False, 0.0, f"column {i}, {len(bad)} row(s): "
                    f"{gk[i][j]!r} vs reference {rk[i][j]!r}")
    gap = 0.0
    for i in real:
        a = pd.to_numeric(g[i], errors="coerce").to_numpy(np.float64)
        b = pd.to_numeric(r[i], errors="coerce").to_numpy(np.float64)
        if not np.array_equal(np.isnan(a), np.isnan(b)):
            return False, 0.0, f"column {i}: NULLs differ"
        ok = ~np.isnan(a)
        if ok.any():
            scale = np.maximum(np.abs(a[ok]), np.abs(b[ok]))
            scale[scale == 0] = 1.0
            gap = max(gap, float(np.max(np.abs(a[ok] - b[ok]) / scale)))
    return True, gap, ""


def verdict(numbers: dict, limits: dict) -> "tuple[bool, dict]":
    """numbers/limits -> (correct, {name: {"value", "limit"}})."""
    checks = {k: {"value": numbers[k], "limit": limits[k]}
              for k in numbers}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


NULL_NUMBER = -(2 ** 62)      # a NULL number, in a table digest


def table_digest(columns: dict) -> "tuple[int, str]":
    """(rows, digest) of a whole table, in no order: ``columns`` maps
    each column name, in the schema's order, to ``(values, kind)`` with
    a NULL as NaN or None.  A number is taken as an exact int64 (scaled
    decimals, days and keys are integers), a string as ``_canon`` takes
    it (no trailing blanks; a NULL is the empty string, as the raw
    files write it).  Each row is hashed with pandas' fixed-key hash and
    the row hashes are summed mod 2**64, so equal multisets of rows give
    equal digests whatever their order."""
    frame = {}
    for name, (values, kind) in columns.items():
        if kind == "str":
            frame[name] = pd.Series(values, dtype=object).map(_canon)
        else:
            v = np.asarray(values)
            if v.dtype.kind == "f":
                v = np.where(np.isnan(v), NULL_NUMBER, v)
            frame[name] = pd.Series(v.astype(np.int64))
    df = pd.DataFrame(frame)
    rows = pd.util.hash_pandas_object(df, index=False).to_numpy(np.uint64)
    return len(df), f"{int(np.add.reduce(rows, dtype=np.uint64)):016x}"


def frame_kinds(frame: pd.DataFrame) -> list:
    """Kinds of a reference frame's columns, for the control, which
    puts a reference in the program's place: the references return
    reals as floats and everything else as integers or strings."""
    return [REAL if frame[c].dtype.kind == "f" else EXACT
            for c in frame.columns]

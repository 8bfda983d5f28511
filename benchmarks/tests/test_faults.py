"""The rest of a run with the timed path broken underneath: `correct`
has to come out false.  The faults this system can have: an answer
altered where it is produced (a value, a row dropped, an exact key, the
final sort), an answer that never comes, an answer that changes between
executions."""

import numpy as np
import pytest


def _wrap(session, change):
    inner = session.sql
    calls = {"n": 0}

    def sql(text):
        result = inner(text)
        calls["n"] += 1
        return change(result, calls["n"], text)

    session.sql = sql


def _bump_real(result, _n, _text):
    """The last real-valued cell of every answer moves by 1e-6 of itself."""
    for i in reversed(range(len(result.cols))):
        if type(result.dtypes[i]).__name__ in ("DecimalType", "FloatType") \
                and len(result.cols[i]):
            col = np.array(result.cols[i])
            col[-1] = col[-1] + max(1, abs(int(col[-1])) // 1_000_000) \
                if col.dtype.kind in "iu" else col[-1] * (1 + 1e-6)
            result.cols[i] = col
            break
    return result


def _drop_row(result, _n, _text):
    if result.nrows > 1:
        result.cols = [c[:-1] for c in result.cols]
        result.valids = [None if v is None else v[:-1]
                         for v in result.valids]
    return result


def _bump_key(result, _n, _text):
    for i, col in enumerate(result.cols):
        if np.asarray(col).dtype.kind in "iu" and type(
                result.dtypes[i]).__name__ not in ("DecimalType",):
            col = np.array(col)
            col[0] += 1
            result.cols[i] = col
            break
    return result


def _reverse_rows(result, _n, _text):
    """The right rows in the wrong order: the final sort went wrong."""
    result.cols = [c[::-1] for c in result.cols]
    result.valids = [None if v is None else v[::-1]
                     for v in result.valids]
    return result


def _raise_once(result, n, _text):
    if n == 5:
        raise RuntimeError("planted: the answer never comes")
    return result


def _flip_later(result, n, _text):
    """Sound in the warm pass (12 statements) and in the window's first
    passes, altered after that."""
    return _bump_real(result, n, _text) if n > 40 else result


@pytest.mark.parametrize("workload,change,number", [
    ("rehearsal.short", _bump_real, "max_rel_gap"),
    ("rehearsal.power_nds", _bump_real, "max_rel_gap"),
    ("rehearsal.power_nds_h", _drop_row, "rows_wrong"),
    ("rehearsal.power_nds_h", _bump_key, "rows_wrong"),
    ("rehearsal.power_nds_h", _reverse_rows, "rows_wrong"),
    ("rehearsal.power_nds", _reverse_rows, "rows_wrong"),
    ("rehearsal.power_nds", _raise_once, "failed_statements"),
    ("rehearsal.short", _flip_later, "repeats_differ"),
])
def test_broken_path_is_not_correct(run_cell, workload, change, number):
    rc, line, err = run_cell(workload, seconds=0.5,
                             tamper=lambda s: _wrap(s, change))
    assert rc == 0
    assert line["correct"] is False, err[-1500:]
    check = line["checks"][number]
    assert check["value"] > check["limit"], line["checks"]


def test_sound_path_is_correct(run_cell):
    rc, line, err = run_cell("rehearsal.power_nds_h", seconds=0.5,
                             tamper=lambda s: _wrap(s, lambda r, n, t: r))
    assert rc == 0 and line["correct"] is True, err[-1500:]

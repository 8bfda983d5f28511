"""The ORDER BY check of compare.py on small frames: the returned rows
have to come in the statement's order, and only rows that tie on every
key may come in any order."""

import pandas as pd
import pytest

from benchmarks import compare

ROWS = [("a", 3, 1.5), ("a", 3, 0.5), ("b", 2, 9.0), ("c", 2, 7.0)]


def _frame(rows):
    return pd.DataFrame({i: [r[i] for r in rows] for i in range(3)})


@pytest.mark.parametrize("order_by,rows,bad", [
    ((), ROWS[::-1], None),                              # no ORDER BY
    (((0, "asc"),), ROWS, None),
    (((0, "asc"),), [ROWS[1], ROWS[0], ROWS[2], ROWS[3]], None),  # a tie
    (((0, "asc"),), [ROWS[0], ROWS[2], ROWS[1], ROWS[3]], 2),
    (((1, "desc"), (0, "asc")), ROWS, None),
    (((1, "desc"), (0, "asc")), [ROWS[0], ROWS[1], ROWS[3], ROWS[2]], 3),
    (((1, "desc"), (2, "desc")), ROWS, None),
    (((1, "desc"), (2, "asc")), ROWS, 1),
    # NULL sorts lowest: first ascending, last descending
    (((1, "asc"),), [("n", None, 0.0), ("c", 2, 7.0), ("a", 3, 1.5)], None),
    (((1, "asc"),), [("c", 2, 7.0), ("n", None, 0.0), ("a", 3, 1.5)], 1),
    (((2, "desc"),), [("a", 3, 1.5), ("n", 1, float("nan"))], None),
    (((2, "desc"),), [("n", 1, float("nan")), ("a", 3, 1.5)], 1),
    (((0, "desc"),), [("b ", 1, 0.0), ("b", 2, 0.0), ("a", 3, 0.0)], None),
])
def test_out_of_order(order_by, rows, bad):
    assert compare.out_of_order(_frame(rows), order_by) == bad


def test_right_rows_in_the_wrong_order_are_wrong():
    ref = _frame(ROWS)
    kinds = [compare.EXACT, compare.EXACT, compare.REAL]
    by = ((1, "desc"), (0, "asc"))
    assert compare.compare_statement(_frame(ROWS), kinds, ref, by)[0]
    ok, _gap, note = compare.compare_statement(
        _frame(ROWS[::-1]), kinds, ref, by)
    assert not ok and "ORDER BY" in note
    # the same rows with no ORDER BY stated: any order is right
    assert compare.compare_statement(_frame(ROWS[::-1]), kinds, ref)[0]

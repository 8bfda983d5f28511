"""op_reduce.py and the two readers built on it, on planes made by hand:
two programs, each launched once inside its ``device.launch``
annotation, their op events named by instruction and the instructions'
scope paths given by a stand-in for ``costs.sites``."""

import pytest

from benchmarks import op_reduce as opr
from benchmarks import trace_reduce as tr

MS = 1e6                                   # the planes are in nanoseconds
OFFSET = -1.0 * MS                         # device clock 1 ms behind

SITES = {
    7: {"fusion.1": ("jit(fn)/op.join/gather/jit(_take)/gather", "fusion"),
        "sort.2": ("jit(fn)/op.aggregate/sort", "sort"),
        "copy.3": ("", "copy")},
    8: {"fusion.1": ("jit(fn)/shard_map/op.aggregate/exchange/gather/"
                     "jit(_take)/gather", "fusion"),
        "all-to-all.4": ("jit(fn)/shard_map/op.aggregate/exchange/"
                         "all_to_all", "all-to-all"),
        "all-gather.5": ("jit(fn)/shard_map/op.root/replicate/all_gather",
                         "all-gather"),
        "fusion.6": ("jit(fn)/shard_map/op.root/reduce_sum", "fusion")},
}


def planes(chips=1, program_stat=True, launches=True) -> dict:
    """q1 runs program 7 (ops 10-16 ms: 4 ms of a join's gather, 1 of a
    sort, 1 of an unnamed copy), q2 program 8 (ops 30-40 ms: 4 ms of the
    exchange's gather, 2 of its all-to-all, 2 of a replicate, 2 of a
    reduction); true times, every chip alike."""
    def ev(name, a, b):
        return (f"%{name} = s32[8]{{0}} op()", a * MS + OFFSET,
                b * MS + OFFSET)
    ops = [ev("fusion.1", 10, 14), ev("sort.2", 14, 15),
           ev("copy.3", 15, 16),
           ev("fusion.1", 30, 34), ev("all-to-all.4", 34, 36),
           ev("all-gather.5", 36, 38), ev("fusion.6", 38, 40)]
    modules = [("m7", 10 * MS + OFFSET, 16 * MS + OFFSET),
               ("m8", 30 * MS + OFFSET, 40 * MS + OFFSET)]
    stat = (lambda p: {"program": p, "bytes_accessed": 1.0}) \
        if program_stat else (lambda p: {"bytes_accessed": 1.0})
    return {
        "devices": {f"/device:TPU:{i}": {"XLA Ops": ops,
                                         "XLA Modules": modules}
                    for i in range(chips)},
        "annotations": [(tr.SLICE, 0.0, 50 * MS),
                        (tr.STMT + "q1#0", 1 * MS, 20 * MS),
                        (tr.STMT + "q2#0", 21 * MS, 45 * MS)],
        "launches": ([(tr.LAUNCH, 9.0 * MS, 9.1 * MS),
                      (tr.LAUNCH, 29.0 * MS, 29.1 * MS)]
                     if launches else []),
        "spans": {"main": [("device.launch", 8.5 * MS, 9.5 * MS, stat(7)),
                           ("device.launch", 28.5 * MS, 29.5 * MS,
                            stat(8))]},
    }


def test_scopes_splits_operators_from_mechanisms():
    assert opr.scopes(SITES[8]["fusion.1"][0]) == (
        "op.aggregate", ("exchange", "gather"))
    assert opr.scopes("jit(fn)/op.join/op.scan/sort") == (
        "op.join/op.scan", ())
    assert opr.scopes("") == ("", ())
    assert opr.scopes("jit(fn)/op.join/exchange/exchange/gather/"
                      "jit(_take)/gather") == ("op.join",
                                               ("exchange", "gather"))


@pytest.mark.parametrize("launches", [True, False])
def test_each_module_is_paired_with_its_program(launches):
    p = planes(launches=launches)
    modules = p["devices"]["/device:TPU:0"]["XLA Modules"]
    assert opr.pair_modules(p, modules) == [7, 8]


@pytest.mark.parametrize("chips", [1, 4])
def test_seconds_by_statement_operator_and_mechanism(chips):
    out = opr.reduce_planes(planes(chips), SITES.get)
    assert out["chips"] == chips
    assert out["busy_s"] == pytest.approx(chips * 16e-3)
    # only the copy (1 of 16 ms) has no op.* scope
    assert out["named_s"] == pytest.approx(chips * 15e-3)
    assert out["named_pct"] == pytest.approx(100 * 15 / 16)
    mech = out["mechanism_s"]
    assert mech["gather"] == pytest.approx(chips * 8e-3)
    assert mech["exchange"] == pytest.approx(chips * 6e-3)
    assert mech["replicate"] == pytest.approx(chips * 2e-3)
    assert mech["exchange|replicate"] == pytest.approx(chips * 8e-3)
    rows = {(r["statement"], r["operator"], r["mechanism"]): r
            for r in out["table"]}
    assert rows[("q1", "op.join", "gather")]["seconds"] == \
        pytest.approx(chips * 4e-3)
    assert rows[("q1", "op.aggregate", "sort")]["count"] == chips
    assert ("q1", "(unnamed)", "copy") in rows
    exchange = rows[("q2", "op.aggregate", "exchange/gather")]
    assert exchange["top"] == [["fusion.1", pytest.approx(chips * 4e-3)]]
    assert ("q2", "op.root", "replicate") in rows
    assert ("q2", "op.root", "fusion") in rows
    assert out["table"][0]["seconds"] == pytest.approx(chips * 4e-3)


def test_readers_need_named_ops():
    """95 % named or more: a share; under it, or with no program on the
    launches (a program older than the scopes), left out."""
    full = dict(SITES)
    full[7] = {**SITES[7], "copy.3": ("jit(fn)/op.root/copy", "copy")}
    out = opr.reduce_planes(planes(4), full.get)
    assert out["named_pct"] == pytest.approx(100.0)
    assert opr.busy_pct(out, "exchange|replicate") == pytest.approx(
        100 * 8 / 16)
    assert opr.busy_pct(out, "gather") == pytest.approx(100 * 8 / 16)
    partly = opr.reduce_planes(planes(4), SITES.get)
    assert partly["named_pct"] < opr.NAMED_MIN_PCT
    assert opr.busy_pct(partly, "gather") is None
    older = opr.reduce_planes(planes(4, program_stat=False), full.get)
    assert older["named_pct"] == 0.0
    assert opr.busy_pct(older, "gather") is None
    assert opr.busy_pct(None, "gather") is None


def test_no_device_op_reduces_to_none():
    p = planes()
    p["devices"] = {}
    assert opr.reduce_planes(p, SITES.get) is None

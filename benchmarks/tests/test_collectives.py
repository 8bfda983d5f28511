"""collectives.py and the four readers of the four-chip cell, on planes
made by hand with four device planes and known numbers."""

import pytest

from benchmarks import collectives as co
from benchmarks import run as bench_run
from benchmarks import span_reduce as sr

MS = 1e6                                   # the planes are in nanoseconds
A2A = ("%all-to-all.7 = s64[4,1000]{1,0:T(4,128)} all-to-all("
       "s64[4,1000]{1,0:T(4,128)} %fusion.3), channel_id=5, "
       "replica_groups={{0,1,2,3}}, dimensions={0}")
GATHER_START = ("%all-gather-start.2 = (s32[250]{0}, s32[1000]{0}) "
                "all-gather-start(s32[250]{0} %x), dimensions={0}")
GATHER_DONE = ("%all-gather-done.2 = s32[1000]{0:T(1024)} "
               "all-gather-done((s32[250]{0}, s32[1000]{0}) "
               "%all-gather-start.2)")
REDUCE = "%all-reduce.1 = s64[] all-reduce(s64[] %sum), to_apply=%add"
FUSION = "%fusion.3 = s64[4,1000]{1,0} fusion(s64[4000]{0} %p), kind=kLoop"
PEAKS = {"hbm_bytes_per_s": 800e9, "ici_bits_per_s": 1600e9}


def chip(scale: float = 1.0) -> dict:
    """One chip's plane: 10 ms of a fusion, then 2 ms all-to-all, 1 ms
    all-gather-start, 1 ms all-gather-done, 0.5 ms all-reduce."""
    def op(raw, a, b):
        return (raw, a * MS, (a + (b - a) * scale) * MS)
    ops = [op(FUSION, 10, 20), op(A2A, 20, 22), op(GATHER_START, 22, 23),
           op(GATHER_DONE, 23, 24), op(REDUCE, 24, 24.5)]
    return {"XLA Ops": ops, "XLA Modules": [("m", 10 * MS, 25 * MS)]}


def planes(n_chips: int = 4, collectives: bool = True) -> dict:
    one = chip()
    if not collectives:
        one = {"XLA Ops": one["XLA Ops"][:1],
               "XLA Modules": one["XLA Modules"]}
    return {"devices": {f"/device:TPU:{i}": one for i in range(n_chips)},
            "annotations": [("bench.slice", 0, 100 * MS),
                            ("bench.stmt:q3#0", 5 * MS, 30 * MS)],
            "launches": []}


def read(monkeypatch, name, found, busy_s=0.0145, launches=None, passes=2,
         chips=4):
    monkeypatch.setattr(co, "for_run", lambda run: found)
    monkeypatch.setattr(sr, "for_run", lambda run: launches)

    class Stmt:
        need_bytes = 58_000_000

    run = {"trace": {"busy_s": busy_s, "window_s": 0.1},
           "cell": {"name": "none", "chips": chips}, "peaks": PEAKS,
           "window": {"slice": (0.0, 0.1, passes, 3),
                      "records": [{"stmt": Stmt()}] * 3}}
    return bench_run.per_layer([name], run).get(name)


def test_parse_reads_kind_half_and_result_bytes():
    assert co.parse(A2A) == ("all-to-all", "", 4 * 1000 * 8)
    assert co.parse(GATHER_START) == ("all-gather", "-start",
                                      250 * 4 + 1000 * 4)
    assert co.parse(GATHER_DONE) == ("all-gather", "-done", 1000 * 4)
    assert co.parse(REDUCE) == ("all-reduce", "", 8)
    assert co.parse(FUSION) is None
    # a fusion that CONSUMES a collective's result is no collective
    assert co.parse("%fusion.9 = s32[8]{0} fusion(s32[8]{0} "
                    "%all-to-all.7), kind=kLoop") is None
    assert co.parse("%collective-permute-done.1 = pred[16]{0} "
                    "collective-permute-done(%cp)") == (
        "collective-permute", "-done", 16)


def test_four_planes_reduce_to_four_rows():
    found = co.reduce_planes(planes())
    assert len(found["chips"]) == 4
    for row in found["chips"]:
        assert row["count"] == 4
        assert row["collective_s"] == pytest.approx(0.0045)
        assert row["wire_s"] == pytest.approx(0.004)     # no all-reduce
        # the -start half's tuple is not counted: its -done carries it
        assert row["wire_bytes"] == 32_000 + 4_000
    # only the slice counts
    clipped = planes()
    clipped["annotations"][0] = ("bench.slice", 21 * MS, 23.5 * MS)
    row = co.reduce_planes(clipped)["chips"][0]
    assert row["collective_s"] == pytest.approx(0.0025)
    assert co.reduce_planes({"devices": {}, "annotations": [],
                             "launches": []}) is None


def test_collective_pct_is_collective_over_busy_seconds(monkeypatch):
    found = co.reduce_planes(planes())
    # 4.5 ms of 14.5 ms busy on every chip
    assert read(monkeypatch, "collective_pct", found) == pytest.approx(
        100 * 4.5 / 14.5)
    # a chip that waits twice as long for its peers doubles its own
    slow = planes()
    slow["devices"]["/device:TPU:3"] = chip(scale=2.0)
    got = read(monkeypatch, "collective_pct", co.reduce_planes(slow),
               busy_s=(3 * 14.5 + 29.0) / 4 / 1e3)
    assert got == pytest.approx(100 * (3 * 4.5 + 9.0) / (3 * 14.5 + 29.0))


def test_ici_roofline_counts_three_quarters_of_a_buffer(monkeypatch):
    found = co.reduce_planes(planes())
    # each of 4 chips: 36,000 result bytes, of which (4-1)/4 leave the
    # chip, at 200 GB/s, in 4 ms of all-to-all and all-gather
    least_s = 4 * 36_000 * 0.75 / 200e9
    assert read(monkeypatch, "ici_roofline_pct", found) == pytest.approx(
        100 * least_s / (4 * 0.004))
    assert co.wire_share(4) == 0.75 and co.wire_share(2) == 0.5
    # two chips: half of a buffer
    two = co.reduce_planes(planes(n_chips=2))
    assert read(monkeypatch, "ici_roofline_pct", two) == pytest.approx(
        100 * (2 * 36_000 * 0.5 / 200e9) / (2 * 0.004))


@pytest.mark.parametrize("name", ["ici_roofline_pct", "collective_pct"])
def test_no_collective_leaves_the_metric_out_never_zero(monkeypatch, name):
    found = co.reduce_planes(planes(collectives=False))
    assert found["chips"][0]["count"] == 0
    assert read(monkeypatch, name, found) is None
    assert read(monkeypatch, name, None) is None         # no trace at all
    # one chip: a lone all-reduce moves nothing over the interconnect
    lone = planes(n_chips=1)
    assert read(monkeypatch, "ici_roofline_pct",
                co.reduce_planes(lone)) is None


def test_exchange_mb_per_pass_sums_the_launch_spans(monkeypatch):
    spans = {"spans": {"device.launch": {"count": 8,
                                         "exchange_bytes": 6.0e9,
                                         "bytes_accessed": 1e12}}}
    assert read(monkeypatch, "exchange_mb_per_pass", None,
                launches=spans, passes=2) == pytest.approx(3000.0)
    # a program without the attribute (the parent, one chip): left out
    bare = {"spans": {"device.launch": {"count": 8, "bytes_accessed": 1}}}
    assert read(monkeypatch, "exchange_mb_per_pass", None,
                launches=bare) is None
    assert read(monkeypatch, "exchange_mb_per_pass", None,
                launches=None) is None


def test_hbm_roofline_x4_is_the_one_chip_share_over_four(monkeypatch):
    # 3 statements x 58 MB over 4 x 800 GB/s, against 14.5 ms busy
    want = 100 * (3 * 58e6 / (4 * 800e9)) / 0.0145
    assert read(monkeypatch, "hbm_roofline_pct.x4", None) == \
        pytest.approx(want)
    one = read(monkeypatch, "hbm_roofline_pct", None)
    assert one == pytest.approx(4 * want)
    assert read(monkeypatch, "hbm_roofline_pct.x4", None,
                busy_s=None) is None

"""span_reduce.py and the readers built on it: on planes made by hand,
with known numbers for every reader, and on a small recorded trace (the
first 15 statements of a traced chip run of nds_h_sf1.short on a v5e
with the program's spans, PR 25, cut like ``short_first15.xplane.pb``
with the host's other events dropped)."""

import os

import pytest

from benchmarks import run as bench_run
from benchmarks import span_reduce as sr
from benchmarks import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "short_spans_first15.xplane.pb")
MS = 1e6                                   # the planes are in nanoseconds
OFFSET = -1.25 * MS                        # device clock 1.25 ms behind

IDLE = ("idle_front_ms_per_stmt.stmt", "idle_dispatch_ms_per_stmt.stmt",
        "idle_readback_ms_per_stmt.stmt", "idle_finish_ms_per_stmt.stmt")
IN_SLICE = IDLE + ("readbacks_per_stmt.stmt", "program_mb_per_stmt.stmt",
                   "program_gb_per_pass")
SETUP = {"engine_init_s": "engine.init", "load_read_s": "load.read",
         "load_build_s": "load.build", "lower_s": "compile.lower",
         "cache_read_s": "compile.xla",
         "first_bind_s": "device.bind:first"}


def statement(t0: float, syncs: int) -> list:
    """The program's spans of one warm statement whose bench.stmt opens
    at ``t0`` ms: its one program runs from t0+6 to t0+12 on the device."""
    def span(name, a, b, **stats):
        return (name, (t0 + a) * MS, (t0 + b) * MS, stats)
    return [span("stmt", 0.5, 19.5, stmt_id=1, plan_cache_hit=1),
            span("sched.place", 1, 2),
            span("sched.run", 2, 18),
            span("device.dispatch", 2.5, 6),
            span("device.bind", 3, 4, first=0, uploads=0, upload_bytes=0),
            span("device.launch", 5, 6, bytes_accessed=4e6, flops=10.0),
            span("device.readback", 7, 14, syncs=syncs, bytes=100),
            span("device.materialize", 14, 16),
            span("device.finish", 16, 17),
            span("sched.note", 18, 19)]


def handmade(spans=True) -> dict:
    """A 100 ms slice with three statements of 20, 20 and 10 ms.  The
    first two carry the program's spans; the third does not.  Device
    events are on the device's clock, 1.25 ms behind the host's."""
    programs = [(16, 22), (46, 52), (72, 75)]          # true times, ms
    modules = [(f"m{i}", a * MS + OFFSET, b * MS + OFFSET)
               for i, (a, b) in enumerate(programs)]
    launches = [(tr.LAUNCH, 16.0 * MS, 16.1 * MS),     # this one sets the
                (tr.LAUNCH, 45.8 * MS, 45.9 * MS),     # offset exactly
                (tr.LAUNCH, 71.9 * MS, 72.0 * MS)]
    return {
        "devices": {"/device:TPU:0": {
            "XLA Ops": [("%fusion = x", s, e) for _n, s, e in modules],
            "XLA Modules": modules}},
        "annotations": [("bench.slice", 0, 100 * MS),
                        ("bench.stmt:a#0", 10 * MS, 30 * MS),
                        ("bench.stmt:b#0", 40 * MS, 60 * MS),
                        ("bench.stmt:c#0", 70 * MS, 80 * MS)],
        "launches": launches,
        "spans": ({"main": statement(10, 1) + statement(40, 2),
                   # another thread's spans are not the statements'
                   "helper": [("stmt", 0, 100 * MS, {})]}
                  if spans else {}),
        "stmt_line": "main"}


def read_all(monkeypatch, reduced, names, passes=1) -> dict:
    """The readers' values as run.py collects them, with the reduced
    spans put in the xplane's place."""
    monkeypatch.setattr(sr, "for_run", lambda run: reduced)
    run = {"trace": {"busy_s": 1.0}, "cell": {"name": "none"},
           "window": {"slice": (0.0, 0.1, passes, 3)}}
    return bench_run.per_layer(list(names), run)


def test_innermost_pieces_and_their_paths():
    pieces = sr.innermost([("a", 0, 10), ("b", 2, 5), ("c", 3, 4),
                           ("d", 5, 12),       # outlasts a: cut to it
                           ("e", 20, 21)])
    assert pieces == [(0, 2, ("a",)), (2, 3, ("a", "b")),
                      (3, 4, ("a", "b", "c")), (4, 5, ("a", "b")),
                      (5, 10, ("a", "d")), (20, 21, ("e",))]
    assert list(sr.overlap(pieces, [(1, 3.5), (9, 30)])) == [
        (("a",), 1), (("a", "b"), 1), (("a", "b", "c"), 0.5),
        (("a", "d"), 1), (("e",), 1)]


def test_handmade_idle_goes_to_the_innermost_span():
    out = sr.reduce_planes(handmade())
    assert out["clock_offset_s"] == pytest.approx(-1.25e-3)
    assert out["statements"] == 3
    assert out["window_s"] == pytest.approx(0.100)
    assert out["idle_s"] == pytest.approx(0.085)        # busy 6 + 6 + 3
    spans = out["spans"]
    assert spans["stmt"]["count"] == 2                  # the helper's: no
    assert spans["stmt"]["total_s"] == pytest.approx(0.038)
    assert spans["stmt"]["self_s"] == pytest.approx(2 * 0.001)
    assert spans["sched.run"]["self_s"] == pytest.approx(2 * 0.0025)
    assert spans["device.dispatch"]["self_s"] == pytest.approx(2 * 0.0015)
    # the gap after each program (22..30, 52..60) is split between the
    # read-back, which it ends under, and the four spans after it
    assert spans["device.readback"]["idle_s"] == pytest.approx(2 * 0.002)
    assert spans["device.materialize"]["idle_s"] == pytest.approx(0.004)
    assert spans["device.finish"]["idle_s"] == pytest.approx(0.002)
    assert spans["sched.note"]["idle_s"] == pytest.approx(0.002)
    assert spans["sched.run"]["idle_s"] == pytest.approx(2 * 0.0015)
    assert spans["stmt"]["idle_s"] == pytest.approx(2 * 0.001)
    # the gap before it (10..16, 40..46) under the dispatch's spans
    assert spans["device.bind"]["idle_s"] == pytest.approx(0.002)
    assert spans["device.launch"]["idle_s"] == pytest.approx(0.002)
    assert spans["device.dispatch"]["idle_s"] == pytest.approx(0.003)
    assert spans["device.readback"]["syncs"] == 3
    assert spans["device.launch"]["bytes_accessed"] == 8e6
    assert out["groups"] == pytest.approx(
        {"front": 0.007, "dispatch": 0.007, "readback": 0.004,
         "finish": 0.008})
    # statement c has no program spans, and the harness's own half
    # millisecond at either end of a and b is under none
    assert out["uncovered_s"] == pytest.approx(0.007 + 4 * 0.0005)
    assert out["outside_s"] == pytest.approx(0.050)
    assert (sum(out["groups"].values()) + out["uncovered_s"]
            + out["outside_s"]) == pytest.approx(out["idle_s"], abs=1e-12)
    assert sum(s["idle_s"] for s in spans.values()) == pytest.approx(
        sum(out["groups"].values()))


def test_handmade_readers(monkeypatch):
    got = read_all(monkeypatch, sr.reduce_planes(handmade()), IN_SLICE,
                   passes=2)
    assert got == pytest.approx({
        "idle_front_ms_per_stmt.stmt": 7 / 3,
        "idle_dispatch_ms_per_stmt.stmt": 7 / 3,
        "idle_readback_ms_per_stmt.stmt": 4 / 3,
        "idle_finish_ms_per_stmt.stmt": 8 / 3,
        "readbacks_per_stmt.stmt": 1.0,
        "program_mb_per_stmt.stmt": 8 / 3,
        "program_gb_per_pass": 0.004})


def test_clocks_left_alone_move_the_idle_time_between_spans():
    planes = handmade()
    planes["launches"] = planes["launches"][:-1]       # cannot be paired
    out = sr.reduce_planes(planes)
    assert out["clock_offset_s"] == 0.0
    # the programs now seem to end 1.25 ms early: the read-backs'
    # share of the gap after them grows from 2 to 3.25 ms
    assert out["groups"]["readback"] == pytest.approx(2 * 0.00325)


def test_no_program_spans_read_none(monkeypatch):
    assert sr.reduce_planes(handmade(spans=False)) is None
    assert read_all(monkeypatch, None, IN_SLICE) == {}
    # spans but no device plane: nothing to lay them over
    planes = handmade()
    planes["devices"] = {}
    assert sr.reduce_planes(planes) is None


def test_bytes_left_out_where_the_backend_gives_none(monkeypatch):
    planes = handmade()
    planes["spans"]["main"] = [
        (n, s, e, {k: v for k, v in stats.items() if k != "bytes_accessed"})
        for n, s, e, stats in planes["spans"]["main"]]
    got = read_all(monkeypatch, sr.reduce_planes(planes), IN_SLICE)
    assert "program_mb_per_stmt.stmt" not in got
    assert "program_gb_per_pass" not in got
    assert got["readbacks_per_stmt.stmt"] == pytest.approx(1.0)


def test_setup_readers_read_the_programs_totals(monkeypatch):
    totals = {span: {"count": 2, "total_s": 1.5 + i, "self_s": 1.0}
              for i, span in enumerate(SETUP.values())}
    totals["device.bind"] = {"count": 9, "total_s": 99.0, "self_s": 9.0}
    monkeypatch.setattr(sr, "program_totals", lambda: totals)
    run = {"trace": None, "cell": {"name": "none"}}
    assert bench_run.per_layer(list(SETUP), run) == {
        metric: 1.5 + i for i, metric in enumerate(SETUP)}
    # a span that never ran, or a program that keeps no totals: left out
    monkeypatch.setattr(sr, "program_totals", lambda: {})
    assert bench_run.per_layer(list(SETUP), run) == {}
    monkeypatch.setattr(sr, "program_totals", lambda: None)
    assert bench_run.per_layer(list(SETUP), run) == {}


def test_program_totals_come_from_the_tracer(monkeypatch):
    from nds_tpu.obs import trace
    tracer = trace.Tracer(enabled=True)
    with tracer.span("load.read"):
        pass
    monkeypatch.setattr(trace, "get_tracer", lambda: tracer)
    assert sr.program_totals()["load.read"]["count"] == 1
    assert sr.setup_seconds("load.read") > 0
    assert sr.setup_seconds("load.build") is None
    monkeypatch.setattr(trace, "get_tracer", lambda: object())  # the parent's
    assert sr.program_totals() is None


def test_for_run_without_a_trace_reads_nothing():
    assert sr.for_run({"trace": None, "cell": {"name": "none"}}) is None
    assert sr.for_run({"trace": {"busy_s": 1.0},
                       "cell": {"name": "no-such-cell"}}) is None


def test_every_new_metric_has_its_entry_and_its_reader():
    import json
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in IN_SLICE + tuple(SETUP):
        assert entries[name]["better"] == "lower"
        assert entries[name]["workloads"]
        assert os.path.exists(os.path.join(bench_run.HERE, "layers",
                                           f"{name}.py"))
    assert {entries[n]["moves"] for n in IDLE} == {"stmt_mean_ms"}
    assert {entries[n]["moves"] for n in SETUP} == {"setup_s"}


# ------------------------------------------------- the recorded trace

@pytest.fixture(scope="module")
def recorded():
    return sr.reduce(FIXTURE), tr.reduce(FIXTURE)


def test_recorded_spans_are_found(recorded):
    spans, _whole = recorded
    assert spans["statements"] == 15
    for name in ("stmt", "sched.place", "sched.run", "device.dispatch",
                 "device.bind", "device.launch", "device.readback",
                 "device.materialize", "device.finish", "sched.note"):
        assert spans["spans"][name]["count"] == 15, name
    assert spans["spans"]["device.readback"]["syncs"] == 15


def test_recorded_idle_adds_up_to_the_trace_reducers(recorded):
    spans, whole = recorded
    idle = whole["window_s"] - whole["busy_s"]
    assert spans["window_s"] == pytest.approx(whole["window_s"], abs=1e-12)
    assert spans["idle_s"] == pytest.approx(idle, abs=1e-9)
    assert spans["clock_offset_s"] == whole["clock_offset_s"]
    named = sum(spans["groups"].values())
    assert named + spans["uncovered_s"] + spans["outside_s"] == \
        pytest.approx(idle, abs=1e-9)
    # the four idle_* metrics hold the idle time but for what no span
    # covers, and that is under 5 % of it
    assert named == pytest.approx(idle, rel=0.05)
    assert spans["uncovered_s"] + spans["outside_s"] < 0.05 * idle
    gaps = dict(whole["idle_gaps"])
    assert spans["outside_s"] == pytest.approx(
        gaps.get("between statements", 0.0), abs=1e-9)

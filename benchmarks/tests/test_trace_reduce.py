"""trace_reduce.py on a small recorded trace (the first 15 statements of
the first traced chip run of nds_h_sf1.short on a v5e, PR 24, cut with
the host's events other than the benchmark's annotations dropped) and on
planes made by hand."""

import os

import pytest

from benchmarks import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "short_first15.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(FIXTURE)


def test_recorded_planes_are_found():
    planes = tr.read_planes(FIXTURE)
    assert list(planes["devices"]) == ["/device:TPU:0"]
    lines = planes["devices"]["/device:TPU:0"]
    assert len(lines["XLA Ops"]) == 400 and len(lines["XLA Modules"]) == 15
    names = [a[0] for a in planes["annotations"]]
    assert names.count("bench.slice") == 1
    assert sum(n.startswith("bench.stmt:") for n in names) == 15
    assert len(planes["launches"]) == 15       # one a program execution


def test_recorded_busy_and_window(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.128284874, abs=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.090389655, abs=1e-9)
    # on that run the device's clock was 1.24 ms behind the host's
    assert reduced["clock_offset_s"] == pytest.approx(-0.001241374,
                                                      abs=1e-9)


def test_recorded_ops_and_gaps(reduced):
    ops = dict(reduced["device_ops"])
    assert reduced["device_ops"][0][0] == "q19/fusion.7"
    assert ops["q19/fusion.7"] == pytest.approx(0.022924591, abs=1e-9)
    # the 1.17 ms lookup of the 200000-row part table belongs to q19 and
    # q14; by the raw clocks it fell into the q6 that ran before them.
    # q6's own program is microseconds
    assert ops["q19/fusion.4"] == pytest.approx(0.00586254, abs=1e-9)
    assert ops["q14/fusion.3"] == pytest.approx(0.005862547, abs=1e-9)
    assert sum(v for k, v in ops.items() if k.startswith("q6/")) < 1e-4
    assert sum(ops.values()) >= reduced["busy_s"]     # ops may overlap
    # busy + gaps is the window; gaps carry statement and phase
    gaps = dict(reduced["idle_gaps"])
    assert sum(gaps.values()) + reduced["busy_s"] == pytest.approx(
        reduced["window_s"], abs=1e-9)
    assert reduced["idle_gaps"][0][0] == "q19: after last program"
    assert gaps["q19: before first program"] == pytest.approx(
        0.002996503, abs=1e-9)
    assert not any("between programs" in k for k in gaps)  # one program each


def test_clocks_are_left_alone_where_launches_do_not_pair():
    planes = tr.read_planes(FIXTURE)
    planes["launches"] = planes["launches"][:-1]
    out = tr.reduce_planes(planes)
    assert out["clock_offset_s"] == 0.0
    assert "q6/fusion.4" in dict(out["device_ops"])   # the raw clocks' error


def _planes(ops, modules, annotations):
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": modules}},
            "annotations": annotations, "launches": []}


def test_union_and_clip():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_handmade_gaps_are_split_at_statement_edges():
    s = 1e9
    out = tr.reduce_planes(_planes(
        ops=[("%a = x", 1 * s, 2 * s), ("%b = y", 1.5 * s, 3 * s),
             ("%c = z", 6 * s, 7 * s)],
        modules=[("m1", 1 * s, 3 * s), ("m2", 6 * s, 7 * s)],
        annotations=[("bench.slice", 0, 10 * s),
                     ("bench.stmt:s1#0", 0.5 * s, 4 * s),
                     ("bench.stmt:s2#3", 5 * s, 8 * s)]))
    assert out["busy_s"] == pytest.approx(3.0)
    assert out["window_s"] == pytest.approx(10.0)
    gaps = dict(out["idle_gaps"])
    assert gaps["between statements"] == pytest.approx(0.5 + 1.0 + 2.0)
    assert gaps["s1: before first program"] == pytest.approx(0.5)
    assert gaps["s1: after last program"] == pytest.approx(1.0)
    assert gaps["s2: before first program"] == pytest.approx(1.0)
    assert gaps["s2: after last program"] == pytest.approx(1.0)
    assert set(gaps) == {"between statements", "s1: before first program",
                         "s1: after last program",
                         "s2: before first program",
                         "s2: after last program"}
    ops = dict(out["device_ops"])
    assert ops["s1/a"] == pytest.approx(1.0)
    assert ops["s1/b"] == pytest.approx(1.5)
    assert ops["s2/c"] == pytest.approx(1.0)


def test_no_device_plane_reads_nothing():
    out = tr.reduce_planes({"devices": {}, "annotations": [],
                            "launches": []})
    assert out["busy_s"] is None and out["window_s"] is None


def test_handmade_clock_offset_and_phases():
    """Device clock 2 s behind: the launches put it right; a gap inside
    a program and one between two programs of one statement are named."""
    s = 1e9
    out = tr.reduce_planes({
        "devices": {"/device:TPU:0": {
            "XLA Ops": [("%a = x", -1 * s, 0 * s), ("%b = y", 1 * s, 2 * s),
                        ("%c = z", 3 * s, 4 * s)],
            "XLA Modules": [("m1", -1 * s, 2 * s), ("m2", 3 * s, 4 * s)]}},
        "annotations": [("bench.slice", 0, 10 * s),
                        ("bench.stmt:s1#0", 0.5 * s, 7 * s)],
        "launches": [("PJRT_LoadedExecutable_Execute", 1 * s, 1.1 * s),
                     ("PJRT_LoadedExecutable_Execute", 5 * s, 5.1 * s)]})
    assert out["clock_offset_s"] == pytest.approx(-2.0)
    gaps = dict(out["idle_gaps"])
    assert gaps["s1: before first program"] == pytest.approx(0.5)
    assert gaps["s1: inside a program"] == pytest.approx(1.0)
    assert gaps["s1: between programs"] == pytest.approx(1.0)
    assert gaps["s1: after last program"] == pytest.approx(1.0)
    assert gaps["between statements"] == pytest.approx(0.5 + 3.0)
    assert out["busy_s"] == pytest.approx(3.0)


def test_without_a_slice_the_window_is_the_devices_own_span():
    s = 1e9
    out = tr.reduce_planes(_planes(
        ops=[("%a = x", 2 * s, 3 * s), ("%b = y", 5 * s, 6 * s)],
        modules=[], annotations=[]))
    assert out["window_s"] == pytest.approx(4.0)
    assert out["busy_s"] == pytest.approx(2.0)

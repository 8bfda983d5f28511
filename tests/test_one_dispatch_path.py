"""One statement, one way through an executor (PR 29): the sharded
executor rides ``DeviceExecutor``'s dispatch skeleton and overrides what
is true of a sharded program only. Four virtual CPU devices stand for
the four chips.
"""

import threading

import numpy as np
import pytest

N, N_DIM = 8192, 2048


def _sessions(kinds=("device", "sharded")):
    """(single-device session, four-device session, both tables
    sharded) over one fact and one dimension table; ``d_three`` takes
    each value three times, so a join on it is M:N.  ``kinds`` may ask
    for a ``chunked`` session too, which streams the fact in chunks."""
    from nds_tpu.engine.chunked_exec import make_chunked_factory
    from nds_tpu.engine.device_exec import make_device_factory
    from nds_tpu.engine.session import Session
    from nds_tpu.engine.types import INT32, Schema
    from nds_tpu.io.host_table import from_arrays
    from nds_tpu.parallel.dist_exec import make_distributed_factory
    from nds_tpu.sql.planner import CatalogInfo
    rng = np.random.default_rng(29)
    schemas = {
        "fact": Schema.of(("f_id", INT32, False), ("f_dim_sk", INT32, False),
                          ("f_grp", INT32, False), ("f_three", INT32, False),
                          ("f_val", INT32, False)),
        "dim": Schema.of(("d_sk", INT32, False), ("d_three", INT32, False),
                         ("d_val", INT32, False))}
    arrays = {
        "fact": {"f_id": np.arange(N, dtype=np.int32),
                 "f_dim_sk": rng.integers(0, N_DIM, N).astype(np.int32),
                 "f_grp": rng.integers(0, 64, N).astype(np.int32),
                 "f_three": rng.integers(0, N_DIM // 3, N).astype(np.int32),
                 "f_val": rng.integers(0, 100, N).astype(np.int32)},
        "dim": {"d_sk": np.arange(N_DIM, dtype=np.int32),
                "d_three": (np.arange(N_DIM) // 3).astype(np.int32),
                "d_val": (np.arange(N_DIM) * 3).astype(np.int32)}}
    cat = CatalogInfo(schemas, {"dim": ["d_sk"], "fact": ["f_id"]},
                      {"fact": N, "dim": N_DIM})

    def build(factory):
        s = Session(cat, factory)
        for t in schemas:
            s.register_table(from_arrays(t, schemas[t], arrays[t]))
        return s

    factories = {
        "device": make_device_factory,
        "sharded": lambda: make_distributed_factory(
            n_devices=4, shard_threshold=1000),
        "chunked": lambda: make_chunked_factory(stream_bytes=1,
                                                chunk_rows=2048)}
    return {k: build(factories[k]()) for k in kinds}


@pytest.fixture(scope="module")
def sessions():
    return _sessions()


@pytest.fixture
def kept_trees(monkeypatch, tmp_path):
    """Span trees are kept where something reads them: the export."""
    monkeypatch.setenv("NDS_TPU_TRACE", str(tmp_path / "t.jsonl"))


JOIN = ("select f_grp, count(*) as n, sum(f_val + d_val) as s "
        "from fact join dim on f_dim_sk = d_sk "
        "group by f_grp order by f_grp")


def _executor(session):
    return session._executor_factory(session.tables)


def _frame(result):
    return result.to_pandas()


def _tree(span):
    return [(c.name, [g.name for g in c.children if
                      g.name.startswith("device.")])
            for c in span.children if c.name.startswith("device.")]


@pytest.mark.parametrize("which", ["device", "sharded"])
def test_execute_async_is_execute(sessions, kept_trees, which):
    """``execute_async(p).result()`` is ``execute(p)``: the same rows
    under the same spans, from either executor (the sharded executor's
    inherited ``execute_async`` used to take the single-device compile
    path and fail)."""
    import pandas as pd
    session = sessions[which]
    ex = _executor(session)
    planned = session.plan(JOIN)
    ex.execute(planned)                             # compiles
    want = _frame(ex.execute(planned))
    sync_span = ex.last_query_span
    handle = ex.execute_async(planned)
    got = _frame(handle.result())
    pd.testing.assert_frame_equal(got, want, check_exact=True)
    assert int(got["n"].sum()) == N
    assert handle.result() is handle.result()       # finished once
    async_span = ex.last_query_span
    assert async_span is not sync_span
    assert _tree(async_span) == _tree(sync_span) == [
        ("device.dispatch", ["device.bind", "device.launch"]),
        ("device.readback", []), ("device.run", []),
        ("device.materialize", []), ("device.finish", [])]
    assert async_span.attrs["executor"] == type(ex).__name__
    assert ("devices" in async_span.attrs) == (which == "sharded")
    if which == "sharded":
        # nothing left to pipeline: it was over when the handle came
        from nds_tpu.parallel import dist_exec
        assert not dist_exec._DISPATCH_LOCK.locked()


def test_sharded_executor_keeps_no_copy_of_the_skeleton(sessions):
    from nds_tpu.engine.device_exec import DeviceExecutor
    from nds_tpu.parallel.dist_exec import DistributedExecutor
    own = set(DistributedExecutor.__dict__)
    assert not own & {"execute", "_entry", "_execute_traced",
                      "_compile_or_load_sharded", "_compile_or_load",
                      "_dispatch_traced", "_finish", "_finish_traced",
                      "_bound_compiled", "_launch", "_readback"}
    assert DistributedExecutor.execute is DeviceExecutor.execute
    shapes = {}
    for which, session in sessions.items():
        session.sql(JOIN)
        ex = _executor(session)
        entries = [e for k, e in ex._compiled.items()
                   if not (isinstance(k, tuple) and k[0] == "__compact__")]
        assert entries
        shapes[which] = {frozenset(e) for e in entries}
        for e in entries:
            assert set(type(ex).SIDE_KEYS) <= set(e["side"])
    assert shapes["device"] == shapes["sharded"] == {
        frozenset(("slack", "ref", "compiled", "side"))}


class _RecordingLock:
    """Stands in for the dispatch lock: notes which spans were open and
    which had ended whenever it is taken or given back."""

    def __init__(self, tracer):
        self.tracer, self.inner = tracer, threading.Lock()
        self.events = []

    def _note(self, what):
        open_spans, cur = [], self.tracer.current()
        while cur is not None:
            open_spans.append(cur.name)
            cur = cur.parent
        root = self.tracer.current()
        while root is not None and root.name != "device.execute":
            root = root.parent
        done = [s.name for s in root.walk()
                if s.t1 is not None] if root else []
        self.events.append((what, open_spans, done))

    def acquire(self):
        self.inner.acquire()
        self._note("acquire")

    def release(self):
        self._note("release")
        self.inner.release()

    def locked(self):
        return self.inner.locked()


@pytest.mark.parametrize("case", ["warm", "cold_compile", "plan_cache_load",
                                  "readback_raises"])
def test_dispatch_lock_spans_launch_to_readback_only(
        sessions, kept_trees, monkeypatch, tmp_path, case):
    """Taken after ``device.bind`` ended, immediately before the
    launch; given back when the read-back returned or raised, before
    ``device.materialize``; never held inside a compile or a plan-cache
    load (four sharded programs compile at once in a warm-up)."""
    from nds_tpu import cache as plan_cache
    from nds_tpu.obs.trace import get_tracer
    from nds_tpu.parallel import dist_exec
    lock = _RecordingLock(get_tracer())
    monkeypatch.setattr(dist_exec, "_DISPATCH_LOCK", lock)
    session = sessions["sharded"]
    ex = _executor(session)
    sql = JOIN if case in ("warm", "readback_raises") else (
        f"select count(*) as n, sum(f_val) as s from fact "
        f"where f_val < {40 + len(case)}")
    if case == "warm":
        session.sql(sql)
        lock.events.clear()
    if case == "plan_cache_load":
        plan_cache.configure(str(tmp_path / "plans"))
        try:
            session.sql(sql)                    # compiles, persists
            ex._compiled.clear()
            lock.events.clear()
            session.sql(sql)                    # loads
        finally:
            plan_cache.reset()
        assert ex.last_query_span.find("cache.load")
        assert not ex.last_query_span.find("device.compile")
    elif case == "readback_raises":
        def broken(tracer, devs, describe=None):
            with tracer.span("device.readback"):
                raise RuntimeError("lost the device")
        monkeypatch.setattr(ex, "_readback", broken)
        with pytest.raises(RuntimeError, match="lost the device"):
            session.sql(sql)
    else:
        session.sql(sql)
        if case == "cold_compile":
            assert ex.last_query_span.find("device.compile")
    assert [e[0] for e in lock.events] == ["acquire", "release"]
    assert not lock.locked()
    # whose program is in flight is the executor's to know, not the
    # statement's bill
    assert ex._in_flight is None
    assert not any(k.startswith("__in") for k in ex.last_timings)
    (_, open_at_acquire, done_at_acquire), \
        (_, open_at_release, done_at_release) = lock.events
    # between the bind and the launch, under the dispatch
    assert open_at_acquire[0] == "device.dispatch"
    assert "device.bind" in done_at_acquire
    assert "device.launch" not in done_at_acquire
    assert not {"device.compile", "cache.load"} & set(open_at_acquire)
    # after the read-back, before anything else
    assert open_at_release[0] == "device.execute"
    assert {"device.launch", "device.readback"} <= set(done_at_release)
    assert not {"device.run", "device.materialize",
                "device.finish"} & set(done_at_release)
    if case == "cold_compile":
        assert "device.compile" in done_at_acquire
    if case == "plan_cache_load":
        assert "cache.load" in done_at_acquire


def test_single_device_overflow_goes_round_the_shared_loop(
        sessions, kept_trees):
    """An M:N join whose expansion does not fit slack x rows: counted,
    recompiled at doubled slack under the same ``device.execute``,
    exact, and the scan bytes accounted live are given back once."""
    import pandas as pd
    from nds_tpu.obs import memwatch
    from nds_tpu.obs import metrics as obs_metrics
    session = sessions["device"]
    ex = _executor(session)
    # each fact row meets three dimension rows: 3 x 8192 slots against
    # the 2 x 8192 a first program has, inside the 4 x 8192 of a second
    sql = ("select f_grp, count(*) as n, sum(d_val) as s "
           "from fact join dim on f_three = d_three "
           "group by f_grp order by f_grp")
    counters = ("slack_retries_total", "recompiles_total",
                "compiles_total", "exchange_overflow_retries_total")
    before = obs_metrics.snapshot()["counters"]
    live_before = memwatch.live_bytes()
    got = _frame(session.sql(sql))
    after = obs_metrics.snapshot()["counters"]
    assert {k: after.get(k, 0) - before.get(k, 0) for k in counters} == {
        "slack_retries_total": 1, "recompiles_total": 1,
        "compiles_total": 1, "exchange_overflow_retries_total": 0}
    assert memwatch.live_bytes() == live_before
    assert "__live_bytes" not in ex.last_timings
    fact, dim = (session.tables[t] for t in ("fact", "dim"))
    frame = pd.DataFrame({
        "f_grp": fact.columns["f_grp"].values,
        "three": fact.columns["f_three"].values}).merge(
        pd.DataFrame({"three": dim.columns["d_three"].values,
                      "d_val": dim.columns["d_val"].values}), on="three")
    want = frame.groupby("f_grp").agg(
        n=("d_val", "size"), s=("d_val", "sum")).reset_index()
    assert got["n"].tolist() == want["n"].tolist()
    assert got["s"].tolist() == want["s"].tolist()
    assert int(got["n"].sum()) == 3 * N
    # one statement span, a dispatch / read-back pair a round
    span = ex.last_query_span
    assert len(span.find("device.dispatch")) == 2
    assert len(span.find("device.readback")) == 2
    compiles = span.find("device.compile")
    assert [c.attrs["slack"] for c in compiles] == [2.0, 4.0]
    (entry,) = [e for e in ex._compiled.values()
                if isinstance(e, dict) and e["slack"] > ex.slack]
    assert entry["slack"] == 4.0
    # the bill carries both compiles
    assert ex.last_timings["compile_ms"] > max(c.dur_ms for c in compiles)


@pytest.mark.parametrize("which", ["device", "sharded", "chunked"])
def test_every_launch_names_its_program(kept_trees, which):
    """``device.launch`` carries ``program``, the executable's number in
    the registry of ``obs.costs``, on every path (the chunked one's
    per-chunk programs included); ``costs.sites`` reads that program's
    instructions, each with its scope path and opcode."""
    from nds_tpu.obs import costs
    session = _sessions((which,))[which]
    ex = _executor(session)
    session.sql(JOIN)
    root = ex.last_query_span
    while root.parent is not None:
        root = root.parent
    launches = root.find("device.launch")
    if which == "chunked":
        assert root.find("chunk.reduce")
    assert launches
    for launch in launches:
        program = launch.attrs["program"]
        sites = costs.sites(program)
        assert sites and all(isinstance(op_name, str) and opcode
                             for op_name, opcode in sites.values())
        assert "fusion" in {opcode for _o, opcode in sites.values()}
    # one number an executable: a repeat launches the same program
    session.sql(JOIN)
    again = ex.last_query_span.find("device.launch")
    assert [a.attrs["program"] for a in again][-1] == \
        launches[-1].attrs["program"]


def test_a_sharded_compile_binds_its_uploads_as_a_first_bind(kept_trees):
    """The sharded program's buffers are placed on the mesh when it is
    first compiled: under ``device.compile``, in a ``device.bind`` with
    ``first``, which the tracer sums under ``device.bind:first`` as it
    does the single-device path's."""
    from nds_tpu.obs.trace import get_tracer
    session = _sessions(("sharded",))["sharded"]
    ex = _executor(session)
    before = get_tracer().totals().get("device.bind:first", {})
    session.sql(JOIN)
    (compiled,) = ex.last_query_span.find("device.compile")
    bind = compiled.find("device.bind")[0]
    assert bind.attrs["first"] is True
    assert bind.attrs["uploads"] > 0 and bind.attrs["upload_bytes"] > 0
    after = get_tracer().totals()["device.bind:first"]
    assert after["count"] > before.get("count", 0)

"""Data-maintenance tests: DML engine support (INSERT/DELETE), the 11
LF_*/DF_* refresh functions end-to-end against a versioned warehouse,
DATE1/DATE2 substitution, snapshot commit and rollback — the vertical
slice of `nds/nds_maintenance.py` + `nds_rollback.py`."""

import os

import numpy as np
import pytest

from nds_tpu.columnar import delta
from nds_tpu.datagen import tpcds
from nds_tpu.engine.session import Session
from nds_tpu.io.host_table import from_arrays
from nds_tpu.io.snapshots import SnapshotLog
from nds_tpu.nds import gen_data, maintenance, transcode
from nds_tpu.nds.schema import get_schemas

SF = 0.01


def _nrows(sess, table):
    """Logical row count: DELETEs land as delta bitmasks, so
    ``table.nrows`` stays physical and the visible count subtracts the
    masked rows."""
    return delta.visible_rows(sess.tables[table])


def _session(tables=("store_sales", "store_returns", "date_dim",
                     "reason")):
    schemas = get_schemas()
    sess = Session.for_nds()
    for t in tables:
        sess.register_table(
            from_arrays(t, schemas[t], tpcds.gen_table(t, SF)))
    return sess


class TestDml:
    def test_insert_select(self):
        sess = _session()
        n0 = sess.tables["store_sales"].nrows
        r = sess.sql("select count(*) as c from store_sales "
                     "where ss_quantity > 95")
        expected = int(r.cols[0][0])
        out = sess.sql("insert into store_sales (select * from "
                       "store_sales where ss_quantity > 95)")
        assert out is None
        assert sess.tables["store_sales"].nrows == n0 + expected

    def test_insert_preserves_null_masks(self):
        sess = _session()
        col0 = sess.tables["store_sales"].column("ss_customer_sk")
        nulls0 = int((~col0.null_mask).sum())
        sess.sql("insert into store_sales "
                 "(select * from store_sales)")
        col1 = sess.tables["store_sales"].column("ss_customer_sk")
        assert int((~col1.null_mask).sum()) == 2 * nulls0

    def test_delete_scalar_subquery_range(self):
        sess = _session()
        n0 = sess.tables["store_sales"].nrows
        r = sess.sql(
            "select count(*) as c from store_sales where "
            "ss_sold_date_sk >= 2450815 and ss_sold_date_sk <= 2450845")
        in_window = int(r.cols[0][0])
        assert in_window > 0
        sess.sql(
            "delete from store_sales where ss_sold_date_sk >= "
            "(select min(d_date_sk) from date_dim where d_date between "
            "'1998-01-01' and '1998-01-31') and ss_sold_date_sk <= "
            "(select max(d_date_sk) from date_dim where d_date between "
            "'1998-01-01' and '1998-01-31')")
        assert _nrows(sess, "store_sales") == n0 - in_window

    def test_delete_null_dates_survive(self):
        """SQL DELETE keeps rows where the predicate is NULL — the
        nullable ss_sold_date_sk FK must never be deleted by a date
        range (3-valued logic, unlike a complemented filter)."""
        sess = _session()
        col = sess.tables["store_sales"].column("ss_sold_date_sk")
        n_null = int((~col.null_mask).sum())
        assert n_null > 0
        sess.sql("delete from store_sales where ss_sold_date_sk >= 0")
        tbl = sess.tables["store_sales"]
        assert _nrows(sess, "store_sales") == n_null
        # every surviving (live) row has a NULL date
        live = delta.live_mask(tbl)
        col2 = tbl.column("ss_sold_date_sk")
        assert col2.null_mask is not None
        surviving_valid = col2.null_mask if live is None \
            else col2.null_mask[live]
        assert not surviving_valid.any()

    def test_delete_in_subquery(self):
        sess = _session()
        r = sess.sql(
            "select count(*) as c from store_returns where "
            "sr_ticket_number in (select distinct ss_ticket_number from "
            "store_sales, date_dim where ss_sold_date_sk=d_date_sk and "
            "d_date between '1998-02-01' and '1998-03-01')")
        expected = int(r.cols[0][0])
        n0 = sess.tables["store_returns"].nrows
        sess.sql(
            "delete from store_returns where sr_ticket_number in "
            "(select distinct ss_ticket_number from store_sales, "
            "date_dim where ss_sold_date_sk=d_date_sk and d_date "
            "between '1998-02-01' and '1998-03-01')")
        assert _nrows(sess, "store_returns") == n0 - expected

    def test_dml_invalidates_plan_cache(self):
        sess = _session()
        q = "select count(*) as c from store_sales"
        before = int(sess.sql(q).cols[0][0])
        sess.sql("delete from store_sales where ss_quantity > 0")
        after = int(sess.sql(q).cols[0][0])
        assert after < before

    def test_drop_view_requires_existence(self):
        sess = _session()
        sess.sql("drop view if exists nope")  # silent
        with pytest.raises(ValueError):
            sess.sql("drop view nope")

    def test_delete_decimal_literal_coercion(self):
        """WHERE money_col > 100 means $100, not 100 scaled cents."""
        sess = _session()
        r = sess.sql("select count(*) as c from store_sales "
                     "where ss_sales_price > 50.00")
        over_50_dollars = int(r.cols[0][0])
        n0 = sess.tables["store_sales"].nrows
        sess.sql("delete from store_sales where ss_sales_price > 50.00")
        assert _nrows(sess, "store_sales") == n0 - over_50_dollars

    def test_delete_date_string_literal_coercion(self):
        sess = _session(("date_dim",))
        n0 = sess.tables["date_dim"].nrows
        r = sess.sql("select count(*) as c from date_dim "
                     "where d_date >= '2000-01-01'")
        after = int(r.cols[0][0])
        sess.sql("delete from date_dim where d_date >= '2000-01-01'")
        assert _nrows(sess, "date_dim") == n0 - after

    def test_insert_rejects_trailing_statement(self):
        sess = _session()
        with pytest.raises(Exception, match="trailing"):
            sess.sql("insert into store_sales (select * from "
                     "store_sales); delete from store_sales")


@pytest.fixture(scope="module")
def warehouse(tmp_path_factory):
    root = tmp_path_factory.mktemp("maint")
    raw = str(root / "raw")
    wh = str(root / "wh")
    refresh = str(root / "refresh1")
    gen_data.generate_data_local(SF, 1, raw, workers=1)
    transcode.transcode(raw, wh, str(root / "load.txt"))
    gen_data.generate_refresh_data(SF, 1, refresh)
    return {"wh": wh, "refresh": refresh, "root": str(root)}


class TestMaintenanceRun:
    def test_full_maintenance_and_rollback(self, warehouse, tmp_path):
        from nds_tpu.nds.power import SUITE
        from nds_tpu.utils import power_core
        from nds_tpu.utils.config import EngineConfig

        cfg = EngineConfig(overrides={"engine.backend": "cpu"})

        def fact_counts():
            sess = power_core.make_session(SUITE, cfg)
            power_core.load_warehouse(
                SUITE, sess, warehouse["wh"],
                tables=maintenance.MUTABLE_TABLES)
            return {t: delta.visible_rows(sess.tables[t])
                    for t in maintenance.MUTABLE_TABLES}

        before = fact_counts()
        failures = maintenance.run_maintenance(
            warehouse["wh"], warehouse["refresh"],
            str(tmp_path / "dm.csv"), config=cfg,
            json_summary_folder=str(tmp_path / "json"))
        assert failures == 0
        after = fact_counts()
        # every channel changed: inserts extend history past the base
        # window, deletes remove a base window
        assert after != before
        # the delete windows are inside base history and the refresh
        # sets are small, so deletes dominate
        assert after["store_sales"] != before["store_sales"]
        assert after["inventory"] < before["inventory"]
        # inserted rows reference resolvable dimension SKs
        sess = power_core.make_session(SUITE, cfg)
        power_core.load_warehouse(SUITE, sess, warehouse["wh"],
                                  tables=["store_sales"])
        tn = sess.tables["store_sales"].column("ss_ticket_number").values
        assert (tn >= 1_000_000_000).any()
        # time log carries the Tdm row the orchestrator reads
        rows = open(str(tmp_path / "dm.csv")).read()
        assert "Data Maintenance Time" in rows
        # rollback restores the baseline
        from nds_tpu.nds.rollback import rollback
        rollback(warehouse["wh"], 0.0)
        assert fact_counts() == before

    def test_snapshot_log_versions(self, tmp_path):
        wh = str(tmp_path / "wh")
        os.makedirs(os.path.join(wh, "t1"))
        # fake baseline parquet
        import pyarrow as pa
        import pyarrow.parquet as pq
        pq.write_table(pa.table({"a": [1, 2]}),
                       os.path.join(wh, "t1", "part-0.parquet"))
        log = SnapshotLog(wh)
        v1dir = log.version_dir("t1", 1)
        pq.write_table(pa.table({"a": [1, 2, 3]}),
                       os.path.join(v1dir, "part-0.parquet"))
        log.commit({"t1": [os.path.relpath(
            os.path.join(v1dir, "part-0.parquet"), wh)]})
        cur = SnapshotLog(wh).current(["t1"])
        assert "_v1" in cur["t1"][0]
        SnapshotLog(wh).rollback_to_timestamp(0.0)
        cur = SnapshotLog(wh).current(["t1"])
        assert "_v1" not in cur["t1"][0]

    def test_date_substitution(self):
        sql = "where d_date between 'DATE1' and 'DATE2'"
        out = maintenance.replace_date(sql, "1998-01-01", "1998-01-31")
        assert "'1998-01-01'" in out and "DATE1" not in out

    def test_all_eleven_functions_ship(self):
        qs = maintenance.get_maintenance_queries(
            maintenance.INSERT_FUNCS + maintenance.DELETE_FUNCS
            + maintenance.INVENTORY_DELETE_FUNCS)
        assert len(qs) == 11
        for name, sql in qs.items():
            stmts = maintenance.statements(sql)
            assert stmts, name
            if name.startswith("LF_"):
                assert any("insert into" in s.lower() for s in stmts)
            else:
                assert any("delete from" in s.lower() for s in stmts)


def test_maintenance_functions_on_device_engine():
    """The LF_*/DF_* refresh SQL also runs through the TPU device
    engine (INSERT's SELECT executes on-device; DML mutation stays
    host-side and invalidates the executor)."""
    from nds_tpu.datagen import tpcds_refresh
    from nds_tpu.engine.device_exec import make_device_factory
    from nds_tpu.nds.schema import get_maintenance_schemas

    schemas = get_schemas()
    msch = get_maintenance_schemas()
    sess = Session.for_nds(make_device_factory(),
                           include_maintenance=True)
    for t in ("store_sales", "store_returns", "date_dim", "item",
              "customer", "store", "promotion", "time_dim", "reason"):
        sess.register_table(
            from_arrays(t, schemas[t], tpcds.gen_table(t, SF)))
    for t in ("s_purchase", "s_purchase_lineitem", "delete",
              "inventory_delete"):
        sess.register_table(from_arrays(
            t, msch[t], tpcds_refresh.gen_refresh_table(t, SF, 1)))
    n0 = sess.tables["store_sales"].nrows
    d1, d2, _i1, _i2 = maintenance.get_delete_date(sess)
    qs = maintenance.get_maintenance_queries(["LF_SS", "DF_SS"])
    maintenance.run_dm_query(sess, qs["LF_SS"])
    n1 = sess.tables["store_sales"].nrows
    assert n1 > n0, "device-engine LF_SS must insert rows"
    maintenance.run_dm_query(
        sess, maintenance.replace_date(qs["DF_SS"], d1, d2))
    assert delta.visible_rows(sess.tables["store_sales"]) < n1


@pytest.mark.slow
class TestDistributedBackend:
    """Maintenance + throughput drives through the `distributed` backend
    (VERDICT r3 "next" #7): DML and concurrent streams must work over
    the mesh executor, not only single-device."""

    def test_maintenance_distributed_backend(self, warehouse, tmp_path):
        from nds_tpu.utils.config import EngineConfig

        cfg = EngineConfig(overrides={"engine.backend": "distributed"})
        failures = maintenance.run_maintenance(
            warehouse["wh"], warehouse["refresh"],
            str(tmp_path / "dm_dist.csv"), config=cfg,
            commit=False)  # no_commit: the cpu test owns the warehouse
        assert failures == 0
        from nds_tpu.utils.timelog import TimeLog
        rows = {q: ms for _a, q, ms in TimeLog.read(
            str(tmp_path / "dm_dist.csv"))}
        assert "Data Maintenance Time" in rows
        assert sum(1 for q in rows if q.startswith(("LF_", "DF_"))) == 11

    def test_throughput_distributed_backend(self, warehouse, tmp_path):
        from nds_tpu.nds.streams import generate_query_streams
        from nds_tpu.nds.throughput import run_streams

        sdir = tmp_path / "streams"
        generate_query_streams(str(sdir), 3, rng_seed=11)  # query_0..2
        # truncate each stream to its first 3 queries: the test is the
        # concurrent distributed drive, not 99-query latency
        short = []
        for i in (1, 2):
            txt = (sdir / f"query_{i}.sql").read_text()
            parts = txt.split("-- start query")
            cut = "-- start query".join(parts[:4])
            p = tmp_path / f"short_{i}.sql"
            p.write_text(cut)
            short.append(str(p))
        elapse, codes = run_streams(
            warehouse["wh"], short, str(tmp_path / "tp"),
            backend="distributed")
        assert codes == [0, 0]
        assert elapse > 0
        logs = sorted(os.listdir(tmp_path / "tp"))
        assert [f for f in logs if f.endswith("_time.csv")], logs

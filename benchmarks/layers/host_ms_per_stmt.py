"""Host path per statement: the benchmark's own span round session.sql
minus the statement's execute phase (the pipeline's execute_ms, a host
bracket that ends in device_get), mean over the window's statements.
What is left is parse, plan, placement, dispatch bookkeeping and
materialize.  Source: program_span."""


def read(run):
    rows = [(r["end"] - r["start"]) * 1e3 - r["execute_ms"]
            for r in run["window"]["records"]
            if r.get("execute_ms") is not None and r["error"] is None]
    if not rows:
        return None
    return sum(rows) / len(rows)

"""Hash-partition exchange over ICI: the engine's shuffle operator.

This is the component the reference leaves entirely to Spark's
block-based shuffle (config-only, `spark.sql.shuffle.partitions`,
SURVEY.md §2.6): here it is first-class and TPU-native — rows hash to a
destination device and move in ONE `lax.all_to_all` across the mesh axis
(ICI within a pod, DCN across slices; XLA picks the transport).

Static-shape contract: each device sends a fixed-capacity bucket of
``ceil(min(n, rows) * slack / n_dev)`` rows to every peer, where ``n``
is the local buffer's capacity and ``rows`` the static bound the
relation carries on how many rows a device holds of it (``bucket_for``;
``rows`` None: the capacity). A hash partition moves rows and makes
none, so a relation that has been through an exchange still holds
``rows`` a device, in a buffer ``slack`` times as large: sized from
``rows``, k exchanges in a row leave capacity ``slack x rows``, not
``slack**k x rows``. Hash partitioning spreads keys uniformly, so
slack=2 covers real skew; rows that overflow a bucket are dropped AND
counted — the executor surfaces the count so the host can retry with a
bigger slack (adaptive, one recompile, never silent).

How the send buffer is filled: ONE stable int32 sort groups the rows by
destination (dead rows last), so slot ``r`` of peer ``d``'s bucket is
row ``order[bounds[d] + r]`` while ``r < min(count[d], bucket)``
(``bounds``: where each destination's rows start in the sorted order).
One int32 index array a shuffle (``src``, shape ``(n_dev, bucket)``)
says so, and every payload column is READ into place by one gather
through it, zeros behind the kept rows. Nothing is scattered: on the TPU
an ``at[].set`` of a 64-bit column costs 80 ns an update, a gather 6-10
ns a 32-bit word (PERF.md section 5).
"""

from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from nds_tpu.obs import metrics as obs_metrics
from nds_tpu.parallel.mesh import DATA_AXIS

# trace-time collector: while a sink is active (the distributed
# executor's program build opens one around run_query), every
# exchange_by_dest appends its per-shuffle destination-skew ratio
# (max/mean destination rows, a TRACED scalar) so the program can
# return the worst skew alongside the overflow count and the executor
# can publish the ``exchange_skew_ratio`` gauge host-side, and adds
# what the shuffle moves (static, from its shapes) to the program's
# totals. NOT a debug callback on purpose: callback-bearing executables
# cannot serialize into the persistent AOT plan cache (PyCapsule
# pickling).
_SINK: "ExchangeTrace | None" = None


class ExchangeTrace:
    """What one program's trace says of its exchanges: the traced skew
    scalars, and five static totals. ``rows`` is the bucket capacity a
    chip sends (``n_dev x bucket``) and ``nbytes`` the bytes it hands
    to ``all_to_all`` (every payload array and the ``ok`` mask at that
    capacity), each summed over the program's exchanges: what ONE chip
    moves in ONE run of the program. ``resized`` counts the exchanges
    whose bucket is smaller than the buffer's capacity would make it:
    sized from the relation's row bound. ``send_words`` is the 32-bit
    words a chip GATHERS into its send buffers: every payload array at
    that capacity, and the one int32 index array a shuffle they are
    read through."""

    def __init__(self):
        self.skews: list = []
        self.count = self.resized = self.rows = self.nbytes = 0
        self.send_words = 0

    def stats(self) -> dict:
        """The ``device.launch`` attributes of a sharded program."""
        return {"exchanges": self.count, "resized": self.resized,
                "exchange_rows": self.rows, "exchange_bytes": self.nbytes,
                "send_words": self.send_words}


@contextlib.contextmanager
def exchange_trace():
    """Collect the exchanges traced during one program build."""
    global _SINK
    prev, _SINK = _SINK, ExchangeTrace()
    try:
        yield _SINK
    finally:
        _SINK = prev


def _in_exchange_scope(fn):
    """Trace ``fn`` in the scope ``exchange``: the hash, the
    destination sort, the send-buffer gathers and the ``all_to_all``
    of a shuffle are named so in the compiled program, where the
    profile's op events are read by mechanism (README
    "Observability")."""
    @functools.wraps(fn)
    def scoped(*args, **kw):
        with jax.named_scope("exchange"):
            return fn(*args, **kw)
    return scoped


def _mix64(x):
    """splitmix64 finalizer: avalanche int64 keys before bucketing (raw
    TPC keys are sequential — modulo alone would stripe, not spread)."""
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> 27)) * jnp.uint64(0x94D049BB133111EB)
    x = x ^ (x >> 31)
    return x


def hash_columns(columns: list):
    """One int64 routing key per row from several key columns, for a
    key too wide to pack: ``columns`` are (values, validity or None)
    pairs of integer (or dictionary-code) arrays, values already 0
    under NULL. Equal rows get equal keys; unequal rows may collide,
    which costs balance and nothing else (the aggregate behind the
    exchange compares the columns themselves)."""
    acc = None
    for arr, valid in columns:
        x = arr.astype(jnp.int64).astype(jnp.uint64)
        if valid is not None:
            # NULL and 0 are different keys
            x = (x << jnp.uint64(1)) | (~valid).astype(jnp.uint64)
        acc = _mix64(x if acc is None else acc ^ x)
    return acc.astype(jnp.int64)


def bucket_for(n: int, rows: "int | None", slack: float,
               n_dev: int) -> int:
    """Per-peer bucket of an exchange of a buffer of capacity ``n``
    that holds at most ``rows`` rows a device (None: not known, the
    capacity)."""
    live = n if rows is None else min(n, rows)
    return max(1, int(-(-live * slack // n_dev)))


@_in_exchange_scope
def exchange(arrays: list, key, ok, n_dev: int, slack: float = 2.0,
             axis: str = DATA_AXIS, rows: "int | None" = None):
    """Repartition rows by hash(key) across the mesh axis.

    arrays: per-row payload arrays (local shard). key: int64 per row.
    ok: bool per row (invalid rows don't travel). rows: the static
    bound on the rows a device holds (``bucket_for``).
    Returns (out_arrays, out_ok, overflow_count) where out_* have
    capacity n_dev * bucket ( = min(local_n, rows) * slack rounded up).
    """
    dest = (_mix64(key) % jnp.uint64(n_dev)).astype(jnp.int32)
    return exchange_by_dest(
        arrays, dest, ok, n_dev, slack, axis,
        bucket=bucket_for(dest.shape[0], rows, slack, n_dev))


@_in_exchange_scope
def exchange_by_dest(arrays: list, dest, ok, n_dev: int,
                     slack: float = 2.0, axis: str = DATA_AXIS,
                     bucket: int | None = None):
    """Exchange core routed by an explicit per-row destination index in
    [0, n_dev) along ``axis`` (the hierarchical DCN/ICI exchange routes
    each stage with a different destination derivation). ``bucket``
    overrides the per-peer capacity: the caller's ``bucket_for`` of the
    relation's row bound, where the buffer's length says more slots
    than rows (it came through an exchange)."""
    n = dest.shape[0]
    # chaos site (trace time, like the counter below): an injected
    # fault here surfaces during compile, where the executor's retry
    # loop classifies and handles it like a real capacity failure
    from nds_tpu.resilience import faults, watchdog
    faults.fault_point("exchange", n_dev=n_dev)
    # trace-time heartbeat: big multi-exchange programs show liveness
    # to the hang watchdog per exchange traced, not just per query
    watchdog.beat("engine", phase="exchange")
    # trace-time count: how many exchange ops the compiled programs
    # contain (runtime executions multiply by program runs; in-program
    # counting would cost a collective per query for a vanity number)
    obs_metrics.counter("exchanges_traced_total").inc()
    by_capacity = bucket_for(n, None, slack, n_dev)
    if bucket is None:
        bucket = by_capacity
    # dead rows get a sentinel dest PAST every real bucket so they never
    # take a slot (a heavily filtered shard must not overflow its own
    # bucket with corpses) and sort behind every live row
    dest = jnp.where(ok, dest, jnp.int32(n_dev))
    # stable-group rows by destination, and keep BOTH outputs: the
    # sorted key is read below, never re-gathered through the
    # permutation. Explicit int32 iota operand: jnp.argsort would carry
    # an int64 index operand under x64, pushing the whole
    # shuffle-grouping sort onto the TPU's emulated 64-bit path (NDS112
    # — same trap as device_exec._build_lookup)
    iota = jnp.arange(n, dtype=jnp.int32)
    dest_s, order = lax.sort([dest, iota], num_keys=1, is_stable=True)
    # per-destination fenceposts: [:-1] are the rows each bucket starts
    # at, the differences the per-destination row COUNTS (overflow, the
    # skew gauge). bounds[-1] counts the live rows
    bounds = jnp.searchsorted(dest_s,
                              jnp.arange(n_dev + 1, dtype=jnp.int32))
    counts = bounds[1:] - bounds[:-1]
    kept = jnp.minimum(counts, bucket)
    n_overflow = jnp.sum(counts - kept)
    if _SINK is not None:
        capacity = n_dev * bucket
        widths = [a.dtype.itemsize * math.prod(a.shape[1:]) for a in arrays]
        _SINK.count += 1
        _SINK.resized += bucket < by_capacity
        _SINK.rows += capacity
        _SINK.nbytes += capacity * (1 + sum(widths))   # 1: the bool ok mask
        _SINK.send_words += capacity * (               # 1: the index array
            1 + sum(-(-w // 4) for w in widths))
        # partition-skew visibility (README "Fleet & profiling"):
        # max/mean live rows per destination for THIS shuffle — the
        # signal that a key distribution is loading one device before
        # it becomes a straggler
        total = bounds[-1].astype(jnp.float32)
        ratio = jnp.where(
            total > 0,
            jnp.max(counts).astype(jnp.float32)
            / jnp.maximum(total / n_dev, 1e-9),
            jnp.float32(1.0))
        _SINK.skews.append(ratio)
    # slot r of peer d's bucket holds sorted row bounds[d] + r while
    # r < kept[d]: the one gather through the sort's permutation, int32,
    # once a shuffle. Slots past the kept rows read some row in bounds
    # (clipped) and are zeroed; rows ranked at or past the bucket are
    # the overflow counted above, read by no slot
    r = jnp.arange(bucket, dtype=jnp.int32)[None, :]
    live = r < kept[:, None]
    with jax.named_scope("gather"):
        src = jnp.take(order, bounds[:-1, None] + r, mode="clip")
    out_ok = lax.all_to_all(live, axis, 0, 0).reshape(-1)
    outs = []
    for a in arrays:
        with jax.named_scope("gather"):
            sent = jnp.where(live, jnp.take(a, src, axis=0),
                             jnp.zeros((), a.dtype))
        outs.append(lax.all_to_all(sent, axis, 0, 0).reshape(-1))
    return outs, out_ok, n_overflow


@_in_exchange_scope
def exchange_hierarchical(arrays: list, key, ok, n_hosts: int,
                          n_lanes: int, slack: float = 2.0,
                          host_axis: str = "h",
                          lane_axis: str = DATA_AXIS,
                          key_index: int | None = None,
                          rows: "int | None" = None):
    """Two-stage shuffle for multi-host meshes (SURVEY.md §7 hard part
    4: the ICI-instead-of-UCX deliverable at DCN scale): rows first move
    to their destination HOST over the ``host_axis`` (DCN — one
    all_to_all of host-sized buckets, minimizing cross-slice bytes),
    then to their destination LANE over ``lane_axis`` (ICI within the
    slice). The destination device for a key is stable:
    g = hash(key) % (hosts * lanes); host = g // lanes; lane = g %
    lanes — so downstream grouped operators see the same colocation
    contract as the flat 1-D exchange.

    Both stages size their buckets from ``rows``, the static bound on
    the rows a device holds (``bucket_for``; None: the input's
    capacity), never from the padded length a stage was handed —
    otherwise every downstream operator pays n * slack^2.

    Returns (out_arrays, out_ok, overflow_count) with the overflow
    counts of both stages summed (the executor's retry-with-bigger-slack
    loop treats them uniformly).
    """
    n = key.shape[0]
    g = (_mix64(key) % jnp.uint64(n_hosts * n_lanes)).astype(jnp.int32)
    dest_h = g // n_lanes
    # stage 1 (DCN): deliver rows to the right host. When the caller's
    # payload already carries the key (key_index), reuse it for stage 2
    # instead of shipping a second copy over the cross-slice link
    payload = list(arrays)
    appended = key_index is None
    if appended:
        payload = payload + [key]
        key_index = len(payload) - 1
    outs1, ok1, over1 = exchange_by_dest(
        payload, dest_h, ok, n_hosts, slack, host_axis,
        bucket=bucket_for(n, rows, slack, n_hosts))
    key1 = outs1[key_index]
    # stage 2 (ICI): recompute the lane from the carried key
    g1 = (_mix64(key1) % jnp.uint64(n_hosts * n_lanes)).astype(jnp.int32)
    dest_d = g1 % n_lanes
    outs2, ok2, over2 = exchange_by_dest(
        outs1[:-1] if appended else outs1, dest_d, ok1, n_lanes, slack,
        lane_axis, bucket=bucket_for(n, rows, slack, n_lanes))
    return outs2, ok2, over1 + over2

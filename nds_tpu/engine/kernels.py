"""TPU-shaped relational kernels + the planner's kernel-selection pass.

The static-shape engine's original operators fight the hardware in three
places the BENCH_r02 traces point at (ROADMAP item 2): every gather join
pays a full-table ``lax.sort`` + ``searchsorted`` probe even when the
build side is a small dimension table with host-known key bounds; every
EXISTS chain (q21/q22) runs the same sort machinery just to answer a
membership question; and grouped min/max lower to ``segment_min/max``
scatters, which XLA emulates element-at-a-time for 64-bit operands on
TPU. This module is the TQP-style answer ("Query Processing on Tensor
Computation Runtimes", PAPERS.md): reformulate the hot operators as
dense gathers, one-hot matmuls that ride the MXU, radix-partitioned
batched sorts, and segmented scans that ride the VPU.

Kernel catalog (selection rules in ``annotate``; README "Kernels &
roofline"):

- ``direct``       unique-build equi-join as a dense direct-address
                   table over the key domain: build = one scatter,
                   probe = one gather. Replaces sort+searchsorted when
                   host key bounds give a domain comparable to the
                   build cardinality (true for every NDS surrogate-key
                   dimension).
- ``matmul``       one-hot equality formulated as an f32 matmul so tiny
                   build sides (region/nation-class) probe on the MXU.
- ``partitioned``  M:N expanding join with on-device radix
                   partitioning: both sides scatter into R hash
                   partitions, per-partition sorts run BATCHED (one
                   ``lax.sort`` over an (R, cap) block sorts all
                   partitions at once at n/R sort depth), probes and
                   expansion stay per-partition. The q21-class
                   large-by-large answer.
- ``bitmask``      semi/anti joins as membership bitmaps (EXISTS) or
                   dense per-key min/max tables (EXISTS with the q21
                   ``<>`` residual) instead of gather joins.
- ``segscan``      grouped min/max as a segmented scan over the
                   already-sorted group ids + a gather at segment ends
                   (sum/count/avg were already scan-based): no scatter
                   anywhere in the grouped-aggregation path, and the
                   one group sort is amortized across every AggSpec of
                   the node.
- ``dense``        (no plan stamp: the trace's own choice) a GROUP BY
                   whose key domains the host knows, at or under
                   ``DENSE_AGG_MAX_GROUPS`` slots, is one masked
                   reduction a slot and aggregate: no group sort at all.

The SELECTION is a planning-time decision: ``annotate`` walks a planned
tree and stamps ``node.kernel`` on Join/SemiJoin/Aggregate nodes from
the same catalog size statistics the scheduler's cost model uses
(``plan_verify.estimate_plan``). The choice is recorded IN the plan
(a dataclass field), so ``cache.fingerprint.canonical`` folds it into
the AOT fingerprint for free — two plans differing only in kernel
choice can never collide on one compiled program. The trace validates
feasibility at compile time (host bounds present, domain small enough)
and falls back to the sort path otherwise; the kernel actually USED is
counted per query and lands in the BenchReport ``kernels`` block, which
``ndsreport diff`` watches for silent demotions.

jax is imported lazily inside the device kernels: ``annotate`` and the
selection rules must stay importable on bare CPU (tools/ndsverify.py
plans and verifies the whole workload with no accelerator).
"""

from __future__ import annotations

import os

import numpy as np

from nds_tpu.sql import plan as P

# ------------------------------------------------------------ selection

# Join kernels (Join.kernel). "" = unannotated: legacy trace heuristics.
JOIN_SORT = "sortmerge"
JOIN_DIRECT = "direct"
JOIN_MATMUL = "matmul"
JOIN_PARTITIONED = "partitioned"
JOIN_KERNELS = ("", JOIN_SORT, JOIN_DIRECT, JOIN_MATMUL,
                JOIN_PARTITIONED)

# SemiJoin kernels
SEMI_SORT = "sortmerge"
SEMI_BITMASK = "bitmask"
SEMI_KERNELS = ("", SEMI_SORT, SEMI_BITMASK)

# Aggregate kernels
AGG_SEGSCAN = "segscan"
AGG_SCATTER = "scatter"
AGG_KERNELS = ("", AGG_SEGSCAN, AGG_SCATTER)

# builds at or below this many estimated rows probe via one-hot matmul
# (the equality matrix is (probe x build); 64 keeps it a thin MXU tile
# even against multi-million-row probes)
MATMUL_MAX_BUILD = 64
# largest dense direct-address table the trace will materialize
# (entries, not bytes: int32 -> 32 MiB at the cap)
DIRECT_MAX_DOMAIN = 1 << 23
# the dense table may be at most this many times larger than the build
# capacity — beyond it the scatter/gather wins are eaten by the
# table's own HBM traffic (surrogate keys are near-dense, ratio ~1-4)
DIRECT_DOMAIN_FACTOR = 16
# most group slots a GROUP BY may have for the trace to aggregate it
# densely (`_Trace._run_aggregate_dense`: one masked reduction a slot
# and aggregate, so its cost grows as slots x aggregates x rows) and
# not through the group sort (whose cost does not grow with the
# slots). The largest power of two at which q1's aggregates (four
# int64 sums, three float64 avgs, a count) took at most half the
# sorted form's time on a v5e, at 6.0M and at 30.0M rows: 286 against
# 962 ms and 2,019 against 6,418 ms (8 slots: 8.6 and 31 ms; twice as
# many slots cost twice the time from 512 up; PERF.md section 6, PR 32)
DENSE_AGG_MAX_GROUPS = 1024
# both sides of an M:N join must estimate at least this many rows for
# radix partitioning to beat one flat sort
PARTITION_MIN_ROWS = 1 << 16
# radix partition count (power of two; per-partition sort depth drops
# by log2(NPART) and all NPART sorts run as ONE batched lax.sort)
NPART = 8

ENV_FLAG = "NDS_TPU_KERNELS"


def kernels_enabled() -> bool:
    """Kill switch: NDS_TPU_KERNELS=0 leaves every plan unannotated so
    the legacy sort-based paths serve everything (A/B runs, ndsperf's
    "old" lane)."""
    return os.environ.get(ENV_FLAG, "1") not in ("0", "off")


# scan-filter selectivity guess per conjunct for the row estimator —
# only drives kernel thresholds, never correctness (the trace
# re-validates feasibility against real bounds at compile time)
_FILTER_SEL = 0.4


def _est_rows(node: P.Node, sizes: dict, memo: dict) -> float:
    """Planning-time row estimate per node, from the catalog's relative
    size statistics (the estimate_plan source the scheduler cost model
    already uses). Deterministic; coarse is fine — thresholds are
    order-of-magnitude decisions."""
    nid = id(node)
    if nid in memo:
        return memo[nid]
    # ndslint: waive[NDS101] -- memo lives for one annotate() pass over a live plan
    memo[nid] = 1.0  # cycle guard
    if isinstance(node, P.Scan):
        rows = float(sizes.get(node.table, 1000.0))
        rows *= _FILTER_SEL ** min(len(node.filters), 3)
    elif isinstance(node, P.Join):
        lr = _est_rows(node.left, sizes, memo)
        rr = _est_rows(node.right, sizes, memo)
        rows = lr if node.right_unique else max(lr, rr) * 2.0
        if node.kind in ("left", "full"):
            rows = lr + rr if node.kind == "full" else max(lr, rows)
    elif isinstance(node, P.SemiJoin):
        rows = _est_rows(node.left, sizes, memo)
    elif isinstance(node, P.SetOp):
        rows = (_est_rows(node.left, sizes, memo)
                + _est_rows(node.right, sizes, memo))
    elif isinstance(node, P.Aggregate):
        rows = _est_rows(node.child, sizes, memo)
    elif isinstance(node, P.Limit):
        rows = float(min(node.count,
                         _est_rows(node.child, sizes, memo)))
    elif isinstance(node, P.Filter):
        rows = _est_rows(node.child, sizes, memo) * _FILTER_SEL
    else:
        child = getattr(node, "child", None)
        rows = (_est_rows(child, sizes, memo)
                if isinstance(child, P.Node) else 1000.0)
    rows = max(rows, 1.0)
    # ndslint: waive[NDS101] -- memo lives for one annotate() pass over a live plan
    memo[nid] = rows
    return rows


def select_join_kernel(left_rows: float, right_rows: float,
                       right_unique: bool, kind: str) -> str:
    """The selection rule for one Join node (README documents it):
    unique builds go matmul (tiny) or direct (everything else — the
    trace demotes to sortmerge when bounds/domain disqualify); M:N
    inner joins go partitioned when both sides are large enough to
    amortize the radix scatter."""
    if right_unique:
        if right_rows <= MATMUL_MAX_BUILD:
            return JOIN_MATMUL
        return JOIN_DIRECT
    if (kind == "inner"
            and min(left_rows, right_rows) >= PARTITION_MIN_ROWS):
        return JOIN_PARTITIONED
    return JOIN_SORT


def annotate(planned, catalog=None) -> None:
    """Stamp a kernel choice on every Join/SemiJoin/Aggregate of a
    planned statement (in place; nodes already carrying an explicit
    choice are left alone). Called by the planner at the end of
    ``plan_statement``; a disabled env flag leaves plans untouched."""
    if not kernels_enabled():
        return
    if not isinstance(planned, P.PlannedQuery):
        return
    sizes = dict(getattr(catalog, "sizes", None) or {})
    memo: dict = {}
    for root in [planned.root, *planned.scalar_subplans]:
        if not isinstance(root, P.Node):
            continue
        for node in P.walk_plan(root):
            if isinstance(node, P.Join) and not node.kernel:
                node.kernel = select_join_kernel(
                    _est_rows(node.left, sizes, memo),
                    _est_rows(node.right, sizes, memo),
                    node.right_unique, node.kind)
            elif isinstance(node, P.SemiJoin) and not node.kernel:
                node.kernel = SEMI_BITMASK
            elif isinstance(node, P.Aggregate) and not node.kernel:
                node.kernel = AGG_SEGSCAN


def domain_of(lo, hi) -> "int | None":
    """Dense-table entry count for host key bounds, or None when the
    bounds are unusable (unknown, or too wide to enumerate)."""
    if lo is None or hi is None:
        return None
    dom = int(hi) - int(lo) + 1
    if dom < 1 or dom > DIRECT_MAX_DOMAIN:
        return None
    return dom


def direct_feasible(dom: "int | None", build_capacity: int) -> bool:
    """Whether a dense direct-address table of ``dom`` entries is worth
    building for a ``build_capacity``-slot build side (trace-time
    check; a False here demotes the node to the sort path and the
    demotion is visible in the per-query kernel counts)."""
    if dom is None:
        return False
    return dom <= max(build_capacity, 1) * DIRECT_DOMAIN_FACTOR


# -------------------------------------------------------- join kernels
#
# All device kernels import jax lazily (module docstring: annotate()
# must run accelerator-free) and are pure traced functions — no state,
# no host round trips; the caller owns capacity/overflow policy.

def take(arr, idx, **kw):
    """``jnp.take`` in the ``gather`` scope, where the profile's op
    events are read by mechanism (README "Observability")."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("gather"):
        return jnp.take(arr, idx, **kw)


def direct_lookup_join(bkey, bok, pkey, pok, lo: int, dom: int):
    """Unique-build equi-join via a dense direct-address table.

    Build: scatter each valid build row's index at ``key - lo`` (unique
    keys guarantee no collision among valid rows). Probe: one gather.
    Returns ``(ridx, hit)`` with the same contract as the sort path's
    ``_probe`` — ``ridx`` clamped to a valid row wherever ``hit`` is
    False."""
    import jax.numpy as jnp
    n_build = bkey.shape[0]
    slots = (bkey.astype(jnp.int64) - lo).astype(jnp.int32)
    iota = jnp.arange(n_build, dtype=jnp.int32)
    tbl = jnp.full((dom,), -1, jnp.int32)
    # invalid build rows route to the out-of-range slot and drop
    tbl = tbl.at[jnp.where(bok, slots, dom)].set(iota, mode="drop")
    pos = pkey.astype(jnp.int64) - lo
    inb = (pos >= 0) & (pos < dom)
    ridx = take(tbl, jnp.clip(pos, 0, dom - 1).astype(jnp.int32))
    hit = pok & inb & (ridx >= 0)
    return jnp.maximum(ridx, 0), hit


def matmul_probe_join(bkey, bok, pkey, pok):
    """Unique-build equi-join as a one-hot matmul (TQP formulation):
    the (probe x build) equality matrix contracts against the build
    iota on the MXU. Build sides are capped tiny (MATMUL_MAX_BUILD), so
    the matrix is a thin tile against any probe length. f32 is exact
    for indices < 2^24, far above the cap."""
    import jax.numpy as jnp
    n_build = bkey.shape[0]
    eq = (pkey[:, None] == bkey[None, :]) & bok[None, :]
    eqf = eq.astype(jnp.float32)
    iota = jnp.arange(n_build, dtype=jnp.float32)
    ridx = jnp.dot(eqf, iota).astype(jnp.int32)
    hit = pok & (jnp.dot(eqf, jnp.ones((n_build,), jnp.float32)) > 0)
    return jnp.clip(ridx, 0, n_build - 1), hit


def bitmask_semi(bkey, bok, pkey, pok, lo: int, dom: int):
    """EXISTS / NOT EXISTS membership as a dense bitmap: build scatters
    True at each valid key slot, probe is one gather. Returns the
    per-probe-row ``exists`` mask (the caller negates for anti)."""
    import jax.numpy as jnp
    slots = (bkey.astype(jnp.int64) - lo).astype(jnp.int32)
    bm = jnp.zeros((dom,), bool)
    bm = bm.at[jnp.where(bok, slots, dom)].set(True, mode="drop")
    pos = pkey.astype(jnp.int64) - lo
    inb = (pos >= 0) & (pos < dom)
    member = take(bm, jnp.clip(pos, 0, dom - 1).astype(jnp.int32))
    return pok & inb & member


def keyed_minmax_semi(bkey, bok, bval, pkey, pok, pval, lo: int,
                      dom: int):
    """EXISTS with the q21 ``<>`` residual, dense formulation: exists a
    build row with this key and a DIFFERENT value  <=>  the per-key
    [min, max] of the build values is not exactly [pval, pval].
    Scatter-min/max into domain-sized tables replaces the 2-key
    whole-table sort + 2 searchsorteds of the sort path."""
    import jax.numpy as jnp
    slots = jnp.where(bok, (bkey.astype(jnp.int64) - lo), dom).astype(
        jnp.int32)
    vmax = jnp.iinfo(bval.dtype).max
    vmin = jnp.iinfo(bval.dtype).min
    mn = jnp.full((dom,), vmax, bval.dtype).at[slots].min(
        bval, mode="drop")
    mx = jnp.full((dom,), vmin, bval.dtype).at[slots].max(
        bval, mode="drop")
    present = jnp.zeros((dom,), bool).at[slots].set(True, mode="drop")
    pos = pkey.astype(jnp.int64) - lo
    inb = (pos >= 0) & (pos < dom)
    at = jnp.clip(pos, 0, dom - 1).astype(jnp.int32)
    has_key = pok & inb & take(present, at)
    differs = ((take(mn, at) != pval) | (take(mx, at) != pval))
    return has_key & differs


def _pids(key, log2r: int):
    """Radix partition id from the key's low 32 bits via a Knuth
    multiplicative hash — equal keys always co-locate, which is the
    only property partitioning needs."""
    import jax.numpy as jnp
    if log2r == 0:
        return jnp.zeros(key.shape, jnp.int32)
    u = key.astype(jnp.uint32) * jnp.uint32(2654435761)
    return (u >> jnp.uint32(32 - log2r)).astype(jnp.int32)


def _radix_scatter(key, ok, nparts: int, cap: int, log2r: int):
    """Scatter one side into (nparts, cap) partition blocks. Returns
    (keys, gidx, ok, overflow): per-slot key (sentinel-filled), source
    row index, occupancy, and the count of rows dropped because their
    partition overflowed ``cap`` (the caller's slack retry grows it)."""
    import jax.numpy as jnp
    n = key.shape[0]
    pid = _pids(key, log2r)
    oh = ((pid[:, None] == jnp.arange(nparts, dtype=jnp.int32)[None, :])
          & ok[:, None])
    ohi = oh.astype(jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(ohi, axis=0),
                               pid[:, None], axis=1)[:, 0] - 1
    counts = jnp.sum(ohi, axis=0)
    okc = ok & (rank < cap)
    dest = jnp.where(okc, pid * cap + rank, nparts * cap)
    sent = jnp.iinfo(key.dtype).max
    keys = jnp.full((nparts * cap,), sent, key.dtype).at[dest].set(
        key, mode="drop")
    gidx = jnp.zeros((nparts * cap,), jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    occ = jnp.zeros((nparts * cap,), bool).at[dest].set(
        True, mode="drop")
    over = jnp.sum(jnp.maximum(counts - cap, 0))
    return (keys.reshape(nparts, cap), gidx.reshape(nparts, cap),
            occ.reshape(nparts, cap), over)


def partitioned_mn_join(lkey, lok, rkey, rok, out_capacity: int,
                        part_slack: float, nparts: int = NPART):
    """Radix-partitioned M:N expanding inner join.

    Both sides scatter into ``nparts`` hash partitions (equal keys
    co-locate), the build partitions sort as ONE batched ``lax.sort``
    over the (nparts, cap) block — per-partition sort depth is
    log(n/nparts), and the probe searchsorteds batch the same way —
    then the match-range expansion runs per partition at capacity
    ``out_capacity / nparts``. Returns ``(lidx, ridx, present,
    overflow)`` flattened to ``nparts * ceil(out_capacity / nparts)``
    slots; ``overflow`` counts both partition-capacity and
    output-capacity misses so the executor's doubled-slack retry
    (which grows ``part_slack`` and ``out_capacity`` together) covers
    skew the hash didn't balance."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    log2r = max(nparts.bit_length() - 1, 0)
    nl, nr = lkey.shape[0], rkey.shape[0]
    lcap = max(-(-int(nl * part_slack) // nparts), 1)
    rcap = max(-(-int(nr * part_slack) // nparts), 1)
    lk_p, lg_p, lok_p, lover = _radix_scatter(lkey, lok, nparts, lcap,
                                              log2r)
    rk_p, rg_p, rok_p, rover = _radix_scatter(rkey, rok, nparts, rcap,
                                              log2r)
    # batched per-partition build sort: sentinel-filled empty slots
    # sort to the tail exactly like _build_lookup's masked rows
    ks, gs = lax.sort([lk_p, lg_p], num_keys=1, is_stable=True)
    # ndslint: waive[NDS112] -- probe keys inherit the caller's width (narrowed by _join_key_arrays when bounds allow); wider packs need the 64-bit operand
    ss_l = jax.vmap(lambda a, q: jnp.searchsorted(a, q, side="left",
                                                  method="sort"))
    # ndslint: waive[NDS112] -- same operands as ss_l above
    ss_r = jax.vmap(lambda a, q: jnp.searchsorted(a, q, side="right",
                                                  method="sort"))
    lo_i = ss_l(ks, rk_p)
    hi_i = ss_r(ks, rk_p)
    # match counts accumulate in int64 like the legacy M:N path: a
    # skewed partition can expand past 2^31 pairs, and an int32 cumsum
    # wrap would corrupt present/offsets AND zero the overflow count,
    # defeating the doubled-slack retry. Only the clamped offsets
    # narrow to int32 (order-preserving for every slot < kp)
    cnt = jnp.where(rok_p, (hi_i - lo_i).astype(jnp.int64), 0)
    offs = jnp.cumsum(cnt, axis=1)
    total = offs[:, -1]
    kp = max(-(-out_capacity // nparts), 1)
    slots = jnp.arange(kp, dtype=jnp.int32)
    offsc = jnp.minimum(offs, kp + 1).astype(jnp.int32)
    # ndslint: waive[NDS112] -- both operands (offsc, slots) are int32 by construction two lines up
    rloc = jax.vmap(lambda o: jnp.searchsorted(o, slots, side="right",
                                               method="sort"))(offsc)
    rloc = jnp.clip(rloc, 0, rcap - 1)
    prev = jnp.where(rloc > 0,
                     jnp.take_along_axis(offsc,
                                         jnp.maximum(rloc - 1, 0),
                                         axis=1),
                     0)
    within = slots[None, :] - prev
    lpos = jnp.clip(jnp.take_along_axis(lo_i, rloc, axis=1) + within,
                    0, lcap - 1)
    lidx = jnp.take_along_axis(gs, lpos, axis=1)
    ridx = jnp.take_along_axis(rg_p, rloc, axis=1)
    present = slots[None, :] < jnp.minimum(total, kp)[:, None]
    overflow = (jnp.sum(jnp.maximum(total - kp, 0)).astype(jnp.int64)
                + lover.astype(jnp.int64) + rover.astype(jnp.int64))
    return (lidx.reshape(-1), ridx.reshape(-1), present.reshape(-1),
            overflow)


# ------------------------------------------------- aggregation kernels

def seg_scan(op, vals, flags):
    """Segmented inclusive scan: restart ``op`` accumulation at every
    True flag. Classic (value, reset-flag) associative combiner —
    O(n log n) on the VPU via ``lax.associative_scan``. (Moved here
    from device_exec so every segmented kernel shares one
    implementation.)"""
    from jax import lax

    def comb(a, b):
        av, af = a
        bv, bf = b
        import jax.numpy as jnp
        return jnp.where(bf, bv, op(av, bv)), af | bf

    out, _ = lax.associative_scan(comb, (vals, flags))
    return out


def seg_reduce_at_ends(op, data, gid, starts2):
    """Grouped reduction over SORTED group ids with no scatter: a
    segmented scan carries the running reduction, and each group's
    value is the scan at its last row (``starts2`` = first sorted row
    per group, n past the last group — the same array ``_seg_sum``
    differences its cumsum at). Rows outside any group must carry the
    op's identity in ``data``."""
    import jax.numpy as jnp
    n = data.shape[0]
    first = jnp.concatenate(
        [jnp.ones(1, bool), gid[1:] != gid[:-1]])
    run = seg_scan(op, data, first)
    nxt = jnp.concatenate(
        [starts2[1:], jnp.full((1,), n, starts2.dtype)])
    end = jnp.clip(nxt - 1, 0, n - 1)
    return take(run, end)


def part_reduce_broadcast(op, data, part_start, pend):
    """Per-row whole-partition reduction for window functions: the
    segmented scan's value at the partition's LAST row (``pend``,
    already per-row) broadcast back — replaces the ``segment_min/max``
    scatter + gather pair."""
    import jax.numpy as jnp
    run = seg_scan(op, data, part_start)
    return take(run, pend)


def last_of_group(change, n: int):
    """Index of the last row of each row's group, for sorted group
    ``change`` flags (True at each group's first row): a reversed
    running-min over future change positions, no scatter."""
    import jax.numpy as jnp
    from jax import lax
    iota = jnp.arange(n, dtype=jnp.int32)
    chg_at = jnp.where(change, iota, n)
    future = jnp.concatenate(
        [chg_at[1:], jnp.full((1,), n, jnp.int32)])
    nxt = lax.cummin(future, reverse=True)
    return jnp.clip(nxt - 1, 0, n - 1)


# ------------------------------------------------------ buffer donation

def donate_jit(fn, argnums):
    """``jax.jit`` with buffer donation for single-use inputs (the
    chunked phase-A chunk buffers; the result compactor's masked
    full-capacity arrays) so intermediate columns stop double-buffering
    (SNIPPETS [1]/[2] ``donate_argnums``). NDS_TPU_DONATE=0 disables.

    Donation only engages on accelerator backends: on CPU,
    ``jnp.asarray`` of a host numpy view is ZERO-COPY, so a donated
    input buffer can alias a live HostTable column and XLA's in-place
    reuse would scribble over the warehouse itself (observed: a
    donated chunk-scan corrupted ``sales`` for every later query of
    the process). On TPU/GPU the upload is always a device copy, the
    aliasing hazard cannot exist, and HBM residency is the thing worth
    halving. NDS_TPU_DONATE=force overrides for aliasing experiments.

    Donation is best-effort: jax warns (and keeps both buffers) when an
    input is not donatable — e.g. two pytree leaves aliasing one
    buffer — which is noise here, not a defect, so the warning is
    filtered at call sites via ``silence_donation_warnings``."""
    import jax
    if not donate_enabled():
        # ndslint: waive[NDS111] -- builds the traced callable only; lower+compile stays inside cache.aot at the call sites
        return jax.jit(fn)
    # ndslint: waive[NDS111] -- builds the traced callable only; lower+compile stays inside cache.aot at the call sites
    return jax.jit(fn, donate_argnums=argnums)


def donate_enabled() -> bool:
    """The donation decision ``donate_jit`` applies, exported so the
    chunk-scan AOT fingerprint can fold the ACTUAL choice in (a blob
    compiled with donation must not serve a process that decided
    against it, and vice versa)."""
    import jax
    mode = os.environ.get("NDS_TPU_DONATE", "1")
    if mode in ("0", "off"):
        return False
    if mode == "force":
        return True
    try:
        return jax.default_backend() != "cpu"
    except Exception:  # noqa: BLE001 - backend probe must not fail a build
        return False


def silence_donation_warnings():
    """Filter jax's "Some donated buffers were not usable" UserWarning
    once per process: a non-donatable buffer silently keeps the old
    double-buffered behavior, which is the correct degradation."""
    import warnings
    global _DONATION_WARNINGS_SILENCED
    if _DONATION_WARNINGS_SILENCED:
        return
    _DONATION_WARNINGS_SILENCED = True
    warnings.filterwarnings(
        "ignore", message="Some donated buffers were not usable")


_DONATION_WARNINGS_SILENCED = False

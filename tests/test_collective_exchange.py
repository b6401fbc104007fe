"""Collective exchange (engine/collective_exchange.py): parity corpus.

``PATHWAY_TPU_COLLECTIVE_EXCHANGE=1`` forces every codeable repartition
through the shard_map + all_to_all kernel and ``=0`` pins routing.py's
host path; the two runs must be bit-identical — sink values, diffs,
error logs and checkpoint round trips — on the in-process sharded
scheduler and the single-process distributed scheduler (the same
discipline tests/test_device_ops.py applies to the operator kernels).
The corpus deliberately includes retractions, NaN float keys and
values, empty commits, cancelling delta batches, skewed
all-rows-to-one-shard batches, non-codeable (object dtype) columns
declining to host, and a chaos leg that kills the device kernel
mid-collective and recovers through the decline-to-host (PR-6
rollback) seam.  A cross-check test asserts the EXCHANGE_STATS
delivery-plane invariant: elided + host + collective == repartitions.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

pytest.importorskip("jax")

import pathway_tpu as pw
from pathway_tpu.engine import collective_exchange as cx
from pathway_tpu.engine import routing
from pathway_tpu.engine.graph import Scope
from pathway_tpu.engine.persistence import (
    MemoryBackend,
    OperatorSnapshotManager,
)
from pathway_tpu.engine.reducers import CountReducer, SumReducer
from pathway_tpu.engine.sharded import ShardedScheduler
from pathway_tpu.engine.value import ref_scalar
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.runner import GraphRunner, ShardedGraphRunner
from pathway_tpu.stdlib.indexing import DataIndex, TpuKnnFactory

N_WORKERS = 4  # conftest forces 8 host-platform sim devices — mesh_ready


def _set(monkeypatch, on: bool) -> None:
    monkeypatch.setenv(
        "PATHWAY_TPU_COLLECTIVE_EXCHANGE", "1" if on else "0"
    )


def _canon(obj):
    """NaN-safe, ndarray-safe canonical form for equality asserts."""
    if isinstance(obj, np.ndarray):
        obj = obj.tolist()
    if isinstance(obj, (list, tuple)):
        return tuple(_canon(x) for x in obj)
    if isinstance(obj, float) and obj != obj:
        return "NaN"
    return obj


# -- env contract + mesh detection -------------------------------------------


def test_enabled_env_contract(monkeypatch):
    monkeypatch.setenv("PATHWAY_TPU_COLLECTIVE_EXCHANGE", "0")
    assert not cx.enabled() and not cx.forced()
    monkeypatch.setenv("PATHWAY_TPU_COLLECTIVE_EXCHANGE", "off")
    assert not cx.enabled()
    monkeypatch.setenv("PATHWAY_TPU_COLLECTIVE_EXCHANGE", "1")
    assert cx.enabled() and cx.forced()
    monkeypatch.setenv("PATHWAY_TPU_COLLECTIVE_EXCHANGE", "force")
    assert cx.enabled() and cx.forced()
    # auto on the CPU sim backend: never silently re-route through
    # jax-on-CPU (the host path is cheaper than a fake collective)
    monkeypatch.delenv("PATHWAY_TPU_COLLECTIVE_EXCHANGE", raising=False)
    assert not cx.enabled()


def test_mesh_ready_needs_one_device_per_shard():
    assert not cx.mesh_ready(0)
    assert not cx.mesh_ready(1)  # nothing to exchange
    assert cx.mesh_ready(N_WORKERS)  # 8 sim devices cover 4 shards
    assert not cx.mesh_ready(4096)


def test_min_rows_env(monkeypatch):
    monkeypatch.delenv("PATHWAY_TPU_COLLECTIVE_MIN_ROWS", raising=False)
    assert cx.min_rows() == 512
    monkeypatch.setenv("PATHWAY_TPU_COLLECTIVE_MIN_ROWS", "7")
    assert cx.min_rows() == 7
    monkeypatch.setenv("PATHWAY_TPU_COLLECTIVE_MIN_ROWS", "bogus")
    assert cx.min_rows() == 512


# -- framework parity corpus --------------------------------------------------


def _corpus():
    def groupby_int():
        # int keys: digests + int64/float64 columns — fully codeable,
        # the collective carries every repartition in forced mode
        t = pw.debug.table_from_rows(
            pw.schema_from_types(k=int, v=int, w=float),
            [(i % 7, i, i * 0.25) for i in range(400)],
        )
        sel = t.select(k=t.k, v=t.v * 2 + 1, w=t.w)
        flt = sel.filter(sel.v > 7)
        return flt.groupby(flt.k).reduce(
            k=flt.k,
            total=pw.reducers.sum(flt.v),
            wsum=pw.reducers.sum(flt.w),
            cnt=pw.reducers.count(),
        )

    def join_int():
        orders = pw.debug.table_from_rows(
            pw.schema_from_types(oid=int, cust=int, amount=float),
            [(i, i % 9, float(i) * 1.5) for i in range(280)],
        )
        custs = pw.debug.table_from_rows(
            pw.schema_from_types(cid=int, region=int),
            [(i, i % 2) for i in range(9)],
        )
        j = orders.join(custs, orders.cust == custs.cid)
        return j.select(
            cust=orders.cust, region=custs.region, amount=orders.amount
        )

    def join_groupby_skew():
        # every order lands on ONE customer key: the all-to-all sees one
        # full bucket and n-1 empty ones on the skewed edge
        orders = pw.debug.table_from_rows(
            pw.schema_from_types(oid=int, cust=int, amount=float),
            [(i, 3, float(i)) for i in range(300)],
        )
        custs = pw.debug.table_from_rows(
            pw.schema_from_types(cid=int, region=int),
            [(i, i % 2) for i in range(4)],
        )
        j = orders.join(custs, orders.cust == custs.cid).select(
            region=custs.region, amount=orders.amount
        )
        return j.groupby(j.region).reduce(
            region=j.region,
            total=pw.reducers.sum(j.amount),
            cnt=pw.reducers.count(),
        )

    def groupby_str():
        # str keys columnarize as fixed-width numpy unicode — raw-byte
        # codeable, so the collective carries them like numerics
        t = pw.debug.table_from_rows(
            pw.schema_from_types(k=str, v=int),
            [(f"k{i % 5}", i) for i in range(300)],
        )
        return t.groupby(t.k).reduce(
            k=t.k, total=pw.reducers.sum(t.v), cnt=pw.reducers.count()
        )

    def knn():
        docs = pw.debug.table_from_rows(
            pw.schema_from_types(doc=int, emb=tuple),
            [
                (i, tuple(float((i * 7 + j * 3) % 13 - 6) for j in range(4)))
                for i in range(40)
            ],
        )
        queries = pw.debug.table_from_rows(
            pw.schema_from_types(q=int, qemb=tuple),
            [
                (i, tuple(float((i * 5 + j) % 13 - 6) for j in range(4)))
                for i in range(9)
            ],
        )
        index = DataIndex(
            docs, TpuKnnFactory(dimensions=4, capacity=8), docs.emb
        )
        return index.query_as_of_now(
            queries, queries.qemb, number_of_matches=3
        )

    return {
        "groupby_int": groupby_int,
        "join_int": join_int,
        "join_groupby_skew": join_groupby_skew,
        "groupby_str": groupby_str,
        "knn": knn,
    }


def _capture(build, runner_factory, monkeypatch, on):
    _set(monkeypatch, on)
    G.clear()
    try:
        (state,) = runner_factory().capture(build())
    finally:
        G.clear()
    return {k: _canon(v) for k, v in state.items()}


@pytest.mark.parametrize("name", sorted(_corpus()))
def test_sharded_parity(name, monkeypatch):
    build = _corpus()[name]
    cx.reset_counters()
    off = _capture(
        build, lambda: ShardedGraphRunner(N_WORKERS), monkeypatch, False
    )
    assert cx.COLLECTIVE_STATS["exchanges"] == 0  # off run stayed host
    on = _capture(
        build, lambda: ShardedGraphRunner(N_WORKERS), monkeypatch, True
    )
    assert off == on
    if name != "knn":  # knn edges route via pin/entry, not columnar
        assert cx.COLLECTIVE_STATS["exchanges"] > 0  # non-vacuous


@pytest.mark.parametrize("name", ["groupby_int", "join_int"])
def test_sharded_matches_single_worker(name, monkeypatch):
    build = _corpus()[name]
    base = _capture(build, GraphRunner, monkeypatch, False)
    on = _capture(
        build, lambda: ShardedGraphRunner(N_WORKERS), monkeypatch, True
    )
    assert base == on


# -- raw-scope corpus: retractions, NaN, cancelling batches -------------------


def _build_scopes(n_workers):
    scopes, sessions, aggs = [], [], []
    for _w in range(n_workers):
        sc = Scope()
        sess = sc.input_session(3)
        agg = sc.group_by_table(
            sess,
            by_cols=[0],
            reducers=[
                (SumReducer(), [1]),
                (SumReducer(), [2]),
                (CountReducer(), []),
            ],
        )
        scopes.append(sc)
        sessions.append(sess)
        aggs.append(agg)
    return scopes, sessions, aggs


def _feed(sess, sched, nan_keys=False, nan_vals=False):
    live = {}

    def key(i):
        if nan_keys and i % 97 == 0:
            return float("nan")
        return float(i % 7) if nan_keys else i % 7

    def ins(i, row):
        live[i] = row
        sess.insert(ref_scalar(i), row)

    def rm(i):
        sess.remove(ref_scalar(i), live.pop(i))

    for i in range(600):
        v = float("nan") if nan_vals and i % 89 == 0 else i * 0.5
        ins(i, (key(i), i, v))
    sched.commit()
    for i in range(100, 150):  # retract + reinsert modified
        rm(i)
        ins(i, (key(i), i + 1000, i * 0.25))
    sched.commit()
    sched.commit()  # empty commit
    ins(10_000, (key(3), 1, 1.0))  # cancelling batch: net-zero delta
    rm(10_000)
    sched.commit()
    for i in [k for k in list(live) if _canon(live[k][0]) == _canon(key(6))]:
        rm(i)  # retract an entire group to extinction
    sched.commit()


def _run_sharded(on, monkeypatch, nan_keys=False, nan_vals=False):
    _set(monkeypatch, on)
    scopes, sessions, aggs = _build_scopes(N_WORKERS)
    sched = ShardedScheduler(scopes)
    _feed(sessions[0], sched, nan_keys=nan_keys, nan_vals=nan_vals)
    merged = {}
    for agg in aggs:
        merged.update(agg.current)
    return {k: _canon(v) for k, v in merged.items()}


def test_raw_scope_retraction_parity(monkeypatch):
    cx.reset_counters()
    off = _run_sharded(False, monkeypatch)
    assert cx.COLLECTIVE_STATS["exchanges"] == 0
    on = _run_sharded(True, monkeypatch)
    assert off == on
    assert cx.COLLECTIVE_STATS["exchanges"] > 0


def test_raw_scope_nan_key_parity(monkeypatch):
    # NaN float keys stay vectorized in routing (fixed bit pattern), so
    # the payload is codeable and the collective still engages
    cx.reset_counters()
    off = _run_sharded(False, monkeypatch, nan_keys=True)
    on = _run_sharded(True, monkeypatch, nan_keys=True)
    assert off == on
    assert cx.COLLECTIVE_STATS["exchanges"] > 0
    assert any("NaN" in repr(k) for k in (repr(off),))  # corpus non-vacuous


def test_raw_scope_nan_value_parity(monkeypatch):
    off = _run_sharded(False, monkeypatch, nan_vals=True)
    on = _run_sharded(True, monkeypatch, nan_vals=True)
    assert off == on
    assert any("NaN" in repr(v) for v in off.values())


# -- error-log parity ---------------------------------------------------------


def test_error_log_parity(monkeypatch):
    from pathway_tpu.engine import expression as ex
    from pathway_tpu.engine.graph import Scheduler

    def run(on):
        _set(monkeypatch, on)
        scopes, logs, aggs = [], [], []
        for _w in range(N_WORKERS):
            sc = Scope()
            sess = sc.input_session(2)
            e1 = sc.expression_table(
                sess,
                [
                    ex.Binary("%", ex.ColumnRef(0), ex.Const(5)),
                    # 1/x poisons x == 0 rows with ERROR
                    ex.Binary("/", ex.Const(1.0), ex.ColumnRef(1)),
                ],
            )
            gb = sc.group_by_table(
                e1,
                by_cols=[0],
                reducers=[(SumReducer(), [1]), (CountReducer(), [])],
            )
            scopes.append(sc)
            logs.append(sc.error_log_default)
            aggs.append(gb)
            if _w == 0:
                feed = sess
        sched = ShardedScheduler(scopes)
        for i in range(400):
            feed.insert(ref_scalar(i), (i, float(i % 5)))
        sched.commit()
        log = sorted(
            entry for lg in logs for entry in lg.current.values()
        )
        merged = {}
        for agg in aggs:
            merged.update(agg.current)
        return {k: _canon(v) for k, v in merged.items()}, log

    cur_off, log_off = run(False)
    cur_on, log_on = run(True)
    assert cur_off == cur_on
    assert log_off == log_on
    assert log_on  # the corpus actually exercised the error path


def test_object_column_declines_to_host(monkeypatch):
    """A mixed-type value column columnarizes as object dtype — not
    raw-byte codeable — so the payload packer declines and the host path
    must deliver bit-identically (declined_non_codeable ticks)."""

    def run(on):
        _set(monkeypatch, on)
        scopes, sessions, aggs = [], [], []
        for _w in range(N_WORKERS):
            sc = Scope()
            sess = sc.input_session(2)
            agg = sc.group_by_table(
                sess, by_cols=[0], reducers=[(CountReducer(), [])]
            )
            scopes.append(sc)
            sessions.append(sess)
            aggs.append(agg)
        sched = ShardedScheduler(scopes)
        for i in range(300):
            v = i if i % 2 else f"s{i}"  # mixed types -> object column
            sessions[0].insert(ref_scalar(i), (i % 7, v))
        sched.commit()
        merged = {}
        for agg in aggs:
            merged.update(agg.current)
        return {k: _canon(v) for k, v in merged.items()}

    cx.reset_counters()
    off = run(False)
    assert cx.COLLECTIVE_STATS["declined_non_codeable"] == 0  # off: no consult
    on = run(True)
    assert off == on
    assert cx.COLLECTIVE_STATS["declined_non_codeable"] > 0


# -- chaos: kernel dies mid-collective ----------------------------------------


def test_kernel_failure_declines_to_host(monkeypatch):
    """A device error mid-collective performs NO pushes; the caller's
    host path delivers the whole batch (the PR-6 rollback seam), so the
    run completes bit-identically with the errors counter ticking."""
    cx.reset_counters()
    off = _run_sharded(False, monkeypatch)

    def boom(n):
        def dead_kernel(payload, gidx):
            raise RuntimeError("simulated worker loss mid-collective")

        return dead_kernel

    monkeypatch.setattr(cx, "_kernel", boom)
    chaos = _run_sharded(True, monkeypatch)
    assert chaos == off
    assert cx.COLLECTIVE_STATS["errors"] > 0
    assert cx.COLLECTIVE_STATS["exchanges"] == 0  # nothing half-delivered


# -- EXCHANGE_STATS delivery-plane invariant ----------------------------------


def test_exchange_stats_path_invariant(monkeypatch):
    """Every repartition decision lands on exactly one delivery plane:
    elided + host_deliveries + collective_deliveries == repartitions."""
    stats = routing.EXCHANGE_STATS
    for on in (False, True):
        before = {
            k: stats[k]
            for k in (
                "elided",
                "host_deliveries",
                "collective_deliveries",
                "repartitions",
            )
        }
        _run_sharded(on, monkeypatch)
        delta = {k: stats[k] - before[k] for k in before}
        assert delta["repartitions"] > 0
        assert (
            delta["elided"]
            + delta["host_deliveries"]
            + delta["collective_deliveries"]
            == delta["repartitions"]
        )
        if on:
            assert delta["collective_deliveries"] > 0
        else:
            assert delta["collective_deliveries"] == 0


def test_exchange_stats_invariant_with_elision(monkeypatch):
    """The invariant holds when the optimizer elides edges too — the
    framework runner's elision plane increments `elided`, never `host`
    or `collective`."""
    stats = routing.EXCHANGE_STATS
    before = {
        k: stats[k]
        for k in (
            "elided",
            "host_deliveries",
            "collective_deliveries",
            "repartitions",
        )
    }
    _capture(
        _corpus()["groupby_int"],
        lambda: ShardedGraphRunner(N_WORKERS),
        monkeypatch,
        True,
    )
    delta = {k: stats[k] - before[k] for k in before}
    assert delta["repartitions"] > 0
    assert (
        delta["elided"]
        + delta["host_deliveries"]
        + delta["collective_deliveries"]
        == delta["repartitions"]
    )


# -- checkpoint round trips across modes --------------------------------------


class TestCheckpointCompat:
    """The exchange plane is a runtime decision, not graph structure: a
    snapshot taken with the collective forced must restore under a
    host-only run (and vice versa) with identical state."""

    def _snap(self, on, backend, monkeypatch, restore_only=False):
        _set(monkeypatch, on)
        scopes, sessions, aggs = _build_scopes(N_WORKERS)
        mgr = OperatorSnapshotManager(backend)
        if restore_only:
            restored = mgr.restore(scopes, [])
            assert restored is not None
            merged = {}
            for agg in aggs:
                merged.update(agg.current)
            return merged
        sched = ShardedScheduler(scopes)
        for i in range(600):
            sessions[0].insert(ref_scalar(i), (i % 7, i, i * 0.5))
        sched.commit()
        for i in range(100, 150):
            sessions[0].remove(ref_scalar(i), (i % 7, i, i * 0.5))
        sched.commit()
        mgr.snapshot(scopes, [], sched.time)
        merged = {}
        for agg in aggs:
            merged.update(agg.current)
        return merged

    @pytest.mark.parametrize(
        "snap_on,restore_on", [(True, False), (False, True)]
    )
    def test_cross_restore(self, snap_on, restore_on, monkeypatch):
        backend = MemoryBackend()
        live = self._snap(snap_on, backend, monkeypatch)
        restored = self._snap(
            restore_on, backend, monkeypatch, restore_only=True
        )
        assert {k: _canon(v) for k, v in restored.items()} == {
            k: _canon(v) for k, v in live.items()
        }


# -- single-process distributed scheduler -------------------------------------


def test_distributed_single_process_collective(monkeypatch):
    """A single-process DistributedScheduler (all destination workers
    process-local) routes columnar repartitions through the collective;
    parity vs the host path and the engagement counter both hold."""
    from pathway_tpu.engine import distributed as dist

    def run(on):
        _set(monkeypatch, on)
        scopes, sessions, aggs = [], [], []
        for _w in range(2):
            sc = Scope()
            sess = sc.input_session(2)
            agg = sc.group_by_table(
                sess,
                by_cols=[0],
                reducers=[(SumReducer(), [1]), (CountReducer(), [])],
            )
            scopes.append(sc)
            sessions.append(sess)
            aggs.append(agg)
        transport = dist.MeshTransport(0, 1, addresses=[("127.0.0.1", 0)])
        try:
            sched = dist.DistributedScheduler(
                scopes, 0, 1, transport, n_shared=len(scopes[0].nodes)
            )
            sched.announce_topology()
            for i in range(500):
                sessions[0].insert(ref_scalar(i), (i % 13, float(i)))
            sched.commit()
            for i in range(50, 80):
                sessions[0].remove(ref_scalar(i), (i % 13, float(i)))
            sched.commit()
        finally:
            transport.close()
        merged = {}
        for agg in aggs:
            merged.update(agg.current)
        return {k: _canon(v) for k, v in merged.items()}

    cx.reset_counters()
    off = run(False)
    assert cx.COLLECTIVE_STATS["exchanges"] == 0
    on = run(True)
    assert off == on
    assert cx.COLLECTIVE_STATS["exchanges"] > 0


# -- counters + stats shape ---------------------------------------------------


def test_stats_shape(monkeypatch):
    monkeypatch.setenv("PATHWAY_TPU_COLLECTIVE_EXCHANGE", "1")
    cx.reset_counters()
    s = cx.stats()
    assert s["enabled"] is True and s["forced"] is True
    assert s["events"] == {
        "exchanges": 0,
        "declined_non_codeable": 0,
        "errors": 0,
    }
    assert s["ns_total"] == 0 and s["bytes_total"] == 0
    assert "placement" in s


def test_metric_families_registered(monkeypatch):
    from pathway_tpu.internals import metrics as m

    cx.reset_counters()
    _run_sharded(True, monkeypatch)
    snap = m.REGISTRY.snapshot()
    assert "pathway_collective_exchange_events_total" in snap
    assert "pathway_collective_exchange_ns_total" in snap
    assert "pathway_collective_exchange_bytes_total" in snap
    # the path label distinguishes delivery planes on the exchange family
    paths = {
        s["labels"].get("path")
        for s in snap["pathway_exchange_events_total"]["series"]
    }
    assert {"device", "host", "elided", "total"} <= paths

"""Device residency (engine/device_residency.py): parity corpus.

``PATHWAY_TPU_DEVICE_RESIDENCY=1`` keeps collective-exchange outputs
bound for device-eligible consumers resident on device (and re-packs
still-resident inputs without a host round trip); ``=0`` pins the
PR-16 behavior of materializing every exchange output to host.  The two
modes must be bit-identical — sink values, diffs, checkpoint round
trips — on the in-process sharded scheduler, the framework runners and
the single-process distributed scheduler, with the collective forced on
in BOTH runs so residency is the only variable (the same discipline
tests/test_collective_exchange.py applies to the exchange itself).  The
corpus includes retractions, NaN float keys and values, cancelling
batches, empty commits, group extinction, non-codeable columns
declining mid-chain, and chaos legs that kill the device kernel and the
resident-egress wrap — both must fall back with exactly-once delivery
intact.  A cross-check extends the PR-16 EXCHANGE_STATS invariant:
elided + host + collective == repartitions even when collective
deliveries stay device-resident (no double count).
"""

from __future__ import annotations

import numpy as np
import pytest

pytest.importorskip("jax")

import pathway_tpu as pw
from pathway_tpu.engine import collective_exchange as cx
from pathway_tpu.engine import device_residency as dres
from pathway_tpu.engine import routing
from pathway_tpu.engine.batch import Columns
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
from pathway_tpu.optimize.placement import PlacementPolicy

N_WORKERS = 4  # conftest forces 8 host-platform sim devices — mesh_ready


def _set_env(monkeypatch, residency_on, device_ops=False):
    # the collective is forced in BOTH modes so residency is the only
    # variable under test; device ops are forced only for framework runs
    # (the optimizer's placement pass does the eligibility annotation)
    monkeypatch.setenv("PATHWAY_TPU_COLLECTIVE_EXCHANGE", "1")
    monkeypatch.setenv(
        "PATHWAY_TPU_DEVICE_OPS", "1" if device_ops else "0"
    )
    monkeypatch.setenv(
        "PATHWAY_TPU_DEVICE_RESIDENCY", "1" if residency_on else "0"
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


# -- env contract + seam predicates -------------------------------------------


def test_enabled_env_contract(monkeypatch):
    monkeypatch.setenv("PATHWAY_TPU_DEVICE_RESIDENCY", "0")
    assert not dres.enabled() and not dres.forced()
    monkeypatch.setenv("PATHWAY_TPU_DEVICE_RESIDENCY", "off")
    assert not dres.enabled()
    monkeypatch.setenv("PATHWAY_TPU_DEVICE_RESIDENCY", "1")
    assert dres.enabled() and dres.forced()
    monkeypatch.setenv("PATHWAY_TPU_DEVICE_RESIDENCY", "force")
    assert dres.enabled() and dres.forced()
    # auto on the CPU sim backend: keeping buffers on a jax-CPU
    # "device" saves nothing, so auto stays off
    monkeypatch.delenv("PATHWAY_TPU_DEVICE_RESIDENCY", raising=False)
    assert not dres.enabled()


class _FakeConsumer:
    def __init__(self, kind=None, index=0, downstream=None):
        if kind is not None:
            self._device_ops_eligible = kind
        if downstream is not None:
            self._device_residency_downstream = downstream
        self.index = index


def test_consumer_seam_key(monkeypatch):
    assert dres.consumer_seam_key(None) is None
    assert dres.consumer_seam_key(_FakeConsumer()) is None
    assert dres.consumer_seam_key(
        _FakeConsumer(kind="groupby", index=7)
    ) == ("groupby", 7)
    # a row-local feeder marked by the placement pass belongs to the
    # downstream operator's seam
    assert dres.consumer_seam_key(
        _FakeConsumer(downstream=("join", 3))
    ) == ("join", 3)


def test_consumer_resident_ok(monkeypatch):
    eligible = _FakeConsumer(kind="groupby", index=7)
    monkeypatch.setenv("PATHWAY_TPU_DEVICE_RESIDENCY", "0")
    assert not dres.consumer_resident_ok(eligible)
    monkeypatch.setenv("PATHWAY_TPU_DEVICE_RESIDENCY", "1")
    assert dres.consumer_resident_ok(eligible)
    # forced mode never keeps a batch resident for an unannotated
    # consumer — there is no device-side reader to hand it to
    assert not dres.consumer_resident_ok(_FakeConsumer())
    assert not dres.consumer_resident_ok(None)


# -- DeviceResidentColumns unit behavior --------------------------------------


def _packed_fixture(n=640, with_diffs=True):
    """A host Columns + its packed wire payload (the exchange layout)."""
    kb = (np.arange(n * 16, dtype=np.int64) % 251).astype(np.uint8)
    kb = np.ascontiguousarray(kb.reshape(n, 16))
    cols = [
        np.arange(n, dtype=np.int64) * 3 - 7,
        (np.arange(n, dtype=np.float64) * 0.5 - 2.0),
    ]
    diffs = None
    if with_diffs:
        diffs = np.where(np.arange(n) % 5 == 0, -1, 1).astype(np.int64)
    host = Columns(n, cols, kbytes=kb, diffs=diffs)
    payload, layout, has_diffs = cx._pack_payload(host)
    assert payload is not None
    return host, payload, layout, has_diffs


def _resident_from(payload, layout, has_diffs, seam_key=None):
    import jax.numpy as jnp

    return dres.DeviceResidentColumns.from_device_rows(
        jnp.asarray(payload), layout, has_diffs, seam_key=seam_key
    )


def test_resident_columns_lazy_then_bit_exact():
    dres.reset_counters()
    host, payload, layout, has_diffs = _packed_fixture()
    res = _resident_from(payload, layout, has_diffs)
    # diffs are eager (every delivery path screens them); host slots are
    # not — nothing materialized yet
    assert res.n == host.n
    assert np.array_equal(res.diffs, host.diffs)
    assert res.resident() and not res._materialized()
    assert dres.RESIDENCY_STATS["materializations"] == 0
    # first host access materializes bit-exactly through the wire spec
    assert np.array_equal(res.kbytes(), host.kbytes())
    assert res._materialized()
    assert dres.RESIDENCY_STATS["materializations"] == 1
    for got, want in zip(res.cols, host.cols):
        assert got.dtype == want.dtype
        assert np.array_equal(got, want)
    # the device buffer survives materialization (re-pack stays possible)
    assert res.resident()
    # second access is a no-op, not a second fetch
    res.kbytes()
    assert dres.RESIDENCY_STATS["materializations"] == 1


def test_resident_columns_no_diffs():
    host, payload, layout, has_diffs = _packed_fixture(with_diffs=False)
    assert not has_diffs
    res = _resident_from(payload, layout, has_diffs)
    assert res.diffs is None  # all-(+1) stays the None encoding
    for got, want in zip(res.cols, host.cols):
        assert np.array_equal(got, want)


def test_device_column_views():
    host, payload, layout, has_diffs = _packed_fixture()
    res = _resident_from(payload, layout, has_diffs)
    for i, want in enumerate(host.cols):
        dev = res.device_column(i)
        assert dev is not None
        got = np.asarray(dev)
        assert got.dtype == want.dtype and np.array_equal(got, want)
    # the device view never forced host materialization
    assert not res._materialized()


def test_decay_materializes_and_drops_buffer():
    host, payload, layout, has_diffs = _packed_fixture()
    res = _resident_from(payload, layout, has_diffs)
    res.decay()
    assert not res.resident()
    assert res.device_rows() is None and res.device_column(0) is None
    # decayed batches read as plain host data, bit-exactly
    assert np.array_equal(res.kbytes(), host.kbytes())
    assert np.array_equal(res.cols[1], host.cols[1])
    res.decay()  # idempotent


def test_decay_resident_batches_sweeps_live_set():
    host, payload, layout, has_diffs = _packed_fixture()
    a = _resident_from(payload, layout, has_diffs)
    b = _resident_from(payload, layout, has_diffs)
    assert a.resident() and b.resident()
    dres.decay_resident_batches()
    assert not a.resident() and not b.resident()
    assert np.array_equal(a.cols[0], host.cols[0])
    dres.decay_resident_batches()  # empty sweep is a no-op


def test_gather_after_materialize_matches_host():
    host, payload, layout, has_diffs = _packed_fixture()
    res = _resident_from(payload, layout, has_diffs)
    idx = np.arange(0, host.n, 3, dtype=np.int64)
    got, want = res.gather(idx), host.gather(idx)
    assert np.array_equal(got.kbytes(), want.kbytes())
    assert np.array_equal(got.diffs, want.diffs)
    for g, w in zip(got.cols, want.cols):
        assert np.array_equal(g, w)


# -- exchange ingress/egress unit parity --------------------------------------


def _run_exchange(columns, shards, consumer, monkeypatch, residency_on):
    _set_env(monkeypatch, residency_on)
    parts = cx.exchange(0, columns, shards, N_WORKERS, consumer=consumer)
    assert parts is not None
    return parts


def _parts_canon(parts):
    out = []
    for p in parts:
        if p is None:
            out.append(None)
            continue
        out.append(
            (
                p.kbytes().tobytes(),
                None if p.diffs is None else p.diffs.tobytes(),
                tuple(
                    (c.dtype.str, c.tobytes()) for c in p.cols
                ),
            )
        )
    return out


def test_exchange_resident_egress_parity(monkeypatch):
    """Resident egress parts materialize bit-identically to the host
    fetch, and the trimmed lazy fetch moves strictly fewer D2H bytes
    than the whole padded buffer."""
    host, payload, layout, has_diffs = _packed_fixture(n=700)
    shards = (np.arange(700, dtype=np.int64) * 7) % N_WORKERS
    consumer = _FakeConsumer(kind="groupby", index=7)
    dres.reset_counters()
    off = _run_exchange(host, shards, consumer, monkeypatch, False)
    assert dres.RESIDENCY_STATS["resident_batches"] == 0
    d2h_off = dres.stats()["d2h"]["bytes"]
    assert d2h_off > 0

    dres.reset_counters()
    on = _run_exchange(host, shards, consumer, monkeypatch, True)
    assert all(
        p is None or isinstance(p, dres.DeviceResidentColumns) for p in on
    )
    assert dres.RESIDENCY_STATS["resident_batches"] > 0
    assert _parts_canon(on) == _parts_canon(off)  # materializes lazily
    d2h_on = dres.stats()["d2h"]["bytes"]
    assert d2h_on < d2h_off
    assert dres.stats()["bytes_saved"] > 0


def test_exchange_resident_ingress_repack(monkeypatch):
    """A still-resident input re-packs from device rows: only the index
    matrix crosses H2D, and the delivered parts are bit-identical to
    packing the same batch from host."""
    host, payload, layout, has_diffs = _packed_fixture(n=650)
    shards = (np.arange(650, dtype=np.int64) * 11) % N_WORKERS

    dres.reset_counters()
    off = _run_exchange(host, shards, None, monkeypatch, False)
    h2d_host = dres.stats()["h2d"]["bytes"]

    res = _resident_from(payload, layout, has_diffs)
    dres.reset_counters()
    on = _run_exchange(res, shards, None, monkeypatch, True)
    s = dres.stats()
    assert s["events"]["device_consumes"] == 1
    assert s["h2d"]["bytes"] < h2d_host  # payload never re-crossed
    assert s["bytes_saved"] > 0
    assert _parts_canon(on) == _parts_canon(off)


def test_exchange_resident_egress_failure_falls_back(monkeypatch):
    """A failure while wrapping resident egress parts declines cleanly:
    the whole buffer is fetched, host parts are delivered bit-exactly,
    and nothing was half-pushed."""
    host, payload, layout, has_diffs = _packed_fixture(n=600)
    shards = np.arange(600, dtype=np.int64) % N_WORKERS
    consumer = _FakeConsumer(kind="groupby", index=7)
    off = _run_exchange(host, shards, consumer, monkeypatch, False)

    def boom(*a, **k):
        raise RuntimeError("simulated resident-wrap failure")

    monkeypatch.setattr(
        dres.DeviceResidentColumns, "from_device_rows", boom
    )
    dres.reset_counters()
    on = _run_exchange(host, shards, consumer, monkeypatch, True)
    assert dres.RESIDENCY_STATS["declines"] > 0
    assert all(not isinstance(p, dres.DeviceResidentColumns) for p in on)
    assert _parts_canon(on) == _parts_canon(off)


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
        # raw scopes bypass the optimizer: annotate eligibility by hand
        # (exactly what optimize/placement.run_pass stamps)
        agg._device_ops_eligible = "groupby"
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
    return live


def _run_sharded(on, monkeypatch, nan_keys=False, nan_vals=False):
    _set_env(monkeypatch, on)
    scopes, sessions, aggs = _build_scopes(N_WORKERS)
    sched = ShardedScheduler(scopes)
    _feed(sessions[0], sched, nan_keys=nan_keys, nan_vals=nan_vals)
    merged = {}
    for agg in aggs:
        merged.update(agg.current)
    return {k: _canon(v) for k, v in merged.items()}


@pytest.mark.parametrize(
    "nan_keys,nan_vals", [(False, False), (True, False), (False, True)]
)
def test_raw_scope_parity(nan_keys, nan_vals, monkeypatch):
    dres.reset_counters()
    off = _run_sharded(False, monkeypatch, nan_keys, nan_vals)
    assert dres.RESIDENCY_STATS["resident_batches"] == 0  # off stayed host
    on = _run_sharded(True, monkeypatch, nan_keys, nan_vals)
    assert off == on
    assert dres.RESIDENCY_STATS["resident_batches"] > 0  # non-vacuous
    if nan_keys:
        assert "NaN" in repr(off)
    if nan_vals:
        assert any("NaN" in repr(v) for v in off.values())


def test_raw_scope_transfer_bytes_strictly_lower(monkeypatch):
    """The acceptance metric at unit scale: the same feed moves strictly
    fewer h2d+d2h bytes with residency on (the padded all-to-all tail
    never crosses; only trimmed rows materialize)."""
    dres.reset_counters()
    off = _run_sharded(False, monkeypatch)
    s_off = dres.stats()
    dres.reset_counters()
    on = _run_sharded(True, monkeypatch)
    s_on = dres.stats()
    assert off == on
    total_off = s_off["h2d"]["bytes"] + s_off["d2h"]["bytes"]
    total_on = s_on["h2d"]["bytes"] + s_on["d2h"]["bytes"]
    assert 0 < total_on < total_off
    assert s_on["bytes_saved"] > 0 and s_off["bytes_saved"] == 0


def test_commit_boundary_decays_residents(monkeypatch):
    """Drain-before-persistence: no resident batch survives a commit
    boundary, so snapshots only ever see host-resident state."""
    _set_env(monkeypatch, True)
    scopes, sessions, aggs = _build_scopes(N_WORKERS)
    sched = ShardedScheduler(scopes)
    dres.reset_counters()
    for i in range(600):
        sessions[0].insert(ref_scalar(i), (i % 7, i, i * 0.5))
    sched.commit()
    assert dres.RESIDENCY_STATS["resident_batches"] > 0
    assert not dres._LIVE_RESIDENT  # swept at the boundary


def test_kernel_failure_declines_to_host(monkeypatch):
    """A device error mid-collective performs NO pushes; the host path
    delivers the whole batch (the PR-6 rollback seam) bit-identically,
    with residency never engaging on the failed exchange."""
    off = _run_sharded(False, monkeypatch)

    def boom(n):
        def dead_kernel(payload, gidx):
            raise RuntimeError("simulated worker loss mid-collective")

        return dead_kernel

    monkeypatch.setattr(cx, "_kernel", boom)
    cx.reset_counters()
    dres.reset_counters()
    chaos = _run_sharded(True, monkeypatch)
    assert chaos == off
    assert cx.COLLECTIVE_STATS["errors"] > 0
    assert dres.RESIDENCY_STATS["resident_batches"] == 0


def test_object_column_mid_chain_decline(monkeypatch):
    """A mixed-type column is not raw-byte codeable: the exchange
    declines before residency is even consulted and the host path
    delivers bit-identically (no partial pushes)."""

    def run(on):
        _set_env(monkeypatch, on)
        scopes, sessions, aggs = [], [], []
        for _w in range(N_WORKERS):
            sc = Scope()
            sess = sc.input_session(2)
            agg = sc.group_by_table(
                sess, by_cols=[0], reducers=[(CountReducer(), [])]
            )
            agg._device_ops_eligible = "groupby"
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
    dres.reset_counters()
    off = run(False)
    on = run(True)
    assert off == on
    assert cx.COLLECTIVE_STATS["declined_non_codeable"] > 0
    assert dres.RESIDENCY_STATS["resident_batches"] == 0


# -- EXCHANGE_STATS invariant with resident deliveries ------------------------


def test_exchange_stats_invariant_with_residency(monkeypatch):
    """PR-16 delivery-plane invariant, extended: a collective delivery
    that stays device-resident still counts exactly once —
    elided + host + collective == repartitions in both modes."""
    stats = routing.EXCHANGE_STATS
    for on in (False, True):
        dres.reset_counters()
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
        assert delta["collective_deliveries"] > 0
        if on:
            # resident deliveries rode the collective plane, not a new one
            assert dres.RESIDENCY_STATS["resident_batches"] > 0


# -- framework runners ---------------------------------------------------------


def _chain():
    """The acceptance workload shape: device groupby feeding a join
    through a repartition seam."""
    t = pw.debug.table_from_rows(
        pw.schema_from_types(k=int, v=int, w=float),
        [(i % 50, i, i * 0.25) for i in range(800)],
    )
    g = t.groupby(t.k).reduce(
        k=t.k, total=pw.reducers.sum(t.v), cnt=pw.reducers.count()
    )
    d = pw.debug.table_from_rows(
        pw.schema_from_types(k2=int, label=int),
        [(i, i % 3) for i in range(50)],
    )
    j = g.join(d, g.k == d.k2)
    return j.select(k=g.k, total=g.total, cnt=g.cnt, label=d.label)


def _groupby_only():
    t = pw.debug.table_from_rows(
        pw.schema_from_types(k=int, v=int, w=float),
        [(i % 7, i, i * 0.5) for i in range(700)],
    )
    sel = t.select(k=t.k, v=t.v * 2 + 1, w=t.w)
    flt = sel.filter(sel.v > 7)
    return flt.groupby(flt.k).reduce(
        k=flt.k,
        total=pw.reducers.sum(flt.v),
        wsum=pw.reducers.sum(flt.w),
        cnt=pw.reducers.count(),
    )


def _capture(build, runner_factory, monkeypatch, on, device_ops=True):
    _set_env(monkeypatch, on, device_ops=device_ops)
    G.clear()
    try:
        (state,) = runner_factory().capture(build())
    finally:
        G.clear()
    return {k: _canon(v) for k, v in state.items()}


@pytest.mark.parametrize("name", ["chain", "groupby_only"])
def test_framework_sharded_parity(name, monkeypatch):
    build = {"chain": _chain, "groupby_only": _groupby_only}[name]
    dres.reset_counters()
    off = _capture(
        build, lambda: ShardedGraphRunner(N_WORKERS), monkeypatch, False
    )
    assert dres.RESIDENCY_STATS["resident_batches"] == 0
    on = _capture(
        build, lambda: ShardedGraphRunner(N_WORKERS), monkeypatch, True
    )
    assert off == on
    # the optimizer's placement pass (not hand annotation) found the
    # eligible consumers behind the fused/pushed-down delivery nodes
    assert dres.RESIDENCY_STATS["resident_batches"] > 0


def test_framework_matches_single_worker(monkeypatch):
    base = _capture(_chain, GraphRunner, monkeypatch, False)
    on = _capture(
        _chain, lambda: ShardedGraphRunner(N_WORKERS), monkeypatch, True
    )
    assert base == on


# -- checkpoint round trips across modes --------------------------------------


class TestCheckpointCompat:
    """Residency is a runtime decision, not graph structure: a snapshot
    taken with residency forced must restore under a residency-off run
    (and vice versa) with identical state — resident batches decay at
    commit boundaries, so snapshots only ever serialize host state."""

    def _snap(self, on, backend, monkeypatch, restore_only=False):
        _set_env(monkeypatch, on)
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


def test_distributed_single_process_residency(monkeypatch):
    from pathway_tpu.engine import distributed as dist

    def run(on):
        _set_env(monkeypatch, on)
        scopes, sessions, aggs = [], [], []
        for _w in range(2):
            sc = Scope()
            sess = sc.input_session(2)
            agg = sc.group_by_table(
                sess,
                by_cols=[0],
                reducers=[(SumReducer(), [1]), (CountReducer(), [])],
            )
            agg._device_ops_eligible = "groupby"
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

    dres.reset_counters()
    off = run(False)
    assert dres.RESIDENCY_STATS["resident_batches"] == 0
    on = run(True)
    assert off == on
    assert dres.RESIDENCY_STATS["resident_batches"] > 0


# -- chain-aware placement -----------------------------------------------------


class TestChainAwarePlacement:
    def _policy(self):
        return PlacementPolicy(
            enabled_fn=lambda: True,
            forced_fn=lambda: False,
            min_rows_fn=lambda: 0,
        )

    def _probe(self, pol, host_ns, device_ns):
        # order matters: host first so the bootstrap device-credit in
        # record() does not pre-place the operator on device
        for _ in range(pol.PROBE_CALLS):
            pol.record("groupby", 1, False, 1, host_ns)
        for _ in range(pol.PROBE_CALLS):
            pol.record("groupby", 1, True, 1, device_ns)

    def test_seam_credit_flips_placement(self, monkeypatch):
        monkeypatch.setenv("PATHWAY_TPU_DEVICE_RESIDENCY", "1")
        pol = self._policy()
        # device measures slightly slower than host: stays host under
        # the 1.2x hysteresis
        self._probe(pol, host_ns=100, device_ns=110)
        assert not pol.choose("groupby", 1, 1000)
        assert not pol.is_device("groupby", 1)
        # a device-placed neighbor across the seam + a measured seam
        # cost credit the device side past the hysteresis
        pol.seed("join", 2, device=True)
        pol.link("groupby", 1, "join", 2)
        pol.record_seam("groupby", 1, 1, 50)
        assert pol.choose("groupby", 1, 1000)
        assert pol.is_device("groupby", 1)
        dec = pol.decisions()["groupby:1"]
        assert dec["links"] == ["join:2"] and dec["seam_events"] == 1
        assert dec["seam_ns_per_row"] == 50.0

    def test_no_credit_when_residency_off(self, monkeypatch):
        monkeypatch.setenv("PATHWAY_TPU_DEVICE_RESIDENCY", "0")
        pol = self._policy()
        self._probe(pol, host_ns=100, device_ns=110)
        pol.seed("join", 2, device=True)
        pol.link("groupby", 1, "join", 2)
        pol.record_seam("groupby", 1, 1, 50)
        assert not pol.choose("groupby", 1, 1000)

    def test_no_credit_without_device_neighbor(self, monkeypatch):
        monkeypatch.setenv("PATHWAY_TPU_DEVICE_RESIDENCY", "1")
        pol = self._policy()
        self._probe(pol, host_ns=100, device_ns=110)
        pol.seed("join", 2)  # neighbor exists but sits on host
        pol.link("groupby", 1, "join", 2)
        pol.record_seam("groupby", 1, 1, 50)
        assert not pol.choose("groupby", 1, 1000)

    def test_reset_clears_links(self):
        pol = self._policy()
        pol.link("groupby", 1, "join", 2)
        pol.reset()
        assert pol.decisions() == {}


def test_placement_pass_marks_feeders_and_links(monkeypatch):
    """optimize.run_pass stamps non-eligible feeders with their
    downstream operator's seam and links eligible neighbors."""
    from pathway_tpu.optimize import placement as pl

    monkeypatch.setenv("PATHWAY_TPU_DEVICE_OPS", "1")
    _set_env(monkeypatch, True, device_ops=True)
    G.clear()
    try:
        runner = ShardedGraphRunner(N_WORKERS)
        pl.POLICY.reset()
        runner.capture(_chain())
        linked = any(
            d["links"] for d in pl.POLICY.decisions().values()
        )
    finally:
        G.clear()
    assert linked


# -- metrics + stats shape -----------------------------------------------------


def test_stats_shape(monkeypatch):
    monkeypatch.setenv("PATHWAY_TPU_DEVICE_RESIDENCY", "1")
    dres.reset_counters()
    s = dres.stats()
    assert s["enabled"] is True and s["forced"] is True
    assert s["events"] == {
        "resident_batches": 0,
        "materializations": 0,
        "device_consumes": 0,
        "declines": 0,
    }
    assert s["h2d"] == {"events": 0, "bytes": 0}
    assert s["d2h"] == {"events": 0, "bytes": 0}
    assert s["bytes_saved"] == 0


def test_metric_families_registered(monkeypatch):
    from pathway_tpu.internals import metrics as m

    dres.reset_counters()
    _run_sharded(True, monkeypatch)
    snap = m.REGISTRY.snapshot()
    for fam in (
        "pathway_device_transfer_h2d_events_total",
        "pathway_device_transfer_h2d_bytes_total",
        "pathway_device_transfer_d2h_events_total",
        "pathway_device_transfer_d2h_bytes_total",
        "pathway_device_residency_bytes_saved_total",
        "pathway_device_residency_events_total",
    ):
        assert fam in snap, fam
    kinds = {
        s["labels"].get("kind")
        for s in snap["pathway_device_residency_events_total"]["series"]
    }
    assert {
        "resident_batches",
        "materializations",
        "device_consumes",
        "declines",
    } <= kinds


def test_pipeline_stats_include_residency(monkeypatch):
    from pathway_tpu.engine import device_pipeline as dp

    monkeypatch.setenv("PATHWAY_TPU_DEVICE_RESIDENCY", "1")
    s = dp.PIPELINE.stats()
    assert "device_residency" in s
    assert s["device_residency"]["forced"] is True

"""Multi-worker parametrization of dataflow ops — every scenario must
produce identical results on 1, 2 and 4 workers (the reference runs its
table-op suites under multiple workers the same way, tests/utils.py:48)."""

import pytest

import pathway_tpu as pw
from pathway_tpu.internals.runner import GraphRunner, ShardedGraphRunner


def people():
    return pw.debug.table_from_rows(
        pw.schema_from_types(name=str, age=int, city=str),
        [
            ("alice", 30, "paris"),
            ("bob", 25, "london"),
            ("carol", 35, "paris"),
            ("dave", 20, "london"),
            ("erin", 28, "berlin"),
            ("frank", 40, "paris"),
        ],
    )


def purchases():
    return pw.debug.table_from_rows(
        pw.schema_from_types(who=str, amount=int),
        [
            ("alice", 10),
            ("bob", 20),
            ("alice", 30),
            ("carol", 5),
            ("erin", 1),
            ("zed", 99),
        ],
    )


SCENARIOS = {
    "select_arith": lambda: people().select(
        name=pw.this.name, next_age=pw.this.age + 1
    ),
    "filter": lambda: people().filter(pw.this.age >= 28),
    "groupby_count_sum": lambda: (
        lambda t: t.groupby(t.city).reduce(
            city=t.city, n=pw.reducers.count(), total=pw.reducers.sum(t.age)
        )
    )(people()),
    "groupby_min_max_avg": lambda: (
        lambda t: t.groupby(t.city).reduce(
            city=t.city,
            youngest=pw.reducers.min(t.age),
            oldest=pw.reducers.max(t.age),
            avg=pw.reducers.avg(t.age),
        )
    )(people()),
    "groupby_tuples": lambda: (
        lambda t: t.groupby(t.city).reduce(
            city=t.city, names=pw.reducers.sorted_tuple(t.name)
        )
    )(people()),
    "inner_join": lambda: (
        lambda p, b: p.join(b, p.name == b.who).select(
            name=p.name, city=p.city, amount=b.amount
        )
    )(people(), purchases()),
    "left_join": lambda: (
        lambda p, b: p.join(b, p.name == b.who, how="left").select(
            name=p.name, amount=b.amount
        )
    )(people(), purchases()),
    "outer_join": lambda: (
        lambda p, b: p.join(b, p.name == b.who, how="outer").select(
            name=p.name, who=b.who, amount=b.amount
        )
    )(people(), purchases()),
    "join_then_groupby": lambda: (
        lambda p, b: (
            lambda j: j.groupby(j.city).reduce(
                city=j.city, spent=pw.reducers.sum(j.amount)
            )
        )(
            p.join(b, p.name == b.who).select(city=p.city, amount=b.amount)
        )
    )(people(), purchases()),
    "concat": lambda: (
        lambda a, b: a.concat_reindex(b)
    )(
        people().select(name=pw.this.name),
        purchases().select(name=pw.this.who),
    ),
    "distinct_via_groupby": lambda: (
        lambda t: t.groupby(t.city).reduce(city=t.city)
    )(people()),
    "flatten": lambda: (
        lambda t: (
            lambda w: w.flatten(w.parts)
        )(t.select(parts=pw.apply(lambda n: tuple(n), t.name)))
    )(people()),
    "update_cells": lambda: (
        lambda t: t.update_cells(
            t.filter(t.age > 30).select(age=pw.this.age + 100)
        )
    )(people()),
    "deduplicate": lambda: (
        lambda t: t.deduplicate(
            value=t.age, instance=t.city, acceptor=lambda new, old: new > old
        )
    )(people()),
    "sort_prev_next": lambda: (
        lambda t: t.sort(key=t.age, instance=t.city)
    )(people()),
    "wordcount_chain": lambda: (
        lambda t: (
            lambda counts: counts.filter(counts.n >= 2).select(
                city=counts.city, n2=counts.n * 10
            )
        )(t.groupby(t.city).reduce(city=t.city, n=pw.reducers.count()))
    )(people()),
    "windowby_tumbling": lambda: (
        lambda t: t.windowby(
            t.age, window=_temporal().tumbling(duration=10)
        ).reduce(
            start=pw.this["_pw_window_start"], n=pw.reducers.count()
        )
    )(people()),
    "windowby_session_instance": lambda: (
        lambda t: t.windowby(
            t.age, window=_temporal().session(max_gap=6), instance=t.city
        ).reduce(
            city=pw.this["_pw_instance"], n=pw.reducers.count()
        )
    )(people()),
    "interval_join": lambda: (
        lambda p, b: p.interval_join(
            b, p.age, b.amount, _temporal().interval(-5, 5)
        ).select(name=pw.left.name, amount=pw.right.amount)
    )(people(), purchases()),
    "asof_join": lambda: (
        lambda p, b: p.asof_join(
            b, p.age, b.amount, direction="backward"
        ).select(name=pw.left.name, amount=pw.right.amount)
    )(people(), purchases()),
    "window_join": lambda: (
        lambda p, b: p.window_join(
            b, p.age, b.amount, _temporal().tumbling(duration=15)
        ).select(name=pw.left.name, amount=pw.right.amount)
    )(people(), purchases()),
    "intersect_difference": lambda: (
        lambda a, b: a.intersect(b).concat_reindex(a.difference(b))
    )(
        people().with_id_from(pw.this.name),
        purchases().with_id_from(pw.this.who),
    ),
    "ix_lookup": lambda: (
        lambda p, b: b.select(
            who=b.who, city=p.ix(p.pointer_from(b.who), optional=True).city
        )
    )(people().with_id_from(pw.this.name), purchases()),
    "sql_group_having": lambda: pw.sql(
        "SELECT city, COUNT(*) AS n FROM t GROUP BY city HAVING COUNT(*) > 1",
        t=people(),
    ),
    "iterate_collatz_steps": lambda: (
        lambda t: pw.iterate(
            lambda tt: dict(
                tt=tt.select(
                    n=pw.if_else(
                        pw.this.n == 1,
                        pw.this.n,
                        pw.if_else(
                            pw.this.n % 2 == 0,
                            pw.this.n // 2,
                            3 * pw.this.n + 1,
                        ),
                    ),
                    steps=pw.if_else(
                        pw.this.n == 1, pw.this.steps, pw.this.steps + 1
                    ),
                )
            ),
            tt=t.select(n=pw.this.age, steps=0),
        ).tt
    )(people()),
}


def _temporal():
    import pathway_tpu.stdlib.temporal as tmp

    return tmp


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
@pytest.mark.parametrize("n_workers", [2, 4])
def test_sharded_matches_single_worker(scenario, n_workers):
    build = SCENARIOS[scenario]
    (base,) = GraphRunner().capture(build())
    (sharded,) = ShardedGraphRunner(n_workers).capture(build())
    assert sorted(base.values(), key=repr) == sorted(
        sharded.values(), key=repr
    ), scenario
    assert set(base.keys()) == set(sharded.keys()), scenario


def test_row_transformer_under_sharding():
    """RecomputeNode pins to worker 0: cross-row pointers must keep working
    (review regression)."""

    @pw.transformer
    class list_len:
        class nodes(pw.ClassArg):
            next = pw.input_attribute()

            @pw.output_attribute
            def length(self) -> int:
                if self.next is None:
                    return 1
                return self.transformer.nodes[self.next].length + 1

    def build():
        base = pw.debug.table_from_rows(
            pw.schema_from_types(tag=str), [("a",), ("b",), ("c",)]
        )
        (bs,) = GraphRunner().capture(base)
        ordered = sorted(bs, key=lambda k: bs[k])
        nodes = pw.debug.table_from_rows(
            pw.schema_from_types(next=pw.Pointer),
            [(ordered[1],), (ordered[2],), (None,)],
        )
        return list_len(nodes).nodes

    (base,) = GraphRunner().capture(build())
    (sharded,) = ShardedGraphRunner(4).capture(build())
    assert sorted(base.values()) == sorted(sharded.values())


def test_gradual_broadcast_under_sharding():
    def build():
        t = pw.debug.table_from_rows(
            pw.schema_from_types(name=str), [(f"r{i}",) for i in range(30)]
        )
        thr = pw.debug.table_from_rows(
            pw.schema_from_types(lo=float, v=float, hi=float),
            [(0.0, 0.5, 1.0)],
        )
        return t._gradual_broadcast(thr, thr.lo, thr.v, thr.hi)

    (base,) = GraphRunner().capture(build())
    (sharded,) = ShardedGraphRunner(4).capture(build())
    assert sorted(base.values(), key=repr) == sorted(
        sharded.values(), key=repr
    )
    assert None not in {r[-1] for r in sharded.values()}


def test_gradual_broadcast_threshold_moves_after_rows_sharded():
    """Threshold change in a LATER commit must re-emit crossers correctly
    when rows live on other workers (review regression)."""
    from pathway_tpu.engine.value import ref_scalar

    runner = ShardedGraphRunner(4)
    t = pw.debug.table_from_rows(
        pw.schema_from_types(name=str), [(f"r{i}",) for i in range(20)]
    )
    thr_rows = [(0.0, 0.1, 1.0)]
    thr = pw.debug.table_from_rows(
        pw.schema_from_types(lo=float, v=float, hi=float), thr_rows
    )
    out = t._gradual_broadcast(thr, thr.lo, thr.v, thr.hi)
    reps = runner.build(out)
    sched = runner._make_scheduler()
    sched.commit()
    low_uppers = sum(
        1 for r in sched.merged_state(reps[0].index).values() if r[-1] == 1.0
    )
    # move the threshold up via the threshold session on worker 0
    thr_node_idx = reps[0].inputs[1].index
    thr_session = None
    for scope in [runner.workers[0].scope]:
        node = scope.nodes[thr_node_idx]
        # walk back to the static source's feeding session is complex;
        # simplest: push a new triplet through a direct batch
    from pathway_tpu.engine.batch import DeltaBatch

    runner.workers[0].scope.nodes[thr_node_idx].push(
        0, DeltaBatch([(ref_scalar("t2"), (0.0, 0.9, 1.0), 1)])
    )
    sched.propagate(sched.time)
    merged = sched.merged_state(reps[0].index)
    high_uppers = sum(1 for r in merged.values() if r[-1] == 1.0)
    assert len(merged) == 20  # no rows lost on re-emit
    assert high_uppers > low_uppers


# -- the runners agree: one sweep, one commit step ---------------------------

RUNNER_WORKERS = [1, 2, 4]  # GraphRunner, ShardedGraphRunner(2), (4)


def _streaming_wordcount(seen: list, persistent_id=None):
    """A small groupby-and-subscribe graph over a python connector that
    feeds two batches, the second once the sink has seen the first."""
    import threading

    from pathway_tpu.internals.parse_graph import G

    G.clear()
    first_seen = threading.Event()

    class Words(pw.io.python.ConnectorSubject):
        def run(self) -> None:
            for w in ("a", "b", "a"):
                self.next(word=w)
            first_seen.wait(10)
            for w in ("b", "c"):
                self.next(word=w)

    words = pw.io.python.read(
        Words(), schema=pw.schema_from_types(word=str),
        autocommit_duration_ms=5, persistent_id=persistent_id,
    )
    counts = words.groupby(words.word).reduce(
        word=words.word, n=pw.reducers.count()
    )

    def on_change(key, row, time, is_addition) -> None:
        seen.append((row["word"], row["n"], is_addition))
        first_seen.set()

    pw.io.subscribe(counts, on_change=on_change)


def _runner_with_sinks(n_workers: int, persistence_config=None):
    """What ``pw.run`` builds for ``threads=n_workers``."""
    from pathway_tpu.internals.parse_graph import G

    if n_workers > 1:
        runner = ShardedGraphRunner(
            n_workers, persistence_config=persistence_config
        )
        runner.attach_sinks()
        return runner
    runner = GraphRunner(persistence_config=persistence_config)
    for sink in G.sinks:
        driver = sink.attach(runner.scope, runner.build(sink.table))
        if driver is not None:
            runner.drivers.append(driver)
    return runner


@pytest.mark.parametrize("n_workers", RUNNER_WORKERS)
def test_every_runner_records_operator_stages_in_a_sampled_commit(n_workers):
    from pathway_tpu.internals import tracing
    from pathway_tpu.internals.parse_graph import G

    seen: list = []
    _streaming_wordcount(seen)
    tracing.TRACER.configure(enabled=True, sample=1, clear=True)
    try:
        _runner_with_sinks(n_workers).run()
        traces = tracing.TRACER.traces()
    finally:
        tracing.TRACER.drop()
        tracing.TRACER.configure(enabled=False, clear=True)
        G.clear()
    assert ("a", 2, True) in seen and ("c", 1, True) in seen
    stages = tracing.stage_totals()["stages"]
    assert {"op.GroupbyNode", "op.SubscribeNode"} <= set(stages)
    assert stages["op.GroupbyNode"]["counts"]["batches"] >= 1
    spans = [
        s for t in traces for s in t["spans"] if s["name"].startswith("op.")
    ]
    cats = {s["name"]: s["cat"] for s in spans}
    assert cats["op.GroupbyNode"] == "op"
    assert cats["op.SubscribeNode"] == "sink"
    # what the sharded and mesh sweeps' hand-written spans carried rides
    # the stage: the node, its replica, its name
    assert {s["args"]["shard"] for s in spans} <= set(range(n_workers))
    assert all("node" in s["args"] and "label" in s["args"] for s in spans)
    if n_workers > 1:
        groupby_shards = {
            s["args"]["shard"] for s in spans if s["name"] == "op.GroupbyNode"
        }
        assert len(groupby_shards) > 1


@pytest.mark.parametrize("n_workers", RUNNER_WORKERS)
def test_every_runner_calls_the_commit_hooks_in_one_order(
    n_workers, monkeypatch, tmp_path
):
    """drain_until -> the journal's on_commit -> the snapshot manager's
    -> the monitor's, each commit, all with that commit's time."""
    import types

    from pathway_tpu.engine import device_pipeline
    from pathway_tpu.engine.persistence import PersistentDriver
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.persistence import Backend, Config

    calls: list = []
    real_drain = device_pipeline.drain_until
    real_journal = PersistentDriver.on_commit

    def drain_until(time):
        calls.append(("drain_until", time))
        return real_drain(time)

    def journal_on_commit(self, time):
        calls.append(("journal", time))
        return real_journal(self, time)

    class Snapshots:
        def restore(self, scopes, drivers):
            return None

        def on_commit(self, scopes, drivers, time):
            calls.append(("snapshot", time))

        def snapshot(self, scopes, drivers, time):
            calls.append(("final_snapshot", time))

    class Monitor:
        scheduler = None

        def connector(self, name):
            return types.SimpleNamespace()

        def on_commit(self, time, started):
            calls.append(("monitor", time))

    monkeypatch.setattr(device_pipeline, "drain_until", drain_until)
    monkeypatch.setattr(PersistentDriver, "on_commit", journal_on_commit)
    monkeypatch.setattr(
        GraphRunner, "_operator_snapshot_manager", lambda self: Snapshots()
    )
    seen: list = []
    _streaming_wordcount(seen, persistent_id="words")
    try:
        runner = _runner_with_sinks(
            n_workers, Config(Backend.filesystem(tmp_path / "journal"))
        )
        runner.monitor = Monitor()
        sched = runner.run()
    finally:
        G.clear()
    assert ("c", 1, True) in seen
    # the run's last offsets and its final snapshot come after the hooks
    assert calls[-2:] == [
        ("journal", sched.time), ("final_snapshot", sched.time)
    ]
    hooks = calls[:-2]
    order = ["drain_until", "journal", "snapshot", "monitor"]
    assert len(hooks) >= 2 * len(order) and len(hooks) % len(order) == 0
    times = []
    for i in range(0, len(hooks), len(order)):
        group = hooks[i:i + len(order)]
        assert [name for name, _ in group] == order, hooks
        assert len({time for _, time in group}) == 1, group
        times.append(group[0][1])
    assert times == sorted(set(times)) and times[-1] < sched.time

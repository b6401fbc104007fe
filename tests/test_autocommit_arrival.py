"""An autocommit window runs from a row's arrival, not from the poll that
found it (ISSUE 33): the time a row queued while the pump was inside a
commit counts against its window, and a row polled as it arrives waits
exactly its window.

No test sleeps: ``FakeClock`` stands in for the ``time`` module of
``internals/runner.py`` and ``engine/connectors.py``, moves only when the
pump sleeps, and fires what was scheduled at the wake that reaches its
time, so a row pushed from there arrives the instant the pump polls it.
"""

from __future__ import annotations

import threading

import pytest

import pathway_tpu as pw
from pathway_tpu.engine import connectors
from pathway_tpu.engine.connectors import (
    INSERT,
    InputDriver,
    ParsedEvent,
    Parser,
    QueueReader,
    Reader,
)
from pathway_tpu.engine.graph import Scope
from pathway_tpu.internals import runner, tracing
from pathway_tpu.internals.parse_graph import G

T0 = 1000.0
MS = 0.001


class FakeClock:
    def __init__(self) -> None:
        self.now = T0
        self._due: list = []  # (at, fn), sorted

    def monotonic(self) -> float:
        return self.now

    def sleep(self, seconds: float) -> None:
        self.now += seconds
        while self._due and self._due[0][0] <= self.now:
            self._due.pop(0)[1]()

    def at(self, when: float, fn) -> None:
        self._due.append((when, fn))
        self._due.sort(key=lambda entry: entry[0])


@pytest.fixture
def clock(monkeypatch, own_stage_table):
    fake = FakeClock()
    monkeypatch.setattr(runner, "_time", fake)
    monkeypatch.setattr(connectors, "_time", fake)
    G.clear()
    yield fake
    G.clear()


class _Feed(pw.io.python.ConnectorSubject):
    """A subject whose rows the test pushes itself, at the clock's times;
    its thread only keeps the reader open until ``stop``."""

    def __init__(self) -> None:
        super().__init__()
        self._stopped = threading.Event()

    def run(self) -> None:
        self._stopped.wait(30)

    def stop(self) -> None:
        self.close()
        self._stopped.set()


def _stream(clock, window_ms):
    """A python connector with ``window_ms`` feeding a sink that notes,
    for each row, the clock's time at its commit."""
    feed = _Feed()
    table = pw.io.python.read(
        feed, schema=pw.schema_from_types(a=int), autocommit_duration_ms=window_ms
    )
    seen: list = []
    pw.io.subscribe(
        table,
        on_change=lambda key, row, time, is_addition: seen.append(
            (row["a"], time, clock.now)
        ),
    )
    return feed, seen


def _commit_counts() -> dict:
    return tracing.stage_totals()["stages"]["commit"]["counts"]


# (i) and (vi): the loop is shared, so the sharded runner shows the same
@pytest.mark.parametrize("threads", [1, 2])
def test_a_row_that_queued_40ms_is_committed_10ms_after_its_poll(clock, threads):
    feed, seen = _stream(clock, 50)
    feed.next(a=1)  # arrives at T0, while the pump is away ...
    clock.now = T0 + 40 * MS  # ... and is first polled 40 ms later
    clock.at(T0 + 200 * MS, feed.stop)
    pw.run(threads=threads)
    ((a, _time, committed),) = seen
    assert a == 1
    # arrival + 50 ms, in 1 ms slices of sleep: not the poll + 50 ms
    assert 9 * MS <= committed - (T0 + 40 * MS) <= 12 * MS
    # (v) both counts are of the oldest row, from its arrival
    counts = _commit_counts()
    assert counts["arrival_to_poll_ns"] == pytest.approx(40e6, abs=1e3)
    assert 50e6 - 1e3 <= counts["commit_wait_ns"] <= 52e6


# (ii) and (vi)
@pytest.mark.parametrize("threads", [1, 2])
def test_a_row_polled_as_it_arrives_waits_its_whole_window(clock, threads):
    feed, seen = _stream(clock, 50)
    for i, at_ms in enumerate((20, 30, 45)):
        clock.at(T0 + at_ms * MS, lambda i=i: feed.next(a=i))
    clock.at(T0 + 300 * MS, feed.stop)
    pw.run(threads=threads)
    assert sorted(a for a, _time, _at in seen) == [0, 1, 2]
    # three pushes inside one window: one commit, a window after the first
    assert len({time for _a, time, _at in seen}) == 1
    arrived = {at for _a, _time, at in seen}
    assert len(arrived) == 1
    first_polled = T0 + 20 * MS
    (committed,) = arrived
    # the pump's idle back-off wakes it a little after 20 ms; the window
    # runs from that wake, which is when the row arrived
    assert 50 * MS - 1e-6 <= committed - first_polled <= 58 * MS
    counts = _commit_counts()
    assert counts["arrival_to_poll_ns"] == 0
    assert 50e6 - 1e3 <= counts["commit_wait_ns"] <= 52e6


# (iv)
@pytest.mark.parametrize("queued_ms", [0, 40])
def test_a_zero_window_connector_commits_at_its_poll(clock, queued_ms):
    feed, seen = _stream(clock, None)
    feed.next(a=7)
    clock.now = polled = T0 + queued_ms * MS
    clock.at(T0 + 100 * MS, feed.stop)
    pw.run()
    assert seen == [(7, seen[0][1], polled)]
    counts = _commit_counts()
    # no sleep between the poll and the commit, whatever the stamp says
    assert counts["commit_wait_ns"] == counts["arrival_to_poll_ns"]
    assert counts["arrival_to_poll_ns"] == pytest.approx(queued_ms * 1e6, abs=1e3)


# (iii)
class _OneColumn(Parser):
    def __init__(self) -> None:
        super().__init__(["a"])

    def parse(self, payload):
        return [ParsedEvent(INSERT, (payload,))]


class _Unstamped(Reader):
    """A reader that cannot say when its entries arrived."""

    def __init__(self) -> None:
        self.entries: list = []

    def poll(self):
        out, self.entries = self.entries, []
        return out, False


def _driver(reader) -> InputDriver:
    return InputDriver(
        Scope().input_session(1), reader, _OneColumn(), autocommit_duration_ms=50
    )


def test_the_stamp_is_the_oldest_uncommitted_push_and_the_commit_pops_it(clock):
    reader = QueueReader()
    driver = _driver(reader)
    assert driver.poll() == "idle" and driver.first_pending_wall is None
    clock.now = T0 + 1.0
    reader.push(1)
    clock.now = T0 + 1.5
    reader.push(2)
    clock.now = T0 + 2.0
    assert driver.poll() == "data"
    assert driver.first_pending_wall == T0 + 1.0
    assert driver.first_pending_polled == T0 + 2.0
    # a later poll of younger rows leaves the oldest one's stamp
    clock.now = T0 + 3.0
    reader.push(3)
    clock.now = T0 + 3.5
    assert driver.poll() == "data"
    assert driver.first_pending_wall == T0 + 1.0
    took = runner._Arrivals([driver])
    assert (took.oldest, took.polled, took.sources) == (
        T0 + 1.0, T0 + 2.0, [driver.source_name],
    )
    assert driver.first_pending_wall is None
    assert driver.first_pending_polled is None
    # popped once: a second commit with no new row has no stamp
    again = runner._Arrivals([driver])
    assert (again.oldest, again.polled, again.sources) == (None, None, [])
    # the next commit's oldest row is the next push
    clock.now = T0 + 4.0
    reader.push(4)
    clock.now = T0 + 4.2
    assert driver.poll() == "data"
    assert driver.first_pending_wall == T0 + 4.0


def test_a_reader_with_no_stamps_gives_the_polls_time(clock):
    reader = _Unstamped()
    driver = _driver(reader)
    reader.entries.append((1, "s", {}))
    clock.now = T0 + 2.0
    assert driver.poll() == "data"
    assert driver.first_pending_wall == T0 + 2.0
    assert driver.first_pending_polled == T0 + 2.0
    took = runner._Arrivals([driver])
    assert runner._elapsed_ns(took.oldest, took.polled) == 0


def test_the_oldest_stamp_of_several_drivers_brings_its_own_poll(clock):
    early, late = QueueReader(), QueueReader()
    drivers = [_driver(late), _driver(early)]
    clock.now = T0 + 1.0
    early.push(1)
    clock.now = T0 + 2.0
    late.push(2)
    clock.now = T0 + 3.0
    assert drivers[0].poll() == "data"
    clock.now = T0 + 4.0
    assert drivers[1].poll() == "data"
    took = runner._Arrivals(drivers)
    assert (took.oldest, took.polled) == (T0 + 1.0, T0 + 4.0)
    assert len(took.sources) == 2
    assert all(d.first_pending_wall is None for d in drivers)


def test_an_event_a_synchronization_group_held_counts_from_its_release(clock):
    from pathway_tpu.io._synchronization import InputSynchronizationGroup

    group = InputSynchronizationGroup(max_difference=10)
    readers = [QueueReader(), QueueReader()]
    drivers = [_driver(reader) for reader in readers]
    for driver in drivers:
        driver.sync_group, driver.sync_col = group, 0
        group.register(driver)
    fast, slow = drivers
    clock.now = T0 + 1.0
    readers[0].push(0)
    readers[0].push(50)  # held: 50 > slow's 0 + 10
    readers[1].push(0)
    clock.now = T0 + 2.0
    for _ in range(2):  # the first round only sets both frontiers
        for driver in drivers:
            driver.poll()
    # fast's first event waited for slow's frontier: held and released
    assert fast.first_pending_wall == T0 + 2.0
    assert slow.first_pending_wall == T0 + 1.0
    runner._Arrivals(drivers)
    clock.now = T0 + 5.0
    readers[1].push(45)
    clock.now = T0 + 6.0
    assert slow.poll() == "data" and slow.first_pending_wall == T0 + 5.0
    clock.now = T0 + 7.0
    assert fast.poll() == "data"  # the backlog drains: no entry of this poll
    assert fast.first_pending_wall == fast.first_pending_polled == T0 + 7.0

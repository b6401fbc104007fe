"""UDF executors: how a batch of pending rows is driven through user code.

Reference: python/pathway/internals/udfs/executors.py:92,132 (SyncExecutor /
AsyncExecutor with capacity+timeout). The engine hands executors whole
commit-batches of rows (engine/graph.py BatchApplyNode), which is also the
microbatching seam for TPU UDFs: a BatchExecutor receives all rows at once
and can pad them into one jit call instead of row-at-a-time dispatch — the
TPU-native replacement for the reference's tokio `map_named_async`
(src/engine/dataflow/operators.rs:182).
"""

from __future__ import annotations

import asyncio
import inspect
import threading
from typing import Any, Callable, Sequence

from typing import Awaitable

from pathway_tpu.internals import tracing as _tracing
from pathway_tpu.internals.udfs.retries import AsyncRetryStrategy

RowResult = tuple[bool, Any]  # (ok, value-or-exception)


def make_kw_fn(fn: Callable, n_pos: int, kw_names: list[str]) -> Callable:
    """Rebind a flat positional arg tuple to ``fn(*pos, **kw)``."""
    if not kw_names:
        return fn

    def wrapped(*vals: Any) -> Any:
        pos = vals[:n_pos]
        kws = dict(zip(kw_names, vals[n_pos:]))
        return fn(*pos, **kws)

    return wrapped


class Executor:
    kind = "sync"

    def run(
        self,
        fn: Callable[..., Any],
        rows: Sequence[tuple],
        retry: AsyncRetryStrategy | None = None,
    ) -> list[RowResult]:
        raise NotImplementedError


class SyncExecutor(Executor):
    def run(self, fn, rows, retry=None):
        out: list[RowResult] = []
        for args in rows:
            try:
                if retry is not None:
                    out.append((True, retry.invoke_sync(lambda: fn(*args))))
                else:
                    out.append((True, fn(*args)))
            except Exception as e:  # noqa: BLE001
                out.append((False, e))
        return out


class _EventLoopThread:
    """A process-wide background event loop for async UDFs.

    The reference runs async UDFs on a shared tokio runtime
    (src/async_runtime.rs); the analog here is one persistent loop thread —
    it survives across commits (async clients keep their loop) and works
    whether or not the caller itself runs inside an event loop (notebooks).
    """

    _lock = threading.Lock()
    _instance: "_EventLoopThread | None" = None

    def __init__(self) -> None:
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(
            target=self.loop.run_forever, name="pw-udf-loop", daemon=True
        )
        self.thread.start()

    @classmethod
    def get(cls) -> "_EventLoopThread":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    def run(self, coro: Awaitable[Any]) -> Any:
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result()


class AsyncExecutor(Executor):
    """Concurrent execution on the shared loop thread, bounded by
    ``capacity``.

    ``timeout`` (seconds) applies per call, inside the retry loop like the
    reference (executors.py:286 async_options).
    """

    kind = "async"

    def __init__(
        self, capacity: int | None = None, timeout: float | None = None
    ) -> None:
        self.capacity = capacity
        self.timeout = timeout

    def run(self, fn, rows, retry=None):
        async def one(args: tuple, sem: asyncio.Semaphore | None):
            async def call():
                coro = fn(*args)
                if self.timeout is not None:
                    return await asyncio.wait_for(coro, self.timeout)
                return await coro

            try:
                if sem is not None:
                    async with sem:
                        if retry is not None:
                            return (True, await retry.invoke(call))
                        return (True, await call())
                if retry is not None:
                    return (True, await retry.invoke(call))
                return (True, await call())
            except Exception as e:  # noqa: BLE001
                return (False, e)

        async def gather():
            sem = (
                asyncio.Semaphore(self.capacity)
                if self.capacity is not None
                else None
            )
            return await asyncio.gather(*(one(args, sem) for args in rows))

        return _EventLoopThread.get().run(gather())


class BatchExecutor(Executor):
    """Whole-batch execution: ``fn`` receives parallel lists (one per arg)
    and returns a list of results — the jit-microbatch entry point.

    ``max_batch_size`` splits oversized commits so padded device buffers
    stay bounded: every chunk holds exactly that many rows except a
    commit's tail (no cap: the whole commit is one chunk).  The
    ``udf.batch`` stage counts a chunk short of the cap as ``narrowed``.
    """

    kind = "batch"

    def __init__(self, max_batch_size: int | None = None) -> None:
        self.max_batch_size = max_batch_size

    def run(self, fn, rows, retry=None):
        out: list[RowResult] = []
        step = self.max_batch_size or len(rows) or 1
        for start in range(0, len(rows), step):
            chunk = rows[start : start + step]
            with _tracing.stage(
                "udf.batch", rows=len(chunk), narrowed=int(len(chunk) < step)
            ):
                cols = tuple(list(c) for c in zip(*chunk))
                try:
                    if retry is not None:
                        results = retry.invoke_sync(lambda: fn(*cols))
                    else:
                        results = fn(*cols)
                    results = list(results)
                    if len(results) != len(chunk):
                        raise ValueError(
                            f"batch UDF returned {len(results)} results "
                            f"for {len(chunk)} rows"
                        )
                    out.extend((True, r) for r in results)
                except Exception as e:  # noqa: BLE001
                    out.extend((False, e) for _ in chunk)
        return out


def sync_executor() -> SyncExecutor:
    return SyncExecutor()


def auto_executor(fn: Callable[..., Any]) -> Executor:
    if inspect.iscoroutinefunction(fn):
        return AsyncExecutor()
    return SyncExecutor()


def async_executor(
    capacity: int | None = None, timeout: float | None = None
) -> AsyncExecutor:
    return AsyncExecutor(capacity=capacity, timeout=timeout)


def batch_executor(max_batch_size: int | None = None) -> BatchExecutor:
    return BatchExecutor(max_batch_size=max_batch_size)

"""Columnar device bridge: column-major batches + vectorized evaluation.

This is the engine's answer to the reference's native columnar hot path
(reference: src/engine/dataflow.rs — tables as differential collections
processed in Rust). Here large commits are processed column-at-a-time:

- :class:`ColumnarView` materialises a column-major, NumPy-backed view of a
  batch's inserted rows. Extraction is lazy per column and falls back (to
  the per-row interpreter) whenever a column is not a clean homogeneous
  numeric/bool/string sequence — so ERROR poisoning, ``None`` handling and
  arbitrary Python values keep their exact row-wise semantics.
- :func:`eval_columnar` evaluates an engine expression tree over a view in
  whole-column NumPy ops (the batch-wise fast path promised by
  engine/expression.py's module docstring).
- :func:`to_device` hands a column to ``jax.Array`` zero-copy (dlpack path
  for aligned arrays); this is how numeric columns ride to TPU HBM without
  a Python-tuple detour (BASELINE's "zero-copy bridge").
- :func:`factorize` / :func:`segment_sum` back the vectorized groupby
  (engine/graph.py GroupbyNode): per-row work collapses to one
  ``np.unique`` + one segment reduction, leaving only per-*group* Python.

Integer semantics note: the vectorized path computes in int64, which is the
reference engine's integer type as well (Value::Int is i64,
src/engine/value.rs:207) — Python bigints beyond int64 fall back to the
row-wise interpreter at extraction time (OverflowError → object dtype).
"""

from __future__ import annotations

import weakref
from typing import Any, Sequence

import numpy as np

from pathway_tpu.engine import expression as ex
from pathway_tpu.internals import tracing as _tracing
from pathway_tpu.native import kernels as _native

# Batches smaller than this are cheaper to run through the per-row
# interpreter than to columnarise.
VECTOR_THRESHOLD = 256

_OK_KINDS = frozenset("bifU")


class ColumnarView:
    """Lazy column-major view over a batch's rows (insertions only).

    ``from_entries=True`` views ``(key, row, diff)`` entries directly —
    saving the 1M-element row list comprehension on the hot paths."""

    __slots__ = ("rows", "n", "_cols", "_entries")

    def __init__(
        self, rows: Sequence[tuple], from_entries: bool = False
    ) -> None:
        self.rows = rows
        self.n = len(rows)
        self._cols: dict[int, np.ndarray | None] = {}
        self._entries = from_entries

    def column(self, index: int) -> np.ndarray | None:
        """The column as a NumPy array, or None if not cleanly columnar
        (mixed types, None/ERROR values, nested containers, bigints)."""
        got = self._cols.get(index, _MISSING)
        if got is not _MISSING:
            return got
        arr = None
        if _native is not None and isinstance(self.rows, list):
            # one C pass for int64/float64/bool columns; returns None for
            # strings and anything non-clean (falls through below)
            arr = _native.extract_column(self.rows, index, self._entries)
        if arr is None:
            values = (
                [e[1][index] for e in self.rows]
                if self._entries
                else [row[index] for row in self.rows]
            )
            arr = _extract(values)
        self._cols[index] = arr
        return arr


def materialize_columns(view: ColumnarView, arity: int) -> list[np.ndarray]:
    """Every column of an entry view as an array — clean dtypes where
    extraction succeeds, an exact-object array otherwise (never None).
    Object columns keep the original Python values, so a row round-trip
    through ``Columns.to_entries`` is lossless."""
    cols = []
    rows = view.rows
    for c in range(arity):
        col = view.column(c)
        if col is None:
            arr = np.empty(view.n, object)
            arr[:] = (
                [e[1][c] for e in rows]
                if view._entries
                else [r[c] for r in rows]
            )
            col = arr
        cols.append(col)
    return cols


_MISSING = object()


class PayloadView:
    """ColumnarView-compatible adapter over a columnar batch payload
    (engine/batch.py Columns): columns are already arrays, so extraction
    is a dtype screen, not a per-row pass."""

    __slots__ = ("_payload", "n")

    def __init__(self, payload: Any) -> None:
        self._payload = payload
        self.n = payload.n

    def column(self, index: int) -> np.ndarray | None:
        col = self._payload.cols[index]
        return col if col.dtype.kind in _OK_KINDS else None


def _extract(values: list) -> np.ndarray | None:
    """list of Python scalars -> homogeneous ndarray, else None."""
    kinds = set(map(type, values))
    if not kinds or not kinds.issubset(_CLEAN_TYPES):
        return None
    if len(kinds) > 1:
        # int+float mixing would silently promote ints in passthrough
        # columns; bool+int would demote. Keep exact dtypes only.
        return None
    if next(iter(kinds)) is str and any("\x00" in v for v in values):
        # NumPy U-dtype strips trailing NULs on round-trip
        return None
    try:
        arr = np.asarray(values)
    except (OverflowError, ValueError):
        return None
    if arr.dtype == object or arr.dtype.kind not in _OK_KINDS:
        return None
    return arr


_CLEAN_TYPES = frozenset((int, float, bool, str))


class NotVectorizable(Exception):
    """Raised when an expression (or its operand columns) can't run
    column-wise; the caller falls back to the row interpreter."""


# Ops where NumPy semantics diverge from the per-row interpreter on edge
# inputs (ZeroDivisionError -> ERROR poisoning vs inf/nan; 0**-1 etc.).
_DIVISION_OPS = frozenset(("/", "//", "%"))

_I64_MAX = (1 << 63) - 1


def _guard_int_overflow(op: str, a: np.ndarray, b: np.ndarray) -> None:
    """int64 wraps silently in NumPy while the row interpreter computes exact
    Python ints — reject any int op whose result could leave int64 range.
    Conservative magnitude bounds (exact Python-int arithmetic, O(n) maxes)."""
    if op not in ("+", "-", "*", "**", "<<"):
        return  # //, %, comparisons, bitwise cannot exceed operand magnitude
    amax = int(np.abs(a).max(initial=0))
    bmax = int(np.abs(b).max(initial=0))
    if amax < 0 or bmax < 0:  # np.abs(INT64_MIN) wraps negative
        raise NotVectorizable(f"possible int64 overflow in {op}")
    if op in ("+", "-"):
        safe = amax + bmax <= _I64_MAX
    elif op == "*":
        safe = amax * bmax <= _I64_MAX
    elif op == "**":
        safe = bmax <= 63 and (amax <= 1 or amax.bit_length() * bmax <= 63)
    else:  # <<
        safe = bmax <= 62 and amax.bit_length() + bmax <= 63
    if not safe:
        raise NotVectorizable(f"possible int64 overflow in {op}")


def eval_columnar(expr: ex.EngineExpression, view: ColumnarView) -> np.ndarray:
    """Evaluate ``expr`` over all rows at once. Raises NotVectorizable when
    any sub-expression or operand column requires row-wise treatment."""
    if isinstance(expr, ex.ColumnRef):
        col = view.column(expr.index)
        if col is None:
            raise NotVectorizable(f"column {expr.index}")
        return col
    if isinstance(expr, ex.Const):
        v = expr.value
        if type(v) not in _CLEAN_TYPES:
            raise NotVectorizable("const")
        return np.broadcast_to(np.asarray(v), (view.n,))
    if isinstance(expr, ex.Binary):
        if expr.op == "@":
            raise NotVectorizable("matmul")
        a = eval_columnar(expr.left, view)
        b = eval_columnar(expr.right, view)
        if expr.op in _DIVISION_OPS:
            if b.dtype.kind not in "bif" or not np.all(b):
                raise NotVectorizable("division edge case")
        if expr.op == "**":
            if a.dtype.kind == "i" and (b.dtype.kind != "i" or np.any(b < 0)):
                raise NotVectorizable("pow edge case")
        if a.dtype.kind == "U" or b.dtype.kind == "U":
            if a.dtype.kind != b.dtype.kind:
                raise NotVectorizable("string vs non-string operands")
            if expr.op not in ("==", "!=", "<", "<=", ">", ">=", "+"):
                raise NotVectorizable("string op")
            if expr.op == "+":
                return np.char.add(a, b)
        if expr.op in ("+", "-", "*", "**", "//", "%") and (
            a.dtype.kind == "b" or b.dtype.kind == "b"
        ):
            # NumPy bool arithmetic (e.g. True+True=True) diverges from
            # Python's int promotion (True+True=2)
            raise NotVectorizable("bool arithmetic")
        if a.dtype.kind == "i" and b.dtype.kind == "i":
            _guard_int_overflow(expr.op, a, b)
        try:
            with np.errstate(all="raise"):
                return expr.fn(a, b)
        except Exception as e:  # noqa: BLE001 — row path owns error semantics
            raise NotVectorizable(str(e)) from None
    if isinstance(expr, ex.Unary):
        a = eval_columnar(expr.arg, view)
        if expr.op == "not":
            if a.dtype.kind != "b":
                raise NotVectorizable("not on non-bool")
            return ~a
        if expr.op == "~" and a.dtype.kind == "b":
            return ~a
        try:
            with np.errstate(all="raise"):
                return expr.fn(a)
        except Exception as e:  # noqa: BLE001
            raise NotVectorizable(str(e)) from None
    if isinstance(expr, ex.BooleanChain):
        parts = [eval_columnar(arg, view) for arg in expr.args]
        for p in parts:
            if p.dtype.kind != "b":
                raise NotVectorizable("boolean chain on non-bool")
        fn = np.logical_and if expr.op == "and" else np.logical_or
        out = parts[0]
        for p in parts[1:]:
            out = fn(out, p)
        return out
    if isinstance(expr, ex.IfElse):
        c = eval_columnar(expr.cond, view)
        if c.dtype.kind != "b":
            raise NotVectorizable("if_else condition not bool")
        t = eval_columnar(expr.then, view)
        f = eval_columnar(expr.otherwise, view)
        if t.dtype != f.dtype:
            raise NotVectorizable("if_else branch dtype mismatch")
        return np.where(c, t, f)
    if isinstance(expr, ex.IsNone):
        # a successfully extracted column holds no Nones by construction
        eval_columnar(expr.arg, view)
        val = bool(expr.negated)
        return np.broadcast_to(np.asarray(val), (view.n,))
    raise NotVectorizable(type(expr).__name__)


def eval_expressions_columnar_cols(
    expressions: Sequence[ex.EngineExpression],
    rows: Sequence[tuple],
    from_entries: bool = False,
) -> list[list] | None:
    """Vectorized ExpressionNode body: all expressions over all rows,
    returned column-major as plain Python lists (exact interpreter types).
    None signals fallback to the row interpreter."""
    view = ColumnarView(rows, from_entries=from_entries)
    outs = []
    for expr in expressions:
        try:
            arr = eval_columnar(expr, view)
        except NotVectorizable:
            return None
        outs.append(np.ascontiguousarray(arr).tolist())
    return outs


def eval_expressions_columnar(
    expressions: Sequence[ex.EngineExpression], rows: Sequence[tuple]
) -> list[tuple] | None:
    """Row-major variant of :func:`eval_expressions_columnar_cols`."""
    outs = eval_expressions_columnar_cols(expressions, rows)
    if outs is None:
        return None
    return list(zip(*outs))


# -- groupby acceleration ----------------------------------------------------


def factorize(values: np.ndarray) -> tuple[list, np.ndarray]:
    """Distinct values + the inverse index of each row's group."""
    uniques, inverse = np.unique(values, return_inverse=True)
    return uniques.tolist(), inverse


def factorize_multi(
    arrays: "list[np.ndarray]",
) -> tuple[np.ndarray, np.ndarray]:
    """Composite factorization over several same-length columns:
    ``(first, inverse)`` where ``first[g]`` is a representative row index
    of distinct tuple ``g`` and ``inverse[i]`` is row ``i``'s tuple id.

    Tuple identity is reduced to integer-code identity column by column:
    per-column dense codes (``np.unique``) chain through a mixed-radix
    combine, re-densified each step so codes stay ``< n**2`` and the
    int64 product cannot overflow. No Python tuples are materialised.
    """
    combined: np.ndarray | None = None
    for a in arrays:
        _u, inv = np.unique(a, return_inverse=True)
        inv = inv.astype(np.int64, copy=False).reshape(-1)
        if combined is None:
            combined = inv
        else:
            _pu, prev = np.unique(combined, return_inverse=True)
            combined = prev.astype(np.int64).reshape(-1) * np.int64(
                len(_u)
            ) + inv
    assert combined is not None
    _uc, first, inverse = np.unique(
        combined, return_index=True, return_inverse=True
    )
    return first, inverse.reshape(-1)


def segment_count(
    inverse: np.ndarray, diffs: np.ndarray, n_groups: int
) -> np.ndarray:
    """Per-group sum of diffs (int64-exact)."""
    out = np.zeros(n_groups, np.int64)
    np.add.at(out, inverse, diffs)
    return out


def int_sum_overflow_risk(col: np.ndarray, n: int, dmax: int) -> bool:
    """True when an int64 segment sum of ``col`` (diff magnitudes up to
    ``dmax`` over ``n`` rows) could leave int64 range — the vectorized
    paths compute in wrapping int64 while the row interpreter uses exact
    Python ints, so risky batches must take the row path."""
    if col.dtype.kind != "i" or col.size == 0:
        return False
    amax = int(np.abs(col).max())
    if amax < 0 or dmax < 0:  # np.abs(INT64_MIN) wraps negative
        return True
    return amax * n * dmax > (1 << 62)


def segment_sum(
    inverse: np.ndarray,
    values: np.ndarray,
    diffs: np.ndarray,
    n_groups: int,
) -> np.ndarray:
    """Per-group sum of value*diff; int64-exact for int/bool inputs."""
    if values.dtype.kind in "ib":
        out = np.zeros(n_groups, np.int64)
        np.add.at(out, inverse, values.astype(np.int64) * diffs)
        return out
    return np.bincount(
        inverse, weights=values * diffs, minlength=n_groups
    )


# -- zero-copy device hand-off ----------------------------------------------


_DEVICE_COUNT: int | None = None


def device_count() -> int:
    """Visible JAX devices, cached; 0 when jax is unavailable — never
    raises.  Mesh-detection gates (collective exchange's one-device-per-
    shard rule) call this on delivery hot paths, so the probe runs once."""
    global _DEVICE_COUNT
    if _DEVICE_COUNT is None:
        try:
            import jax

            _DEVICE_COUNT = len(jax.devices())
        except Exception:
            _DEVICE_COUNT = 0
    return _DEVICE_COUNT


def to_device(arr: np.ndarray, sharding: Any | None = None):
    """NumPy column -> jax.Array, zero-copy where the backend allows (CPU
    dlpack aliasing; on TPU this is the single necessary host->HBM DMA).

    Counted on the ``pathway_device_transfer_*`` ledger in both modes —
    zero-copy backends over-count by the aliased bytes, which is the
    conservative direction for the transfer-reduction gates."""
    import jax

    from pathway_tpu.engine import device_residency as _dres

    _dres.record_h2d(int(getattr(arr, "nbytes", 0)))
    if sharding is not None:
        return jax.device_put(arr, sharding)
    return jax.numpy.asarray(arr)


def rows_to_device_matrix(rows: Sequence[tuple], col: int, dtype=np.float32):
    """Stack a vector-valued column ([dim]-tuples/ndarrays) into one [n, dim]
    device array — the ingest feed for the HBM KNN index."""
    mat = np.asarray([np.asarray(r[col], dtype) for r in rows], dtype)
    return to_device(mat)


# -- device-resident row cells ------------------------------------------------

#: device batches produced since the last commit boundary (weak: a batch
#: no row references anymore needs no decay)
_LIVE_HANDLES: "weakref.WeakSet" = weakref.WeakSet()


def _identity(arr: np.ndarray) -> np.ndarray:
    return arr


class DeviceBatchHandle:
    """A ``[n, dim]`` device array with a lazily-downloaded host twin —
    produced by device UDF batches (the embedder), consumed directly by
    device operators (the HBM index) without a host round trip.

    Lifecycle: within the producing commit BOTH copies may exist — a
    subscribe callback materialising the host twin must not steal the
    device copy from an index operator later in the same sweep. At
    commit end the scheduler calls :func:`decay_device_batches`, which
    downloads any still-live batch (the DMA was prefetched, so this is a
    cheap wait) and releases its HBM. HBM usage is therefore bounded by
    one commit's worth of batches; rows retained in table state hold
    only the host twin — the same RAM the eager path used.
    """

    __slots__ = ("dev", "_host", "_prefetched", "__weakref__")

    def __init__(self, dev: Any) -> None:
        self.dev = dev
        self._host = None
        self._prefetched = False
        _LIVE_HANDLES.add(self)

    def prefetch(self) -> None:
        """Start the device→host DMA without blocking. ``host()`` later
        completes against the cached buffer instead of paying a full
        synchronous round trip — over remote-device links this turns a
        ~100 ms stall per batch into background transfer that overlaps
        the next batch's tokenize+dispatch."""
        if self._host is None and not self._prefetched:
            copy_async = getattr(self.dev, "copy_to_host_async", None)
            if copy_async is not None:
                copy_async()
            self._prefetched = True

    def ready(self) -> bool:
        """Whether ``host()`` would come back without waiting for the chip
        to produce the batch: the host twin is here, or the device array
        says it is computed (what is left is the copy, which ``prefetch``
        started). An array that cannot say (a host array standing in for
        one) counts as ready."""
        if self._host is not None:
            return True
        dev = self.dev  # once: the worker's decay may drop it meanwhile
        is_ready = getattr(dev, "is_ready", None)
        return is_ready is None or is_ready()

    def host(self) -> np.ndarray:
        if self._host is None:
            from pathway_tpu.engine import device_residency as _dres

            # blocks until the device has produced the batch: on the run
            # thread (a sink reading a row of a batch that was ready, or
            # ``_add_host``) or on the pipeline's completion worker (a
            # sink's emission handed over to it, then decay)
            with _tracing.stage("device.fetch_rows", wait=True) as fetch:
                self._host = np.asarray(self.dev)
                fetch.add(d2h_bytes=int(self._host.nbytes))
            _dres.record_d2h(int(self._host.nbytes))
        return self._host

    def decay(self) -> None:
        """Materialise the host twin and release the HBM copy."""
        if self.dev is not None:
            self.prefetch()
            self.host()
            self.dev = None


def decay_device_batches() -> None:
    """Synchronous end-of-commit hook: download + release all device
    batches produced this commit. Keeps HBM bounded by one commit while
    letting any device operator in the commit consume the batch
    transfer-free regardless of sweep order. This is the bit-exact spec
    the async pipeline (engine/device_pipeline.py) is measured against;
    schedulers now route the boundary through
    ``device_pipeline.commit_boundary`` which falls back to this
    behaviour under ``PATHWAY_TPU_ASYNC_DEVICE=0``."""
    if _LIVE_HANDLES:
        for handle in list(_LIVE_HANDLES):
            handle.decay()
        _LIVE_HANDLES.clear()


def stage_device_batches() -> list:
    """Detach and return this commit's live device batches without
    decaying them — the async pipeline's staging primitive. The caller
    (``DevicePipeline.commit_boundary``) owns completion; the WeakSet is
    cleared so the next commit accumulates a fresh generation. Returns
    ``[]`` on host-only commits, making the boundary near-free."""
    if not _LIVE_HANDLES:
        return []
    handles = list(_LIVE_HANDLES)
    _LIVE_HANDLES.clear()
    return handles


def unready_device_batches() -> set:
    """This commit's device batches that the chip has not finished: what a
    sink asks before it reads its rows on the run thread. Empty on a
    host-only commit, at the cost of one truthiness test."""
    if not _LIVE_HANDLES:
        return set()
    return {handle for handle in list(_LIVE_HANDLES) if not handle.ready()}


class LazyDeviceVector:
    """One row of a DeviceBatchHandle. Behaves like the host ndarray on any
    host-side use (``__array__`` downloads the parent batch once), while
    device consumers slice ``batch.dev`` with zero transfers.

    Like ndarrays, instances are unhashable and compare elementwise, so the
    engine's consolidation/diff fallbacks treat them identically.
    """

    __slots__ = ("batch", "index")

    def __init__(self, batch: DeviceBatchHandle, index: int) -> None:
        self.batch = batch
        self.index = index

    # -- host materialisation -------------------------------------------------

    def __array__(self, dtype: Any = None, copy: Any = None) -> np.ndarray:
        row = self.batch.host()[self.index]
        if dtype is not None and row.dtype != dtype:
            row = row.astype(dtype)
        return np.array(row, copy=True) if copy else row

    def _parent_array(self) -> Any:
        dev = self.batch.dev
        return dev if dev is not None else self.batch.host()

    @property
    def shape(self) -> tuple:
        return tuple(self._parent_array().shape[1:])

    @property
    def dtype(self) -> Any:
        return np.dtype(str(self._parent_array().dtype))

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def reshape(self, *shape: Any) -> np.ndarray:
        return np.asarray(self).reshape(*shape)

    def __len__(self) -> int:
        return self.shape[0]

    def __iter__(self):
        return iter(np.asarray(self))

    def __getitem__(self, item: Any) -> Any:
        return np.asarray(self)[item]

    def __eq__(self, other: Any) -> Any:
        return np.asarray(self) == other

    def __ne__(self, other: Any) -> Any:
        return np.asarray(self) != other

    __hash__ = None  # type: ignore[assignment]  # like np.ndarray

    def __repr__(self) -> str:
        return repr(np.asarray(self))

    def __reduce__(self):
        return (_identity, (np.array(np.asarray(self)),))


def lazy_rows(dev_batch: Any, n: int, prefetch: bool = True) -> list:
    """Wrap a device ``[b, dim]`` result as ``n`` lazy per-row cells.

    ``prefetch`` starts the host copy in the background immediately: the
    device consumers (HBM index) slice ``dev`` regardless, and any host
    consumer (subscribe callbacks, persistence) finds the bytes already
    in flight."""
    handle = DeviceBatchHandle(dev_batch)
    if prefetch:
        handle.prefetch()
    return [LazyDeviceVector(handle, i) for i in range(n)]


def device_runs(
    vectors: Sequence[Any],
) -> list[tuple[int, int, Any, list[int] | None]]:
    """Partition ``vectors`` into maximal contiguous runs of
    ``(start, stop, dev_array_or_None, row_indices_or_None)``.

    A run with a device array means every vector in it is a
    LazyDeviceVector of that one live batch — consumable by device
    operators with a transfer-free gather. A ``None`` run is host data.
    Batch-executor chunking makes several parents per commit the normal
    case, so callers should iterate runs rather than requiring a single
    common parent."""
    runs: list[tuple[int, int, Any, list[int] | None]] = []
    i, n = 0, len(vectors)
    while i < n:
        v = vectors[i]
        if isinstance(v, LazyDeviceVector) and v.batch.dev is not None:
            parent = v.batch
            indices = [v.index]
            j = i + 1
            while (
                j < n
                and isinstance(vectors[j], LazyDeviceVector)
                and vectors[j].batch is parent
            ):
                indices.append(vectors[j].index)
                j += 1
            runs.append((i, j, parent.dev, indices))
        else:
            j = i + 1
            while j < n and not (
                isinstance(vectors[j], LazyDeviceVector)
                and vectors[j].batch.dev is not None
            ):
                j += 1
            runs.append((i, j, None, None))
        i = j
    return runs


def common_device_parent(vectors: Sequence[Any]) -> tuple[Any, list[int]] | None:
    """When every vector is a LazyDeviceVector of one live batch, return
    (device array, row indices) for a transfer-free gather. Thin shim over
    :func:`device_runs` so liveness semantics live in one place."""
    runs = device_runs(list(vectors))
    if len(runs) == 1 and runs[0][2] is not None:
        return runs[0][2], runs[0][3]
    return None

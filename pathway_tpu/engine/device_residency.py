"""Device-resident delta batches: the zero-copy plane between device
operators and the collective exchange (ROADMAP item 2, closing note).

PR 12 put the hot stateful operators on device and PR 16 put the
repartition exchange on device, but the two planes still handed off
through host NumPy: a device groupby feeding a device join paid
D2H -> H2D on *both* sides of every exchange.  This module closes that
seam with a :class:`DeviceResidentColumns` — a
:class:`~pathway_tpu.engine.batch.Columns` whose packed payload bytes
(keys | diffs | fixed-width columns, the exact
``collective_exchange._pack_payload`` wire layout) stay on device, while
the host side keeps only the schema/factorization metadata that cannot
live on device (row count, per-column dtypes/widths, the eagerly fetched
diff vector the delivery path must inspect).

Residency is TRANSPARENT: any host access (``cols``, ``kbytes()``,
``gather`` …) materializes the batch bit-exactly through the same
``_unpack_rows`` spec the collective's host path uses, so a consumer
that cannot (or chooses not to) consume device buffers simply pays the
one trimmed D2H it would have paid anyway — there is no partial-push
failure mode, preserving the PR-6 rollback invariant.  A consumer that
CAN consume device-side (the PR-12 join matcher over int64 key codes,
the exchange packing a still-resident batch back out) reads
:meth:`DeviceResidentColumns.device_column` /
:meth:`DeviceResidentColumns.device_rows` and skips the transfer
entirely.

Control surface (the PR-2/PR-12/PR-16 parity discipline):

- ``PATHWAY_TPU_DEVICE_RESIDENCY=0`` — off; every collective exchange
  output materializes to host immediately (the bit-exact fallback spec).
- ``=1`` — force residency wherever the exchange engaged and the
  consumer is a device-eligible operator (CI runs this under the
  host-platform device sim).
- unset/auto — engage only when jax is already resident AND the default
  backend is a real accelerator; additionally the consumer's measured
  placement (:mod:`pathway_tpu.optimize.placement`) must currently have
  the operator on device.  The env is re-read per call, so the knob is
  live mid-run.

Any decline — object columns, non-codeable keys, a device error while
trimming — falls back to the host materialization with NO partial
pushes: the exchange's device output is either delivered whole as
resident parts or fetched whole as host parts.

Lifecycle (the drain-before-persistence exactly-once seam): live
resident batches register in a WeakSet (the
``device.decay_device_batches`` idiom);
:func:`decay_resident_batches` — called from
``device_pipeline.commit_boundary``/``drain``/``drain_until`` —
materializes any survivor and drops its device buffer, so HBM stays
bounded by one commit and a checkpoint for commit N only ever snapshots
host-resident state.

Observability: ``pathway_device_transfer_{h2d,d2h}_{events,bytes}_total``
count every host<->device crossing this plane performs (both modes, so a
residency-on run is comparable against its own baseline),
``pathway_device_residency_bytes_saved_total`` counts bytes that did NOT
cross because a buffer stayed resident, and
``pathway_device_residency_events_total{kind}``
(:data:`RESIDENCY_STATS`) counts resident batches, materializations,
device-side consumes, and declines.  Materialization wall lands in the
tracing ``exchange`` bucket (``residency-materialize`` span) and feeds
the consumer's seam EMA for chain-aware placement
(``PlacementPolicy.record_seam``).
"""

from __future__ import annotations

import os
import sys
import time as _time
import weakref

import numpy as np

from pathway_tpu.engine.batch import Columns
from pathway_tpu.internals import metrics as _metrics
from pathway_tpu.internals import tracing as _tracing

__all__ = [
    "DeviceResidentColumns",
    "RESIDENCY_STATS",
    "consumer_resident_ok",
    "consumer_seam_key",
    "decay_resident_batches",
    "enabled",
    "forced",
    "record_d2h",
    "record_h2d",
    "record_saved",
    "reset_counters",
    "stats",
]

#: residency-plane probe counters; the dict is the authoritative alias
#: (same discipline as routing.EXCHANGE_STATS), mirrored into the
#: ``pathway_device_residency_events_total{kind=...}`` family.
RESIDENCY_STATS = _metrics.MirroredCounterDict(
    "pathway_device_residency_events_total",
    "kind",
    {
        "resident_batches": 0,   # batches kept device-resident at a seam
        "materializations": 0,   # resident batches fetched to host
        "device_consumes": 0,    # device buffers consumed transfer-free
        "declines": 0,           # residency attempted, fell back to host
    },
    help="device-residency events by kind (mirrors RESIDENCY_STATS)",
)

_H2D_EVENTS = _metrics.REGISTRY.counter(
    "pathway_device_transfer_h2d_events_total",
    "host->device transfers performed by the delta-batch plane",
)
_H2D_BYTES = _metrics.REGISTRY.counter(
    "pathway_device_transfer_h2d_bytes_total",
    "host->device bytes moved by the delta-batch plane",
)
_D2H_EVENTS = _metrics.REGISTRY.counter(
    "pathway_device_transfer_d2h_events_total",
    "device->host transfers performed by the delta-batch plane",
)
_D2H_BYTES = _metrics.REGISTRY.counter(
    "pathway_device_transfer_d2h_bytes_total",
    "device->host bytes moved by the delta-batch plane",
)
_SAVED_BYTES = _metrics.REGISTRY.counter(
    "pathway_device_residency_bytes_saved_total",
    "bytes that stayed device-resident instead of crossing the seam",
)

_JAX_OK: bool | None = None
_BACKEND: str | None | bool = False  # False = not probed yet
_ENABLED_CACHE: tuple[str, bool] | None = None

#: this commit's live resident batches (the device._LIVE_HANDLES idiom);
#: decay_resident_batches() materializes survivors at commit boundaries
_LIVE_RESIDENT: "weakref.WeakSet" = weakref.WeakSet()


def _jax_ok() -> bool:
    """jax importable (cached) — never raises."""
    global _JAX_OK
    if _JAX_OK is None:
        try:
            import jax  # noqa: F401
            import jax.numpy  # noqa: F401

            _JAX_OK = True
        except Exception:
            _JAX_OK = False
    return _JAX_OK


def _default_backend() -> str | None:
    global _BACKEND
    if _BACKEND is False:
        try:
            import jax

            _BACKEND = jax.default_backend()
        except Exception:
            _BACKEND = None
    return _BACKEND


def enabled() -> bool:
    """Whether exchange outputs may stay device-resident at all (env
    contract above).  Cached per raw env value — the delivery hot path
    calls this once per batch, so the auto probe runs at most once, and
    flipping ``PATHWAY_TPU_DEVICE_RESIDENCY`` mid-run takes effect on
    the next delivery."""
    global _ENABLED_CACHE
    raw = os.environ.get(
        "PATHWAY_TPU_DEVICE_RESIDENCY", ""
    ).strip().lower()
    cached = _ENABLED_CACHE
    if cached is not None and cached[0] == raw:
        return cached[1]
    if raw in ("0", "false", "off", "no"):
        val = False
    elif raw in ("1", "true", "on", "yes", "force"):
        val = _jax_ok()
    else:
        # auto: only with jax already resident AND a real accelerator —
        # keeping buffers on a jax-CPU "device" saves nothing
        val = (
            "jax" in sys.modules
            and _jax_ok()
            and _default_backend() not in (None, "cpu")
        )
    _ENABLED_CACHE = (raw, val)
    return val


def forced() -> bool:
    """True when ``PATHWAY_TPU_DEVICE_RESIDENCY=1`` pins every eligible
    exchange output resident (parity CI); auto mode instead consults the
    consumer's measured placement."""
    raw = os.environ.get(
        "PATHWAY_TPU_DEVICE_RESIDENCY", ""
    ).strip().lower()
    return raw in ("1", "true", "on", "yes", "force") and enabled()


def consumer_seam_key(consumer) -> "tuple | None":
    """The placement key a delivery to ``consumer`` belongs to: the
    consumer itself when it is an annotated device-eligible operator,
    else the downstream eligible operator the placement pass marked it
    as feeding (repartitions often land on the row-local
    expression/filter stage directly above the stateful operator), else
    ``None``."""
    if consumer is None:
        return None
    kind = getattr(consumer, "_device_ops_eligible", None)
    if kind is not None:
        return (kind, consumer.index)
    return getattr(consumer, "_device_residency_downstream", None)


def consumer_resident_ok(consumer) -> bool:
    """Should an exchange output bound for ``consumer`` stay resident?
    Yes when residency is enabled, the delivery belongs to a
    device-eligible operator's seam (the placement pass annotated the
    consumer, directly or as that operator's feeder), and — in auto
    mode — the measured placement currently has that operator on
    device, so a host-placed consumer never pays a pointless lazy-fetch
    detour."""
    if not enabled():
        return False
    key = consumer_seam_key(consumer)
    if key is None:
        return False
    if forced():
        return True
    from pathway_tpu.optimize.placement import POLICY

    return POLICY.is_device(*key)


# -- transfer accounting ------------------------------------------------------


def record_h2d(nbytes: int) -> None:
    """Count one host->device transfer of ``nbytes``."""
    _H2D_EVENTS.inc()
    _H2D_BYTES.inc(float(nbytes))


def record_d2h(nbytes: int) -> None:
    """Count one device->host transfer of ``nbytes``."""
    _D2H_EVENTS.inc()
    _D2H_BYTES.inc(float(nbytes))


def record_saved(nbytes: int) -> None:
    """Count ``nbytes`` that stayed resident instead of crossing."""
    if nbytes > 0:
        _SAVED_BYTES.inc(float(nbytes))


# -- the resident batch -------------------------------------------------------

#: Columns slots that trigger transparent materialization when unset
_HOST_SLOTS = frozenset(("cols", "_kbytes", "_kobjs", "_kb_thunk"))


class DeviceResidentColumns(Columns):
    """A :class:`Columns` whose payload bytes live on device.

    ``_dev_rows`` holds the ``(n, W)`` uint8 packed-row matrix (the
    ``collective_exchange`` wire layout: 16-byte key digest | optional
    int64 diff | fixed-width columns); ``_layout`` is the host-side
    ``[(dtype, width), ...]`` schema.  ``n`` and ``diffs`` are eager —
    every delivery path inspects them — while the base class's host
    slots (``cols``/``_kbytes``/``_kobjs``/``_kb_thunk``) stay UNSET
    until :meth:`_materialize` fills them, so any host access routes
    through ``__getattr__`` and fetches the batch bit-exactly.  The
    device buffer survives materialization (a key-forced batch can
    still be re-packed device-side) until :meth:`decay` drops it.
    """

    __slots__ = ("_dev_rows", "_layout", "_has_diffs", "_seam_key", "__weakref__")

    def __init__(
        self,
        dev_rows,
        layout: list,
        has_diffs: bool,
        n: int,
        diffs: "np.ndarray | None" = None,
        seam_key: "tuple | None" = None,
    ) -> None:
        # deliberately NOT calling Columns.__init__: the host slots must
        # stay unset so __getattr__ is the single materialization gate
        self.n = n
        self.diffs = diffs
        self._dev_rows = dev_rows
        self._layout = layout
        self._has_diffs = has_diffs
        self._seam_key = seam_key
        _LIVE_RESIDENT.add(self)
        RESIDENCY_STATS["resident_batches"] += 1

    @classmethod
    def from_device_rows(
        cls,
        dev_rows,
        layout: list,
        has_diffs: bool,
        seam_key: "tuple | None" = None,
    ) -> "DeviceResidentColumns":
        """Wrap a device ``(n, W)`` packed-row matrix.  The diff vector
        is fetched eagerly (8n bytes — the one column every delivery
        path inspects for insert-only screening); keys and value
        columns stay on device."""
        n = int(dev_rows.shape[0])
        diffs = None
        if has_diffs:
            seg = np.asarray(dev_rows[:, 16:24])
            record_d2h(seg.nbytes)
            diffs = np.ascontiguousarray(seg).view(np.int64).ravel()
        return cls(
            dev_rows, layout, has_diffs, n, diffs=diffs, seam_key=seam_key
        )

    # -- transparent host fallback ---------------------------------------

    def __getattr__(self, name: str):
        if name in _HOST_SLOTS:
            self._materialize()
            return object.__getattribute__(self, name)
        raise AttributeError(name)

    def resident(self) -> bool:
        """True while the device buffer is still held."""
        return object.__getattribute__(self, "_dev_rows") is not None

    def _materialized(self) -> bool:
        try:
            object.__getattribute__(self, "cols")
            return True
        except AttributeError:
            return False

    def _materialize(self) -> None:
        """Fetch the packed rows once (one trimmed D2H) and fill the
        base-class slots with exactly what the collective's host path
        would have produced — bit-exact by construction, since both
        decode the same wire layout."""
        if self._materialized():
            return
        dev = self._dev_rows
        if dev is None:  # pragma: no cover — decay always materializes
            raise RuntimeError("resident batch decayed before materializing")
        t0 = _time.perf_counter()
        rows = np.asarray(dev)
        record_d2h(rows.nbytes)
        RESIDENCY_STATS["materializations"] += 1
        kb = np.ascontiguousarray(rows[:, :16])
        off = 16 + (8 if self._has_diffs else 0)
        cols = []
        for dtype, width in self._layout:
            seg = np.ascontiguousarray(rows[:, off : off + width])
            cols.append(seg.view(dtype).ravel())
            off += width
        self._kbytes = kb
        self._kobjs = None
        self._kb_thunk = None
        self.cols = cols
        t1 = _time.perf_counter()
        seam = self._seam_key
        if seam is not None:
            from pathway_tpu.optimize.placement import POLICY

            POLICY.record_seam(
                seam[0], seam[1], self.n, int((t1 - t0) * 1e9)
            )
        trace = _tracing.current()
        if trace is not None:
            trace.span(
                "residency-materialize",
                "exchange",
                t0,
                t1,
                rows=self.n,
                bytes=int(rows.nbytes),
            )

    # -- device-side views -----------------------------------------------

    def device_rows(self):
        """The device ``(n, W)`` packed-row matrix (None once decayed).
        The collective exchange re-packs from this buffer instead of
        uploading host bytes when the batch is repartitioned again."""
        return object.__getattribute__(self, "_dev_rows")

    @property
    def layout(self) -> list:
        return self._layout

    @property
    def has_diffs(self) -> bool:
        return self._has_diffs

    def device_column(self, i: int):
        """Device view of packed column ``i`` (an on-device bitcast of
        the column's byte lanes — no transfer), or ``None`` once the
        buffer decayed.  Bit-identical to ``cols[i]`` by construction:
        both reinterpret the same little-endian bytes.  A float64 column
        has no view on a TPU, which has no native float64: the bitcast
        there does not preserve the bits (v5e, CHANGES.md PR 21)."""
        dev = object.__getattribute__(self, "_dev_rows")
        if dev is None:
            return None
        import jax
        from jax import lax

        dtype, width = self._layout[i]
        if dtype == np.float64 and jax.default_backend() == "tpu":
            return None
        off = 16 + (8 if self._has_diffs else 0)
        for j in range(i):
            off += self._layout[j][1]
        seg = dev[:, off : off + width]
        with jax.enable_x64(True):
            out = lax.bitcast_convert_type(seg, dtype)
            if out.ndim == 2:  # same-width bitcast keeps the byte lane
                out = out.reshape(out.shape[0])
        return out

    def decay(self) -> None:
        """Materialize-if-needed, then drop the device buffer — HBM
        stays bounded by one commit, and anything still referencing the
        batch (deferred state, a snapshot walk) sees plain host data."""
        if object.__getattribute__(self, "_dev_rows") is None:
            return
        self._materialize()
        self._dev_rows = None


def decay_resident_batches() -> None:
    """End-of-commit / pre-persistence hook: materialize and release
    every still-live resident batch (the ``decay_device_batches``
    discipline).  Called from ``device_pipeline.commit_boundary`` and
    the drain seams, so checkpoints never observe device-only state —
    the drain-before-persistence exactly-once invariant."""
    if not _LIVE_RESIDENT:
        return
    for batch in list(_LIVE_RESIDENT):
        batch.decay()
    _LIVE_RESIDENT.clear()


# -- stats --------------------------------------------------------------------


def stats() -> dict:
    """Structured roll-up for bench JSON / cli stats."""
    return {
        "enabled": enabled(),
        "forced": forced(),
        "events": dict(RESIDENCY_STATS),
        "h2d": {
            "events": int(_H2D_EVENTS.value),
            "bytes": int(_H2D_BYTES.value),
        },
        "d2h": {
            "events": int(_D2H_EVENTS.value),
            "bytes": int(_D2H_BYTES.value),
        },
        "bytes_saved": int(_SAVED_BYTES.value),
    }


def reset_counters() -> None:
    """Test/bench helper: zero the event and transfer counters."""
    for key in list(RESIDENCY_STATS):
        RESIDENCY_STATS[key] = 0
    for counter in (
        _H2D_EVENTS,
        _H2D_BYTES,
        _D2H_EVENTS,
        _D2H_BYTES,
        _SAVED_BYTES,
    ):
        counter.value = 0.0

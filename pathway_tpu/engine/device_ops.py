"""Device-resident incremental operators: JAX kernels for the hottest
stateful dataflow ops (ROADMAP item 3).

Three operator cores move to the accelerator, each operating directly on
the columnar delta-batch arrays (+1/−1 diff semantics included):

- **groupby semigroup reduction** — the per-commit segment reductions of
  the columnar groupby state machine (``device.segment_count`` + the
  integer ``device.segment_sum``s; float sums stay on the host) become
  one batch of device scatter-adds over the factorized key ``inverse``.
  Dispatch is split from fetch
  (:func:`segment_reduce_dispatch` → :meth:`SegmentReduceJob.fetch`) so
  the kernel launch overlaps the host group-id resolution loop — the
  same overlap discipline as the PR-9 async device pipeline.
- **hash-join probe** — the sort-based pair matcher
  (``graph._match_join_pairs``) re-expressed over int64 key digests on
  device (:func:`match_pairs`): stable argsort + searchsorted +
  vectorized expansion.  The swap rule (smaller side becomes the sorted
  haystack) and the emission order (probe index ascending, build index
  ascending within a probe row) are copied verbatim, so the device
  matcher is interchangeable with the host matcher *pair for pair* —
  ordering depends only on side lengths and key-equality structure,
  never on code values.
- **KNN index maintenance** — ops/knn.py's scatter update and masked
  matmul top-k already run on device; this module adds the accounting
  seam (:func:`record_kernel`) so their launches land in the same
  ``hit_counts``/``kernel_ns`` surface as the C++ host kernels, and
  :class:`~pathway_tpu.engine.external_index.HostKnnIndex` becomes their
  bit-exact host spec.

Bit-exactness discipline (PR 2): the host NumPy/C++ kernels remain the
spec.  The device kernels only *reorder integer additions*
(scatter-add, exact in any order) or *reproduce a deterministic
algorithm* (stable sort matcher) — the multiply producing the weights
happens on host with NumPy, and padding rows contribute exact zeros.
Float additions are not reordered: they stay with the spec (see
:func:`segment_reduce_dispatch`).  The parity gate in tools/check.py
re-runs the corpus with the JAX path forced on, per platform.

Placement is measurement-driven, not static: the optimizer's placement
pass (:mod:`pathway_tpu.optimize.placement`) seeds a per-operator
policy that compares observed device ns/row against host ns/row with
hysteresis.  ``PATHWAY_TPU_DEVICE_OPS`` is the control surface:

- ``0`` — escape hatch, host kernels only (bit-identical, zero new code
  on the hot path);
- ``1`` — force the device path wherever the batch is representable
  (CI uses this under ``JAX_PLATFORMS=cpu`` to exercise the JAX
  kernels without an accelerator);
- unset — auto: device ops engage only when jax is already loaded *and*
  the default backend is a real accelerator; pure-host deployments pay
  one cached env check per batch and nothing else.
"""

from __future__ import annotations

import logging
import os
import sys
import threading
import time as _time
from typing import Any, Sequence

import numpy as np

__all__ = [
    "bucket_size",
    "enabled",
    "forced",
    "hit_counts",
    "kernel_ns",
    "record_error",
    "record_kernel",
    "reset_counters",
    "segment_reduce_dispatch",
    "SegmentReduceJob",
    "match_pairs",
    "stats",
]

_LOCK = threading.Lock()
#: per-kernel launch counts / host-observed ns, mirroring native.hit_counts()
_HITS: dict[str, int] = {}
_NS: dict[str, int] = {}
#: exceptions raised inside a device path whose caller then used the host
#: result, by site — an error, not a placement decision
_ERRORS: dict[str, int] = {}

_JAX_OK: bool | None = None
_BACKEND: str | None | bool = False  # False = not probed yet
_ENABLED_CACHE: tuple[str, bool] | None = None
_SCATTER_ADD = None


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
    """Whether device ops may engage at all (see the env contract above).

    Cached per env value: the scheduler hot path calls this once per
    batch, so the auto probe (backend detection) runs at most once."""
    global _ENABLED_CACHE
    raw = os.environ.get("PATHWAY_TPU_DEVICE_OPS", "").strip().lower()
    cached = _ENABLED_CACHE
    if cached is not None and cached[0] == raw:
        return cached[1]
    if raw in ("0", "false", "off", "no"):
        val = False
    elif raw in ("1", "true", "on", "yes", "force"):
        val = _jax_ok()
    else:
        # auto: only with jax already resident AND a real accelerator —
        # never silently re-route host CPU work through jax-on-CPU
        val = (
            "jax" in sys.modules
            and _jax_ok()
            and _default_backend() not in (None, "cpu")
        )
    _ENABLED_CACHE = (raw, val)
    return val


def forced() -> bool:
    """True when ``PATHWAY_TPU_DEVICE_OPS=1`` pins placement to device
    (parity CI); the policy then skips measurement-driven arbitration."""
    raw = os.environ.get("PATHWAY_TPU_DEVICE_OPS", "").strip().lower()
    return raw in ("1", "true", "on", "yes", "force") and enabled()


# -- accounting (the native.hit_counts()/kernel_ns() twin) --------------------


def record_kernel(name: str, ns: int, hits: int = 1) -> None:
    with _LOCK:
        _HITS[name] = _HITS.get(name, 0) + hits
        _NS[name] = _NS.get(name, 0) + int(ns)


def record_error(site: str) -> None:
    """Count an exception raised inside a device path. Call it from the
    ``except`` block that falls back to the host result (the fallback
    keeps the engine's rollback invariant; it must not hide the failure):
    the first error at each site is logged with its traceback."""
    with _LOCK:
        first = site not in _ERRORS
        _ERRORS[site] = _ERRORS.get(site, 0) + 1
    if first:
        logging.getLogger("pathway_tpu.device_ops").error(
            "device path %r raised; the host result is used instead "
            "(counted in device_ops.stats()['errors'], logged once per site)",
            site,
            exc_info=True,
        )


def error_counts() -> dict[str, int]:
    with _LOCK:
        return dict(_ERRORS)


def hit_counts() -> dict[str, int]:
    with _LOCK:
        return dict(_HITS)


def kernel_ns() -> dict[str, int]:
    with _LOCK:
        return dict(_NS)


def total_ns() -> int:
    """Cumulative device-kernel ns across every kernel — cheap enough to
    sample around a single operator batch (span attribution)."""
    with _LOCK:
        return sum(_NS.values())


def reset_counters() -> None:
    with _LOCK:
        _HITS.clear()
        _NS.clear()
        _ERRORS.clear()


def stats() -> dict:
    """Structured roll-up for bench JSON / cli stats."""
    from pathway_tpu.optimize import placement as _placement

    return {
        "enabled": enabled(),
        "forced": forced(),
        "hit_counts": hit_counts(),
        "kernel_ns": kernel_ns(),
        "errors": error_counts(),
        "placement": _placement.POLICY.decisions(),
    }


# -- shared kernel plumbing ---------------------------------------------------


def bucket_size(n: int, minimum: int = 8) -> int:
    """Power-of-two padding bucket — ragged batch lengths otherwise
    compile one XLA program per distinct shape (the Ragged Paged
    Attention discipline: pad irregular segments to few static shapes).
    Public: the collective exchange pads its chunk/bucket depths through
    the same ladder so both planes share compiled-shape discipline."""
    b = minimum
    while b < n:
        b *= 2
    return b


#: historical internal alias
_bucket = bucket_size


def _scatter_add():
    """The one jitted kernel shape every segment reduction uses:
    ``out0.at[inv].add(w)`` with the (freshly zeroed) output donated.
    jax caches compilations per (dtype, bucketed shape) pair."""
    global _SCATTER_ADD
    if _SCATTER_ADD is None:
        import jax

        _SCATTER_ADD = jax.jit(
            lambda out0, inv, w: out0.at[inv].add(w), donate_argnums=(0,)
        )
    return _SCATTER_ADD


# -- groupby: segment reduction ----------------------------------------------


class SegmentReduceJob:
    """An in-flight device segment reduction: :func:`segment_reduce_dispatch`
    launched the scatter-adds (jax async dispatch — the call returned as
    soon as the work was enqueued); :meth:`fetch` materialises the host
    arrays, blocking only on actual device completion.  The caller runs
    its host-side group-id resolution between the two."""

    __slots__ = ("_gd", "_outs", "_nu", "_n", "_t0")

    def __init__(self, gd, outs, nu: int, n: int, t0: int) -> None:
        self._gd = gd
        self._outs = outs
        self._nu = nu
        self._n = n
        self._t0 = t0

    def fetch(self) -> tuple[np.ndarray, list]:
        """(gdiffs, deltas) with the padding sliced off — dtypes and
        values bit-identical to device.segment_count/segment_sum."""
        from pathway_tpu.engine import device_residency as _dres

        nu = self._nu
        full = np.asarray(self._gd)
        d2h = full.nbytes
        gdiffs = full[:nu]
        deltas = []
        for o in self._outs:
            if o is None or isinstance(o, np.ndarray):  # host float sum
                deltas.append(o)
                continue
            arr = np.asarray(o)
            d2h += arr.nbytes
            deltas.append(arr[:nu])
        _dres.record_d2h(d2h)
        record_kernel(
            "segment_reduce", _time.perf_counter_ns() - self._t0
        )
        return gdiffs, deltas


def segment_reduce_dispatch(
    inverse: np.ndarray,
    diffs: np.ndarray,
    vals: Sequence[np.ndarray | None],
    n_groups: int,
) -> SegmentReduceJob:
    """Device twin of the columnar groupby's per-commit reductions:
    ``segment_count(inverse, diffs)`` plus one ``segment_sum`` per sum
    column, as a single batch of bucketed scatter-adds.

    The integer weight products (``values.astype(int64) * diffs``,
    wrapping int64) are computed on host with NumPy — the device only
    reorders the additions, which is exact for ints. Float columns are
    summed on the host, here, by the spec's own ``segment_sum``: a TPU has
    no native float64 (XLA emulates it; on a v5e the device sums differed
    from NumPy by up to 5e-11 relative — CHANGES.md, PR 21), and a sum
    must not depend on where its batch happened to be placed."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.engine import device as _host
    from pathway_tpu.engine import device_residency as _dres

    t0 = _time.perf_counter_ns()
    n = len(inverse)
    npad = _bucket(n)
    gpad = _bucket(n_groups)
    inv = np.zeros(npad, np.int64)
    inv[:n] = inverse
    h2d = inv.nbytes
    with jax.enable_x64(True):
        add = _scatter_add()
        inv_d = jnp.asarray(inv)
        w = np.zeros(npad, np.int64)
        w[:n] = diffs
        h2d += w.nbytes
        gd = add(jnp.zeros(gpad, jnp.int64), inv_d, jnp.asarray(w))
        outs: list[Any] = []
        for col in vals:
            if col is None:
                outs.append(None)
                continue
            if col.dtype.kind in "ib":
                w = np.zeros(npad, np.int64)
                w[:n] = col.astype(np.int64, copy=False) * diffs
                h2d += w.nbytes
                outs.append(
                    add(jnp.zeros(gpad, jnp.int64), inv_d, jnp.asarray(w))
                )
            else:
                outs.append(_host.segment_sum(inverse, col, diffs, n_groups))
    _dres.record_h2d(h2d)
    return SegmentReduceJob(gd, outs, n_groups, n, t0)


# -- join: sort-based pair matcher -------------------------------------------


def _match_pairs_device(
    la: np.ndarray, ra: np.ndarray, la_dev=None, ra_dev=None
):
    """graph._match_join_pairs transliterated to jnp — identical swap
    rule, stable sort, and emission arithmetic, so the returned pair
    sequence is the host matcher's pair sequence.

    ``la_dev``/``ra_dev`` are optional device twins of the SAME code
    arrays (a still-resident exchange delivery's int64 key column):
    when present the matcher consumes them in place of re-uploading the
    host array — values identical by construction (both views
    reinterpret the same wire bytes), so pair output cannot differ."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.engine import device_residency as _dres

    empty = np.empty(0, np.int64)
    if len(la) == 0 or len(ra) == 0:
        return empty, empty
    if len(ra) > len(la):
        r_idx, l_idx = _match_pairs_device(ra, la, ra_dev, la_dev)
        return l_idx, r_idx
    with jax.enable_x64(True):
        if la_dev is not None:
            la_d = la_dev
            _dres.record_saved(la.nbytes)
            _dres.RESIDENCY_STATS["device_consumes"] += 1
        else:
            la_d = jnp.asarray(la)
            _dres.record_h2d(la.nbytes)
        if ra_dev is not None:
            ra_d = ra_dev
            _dres.record_saved(ra.nbytes)
            _dres.RESIDENCY_STATS["device_consumes"] += 1
        else:
            ra_d = jnp.asarray(ra)
            _dres.record_h2d(ra.nbytes)
        order = jnp.argsort(ra_d, stable=True)
        rs = ra_d[order]
        lo = jnp.searchsorted(rs, la_d, side="left")
        hi = jnp.searchsorted(rs, la_d, side="right")
        counts = hi - lo
        total = int(counts.sum())
        if total == 0:
            return empty, empty
        l_idx = jnp.repeat(jnp.arange(len(la)), counts)
        starts = jnp.repeat(lo, counts)
        csum = jnp.cumsum(counts) - counts
        offs = jnp.arange(total) - jnp.repeat(csum, counts)
        r_idx = order[starts + offs]
        l_out = np.asarray(l_idx, np.int64)
        r_out = np.asarray(r_idx, np.int64)
        _dres.record_d2h(l_out.nbytes + r_out.nbytes)
        return l_out, r_out


def match_pairs(
    l_arrays: "list[np.ndarray]",
    r_arrays: "list[np.ndarray]",
    l_dev=None,
    r_dev=None,
):
    """Device pair matcher over dtype-unified join-key columns; returns
    ``(l_idx, r_idx)`` or ``None`` when a column has no int64 code view
    (caller falls back to the host matcher — state untouched).

    Multi-column keys reduce to joint codes with the same host
    factorization the NumPy path uses; only the matcher itself (the
    sort/search dominated part) runs on device, so pair ordering is the
    host ordering by construction.

    ``l_dev``/``r_dev``: optional device twins of single-column keys (a
    device-resident exchange delivery).  A twin is consumed ONLY when
    the int64 code derivation was the identity on the host array it
    twins (``_as_match_codes`` returns the same object for contiguous
    int64 input) — float normalisation or widening would change bits,
    so any non-identity derivation drops the twin and re-uploads."""
    from pathway_tpu.engine.graph import _as_match_codes

    t0 = _time.perf_counter_ns()
    lc = [_as_match_codes(a) for a in l_arrays]
    if any(c is None for c in lc):
        return None
    rc = [_as_match_codes(a) for a in r_arrays]
    if any(c is None for c in rc):
        return None
    la_dev = ra_dev = None
    if len(lc) == 1:
        la, ra = lc[0], rc[0]
        if l_dev is not None and lc[0] is l_arrays[0]:
            la_dev = l_dev
        if r_dev is not None and rc[0] is r_arrays[0]:
            ra_dev = r_dev
    else:
        from pathway_tpu.engine.device import factorize_multi

        nl = len(lc[0])
        both = [np.concatenate([l, r]) for l, r in zip(lc, rc)]
        _first, inverse = factorize_multi(both)
        la, ra = inverse[:nl], inverse[nl:]
    out = _match_pairs_device(la, ra, la_dev, ra_dev)
    record_kernel("match_pairs", _time.perf_counter_ns() - t0)
    return out

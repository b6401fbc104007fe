"""The ``pathway`` CLI (reference: python/pathway/cli.py).

``python -m pathway_tpu.cli spawn --threads N --processes M prog.py args``
launches M processes of the program with the worker-topology env vars the
runtime reads (PATHWAY_THREADS/PROCESSES/PROCESS_ID/FIRST_PORT/RUN_ID,
reference cli.py:93-107). Threads shard the dataflow in-process
(pw.run threads=N → ShardedGraphRunner); processes partition input at the
connector, as with the reference's per-worker partitioned reads.

``spawn-from-env`` re-reads the full command from PATHWAY_SPAWN_ARGS —
the container-deployment entry point (reference spawn_from_env).

``python -m pathway_tpu.cli analyze prog.py args`` runs the program in
graph-only mode (PATHWAY_TPU_ANALYZE=1): every dataflow graph the program
builds is statically analyzed instead of executed, and a combined report
is printed.  Exit codes: 0 = clean (info-level findings allowed), 1 =
warning/error findings, 2 = the program or the analyzer itself failed.

``python -m pathway_tpu.cli rescale M`` asks a live supervised mesh
(PATHWAY_TPU_RECOVER=1 spawn) to rescale to M processes: the supervisor
quiesces the mesh at a commit boundary, re-shards the operator
snapshots, and relaunches — sink output stays bit-identical.

``python -m pathway_tpu.cli stats <port|host:port|url>`` scrapes a live
monitoring endpoint (pw.run with_http_server=True; port
20000 + process_id) and pretty-prints the mesh-wide per-worker table plus
per-family totals. ``--raw`` dumps the exposition text untouched;
``--watch N`` re-scrapes every N seconds with /timeseries sparklines.

``python -m pathway_tpu.cli profile <port|dir|file>`` merges, validates
(validate_profile), and renders sampling-profiler output — a live
``/profile`` endpoint, a PATHWAY_TPU_PROFILE_DIR of per-process
exports, or one export file; ``--json`` emits speedscope JSON,
``--folded`` collapsed-stack text.
"""

from __future__ import annotations

import argparse
import json
import os
import secrets
import shlex
import subprocess
import sys
import tempfile
import uuid
from typing import Sequence


def spawn(
    program: str,
    arguments: Sequence[str],
    *,
    threads: int = 1,
    processes: int = 1,
    first_port: int = 10000,
    env: dict | None = None,
) -> int:
    env_base = dict(os.environ if env is None else env)
    if env_base.get("PATHWAY_TPU_RECOVER", "").lower() in ("1", "true", "yes"):
        # fault-tolerant runs need a control plane that can restart dead
        # workers; hand the whole launch over to the supervisor
        from pathway_tpu.engine.supervisor import MeshSupervisor

        return MeshSupervisor(
            program,
            arguments,
            threads=threads,
            processes=processes,
            first_port=first_port,
            env=env_base,
        ).run()
    from pathway_tpu.internals.accelerator import chip_env

    run_id = str(uuid.uuid4())
    # fresh per-run key authenticating exchange-mesh frames (all processes
    # share it; engine/distributed.py rejects unauthenticated frames)
    env_base.setdefault("PATHWAY_EXCHANGE_SECRET", secrets.token_hex(32))
    print(
        f"Preparing {processes} process(es) "
        f"({processes * threads} total workers)",
        file=sys.stderr,
    )
    handles = []
    try:
        for process_id in range(processes):
            proc_env = env_base.copy()
            proc_env.update(chip_env(process_id, processes, env_base))
            proc_env["PATHWAY_THREADS"] = str(threads)
            proc_env["PATHWAY_PROCESSES"] = str(processes)
            proc_env["PATHWAY_FIRST_PORT"] = str(first_port)
            proc_env["PATHWAY_PROCESS_ID"] = str(process_id)
            proc_env["PATHWAY_RUN_ID"] = run_id
            handles.append(
                subprocess.Popen([program, *arguments], env=proc_env)
            )
        for handle in handles:
            handle.wait()
    finally:
        for handle in handles:
            if handle.poll() is None:
                handle.terminate()
    for handle in handles:
        rc = handle.returncode
        if rc is None:
            return 1  # never finished: failure
        if rc != 0:
            # negative = killed by signal; report 128+signal like the shell
            return rc if rc > 0 else 128 - rc
    return 0


def analyze_source(
    targets: Sequence[str],
    *,
    as_json: bool = False,
    errors_only: bool = False,
    strict: bool = False,
) -> int:
    """Lint the runtime's own source (``analyze --source``): the PWC
    concurrency/protocol and PWD device-plane passes over files or
    directories, same exit contract as graph mode (0 clean, 1 findings,
    2 analyzer failure).

    ``--json`` emits a machine-readable document for CI diffing: one
    record per finding — ``code``, ``path``, ``line``, ``column``,
    ``severity``, ``message``, ``waived`` — with waived findings
    included (``waived: true``) but never counted toward the exit code.
    """
    from pathway_tpu.analysis import Severity
    from pathway_tpu.analysis.source import analyze_paths

    missing = [t for t in targets if not os.path.exists(t)]
    if missing or not targets:
        print(
            f"analyze: no such source target(s): {missing or '(none given)'}",
            file=sys.stderr,
        )
        return 2
    report = analyze_paths(list(targets), root=os.getcwd())
    if as_json:
        def _rec(f):
            return {
                "code": f.code,
                "path": f.node_name,
                "line": f.node_index,
                "column": f.column,
                "severity": f.severity.value,
                "message": f.message,
                "waived": f.waived,
            }

        doc = {
            "mode": "source",
            "files": report.node_count,
            "findings": [_rec(f) for f in report.sorted_findings()]
            + [_rec(f) for f in report.waived],
            "internal_errors": list(report.internal_errors),
            "summary": {
                "errors": report.count(Severity.ERROR),
                "warnings": report.count(Severity.WARNING),
                "info": report.count(Severity.INFO),
                "waived": len(report.waived),
            },
        }
        print(json.dumps(doc, indent=2))
    else:
        print(report.render())
    if report.internal_errors or report.node_count == 0:
        return 2
    if strict and report.findings:
        return 1
    if report.error_count:
        return 1
    if not errors_only and report.count(Severity.WARNING):
        return 1
    return 0


def analyze(
    program: str,
    arguments: Sequence[str],
    *,
    as_json: bool = False,
    errors_only: bool = False,
    strict: bool = False,
    env: dict | None = None,
) -> int:
    """Run ``program`` under PATHWAY_TPU_ANALYZE=1 and report findings.

    The child builds its graphs exactly as it would for a real run; the
    schedulers intercept before any data flows and append one JSON report
    per analyzed scope to a temp file, aggregated here."""
    from pathway_tpu.analysis import Report, Severity

    fd, out_path = tempfile.mkstemp(prefix="pathway-analyze-", suffix=".jsonl")
    os.close(fd)
    child_env = dict(os.environ if env is None else env)
    child_env["PATHWAY_TPU_ANALYZE"] = "1"
    child_env["PATHWAY_TPU_ANALYZE_OUT"] = out_path
    try:
        proc = subprocess.run(
            [sys.executable, program, *arguments], env=child_env
        )
        if proc.returncode != 0:
            print(
                f"analyze: {program!r} exited with code {proc.returncode} "
                "while building its graph",
                file=sys.stderr,
            )
            return 2
        merged = Report()
        scope_count = 0
        with open(out_path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                merged.merge(Report.from_dict(json.loads(line)))
                scope_count += 1
        if scope_count == 0:
            print(
                f"analyze: {program!r} built no dataflow graph (nothing "
                "reached a scheduler)",
                file=sys.stderr,
            )
            return 2
        if as_json:
            print(json.dumps(merged.to_dict(), indent=2))
        else:
            print(f"analyzed {scope_count} graph(s)")
            print(merged.render())
        if merged.internal_errors:
            return 2
        if strict and merged.findings:
            return 1
        if merged.error_count:
            return 1
        if not errors_only and merged.count(Severity.WARNING):
            return 1
        return 0
    finally:
        try:
            os.unlink(out_path)
        except OSError:
            pass


def _stats_url(target: str) -> str:
    """Accept a bare port, host:port, or full URL; default path /metrics."""
    from urllib.parse import urlparse

    if target.isdigit():
        return f"http://127.0.0.1:{target}/metrics"
    if "://" not in target:
        target = "http://" + target
    if urlparse(target).path in ("", "/"):
        target = target.rstrip("/") + "/metrics"
    return target


def _hist_quantile(buckets: list, q: float) -> float | None:
    """Quantile from cumulative (upper_bound, count) pairs, interpolating
    linearly within the bucket (the usual Prometheus histogram_quantile).

    Returns None — not a fabricated 0.0 — when the histogram carries no
    information: zero observations, or every observation in a lone +Inf
    bucket (no finite bound to anchor an estimate)."""
    if not buckets:
        return None
    total = buckets[-1][1]
    if total <= 0:
        return None
    if not any(ub != float("inf") for ub, _ in buckets):
        return None  # only a +Inf bucket: no finite bound to report
    rank = q * total
    lo_bound, lo_count = 0.0, 0.0
    for ub, c in buckets:
        if c >= rank:
            if ub == float("inf"):
                return lo_bound
            span = c - lo_count
            if span <= 0:
                return ub
            return lo_bound + (ub - lo_bound) * (rank - lo_count) / span
        lo_bound, lo_count = ub, c
    return buckets[-1][0]


def stats(
    target: str,
    *,
    raw: bool = False,
    timeout: float = 5.0,
    watch: float | None = None,
) -> int:
    """Scrape a monitoring endpoint and pretty-print the mesh-wide table.

    On a mesh leader the exposition carries every worker's piggybacked
    snapshot under ``worker="<process_id>"`` labels, so one scrape shows
    the whole cluster; rows without a worker label (the legacy local
    series) print as ``(local)``.  ``--watch N`` re-scrapes every N
    seconds (clearing the screen) and adds history sparklines read off
    the endpoint's ``/timeseries`` ring."""
    if watch:
        import time as _time_mod

        try:
            while True:
                sys.stdout.write("\x1b[2J\x1b[H")
                rc = _stats_once(target, raw=raw, timeout=timeout)
                if rc == 0 and not raw:
                    _print_sparklines(target, timeout=timeout)
                sys.stdout.flush()
                _time_mod.sleep(watch)
        except KeyboardInterrupt:
            return 0
    return _stats_once(target, raw=raw, timeout=timeout)


#: eight-level bar for terminal sparklines (history off /timeseries)
_SPARK_CHARS = "▁▂▃▄▅▆▇█"


def _sparkline(values: Sequence[float], width: int = 48) -> str:
    vals = list(values)[-width:]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    span = hi - lo
    if span <= 0:
        return _SPARK_CHARS[0] * len(vals)
    return "".join(
        _SPARK_CHARS[min(7, int((v - lo) / span * 8))] for v in vals
    )


#: families worth a sparkline row in ``stats --watch``, most
#: operationally interesting first (missing ones are skipped)
_WATCH_FAMILIES = (
    "pathway_device_queue_depth",
    "pathway_ingest_to_sink_latency_seconds",
    "pathway_serving_latency_seconds",
    "pathway_slo_burn_ratio",
    "pathway_commits_total",
    "pathway_profile_samples_total",
)


def _print_sparklines(
    target: str, *, timeout: float = 5.0, window_s: float = 120.0
) -> None:
    """Best-effort trend rows under the ``--watch`` table: windowed
    reads off the endpoint's ``/timeseries`` ring, one sparkline per
    series (capped).  A run without the history ring just shows none."""
    import urllib.request
    from urllib.parse import urlsplit, urlunsplit

    parts = urlsplit(_stats_url(target))
    base = urlunsplit((parts[0], parts[1], "/timeseries", "", ""))
    lines = []
    try:
        for family in _WATCH_FAMILIES:
            url = f"{base}?family={family}&window={window_s:g}"
            with urllib.request.urlopen(url, timeout=timeout) as resp:
                result = json.loads(resp.read().decode())
            for series in result.get("series", [])[:4]:
                pts = series.get("points") or []
                if len(pts) < 2:
                    continue
                labels = series.get("labels") or {}
                tag = ",".join(
                    f"{k}={v}" for k, v in sorted(labels.items())
                )
                last = pts[-1][1]
                last_s = (
                    f"{last:.0f}" if float(last).is_integer()
                    else f"{last:.4g}"
                )
                lines.append(
                    f"  {family}{{{tag}}}"
                    f"  {_sparkline([p[1] for p in pts])}  {last_s}"
                )
            if len(lines) >= 12:
                break
    except Exception:  # noqa: BLE001 — trends are advisory, never fatal
        return
    if lines:
        print()
        print(f"trends (last {window_s:g}s):")
        for line in lines:
            print(line)


def _stats_once(
    target: str, *, raw: bool = False, timeout: float = 5.0
) -> int:
    import urllib.request

    url = _stats_url(target)
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            text = resp.read().decode("utf-8", "replace")
    except Exception as e:  # noqa: BLE001 — report any scrape failure
        print(f"stats: scraping {url} failed: {e}", file=sys.stderr)
        return 2
    if raw:
        sys.stdout.write(text)
        return 0
    from pathway_tpu.internals import metrics as _metrics

    try:
        families = _metrics.parse_prometheus_text(text)
    except ValueError as e:
        print(f"stats: {url} returned a malformed exposition: {e}",
              file=sys.stderr)
        return 2

    def worker_of(labels: dict) -> str:
        return labels.get("worker", "")

    # -- per-worker mesh table -----------------------------------------------
    sums: dict[str, dict[str, float]] = {}
    lat: dict[str, list] = {}
    dev_lat: dict[str, list] = {}
    # device-resident operator kernels: (worker, kernel/op) -> value
    dev_ops_hits: dict[tuple[str, str], float] = {}
    dev_ops_ns: dict[tuple[str, str], float] = {}
    dev_ops_place: dict[tuple[str, str], float] = {}
    # snapshot read plane: per-worker serving counters / histograms
    srv_reqs: dict[str, float] = {}
    srv_shed: dict[str, float] = {}
    srv_lat: dict[str, list] = {}
    srv_stale: dict[str, float] = {}
    srv_seq: dict[str, float] = {}
    srv_uptime: dict[str, float] = {}
    # read tier: result cache, replica lag, federation fan-out
    cache_events: dict[tuple[str, str], float] = {}
    replica_lag: dict[tuple[str, str], float] = {}
    fed_reqs: dict[str, float] = {}
    fed_fanout_sum: dict[str, float] = {}
    fed_fanout_count: dict[str, float] = {}
    # continuous sampling profiler: per-worker sample counts / adaptive
    # rate / per-tick cost histogram (internals/profiling.py)
    prof_samples: dict[str, float] = {}
    prof_rate: dict[str, float] = {}
    prof_cost: dict[str, list] = {}

    def add(worker: str, col: str, value: float) -> None:
        sums.setdefault(worker, {})[col] = (
            sums.setdefault(worker, {}).get(col, 0.0) + value
        )

    col_of = {
        "pathway_output_rows_total": "out_rows",
        "pathway_operator_rows": "op_rows",
        "pathway_operator_batches_total": "batches",
        "pathway_operator_time_seconds": "op_ms",
        "pathway_exchange_events_total": "exchanges",
        "pathway_connector_entries_total": "ingested",
        "pathway_device_queue_depth": "dev_q",
        "pathway_device_occupancy_ratio": "dev_occ",
    }
    for fam_name, fam in families.items():
        col = col_of.get(fam_name)
        for name, labels, value in fam["samples"]:
            w = worker_of(labels)
            if col is not None:
                add(w, col, value * (1000.0 if col == "op_ms" else 1.0))
            elif (
                fam_name == "pathway_ingest_to_sink_latency_seconds"
                and name.endswith("_bucket")
            ):
                lat.setdefault(w, []).append((float(labels["le"]), value))
            elif (
                fam_name == "pathway_device_dispatch_complete_seconds"
                and name.endswith("_bucket")
            ):
                dev_lat.setdefault(w, []).append((float(labels["le"]), value))
            elif fam_name == "pathway_device_ops_kernel_hits_total":
                key = (w, labels.get("kernel", "?"))
                dev_ops_hits[key] = dev_ops_hits.get(key, 0.0) + value
            elif fam_name == "pathway_device_ops_kernel_ns_total":
                key = (w, labels.get("kernel", "?"))
                dev_ops_ns[key] = dev_ops_ns.get(key, 0.0) + value
            elif fam_name == "pathway_device_ops_placement":
                dev_ops_place[(w, labels.get("op", "?"))] = value
            elif fam_name == "pathway_serving_requests_total":
                srv_reqs[w] = srv_reqs.get(w, 0.0) + value
            elif fam_name == "pathway_serving_shed_total":
                srv_shed[w] = srv_shed.get(w, 0.0) + value
            elif (
                fam_name == "pathway_serving_latency_seconds"
                and name.endswith("_bucket")
            ):
                le = labels["le"]
                ub = float("inf") if le in ("+Inf", "inf") else float(le)
                srv_lat.setdefault(w, []).append((ub, value))
            elif fam_name == "pathway_serving_snapshot_staleness_seconds":
                srv_stale[w] = value
            elif fam_name == "pathway_serving_snapshot_seq":
                srv_seq[w] = value
            elif fam_name == "pathway_serving_uptime_seconds":
                srv_uptime[w] = value
            elif fam_name == "pathway_serving_cache_events_total":
                key = (w, labels.get("kind", "?"))
                cache_events[key] = cache_events.get(key, 0.0) + value
            elif fam_name == "pathway_serving_replica_lag_seconds":
                replica_lag[(w, labels.get("replica", "?"))] = value
            elif fam_name == "pathway_serving_federation_requests_total":
                fed_reqs[w] = fed_reqs.get(w, 0.0) + value
            elif fam_name == "pathway_serving_federation_fanout":
                if name.endswith("_sum"):
                    fed_fanout_sum[w] = value
                elif name.endswith("_count"):
                    fed_fanout_count[w] = value
            elif fam_name == "pathway_profile_samples_total":
                prof_samples[w] = prof_samples.get(w, 0.0) + value
            elif fam_name == "pathway_profile_rate_hz":
                prof_rate[w] = value
            elif (
                fam_name == "pathway_profile_sample_seconds"
                and name.endswith("_bucket")
            ):
                le = labels["le"]
                ub = float("inf") if le in ("+Inf", "inf") else float(le)
                prof_cost.setdefault(w, []).append((ub, value))
    # p99 exemplars: the trace id piggybacked on the deepest serving
    # latency bucket (internals/metrics.py) — joins a slow request seen
    # here straight to its assembled trace in ``cli trace --request``
    srv_exemplar: dict[str, tuple[float, str]] = {}
    for fam_name in (
        "pathway_serving_latency_seconds",
        "pathway_serving_federation_latency_seconds",
    ):
        fam = families.get(fam_name) or {}
        for _name, labels, exlabels, exvalue in fam.get("exemplars", []):
            w = worker_of(labels)
            tid = exlabels.get("trace_id")
            if tid and (
                w not in srv_exemplar or exvalue >= srv_exemplar[w][0]
            ):
                srv_exemplar[w] = (float(exvalue), str(tid))
    for w, buckets in lat.items():
        buckets.sort()
        sums.setdefault(w, {})
        sums[w]["lat_n"] = buckets[-1][1] if buckets else 0.0
        for col, q in (("lat_p50_ms", 0.5), ("lat_p99_ms", 0.99)):
            qv = _hist_quantile(buckets, q)
            if qv is not None:
                sums[w][col] = qv * 1000.0
    for w, buckets in dev_lat.items():
        # device-pipeline dispatch->complete latency (async device stage)
        buckets.sort()
        sums.setdefault(w, {})
        qv = _hist_quantile(buckets, 0.99)
        if qv is not None:
            sums[w]["dev_p99_ms"] = qv * 1000.0

    print(f"scraped {url}: {len(families)} families")
    if sums:
        cols = [
            "out_rows", "ingested", "op_rows", "batches", "op_ms",
            "exchanges", "lat_p50_ms", "lat_p99_ms", "lat_n",
            "dev_q", "dev_occ", "dev_p99_ms",
        ]
        header = ["worker"] + cols
        rows = []
        for w in sorted(sums, key=lambda k: (k != "", k)):
            vals = sums[w]
            rows.append(
                [w if w else "(local)"]
                + [
                    (f"{vals[c]:.2f}" if c.endswith("_ms") or c == "dev_occ"
                     else f"{vals[c]:.0f}") if c in vals else "-"
                    for c in cols
                ]
            )
        widths = [
            max(len(header[i]), *(len(r[i]) for r in rows))
            for i in range(len(header))
        ]
        print()
        print("  ".join(h.ljust(widths[i]) for i, h in enumerate(header)))
        for r in rows:
            print("  ".join(v.rjust(widths[i]) if i else v.ljust(widths[i])
                            for i, v in enumerate(r)))

    # -- device-resident operators -------------------------------------------
    if dev_ops_hits or dev_ops_place:
        print()
        print("device ops:")
        for (w, kernel) in sorted(dev_ops_hits):
            ms = dev_ops_ns.get((w, kernel), 0.0) / 1e6
            print(
                f"  {(w or '(local)'):<10}  kernel {kernel:<16}"
                f"  hits={dev_ops_hits[(w, kernel)]:.0f}"
                f"  device_ms={ms:.2f}"
            )
        for (w, op) in sorted(dev_ops_place):
            where = (
                "device" if dev_ops_place[(w, op)] >= 1.0 else "host"
            )
            print(
                f"  {(w or '(local)'):<10}  op     {op:<16}  -> {where}"
            )

    # -- snapshot read plane -------------------------------------------------
    if srv_reqs or srv_shed or srv_stale or srv_exemplar:
        print()
        print("serving:")
        workers = sorted(
            set(srv_reqs) | set(srv_shed) | set(srv_stale) | set(srv_lat)
            | set(srv_exemplar),
            key=lambda k: (k != "", k),
        )
        for w in workers:
            reqs = srv_reqs.get(w, 0.0)
            uptime = srv_uptime.get(w, 0.0)
            qps = f"{reqs / uptime:.1f}" if uptime > 0 else "-"
            buckets = sorted(srv_lat.get(w, []))
            quants = []
            for q in (0.50, 0.95, 0.99):
                qv = _hist_quantile(buckets, q) if buckets else None
                quants.append(f"{qv * 1000.0:.2f}" if qv is not None else "-")
            stale = srv_stale.get(w)
            print(
                f"  {(w or '(local)'):<10}"
                f"  reqs={reqs:.0f}  qps={qps}"
                f"  p50_ms={quants[0]}  p95_ms={quants[1]}"
                f"  p99_ms={quants[2]}"
                f"  shed={srv_shed.get(w, 0.0):.0f}"
                f"  snapshot_seq={srv_seq.get(w, 0.0):.0f}"
                + (f"  staleness_s={stale:.3f}" if stale is not None else "")
            )
            ex = srv_exemplar.get(w)
            if ex is not None:
                print(
                    f"  {'':<10}  p99 exemplar: {ex[1]}"
                    f"  ({ex[0] * 1000.0:.2f}ms)"
                )

    # -- read tier: result cache / replicas / federation ---------------------
    if cache_events or replica_lag or fed_reqs:
        print()
        print("read tier:")
        for w in sorted(
            {w for (w, _k) in cache_events}, key=lambda k: (k != "", k)
        ):
            hits = cache_events.get((w, "hit"), 0.0)
            misses = cache_events.get((w, "miss"), 0.0)
            total = hits + misses
            rate = f"{hits / total * 100.0:.1f}%" if total else "-"
            print(
                f"  {(w or '(local)'):<10}"
                f"  cache hit_rate={rate}"
                f"  hits={hits:.0f}  misses={misses:.0f}"
                f"  evict={cache_events.get((w, 'evict'), 0.0):.0f}"
                f"  invalidate="
                f"{cache_events.get((w, 'invalidate'), 0.0):.0f}"
            )
        for (w, rid) in sorted(replica_lag):
            print(
                f"  {(w or '(local)'):<10}"
                f"  replica {rid}  lag_s={replica_lag[(w, rid)]:.3f}"
            )
        for w in sorted(fed_reqs, key=lambda k: (k != "", k)):
            count = fed_fanout_count.get(w, 0.0)
            mean = (
                f"{fed_fanout_sum.get(w, 0.0) / count:.1f}" if count else "-"
            )
            print(
                f"  {(w or '(local)'):<10}"
                f"  federation reqs={fed_reqs[w]:.0f}"
                f"  fan_out_mean={mean}"
            )

    # -- sampling profiler ---------------------------------------------------
    if prof_samples:
        print()
        print("profiler:")
        for w in sorted(prof_samples, key=lambda k: (k != "", k)):
            buckets = sorted(prof_cost.get(w, []))
            quants = []
            for q in (0.50, 0.95, 0.99):
                qv = _hist_quantile(buckets, q) if buckets else None
                quants.append(
                    f"{qv * 1e6:.0f}" if qv is not None else "-"
                )
            rate = prof_rate.get(w)
            rate_s = f"{rate:.1f}" if rate is not None else "-"
            print(
                f"  {(w or '(local)'):<10}"
                f"  samples={prof_samples[w]:.0f}  rate_hz={rate_s}"
                f"  tick_us: p50={quants[0]}"
                f"  p95={quants[1]}  p99={quants[2]}"
            )

    # -- per-family totals ---------------------------------------------------
    print()
    name_w = max((len(n) for n in families), default=6)
    print(
        f"{'family'.ljust(name_w)}  {'type'.ljust(9)}  series  total"
        "      p50      p95      p99"
    )
    for fam_name in sorted(families):
        fam = families[fam_name]
        quants = ""
        if fam["type"] == "histogram":
            series = {
                tuple(sorted(la.items()))
                for n, la, _ in fam["samples"] if n.endswith("_count")
            }
            total = sum(
                v for n, _, v in fam["samples"] if n.endswith("_count")
            )
            quants = "  ".join(
                f"{q:>7}" for q in _family_percentiles(fam["samples"])
            )
        else:
            series = {
                tuple(sorted(la.items())) for _, la, _ in fam["samples"]
            }
            total = sum(v for _, _, v in fam["samples"])
        total_s = f"{total:.0f}" if float(total).is_integer() else f"{total:.4g}"
        print(
            f"{fam_name.ljust(name_w)}  {fam['type'].ljust(9)}  "
            f"{len(series):>6}  {total_s.rjust(5)}"
            + (f"  {quants}" if quants else "")
        )
    return 0


def _family_percentiles(
    samples: list, qs: tuple = (0.5, 0.95, 0.99)
) -> list[str]:
    """p50/p95/p99 of one histogram family, aggregated across every
    series (mesh-wide: worker labels just add counts).  Cumulative
    ``_bucket`` counts sum across series per ``le`` bound, so the merged
    sequence is itself a valid cumulative histogram."""
    merged: dict[float, float] = {}
    for n, la, v in samples:
        if not n.endswith("_bucket") or "le" not in la:
            continue
        le = la["le"]
        ub = float("inf") if le in ("+Inf", "inf") else float(le)
        merged[ub] = merged.get(ub, 0.0) + v
    buckets = sorted(merged.items())
    out = []
    for q in qs:
        val = _hist_quantile(buckets, q)
        if val is None:
            out.append("-")
        elif val == 0 or 0.001 <= abs(val) < 10000:
            out.append(f"{val:.4g}")
        else:
            out.append(f"{val:.2e}")
    return out


def _request_tree(spans: list) -> list:
    """Parent/child forest over request-span ``args.sid``/``args.parent``
    links: a fan-out leg allocates its sid before the RPC and every
    remote span adopts it as a parent, so the forest IS the scatter
    tree.  Returns serializable nodes (name/cat/track/dur_ms/children),
    siblings ordered by start time."""
    nodes: list[tuple[dict, dict]] = []
    by_sid: dict[str, dict] = {}
    for s in spans:
        args = s.get("args") or {}
        node = {
            "name": s.get("name", "?"),
            "cat": s.get("cat", ""),
            "track": s.get("pid"),
            "ts": s.get("ts", 0),
            "dur_ms": round(s.get("dur", 0) / 1000.0, 3),
            "children": [],
        }
        nodes.append((node, args))
        sid = args.get("sid")
        if sid is not None:
            by_sid.setdefault(str(sid), node)
    roots = []
    for node, args in nodes:
        parent = args.get("parent")
        pnode = by_sid.get(str(parent)) if parent is not None else None
        if pnode is not None and pnode is not node:
            pnode["children"].append(node)
        else:
            roots.append(node)
    for node, _args in nodes:
        node["children"].sort(key=lambda n: n["ts"])
    roots.sort(key=lambda n: n["ts"])
    return roots


def _assemble_requests(reports: list, want_id: str | None) -> list:
    """Merge request-trace ring entries across exported files into one
    summary per trace id.  The root process's entry holds the full
    assembly (remote spans ride the response-header piggyback); any
    hop-side leftover entry contributes spans the piggyback dropped."""
    by_id: dict[str, list[dict]] = {}
    files: dict[str, list[str]] = {}
    for rep in reports:
        for t in rep.get("traces", []):
            if t.get("kind") != "request":
                continue
            tid = str(t.get("trace_id"))
            if want_id is not None and tid != want_id:
                continue
            by_id.setdefault(tid, []).append(t)
            files.setdefault(tid, []).append(rep["file"])
    out = []
    for tid, entries in sorted(by_id.items()):
        base = max(entries, key=lambda t: len(t.get("spans") or []))
        spans = list(base.get("spans") or [])
        seen = {
            (s.get("name"), s.get("ts"), s.get("pid")) for s in spans
        }
        for t in entries:
            if t is base:
                continue
            for s in t.get("spans") or []:
                key = (s.get("name"), s.get("ts"), s.get("pid"))
                if key not in seen:
                    seen.add(key)
                    spans.append(s)
        cp = base.get("critical_path") or {}
        out.append(
            {
                "trace_id": tid,
                "endpoint": base.get("endpoint"),
                "status": base.get("status"),
                "files": sorted(set(files[tid])),
                "spans": len(spans),
                "tracks": sorted(
                    {s.get("pid") for s in spans if s.get("pid") is not None}
                ),
                "wall_ms": round(cp.get("wall_s", 0.0) * 1000.0, 3),
                "critical_path": cp,
                "request": dict(base.get("request") or {}),
                "tree": _request_tree(spans),
            }
        )
    return out


def _print_request_tree(node: dict, depth: int) -> None:
    print(
        f"    {'  ' * depth}{node['name']}"
        f"  {node['dur_ms']:.2f}ms"
        f"  [{node['cat']}]"
        f"  track={node['track']}"
    )
    for child in node["children"]:
        _print_request_tree(child, depth + 1)


def trace(
    target: str | None = None,
    *,
    as_json: bool = False,
    request: str | None = None,
) -> int:
    """Validate and summarize exported Chrome trace files.

    ``target`` is one ``pathway_trace_*.json`` file or a directory of
    them (a run's ``PATHWAY_TPU_TRACE_DIR``).  Each file is checked
    against the Chrome trace-event invariants (complete X events or
    matched B/E pairs, monotonic timestamps per track) and its
    per-commit critical-path summaries are printed.  With ``request``
    (``--request [TRACE_ID]``), read-tier request traces are assembled
    across files instead — fan-out tree plus per-hop critical path —
    optionally filtered to one trace id.  Exit 2 when any file fails
    validation (or a requested trace id is missing) — the timeline
    itself is for Perfetto (https://ui.perfetto.dev) or
    chrome://tracing."""
    import glob as _glob

    from pathway_tpu.internals import tracing as _tracing

    # `cli trace --request <dir>` reads naturally: a --request value
    # that names an existing path is the target, not a trace id
    if request is not None and request and os.path.exists(request):
        if target is None:
            target = request
        request = ""
    if target is None:
        target = os.environ.get("PATHWAY_TPU_TRACE_DIR", "")
        if not target:
            print(
                "trace: no target (pass a file/dir or set "
                "PATHWAY_TPU_TRACE_DIR)",
                file=sys.stderr,
            )
            return 2
    if os.path.isdir(target):
        paths = sorted(
            _glob.glob(os.path.join(target, "pathway_trace_*.json"))
        )
        if not paths:
            print(f"no pathway_trace_*.json files in {target}",
                  file=sys.stderr)
            return 2
    else:
        paths = [target]
    rc = 0
    reports = []
    for path in paths:
        try:
            with open(path) as fh:
                obj = json.load(fh)
            events = _tracing.validate_chrome_trace(obj)
        except (OSError, ValueError) as exc:
            print(f"{path}: INVALID — {exc}", file=sys.stderr)
            rc = 2
            continue
        other = obj.get("otherData", {}) if isinstance(obj, dict) else {}
        reports.append(
            {
                "file": path,
                "events": len(events),
                "worker": other.get("worker"),
                "traces": other.get("traces", []),
            }
        )
    if request is not None:
        summaries = _assemble_requests(reports, request or None)
        if as_json:
            print(json.dumps(summaries, indent=1))
            return rc if summaries else 2
        if not summaries:
            what = f"trace id {request}" if request else "request traces"
            print(f"no {what} in {target}", file=sys.stderr)
            return 2
        for s in summaries:
            print(
                f"request {s['trace_id']}  endpoint={s['endpoint']}  "
                f"status={s['status']}  wall={s['wall_ms']:.2f}ms  "
                f"tracks={len(s['tracks'])}  spans={s['spans']}"
            )
            cp = s["critical_path"]
            print(
                f"  per-hop: queue={cp.get('queue_wait_s', 0) * 1000:.2f}ms"
                f"  exchange={cp.get('exchange_s', 0) * 1000:.2f}ms"
                f"  host={cp.get('host_compute_s', 0) * 1000:.2f}ms"
                f"  device={cp.get('device_s', 0) * 1000:.2f}ms"
            )
            chain = cp.get("chain", [])
            if chain:
                head = " -> ".join(sp["name"] for sp in chain[:8])
                if len(chain) > 8:
                    head += " -> ..."
                print(f"  critical path: {head}")
            if s["request"]:
                kv = "  ".join(
                    f"{k}={v}" for k, v in sorted(s["request"].items())
                )
                print(f"  wide event: {kv}")
            print("  fan-out tree:")
            for node in s["tree"]:
                _print_request_tree(node, 0)
        return rc
    if as_json:
        print(json.dumps(reports, indent=1))
        return rc
    for rep in reports:
        commits = [
            t
            for t in rep["traces"]
            if t.get("kind", "commit") not in ("serving", "request")
        ]
        queries = [
            t for t in rep["traces"] if t.get("kind") == "serving"
        ]
        requests_n = len(
            [t for t in rep["traces"] if t.get("kind") == "request"]
        )
        print(f"{rep['file']}: {rep['events']} events, "
              f"{len(commits)} commit trace(s), "
              f"{len(queries)} query trace(s), "
              f"{requests_n} request trace(s)")
        for t in commits:
            cp = t.get("critical_path", {})
            chain = cp.get("chain", [])
            head = " -> ".join(s["name"] for s in chain[:6])
            if len(chain) > 6:
                head += " -> ..."
            print(
                f"  {t.get('trace_id')}  commit={t.get('commit_time')}  "
                f"wall={cp.get('wall_s', 0) * 1000:.2f}ms  "
                f"host={cp.get('host_compute_s', 0) * 1000:.2f}ms  "
                f"exchange={cp.get('exchange_s', 0) * 1000:.2f}ms  "
                f"queue={cp.get('queue_wait_s', 0) * 1000:.2f}ms  "
                f"device={cp.get('device_s', 0) * 1000:.2f}ms"
            )
            if head:
                print(f"    chain: {head}")
        if queries:
            # per-endpoint rollup: sampled serving spans from the read
            # plane (knn-batch / table-lookup)
            by_name: dict[str, list[float]] = {}
            for t in queries:
                for span in t.get("spans", []):
                    by_name.setdefault(span.get("name", "?"), []).append(
                        span.get("dur", 0) / 1000.0
                    )
            for name in sorted(by_name):
                ms = sorted(by_name[name])
                print(
                    f"  query {name:<14} n={len(ms)}  "
                    f"mean={sum(ms) / len(ms):.2f}ms  "
                    f"max={ms[-1]:.2f}ms"
                )
    return rc


def _load_profile_document(target: str, timeout: float) -> dict:
    """Resolve ``cli profile``'s target into one merged document: a
    live endpoint (port / host:port / URL — fetched from ``/profile``),
    a directory of ``pathway_profile_*.json`` exports (merged, latest
    ``seq`` per worker wins), or a single export file.  Raises
    ValueError with a printable message on any failure."""
    import glob as _glob

    from pathway_tpu.internals import profiling as _profiling

    looks_remote = (
        target.isdigit()
        or "://" in target
        or (":" in target and not os.path.exists(target))
    )
    if looks_remote:
        import urllib.request
        from urllib.parse import urlsplit, urlunsplit

        parts = urlsplit(_stats_url(target))
        url = urlunsplit((parts[0], parts[1], "/profile", "", ""))
        try:
            with urllib.request.urlopen(url, timeout=timeout) as resp:
                return json.loads(resp.read().decode())
        except Exception as exc:  # noqa: BLE001 — any fetch failure
            raise ValueError(f"fetching {url} failed: {exc}") from exc
    if os.path.isdir(target):
        paths = sorted(
            _glob.glob(os.path.join(target, "pathway_profile_*.json"))
        )
        if not paths:
            raise ValueError(
                f"no pathway_profile_*.json files in {target} "
                "(PATHWAY_TPU_PROFILE_DIR of a profiled run)"
            )
        docs = []
        for path in paths:
            try:
                with open(path) as fh:
                    docs.append(json.load(fh))
            except (OSError, ValueError) as exc:
                raise ValueError(f"{path}: unreadable — {exc}") from exc
        return _profiling.merge_documents(docs)
    if os.path.exists(target):
        try:
            with open(target) as fh:
                return json.load(fh)
        except (OSError, ValueError) as exc:
            raise ValueError(f"{target}: unreadable — {exc}") from exc
    raise ValueError(f"no such profile target: {target!r}")


def profile(
    target: str,
    *,
    as_json: bool = False,
    folded: bool = False,
    out: str | None = None,
    timeout: float = 5.0,
) -> int:
    """Merge, validate, and render sampling-profiler output.

    ``target`` is a live monitoring endpoint (``/profile`` is fetched),
    a directory of per-process ``pathway_profile_*.json`` exports, or a
    single export file.  Default output is a human summary; ``--json``
    emits speedscope JSON (load at https://www.speedscope.app),
    ``--folded`` emits collapsed-stack text (flamegraph.pl).  Every
    path goes through ``validate_profile`` — exit 2 on an invalid or
    unreachable profile."""
    from pathway_tpu.internals import profiling as _profiling

    try:
        doc = _load_profile_document(target, timeout)
        _profiling.validate_profile(doc)
    except ValueError as exc:
        print(f"profile: {exc}", file=sys.stderr)
        return 2

    if folded:
        text = _profiling.folded_text(doc)
    elif as_json:
        text = json.dumps(_profiling.speedscope(doc), indent=1) + "\n"
    else:
        lines = [f"profile: {len(doc['workers'])} worker(s)"]
        for wid in sorted(doc["workers"], key=str):
            p = doc["workers"][wid]
            lines.append(
                f"  worker {wid}: pid={p.get('pid')}  "
                f"samples={p.get('sample_count', 0)}  "
                f"rate_hz={p.get('rate_hz', 0)}  "
                f"wall_s={p.get('wall_s', 0)}  "
                f"epoch={p.get('epoch', 0)}"
                + (
                    f"  dropped_stacks={p['dropped_stacks']}"
                    if p.get("dropped_stacks")
                    else ""
                )
            )
        phases = doc.get("phases") or _profiling.phase_totals(doc)
        total = sum(phases.values()) or 1.0
        lines.append("phases (sampled seconds):")
        for phase, weight in sorted(
            phases.items(), key=lambda kv: -kv[1]
        ):
            lines.append(
                f"  {phase:<10} {weight:>10.3f}s  "
                f"{100.0 * weight / total:5.1f}%"
            )
        # hottest folded stacks across the mesh, leaf shown last
        heat: dict[tuple[str, str], float] = {}
        for p in doc["workers"].values():
            for phase, stack, weight, _count in p.get("samples", ()):
                key = (phase, stack)
                heat[key] = heat.get(key, 0.0) + float(weight)
        lines.append("hot stacks:")
        for (phase, stack), weight in sorted(
            heat.items(), key=lambda kv: -kv[1]
        )[:10]:
            leaf = stack.rsplit(";", 2)[-2:]
            lines.append(
                f"  {weight:>8.3f}s  [{phase}] {';'.join(leaf)}"
            )
        text = "\n".join(lines) + "\n"

    if out:
        with open(out, "w") as fh:
            fh.write(text)
        print(f"profile: wrote {out}", file=sys.stderr)
    else:
        sys.stdout.write(text)
    return 0


def rescale(
    target_processes: int, *, supervisor_dir: str | None = None
) -> int:
    """Ask a live supervised mesh to rescale to ``target_processes``.

    Writes a ``rescale`` request file into the supervisor's control
    directory (``--supervisor-dir`` or PATHWAY_TPU_SUPERVISOR_DIR —
    launch the run with that variable preset so other terminals can
    find it).  The supervisor quiesces the mesh at its next commit
    boundary, re-shards the operator snapshots, and relaunches at the
    new size; sink output stays bit-identical."""
    sup_dir = supervisor_dir or os.environ.get("PATHWAY_TPU_SUPERVISOR_DIR")
    if not sup_dir:
        print(
            "rescale: no supervisor directory — pass --supervisor-dir "
            "or set PATHWAY_TPU_SUPERVISOR_DIR to the value the "
            "supervised run was launched with",
            file=sys.stderr,
        )
        return 2
    if not os.path.isdir(sup_dir):
        print(
            f"rescale: supervisor directory {sup_dir!r} does not exist "
            "(is the supervised run alive?)",
            file=sys.stderr,
        )
        return 2
    if target_processes < 1:
        print(
            f"rescale: target process count must be >= 1, "
            f"got {target_processes}",
            file=sys.stderr,
        )
        return 2
    from pathway_tpu.engine.supervisor import RESCALE_REQUEST

    path = os.path.join(sup_dir, RESCALE_REQUEST)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        fh.write(str(target_processes))
    os.replace(tmp, path)
    print(
        f"rescale: requested {target_processes} processes "
        f"(request file {path})",
        file=sys.stderr,
    )
    return 0


def main(argv: Sequence[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="pathway")
    sub = parser.add_subparsers(dest="command", required=True)

    p_spawn = sub.add_parser(
        "spawn", help="run a pathway program over N threads × M processes"
    )
    p_spawn.add_argument("--threads", "-t", type=int, default=1)
    p_spawn.add_argument("--processes", "-n", type=int, default=1)
    p_spawn.add_argument("--first-port", type=int, default=10000)
    p_spawn.add_argument("program")
    p_spawn.add_argument("arguments", nargs=argparse.REMAINDER)

    sub.add_parser(
        "spawn-from-env",
        help="run the command from the PATHWAY_SPAWN_ARGS env variable",
    )

    p_analyze = sub.add_parser(
        "analyze",
        help="statically analyze the graphs a program builds, "
        "without executing them",
    )
    p_analyze.add_argument(
        "--json", action="store_true", help="emit the report as JSON"
    )
    p_analyze.add_argument(
        "--errors-only",
        action="store_true",
        help="exit 1 only on error-severity findings (ignore warnings)",
    )
    p_analyze.add_argument(
        "--strict",
        action="store_true",
        help="exit 1 on ANY finding, info included",
    )
    p_analyze.add_argument(
        "--source",
        action="store_true",
        help="lint runtime source instead of a graph: positional "
        "arguments are .py files/directories for the PWC concurrency "
        "and protocol passes",
    )
    p_analyze.add_argument("program")
    p_analyze.add_argument("arguments", nargs=argparse.REMAINDER)

    p_rescale = sub.add_parser(
        "rescale",
        help="ask a live supervised mesh to rescale to a new process "
        "count (quiesce + re-shard + relaunch, bit-identical sinks)",
    )
    p_rescale.add_argument(
        "--supervisor-dir",
        default=None,
        help="control directory of the supervised run (defaults to "
        "PATHWAY_TPU_SUPERVISOR_DIR)",
    )
    p_rescale.add_argument("target_processes", type=int)

    p_replica = sub.add_parser(
        "replica",
        help="run a read-only serving replica subscribed to a mesh's "
        "snapshot streams (scales query capacity without widening "
        "ingest)",
    )
    p_replica.add_argument("--port", type=int, default=None)
    p_replica.add_argument("--replica-id", type=int, default=0)
    p_replica.add_argument(
        "--sources", default=None,
        help="host:port list of worker stream endpoints (default: "
        "derive from --width and the 22000+pid port scheme)",
    )
    p_replica.add_argument("--width", type=int, default=None)
    p_replica.add_argument("--host", default="127.0.0.1")
    p_replica.add_argument("--max-staleness-s", type=float, default=None)

    p_fed = sub.add_parser(
        "federation",
        help="run a federation front: one read endpoint scattering to "
        "worker query servers and round-robining replica pools",
    )
    p_fed.add_argument("--port", type=int, default=None)
    p_fed.add_argument(
        "--workers", default=None,
        help="comma list of worker query ports (default: derive from "
        "PATHWAY_PROCESSES and the 21000+pid port scheme)",
    )
    p_fed.add_argument(
        "--replicas", default=None,
        help="replica count or host:port list (default: none)",
    )

    p_stats = sub.add_parser(
        "stats",
        help="scrape a /metrics endpoint and pretty-print the "
        "mesh-wide table",
    )
    p_stats.add_argument(
        "--raw", action="store_true",
        help="dump the raw exposition text instead of the table",
    )
    p_stats.add_argument("--timeout", type=float, default=5.0)
    p_stats.add_argument(
        "--watch", type=float, default=None, metavar="SECONDS",
        help="re-scrape every N seconds (clear screen) with history "
        "sparklines from the endpoint's /timeseries ring",
    )
    p_stats.add_argument(
        "target", help="port, host:port, or full URL of the endpoint"
    )

    p_profile = sub.add_parser(
        "profile",
        help="merge + validate + render sampling-profiler output "
        "(live /profile endpoint, a PATHWAY_TPU_PROFILE_DIR, or one "
        "export file)",
    )
    p_profile.add_argument(
        "--json", action="store_true",
        help="emit speedscope JSON (https://www.speedscope.app)",
    )
    p_profile.add_argument(
        "--folded", action="store_true",
        help="emit collapsed-stack text (flamegraph.pl / speedscope)",
    )
    p_profile.add_argument(
        "-o", "--out", default=None, help="write output to a file"
    )
    p_profile.add_argument("--timeout", type=float, default=5.0)
    p_profile.add_argument(
        "target",
        help="port / host:port / URL of a live run, a directory of "
        "pathway_profile_*.json exports, or one export file",
    )

    p_trace = sub.add_parser(
        "trace",
        help="validate + summarize exported Chrome trace files "
        "(pathway_trace_*.json; load them in Perfetto for the timeline)",
    )
    p_trace.add_argument(
        "--json", action="store_true",
        help="emit the per-trace summaries as JSON",
    )
    p_trace.add_argument(
        "--request", nargs="?", const="", default=None,
        metavar="TRACE_ID",
        help="assemble read-tier request traces across the exported "
        "files (fan-out tree + per-hop critical path), optionally "
        "filtered to one trace id",
    )
    p_trace.add_argument(
        "target", nargs="?", default=None,
        help="a trace file, or a directory of pathway_trace_*.json "
        "dumps (defaults to PATHWAY_TPU_TRACE_DIR)",
    )

    args = parser.parse_args(argv)
    if args.command == "spawn":
        return spawn(
            args.program,
            args.arguments,
            threads=args.threads,
            processes=args.processes,
            first_port=args.first_port,
        )
    if args.command == "analyze":
        if args.source:
            return analyze_source(
                [args.program, *args.arguments],
                as_json=args.json,
                errors_only=args.errors_only,
                strict=args.strict,
            )
        return analyze(
            args.program,
            args.arguments,
            as_json=args.json,
            errors_only=args.errors_only,
            strict=args.strict,
        )
    if args.command == "rescale":
        return rescale(
            args.target_processes, supervisor_dir=args.supervisor_dir
        )
    if args.command == "replica":
        from pathway_tpu.serving import replica as _replica

        replica_args = []
        if args.port is not None:
            replica_args += ["--port", str(args.port)]
        replica_args += ["--replica-id", str(args.replica_id)]
        if args.sources:
            replica_args += ["--sources", args.sources]
        if args.width is not None:
            replica_args += ["--width", str(args.width)]
        replica_args += ["--host", args.host]
        if args.max_staleness_s is not None:
            replica_args += ["--max-staleness-s", str(args.max_staleness_s)]
        return _replica.main(replica_args)
    if args.command == "federation":
        from pathway_tpu.serving import federation as _federation

        fed_args = []
        if args.port is not None:
            fed_args += ["--port", str(args.port)]
        if args.workers:
            fed_args += ["--workers", args.workers]
        if args.replicas:
            fed_args += ["--replicas", args.replicas]
        return _federation.main(fed_args)
    if args.command == "stats":
        return stats(
            args.target,
            raw=args.raw,
            timeout=args.timeout,
            watch=args.watch,
        )
    if args.command == "trace":
        return trace(
            args.target, as_json=args.json, request=args.request
        )
    if args.command == "profile":
        return profile(
            args.target,
            as_json=args.json,
            folded=args.folded,
            out=args.out,
            timeout=args.timeout,
        )
    if args.command == "spawn-from-env":
        spawn_args = os.environ.get("PATHWAY_SPAWN_ARGS", "")
        if not spawn_args:
            print("PATHWAY_SPAWN_ARGS is not set", file=sys.stderr)
            return 2
        return main(["spawn", *shlex.split(spawn_args)])
    return 2


if __name__ == "__main__":
    sys.exit(main())

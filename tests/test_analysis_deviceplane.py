"""Seeded-bug corpus for the device-plane discipline pass (PWD6xx).

Each test writes a small module with ONE deliberately planted violation
from the classes the analyzer polices — implicit sync in a hot path,
branch-on-traced-shape, uncounted transfer, partial push on a
decline/except path, unregistered resident state, import-cached live
flag, metric-family drift — and asserts the pass reports exactly that
code at the right line (and nothing else).  Negative twins prove the
exemptions (materialize/fetch helpers, counted functions, static config
branches, registered classes, startup flags, consistent re-registration)
and the ``# pwd-ok`` waivers hold, and the final tests pin the real tree
to strict zero so the tools/check.py gates can never rot silently.
"""

import json
import os
import textwrap

from pathway_tpu.analysis.findings import Severity
from pathway_tpu.analysis.source import analyze_paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _analyze(tmp_path, source: str, name: str = "mod.py"):
    f = tmp_path / name
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    report = analyze_paths([str(f)], root=str(tmp_path))
    assert not report.internal_errors, report.internal_errors
    return report


def _codes(report) -> list[str]:
    return [f.code for f in report.findings]


def _line_of(source: str, needle: str) -> int:
    for i, line in enumerate(textwrap.dedent(source).splitlines(), start=1):
        if needle in line:
            return i
    raise AssertionError(f"needle {needle!r} not in source")


class TestHotPathSync:
    SRC_FLOAT = """\
        import jax.numpy as jnp

        def process(self, batch):
            acc = jnp.sum(batch)
            return float(acc)
        """

    def test_float_on_jnp_value_pwd601(self, tmp_path):
        report = _analyze(tmp_path, self.SRC_FLOAT)
        assert _codes(report) == ["PWD601"]
        (f,) = report.findings
        assert f.severity is Severity.WARNING
        assert f.node_index == _line_of(self.SRC_FLOAT, "float(acc)")
        assert "acc" in f.message and "process" in f.message

    def test_item_in_exchange_path_pwd601(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            import jax.numpy as jnp

            def exchange_totals(rows):
                total = jnp.max(rows)
                return total.item()
            """,
        )
        assert _codes(report) == ["PWD601"]
        assert ".item()" in report.findings[0].message

    def test_materialize_helper_exempt(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            import jax.numpy as jnp

            def materialize_totals(rows):
                total = jnp.max(rows)
                return total.item()
            """,
        )
        assert _codes(report) == []

    def test_counted_fetch_exempt(self, tmp_path):
        # a hot-path function that touches the transfer ledger is an
        # explicit counted fetch — PWD603's jurisdiction, not PWD601's
        report = _analyze(
            tmp_path,
            """\
            import numpy as np
            import jax.numpy as jnp

            def exchange(rows):
                out = jnp.cumsum(rows)
                fetched = np.asarray(out)
                record_d2h(fetched.nbytes)
                return fetched
            """,
        )
        assert _codes(report) == []

    def test_pwd_ok_waiver(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            import jax.numpy as jnp

            def process(self, batch):
                acc = jnp.sum(batch)
                return float(acc)  # pwd-ok: PWD601 per-commit readback
            """,
        )
        assert _codes(report) == []
        assert [f.code for f in report.waived] == ["PWD601"]
        assert report.waived[0].waived is True


class TestRecompileHazard:
    SRC_SHAPE = """\
        import jax

        def _kernel(x):
            if x.shape[0] > 8:
                return x * 2
            return x

        compiled = jax.jit(_kernel)
        """

    def test_shape_branch_in_jitted_fn_pwd602(self, tmp_path):
        report = _analyze(tmp_path, self.SRC_SHAPE)
        assert _codes(report) == ["PWD602"]
        (f,) = report.findings
        assert f.severity is Severity.ERROR
        assert f.node_index == _line_of(self.SRC_SHAPE, "if x.shape[0]")
        assert "shape" in f.message

    def test_value_branch_under_decorator_pwd602(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            import jax

            @jax.jit
            def clip(v):
                if v > 0:
                    return v
                return -v
            """,
        )
        assert _codes(report) == ["PWD602"]
        assert "value" in report.findings[0].message

    def test_python_loop_over_param_bound_pwd602(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            from functools import partial
            import jax

            @partial(jax.jit, static_argnums=())
            def fold(xs, n):
                acc = 0
                for i in range(n):
                    acc = acc + xs[i]
                return acc
            """,
        )
        assert _codes(report) == ["PWD602"]
        assert "fori_loop" in report.findings[0].message

    def test_shard_map_wrapped_fn_pwd602(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            def bucket(payload):
                if len(payload) > 4:
                    return payload
                return payload

            def build(shard_map):
                return shard_map(bucket)
            """,
        )
        assert _codes(report) == ["PWD602"]

    def test_static_config_branch_exempt(self, tmp_path):
        # comparisons against string constants / None are static config,
        # and untraced functions may branch on anything
        report = _analyze(
            tmp_path,
            """\
            import jax

            @jax.jit
            def reduce_op(x, op):
                if op == "sum":
                    return x.sum()
                if x is None:
                    return x
                return x.max()

            def host_side(x):
                if x.shape[0] > 8:
                    return x * 2
                return x
            """,
        )
        assert _codes(report) == []


class TestUncountedTransfer:
    SRC_PUT = """\
        import jax

        def upload(batch):
            return jax.device_put(batch)
        """

    def test_device_put_without_ledger_pwd603(self, tmp_path):
        report = _analyze(tmp_path, self.SRC_PUT, name="engine/mod.py")
        assert _codes(report) == ["PWD603"]
        (f,) = report.findings
        assert f.severity is Severity.ERROR
        assert f.node_index == _line_of(self.SRC_PUT, "device_put")
        assert "record_h2d" in f.message

    def test_materialization_without_ledger_pwd603(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            import numpy as np
            import jax.numpy as jnp

            def download(out):
                dev = jnp.dot(out, out)
                return np.asarray(dev)
            """,
            name="engine/mod.py",
        )
        assert _codes(report) == ["PWD603"]

    def test_outside_engine_exempt(self, tmp_path):
        report = _analyze(tmp_path, self.SRC_PUT, name="tools/mod.py")
        assert _codes(report) == []

    def test_counted_in_same_function_exempt(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            import jax

            def upload(batch, _dres):
                _dres.record_h2d(batch.nbytes)
                return jax.device_put(batch)
            """,
            name="engine/mod.py",
        )
        assert _codes(report) == []

    def test_counted_via_local_helper_exempt(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            import jax

            def _count(nbytes):
                record_h2d(nbytes)

            def upload(batch):
                _count(batch.nbytes)
                return jax.device_put(batch)
            """,
            name="engine/mod.py",
        )
        assert _codes(report) == []

    def test_jitted_body_exempt(self, tmp_path):
        # jnp calls inside a traced function are staged ops, not transfers
        report = _analyze(
            tmp_path,
            """\
            import jax
            import jax.numpy as jnp

            @jax.jit
            def kernel(x):
                return jnp.asarray(x) * 2
            """,
            name="engine/mod.py",
        )
        assert _codes(report) == []

    def test_pwd_ok_waiver(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            import jax

            def upload(batch):
                return jax.device_put(batch)  # pwd-ok: PWD603 test rig
            """,
            name="engine/mod.py",
        )
        assert _codes(report) == []
        assert [f.code for f in report.waived] == ["PWD603"]


class TestPartialPush:
    SRC_EXCEPT = """\
        def deliver_parts(consumer, parts, pack):
            try:
                payload = pack(parts)
            except ValueError:
                consumer.push(parts)
                return None
            return payload
        """

    def test_push_on_except_path_pwd604(self, tmp_path):
        report = _analyze(tmp_path, self.SRC_EXCEPT)
        assert _codes(report) == ["PWD604"]
        (f,) = report.findings
        assert f.severity is Severity.ERROR
        assert f.node_index == _line_of(self.SRC_EXCEPT, "consumer.push(parts)")
        assert "except path" in f.message

    def test_push_after_decline_counter_pwd604(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            STATS = {}

            def run(consumer, stats, parts):
                stats["declined_non_codeable"] += 1
                consumer.push(parts)
            """,
            name="exchange.py",
        )
        assert _codes(report) == ["PWD604"]
        assert "decline path" in report.findings[0].message

    def test_materialize_before_push_exempt(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            import numpy as np

            def deliver_parts(consumer, parts, pack):
                try:
                    payload = pack(parts)
                except ValueError:
                    whole = np.asarray(parts)
                    consumer.push(whole)
                    return None
                return payload
            """,
        )
        assert _codes(report) == []

    def test_normal_path_push_exempt(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            def deliver_parts(consumer, payload):
                consumer.push(payload)
            """,
        )
        assert _codes(report) == []


class TestResidencyLeak:
    SRC_LEAK = """\
        class DeviceResidentColumns:
            def __init__(self, cols):
                self.cols = cols

        def build(cols):
            return DeviceResidentColumns(cols)
        """

    def test_unregistered_class_pwd605(self, tmp_path):
        report = _analyze(tmp_path, self.SRC_LEAK)
        assert _codes(report) == ["PWD605"]
        (f,) = report.findings
        assert f.severity is Severity.ERROR
        assert f.node_index == _line_of(
            self.SRC_LEAK, "return DeviceResidentColumns"
        )
        assert "decay_resident_batches" in f.message

    def test_self_registering_class_exempt(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            import weakref

            _LIVE_RESIDENT = weakref.WeakSet()

            class DeviceResidentColumns:
                def __init__(self, cols):
                    self.cols = cols
                    _LIVE_RESIDENT.add(self)

            def build(cols):
                return DeviceResidentColumns(cols)
            """,
        )
        assert _codes(report) == []

    def test_site_registration_exempt(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            import weakref

            _staged_handles = weakref.WeakSet()

            class DeviceResidentColumns:
                def __init__(self, cols):
                    self.cols = cols

            def build(cols):
                out = DeviceResidentColumns(cols)
                _staged_handles.add(out)
                return out
            """,
        )
        assert _codes(report) == []

    def test_pwd_ok_bare_waiver(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            class DeviceResidentColumns:
                def __init__(self, cols):
                    self.cols = cols

            def build(cols):
                return DeviceResidentColumns(cols)  # pwd-ok: host-only twin
            """,
        )
        assert _codes(report) == []
        assert [f.code for f in report.waived] == ["PWD605"]


class TestFlagLiveness:
    SRC_CACHED = """\
        import os

        _ENABLED = os.environ.get("PATHWAY_TPU_DEVICE_RESIDENCY") == "1"

        def enabled():
            return _ENABLED
        """

    def test_live_flag_cached_at_module_scope_pwd606(self, tmp_path):
        report = _analyze(tmp_path, self.SRC_CACHED)
        assert _codes(report) == ["PWD606"]
        (f,) = report.findings
        assert f.severity is Severity.ERROR
        assert f.node_index == _line_of(self.SRC_CACHED, "_ENABLED = ")
        assert "PATHWAY_TPU_DEVICE_RESIDENCY" in f.message
        assert "flags.py" in f.message

    def test_live_flag_cached_at_class_scope_pwd606(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            import os

            class Plane:
                enabled = os.getenv("PATHWAY_TPU_COLLECTIVE_EXCHANGE", "auto")
            """,
        )
        assert _codes(report) == ["PWD606"]
        assert "class Plane" in report.findings[0].message

    def test_startup_flag_exempt(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            import os

            _INFLIGHT = int(os.environ.get("PATHWAY_TPU_DEVICE_INFLIGHT", "2"))
            """,
        )
        assert _codes(report) == []

    def test_per_call_read_exempt(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            import os

            def enabled():
                return os.environ.get("PATHWAY_TPU_DEVICE_RESIDENCY", "auto")
            """,
        )
        assert _codes(report) == []


class TestMetricFamilies:
    SRC_DRIFT = """\
        from pathway_tpu.internals.metrics import REGISTRY

        A = REGISTRY.counter("pathway_widget_total", "widgets", kind="a")
        B = REGISTRY.counter("pathway_widget_total", "widgets", worker="0")
        """

    def test_label_drift_pwd607(self, tmp_path):
        report = _analyze(tmp_path, self.SRC_DRIFT)
        assert _codes(report) == ["PWD607"]
        (f,) = report.findings
        assert f.severity is Severity.WARNING
        assert f.node_index == _line_of(self.SRC_DRIFT, 'worker="0"')
        assert "label sets must agree" in f.message

    def test_unregistered_family_use_pwd607(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            def bump(store):
                store.inc("pathway_ghost_total", 1)
            """,
        )
        assert _codes(report) == ["PWD607"]
        assert "never registered" in report.findings[0].message

    def test_consistent_reregistration_exempt(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            from pathway_tpu.internals.metrics import REGISTRY

            def fam():
                return REGISTRY.counter("pathway_w_total", "w", kind="a")

            def fam2():
                return REGISTRY.counter("pathway_w_total", "w", kind="b")
            """,
        )
        assert _codes(report) == []

    def test_mirrored_counter_registration_counts(self, tmp_path):
        report = _analyze(
            tmp_path,
            """\
            from pathway_tpu.internals.metrics import MirroredCounterDict

            STATS = MirroredCounterDict(
                "pathway_plane_events_total", "kind", {"hits": 0}
            )

            def bump(store):
                store.inc("pathway_plane_events_total", 1)
            """,
        )
        assert _codes(report) == []


class TestJsonOutput:
    def test_source_json_schema_includes_waived(self, tmp_path, capsys):
        from pathway_tpu import cli

        f = tmp_path / "engine" / "mod.py"
        f.parent.mkdir()
        f.write_text(
            textwrap.dedent(
                """\
                import jax

                def upload(batch):
                    return jax.device_put(batch)

                def upload_waived(batch):
                    return jax.device_put(batch)  # pwd-ok: PWD603 rig
                """
            )
        )
        old = os.getcwd()
        os.chdir(tmp_path)
        try:
            rc = cli.analyze_source([str(f)], as_json=True, strict=True)
        finally:
            os.chdir(old)
        out = json.loads(capsys.readouterr().out)
        assert rc == 1  # the unwaived finding fails strict mode
        assert out["mode"] == "source"
        assert out["files"] == 1
        recs = out["findings"]
        assert {r["code"] for r in recs} == {"PWD603"}
        by_waived = {r["waived"]: r for r in recs}
        assert set(by_waived) == {True, False}
        for r in recs:
            assert set(r) == {
                "code", "path", "line", "column", "severity",
                "message", "waived",
            }
        assert out["summary"]["errors"] == 1
        assert out["summary"]["waived"] == 1

    def test_waived_only_tree_exits_zero(self, tmp_path, capsys):
        from pathway_tpu import cli

        f = tmp_path / "engine" / "mod.py"
        f.parent.mkdir()
        f.write_text(
            "import jax\n\n"
            "def upload(batch):\n"
            "    return jax.device_put(batch)  # pwd-ok: PWD603 rig\n"
        )
        rc = cli.analyze_source([str(f)], as_json=True, strict=True)
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert out["summary"]["waived"] == 1


class TestRealTree:
    def test_runtime_tree_is_strict_clean(self):
        """The shipped tree must analyze strict-clean: zero findings of
        ANY severity across concurrency, protocol, and device-plane
        passes — the pin behind tools/check.py's whole-tree source-lint
        and deviceplane-lint gates."""
        target = os.path.join(REPO, "pathway_tpu")
        report = analyze_paths([target], root=REPO)
        assert report.node_count > 100
        assert not report.internal_errors, report.internal_errors
        assert not report.findings, "\n".join(
            f.render() for f in report.sorted_findings()
        )

    def test_every_pwd_code_is_registered(self):
        from pathway_tpu.analysis.findings import FINDING_CODES

        for code in (
            "PWD601", "PWD602", "PWD603", "PWD604",
            "PWD605", "PWD606", "PWD607",
        ):
            assert code in FINDING_CODES

    def test_flag_registry_covers_live_planes(self):
        from pathway_tpu.analysis.flags import LIVE_FLAGS, REGISTRY

        for name in (
            "PATHWAY_TPU_COLLECTIVE_EXCHANGE",
            "PATHWAY_TPU_DEVICE_RESIDENCY",
            "PATHWAY_TPU_DEVICE_OPS",
            "PATHWAY_TPU_ASYNC_DEVICE",
        ):
            assert name in LIVE_FLAGS
        # startup flags must never be classified live by accident
        assert "PATHWAY_TPU_DEVICE_INFLIGHT" in REGISTRY
        assert "PATHWAY_TPU_DEVICE_INFLIGHT" not in LIVE_FLAGS

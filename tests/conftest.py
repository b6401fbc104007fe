import os

# Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths are
# exercised without TPU hardware (chip_smoke.py is the check on the chip).
os.environ["JAX_PLATFORMS"] = "cpu"
# the parsers' default vision seam compiles a ViT; the tiny preset keeps
# CPU test runs fast while exercising the identical code path
os.environ.setdefault("PATHWAY_VISION_PRESET", "vit-tiny")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running chaos soak / scale tests excluded from tier-1",
    )


@pytest.fixture
def own_stage_table():
    """For a test that reads ``tracing.stage_totals()`` or
    ``commit_timeline()`` after its own ``pw.run()``: a run that an earlier
    test of this worker left open (a ``pw.run()`` on a thread nobody
    stopped) keeps the process-wide table, since a run begun while another
    is open leaves the table alone, and the test would read that run's
    rows. Close it, and take its collector's hook away."""
    import gc

    from pathway_tpu.internals import tracing

    tracing.STAGES._root = None
    gc.callbacks[:] = [
        hook for hook in gc.callbacks
        if getattr(hook, "__self__", None) is not tracing.STAGES
    ]

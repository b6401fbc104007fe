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

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running chaos soak / scale tests excluded from tier-1",
    )

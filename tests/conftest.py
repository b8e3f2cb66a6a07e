"""Test env: force an 8-device virtual CPU platform BEFORE any XLA client
initializes, so multi-chip sharding tests run anywhere (the driver's
multichip dryrun uses the same mechanism).

EDL_TPU_TEST_PLATFORM=tpu leaves the platform to JAX instead — the rig
for tests/test_tpu_smoke.py on a machine with a chip.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

_platform = os.environ.get("EDL_TPU_TEST_PLATFORM", "cpu")
if _platform == "tpu":
    os.environ.pop("JAX_PLATFORMS", None)
else:
    os.environ["JAX_PLATFORMS"] = _platform

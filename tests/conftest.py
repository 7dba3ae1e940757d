"""Test harness: force an 8-device virtual CPU mesh before jax imports.

Mirrors the reference's missing-but-implied multi-node-without-a-cluster
strategy (SURVEY.md §4): all sharding/collective tests run on
``--xla_force_host_platform_device_count=8`` CPU devices so CI needs no
TPU slice.

On-chip subset: ``SRT_TPU_TESTS=1 python -m pytest
tests/test_on_chip.py -m tpu -q`` skips the CPU pin so the
``tpu``-marked tests run against the TPU — closing the gap between
"tests green on the CPU farm" and "works on hardware" without running
the whole suite on the chip.
"""

import os

if os.environ.get("SRT_TPU_TESTS"):
    # the TPU; only `-m tpu` tests should be selected in this mode
    import jax  # noqa: F401
else:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    # pin the platform via jax.config too, so the sharding tests see
    # the 8 virtual CPU devices whatever the environment says
    import jax  # noqa: E402

    jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: on-chip test (run with SRT_TPU_TESTS=1 python -m pytest -m tpu)",
    )
    config.addinivalue_line(
        "markers",
        "faults: fault-injection test (exercises the resilience retry "
        "ladder via sparkrdma_tpu.testing.faults or transport seams)",
    )
    # race harness: SPARKRDMA_LOCK_ORDER=1 arms the lock-order detector
    # for the whole session (sparkrdma_tpu/analysis/lockorder.py) and
    # fails it on acquisition-order cycles or blocking calls under
    # hot-path locks; unset, the plugin is inert
    if not config.pluginmanager.has_plugin("sparkrdma-lockorder"):
        from sparkrdma_tpu.analysis import pytest_plugin

        config.pluginmanager.register(pytest_plugin, "sparkrdma-lockorder")

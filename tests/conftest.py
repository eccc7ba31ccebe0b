"""Test configuration: CPU backend with 8 virtual devices, so distributed
tests exercise real mesh sharding without TPU hardware (the reference's
custom_cpu fake-device trick, SURVEY.md §4). Both are set in the environment
before jax is imported; child processes the tests start inherit them.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavier integration tests excluded from the tier-1 "
        "`-m 'not slow'` sweep (still run by plain pytest and the benches)")
    backend = jax.default_backend()
    if backend != "cpu" or jax.device_count() < 8:
        raise RuntimeError(
            f"tests need the 8-device CPU mesh but jax initialized as "
            f"{backend!r} with {jax.device_count()} device(s) — was jax "
            "imported before this conftest? Run with JAX_PLATFORMS=cpu "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8."
        )


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle

    np.random.seed(0)
    paddle.seed(0)
    yield

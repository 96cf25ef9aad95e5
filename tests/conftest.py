"""Test harness: single-process 8-device CPU mesh.

The reference validated distributed behavior with a loopback Transfer fixture
(one process sending RPCs to itself, ``unitest/core/transfer/transfer_test.h:36-81``).
The modern analog — and our substrate for every sharding test — is XLA's
virtual host platform: 8 CPU devices in one process exercising the real
pjit/shard_map code path (SURVEY §4).

Env vars must be set before jax initializes its backends, hence this conftest.
"""

from swiftsnails_tpu.utils.platform_pin import pin_cpu

pin_cpu(8)  # tests run on the virtual CPU mesh, whatever the host holds

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True)
def _fresh_global_config():
    """Isolate tests from the process-wide config singleton."""
    from swiftsnails_tpu.utils.config import global_config

    global_config().clear()
    yield
    global_config().clear()

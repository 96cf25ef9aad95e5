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


@pytest.fixture(scope="session")
def drill_once(tmp_path_factory):
    """``drill_once(name, fn, limit_s)``: run ``fn(workdir)`` once for the
    whole test run and hand every test its (JSON) result. Under xdist the
    workers share one directory and a file lock on it: the first to hold the
    lock runs the drill and the others read what it wrote, so a drill that
    spawns processes never runs six at a time; a worker that dies holding
    the lock lets go of it, and the next one runs the drill. ``limit_s`` is
    the drill's own time limit: past it the result is an error, not a hang."""
    import fcntl
    import json
    import os
    import threading

    def limited(fn, workdir, limit_s):
        box = {}

        def target():
            try:
                box["result"] = fn(workdir)
            except BaseException as e:  # every worker reads the same error
                box["result"] = {"drill_error": f"{type(e).__name__}: {e}"}

        t = threading.Thread(target=target, daemon=True)
        t.start()
        t.join(limit_s)
        return box.get(
            "result", {"drill_error": f"still running after {limit_s} s"})

    root = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        root = root.parent  # the run's directory, above each worker's own
    results = {}

    def once(name, fn, limit_s):
        if name not in results:
            out = root / f"{name}.json"
            with open(root / f"{name}.lock", "w") as lock:
                fcntl.flock(lock, fcntl.LOCK_EX)
                if not out.exists():
                    tmp = root / f"{name}.json.tmp"
                    tmp.write_text(json.dumps(
                        limited(fn, str(root / name), limit_s)))
                    os.replace(tmp, out)
                results[name] = json.loads(out.read_text())
        if "drill_error" in results[name]:
            pytest.fail(f"drill {name}: {results[name]['drill_error']}")
        return results[name]

    return once

"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh BEFORE any jax
import, so sharding tests exercise real multi-device code paths without TPU
hardware (mirrors the reference's ct_slave multi-node-on-one-host strategy,
``vmq_cluster_test_utils.erl:109-175``)."""

import os
import sys

# tests always run on the virtual CPU mesh, whatever the environment says
# (a pytest plugin may import jax before conftest runs, so set the config
# value as well as the env var)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# property tests share the cores with five other xdist workers: no
# per-example deadline (hypothesis's default 200 ms flakes under load)
from hypothesis import settings as _hyp_settings  # noqa: E402

_hyp_settings.register_profile("tier1", deadline=None)
_hyp_settings.load_profile("tier1")

# Hung-test forensics: a test that wedges (a real stall the watchdog
# misses, a deadlock in test plumbing) used to die SILENTLY at the
# outer `timeout` wall with no clue which test or thread
# hung. With TIER1_FAULTHANDLER_S set (tools/run_tier1.sh sets it just
# below the outer wall), faulthandler dumps every thread's stack to
# stderr at that mark — the run still gets killed, but the log says
# where it was stuck. repeat=True keeps dumping if the hang persists.
import faulthandler  # noqa: E402

_dump_after = int(os.environ.get("TIER1_FAULTHANDLER_S") or 0)
if _dump_after > 0:
    faulthandler.enable()
    faulthandler.dump_traceback_later(_dump_after, repeat=True,
                                      exit=False)

# ---------------------------------------------------------------------------
# Minimal async-test support (pytest-asyncio is not in the image): async test
# functions run on a per-test event loop; fixtures get the same loop via the
# `event_loop` fixture.
# ---------------------------------------------------------------------------
import asyncio
import inspect

import pytest


@pytest.fixture
def event_loop():
    loop = asyncio.new_event_loop()
    asyncio.set_event_loop(loop)
    yield loop
    # let pending callbacks (cancellations) settle before closing
    loop.run_until_complete(asyncio.sleep(0))
    loop.close()
    asyncio.set_event_loop(None)


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    testfn = pyfuncitem.obj
    if inspect.iscoroutinefunction(testfn):
        loop = pyfuncitem._request.getfixturevalue("event_loop")
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        loop.run_until_complete(asyncio.wait_for(testfn(**kwargs), timeout=30))
        return True
    return None


@pytest.fixture(scope="module", autouse=True)
def _reap_worker_processes():
    """Multi-process hygiene: any broker worker / match-service child
    still alive when a test module finishes is reaped here. A leaked
    worker would keep the SO_REUSEPORT socket (and its shm segments)
    open and flake the next module's port/segment setup. Module scope
    tears down AFTER the module's own group fixtures, so this only
    catches what a failed test left behind."""
    yield
    import multiprocessing as mp

    for p in mp.active_children():
        if p.name.startswith(("vmq-worker", "vmq-match-service")):
            p.terminate()
            p.join(3.0)
            if p.is_alive():
                p.kill()
                p.join(1.0)


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: async test (built-in shim)")
    config.addinivalue_line(
        "markers",
        "multiproc: boots real worker processes (reaped on module "
        "teardown by conftest)")
    config.addinivalue_line(
        "markers", "slow: long-running test (excluded from tier-1)")
    config.addinivalue_line(
        "markers",
        "chaos: long fault-injection soak test (opt-in: run with "
        "-m chaos; chaos tests are also marked slow so tier-1's "
        "-m 'not slow' excludes them)")

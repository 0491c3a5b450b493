"""Test harness config: force the CPU backend with 8 virtual devices so the
mesh/sharding tests run without real TPU hardware (the driver separately
dry-runs the multi-chip path). Also enforces the per-test timeout cap
(pyproject ``timeout``) when pytest-timeout isn't installed — one hung
device call must fail ONE test with a traceback, not consume the whole
tier-1 budget."""

import asyncio
import importlib.util
import inspect
import os
import subprocess
import sys

import pytest

_HAVE_PYTEST_TIMEOUT = importlib.util.find_spec("pytest_timeout") is not None

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Pin the platform in jax.config too, before any backend init: the tests
# never claim a chip, whatever the environment of the machine they run on
# names, and the device engines only start on a CPU that was asked for
# (maxmq_tpu/accel.py). The persistent compile cache stays off: an entry
# point under test may place one (bootstrap.run_server), and a test must
# neither read a stale program nor leave files in the checkout.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    """Build the native libraries once, here on the controller, before
    any xdist worker starts. ``native/*.so`` is git-ignored and
    ``maxmq_tpu/native.py`` builds on first use only when a library is
    absent, with ``make`` linking straight into its target: on a fresh
    checkout six workers each started a build, one could load a
    half-written ``maxmq_decode.so``, mark the extension absent for the
    life of its process and fail every intents test it was handed. The
    ``make`` also rebuilds a library older than its source, which the
    on-demand build never does. No compiler: the tests that need the
    extension skip, as before."""
    if hasattr(config, "workerinput"):
        return          # an xdist worker: the controller has built
    native = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native")
    try:
        done = subprocess.run(["make", "-C", native, "-s"], timeout=300,
                              capture_output=True, text=True)
    except (OSError, subprocess.SubprocessError) as exc:
        print(f"conftest: native build not run: {exc!r}", file=sys.stderr)
        return
    if done.returncode:
        print(f"conftest: native build failed:\n{done.stderr[-2000:]}",
              file=sys.stderr)


if not _HAVE_PYTEST_TIMEOUT:
    # Fallback mini-plugin mirroring pytest-timeout's config surface
    # (ini ``timeout`` / ``@pytest.mark.timeout(N)``, signal method):
    # CI installs the real plugin; this image doesn't ship it, and the
    # 870s tier-1 budget cannot absorb a single wedged device call.
    def pytest_addoption(parser):
        parser.addini("timeout", "per-test timeout in seconds "
                      "(conftest fallback for pytest-timeout)",
                      default="0")
        parser.addini("timeout_method", "accepted for pytest-timeout "
                      "compatibility; the fallback always uses signal",
                      default="signal")

    def _item_timeout(item) -> float:
        marker = item.get_closest_marker("timeout")
        if marker is not None and marker.args:
            return float(marker.args[0])
        try:
            return float(item.config.getini("timeout") or 0)
        except ValueError:
            return 0.0

    @pytest.hookimpl(hookwrapper=True)
    def pytest_runtest_call(item):
        import faulthandler
        import signal
        import threading

        timeout = _item_timeout(item)
        if (timeout <= 0 or not hasattr(signal, "SIGALRM")
                or threading.current_thread()
                is not threading.main_thread()):
            yield
            return

        def on_alarm(signum, frame):
            # all-thread dump FIRST: the hang is usually in a worker
            # thread (device dispatch), and the failing frame alone
            # wouldn't say which call wedged
            faulthandler.dump_traceback()
            pytest.fail(f"test timed out after {timeout:.0f}s "
                        "(conftest timeout fallback)", pytrace=False)

        old = signal.signal(signal.SIGALRM, on_alarm)
        signal.setitimer(signal.ITIMER_REAL, timeout)
        try:
            yield
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, old)


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests under asyncio.run (no pytest-asyncio in the
    image)."""
    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        kwargs = {name: pyfuncitem.funcargs[name]
                  for name in pyfuncitem._fixtureinfo.argnames}
        # tests that boot compile-heavy stages (mesh XLA programs) opt
        # into a longer deadline via `_async_timeout` on the function
        deadline = getattr(func, "_async_timeout", 30)
        asyncio.run(asyncio.wait_for(func(**kwargs), timeout=deadline))
        return True
    return None

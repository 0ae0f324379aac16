"""Test harness configuration.

- Forces JAX onto 8 virtual CPU devices (before any jax import) so all
  sharding/mesh tests run without TPU hardware, mirroring the reference's
  "every infra dependency has a mock twin" strategy (SURVEY.md §4).
- Minimal asyncio support: ``async def`` test functions run under a fresh
  event loop (no pytest-asyncio in the image).
"""

import asyncio
import inspect
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("DYN_LOG", "warn")
# first-compile of a pipeline under a loaded CI box can exceed the 30s
# production data-plane rendezvous (observed flake); give tests slack
os.environ.setdefault("DYN_CONNECT_TIMEOUT_S", "120")

import pytest


def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        loop = asyncio.new_event_loop()
        try:
            timeout = pyfuncitem.get_closest_marker("slow") and 300 or 60
            loop.run_until_complete(asyncio.wait_for(fn(**kwargs), timeout=timeout))
        finally:
            # drain leaked tasks/async-gens before closing, so pending
            # queue.get()s don't raise "Event loop is closed" at GC time
            try:
                pending = asyncio.all_tasks(loop)
                for task in pending:
                    task.cancel()
                if pending:
                    loop.run_until_complete(
                        asyncio.wait_for(
                            asyncio.gather(*pending, return_exceptions=True), timeout=10
                        )
                    )
                loop.run_until_complete(loop.shutdown_asyncgens())
            finally:
                loop.close()
        return True
    return None


@pytest.fixture
def anyio_backend():
    return "asyncio"


def free_port() -> int:
    """An OS-assigned free TCP port (shared test helper: subprocess servers
    that cannot bind port 0 themselves)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port

"""Test configuration.

Tests run on the CPU. Sharding/mesh tests use a virtual 8-device CPU
topology (`--xla_force_host_platform_device_count=8`); the real chips are
exercised by `chip_smoke.py` (and its `--chips 4`), and the kernels are
compiled for a described TPU in tests/test_tpu_aot.py.
"""

import os
import sys

# tests force the CPU
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# route batch verification to the host in unit tests: the background
# TPU probe thread would otherwise still be compiling at interpreter
# exit (SIGABRT in XLA teardown). The TPU kernel itself is covered by
# tests/test_tpu_crypto.py, which calls it directly.
os.environ.setdefault("TMTPU_DISABLE_TPU", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# -- minimal async test support (pytest-asyncio is not in the image) --------

import asyncio  # noqa: E402
import gc  # noqa: E402
import inspect  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: run test on a fresh event loop")
    config.addinivalue_line("markers", "slow: long-running multi-process e2e tests")


def pytest_pyfunc_call(pyfuncitem):
    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }

        async def _run_with_leak_check():
            await func(**kwargs)
            # Leak hygiene (the asyncio analog of the reference's leaktest,
            # internal/libs/sync/deadlock.go): cancel anything the test
            # left running and collect garbage WHILE the loop is alive, so
            # transport finalizers close their sockets on a live loop
            # instead of raising "Event loop is closed" at interpreter GC.
            leaked = [
                t
                for t in asyncio.all_tasks()
                if t is not asyncio.current_task() and not t.done()
            ]
            for t in leaked:
                t.cancel()
            if leaked:
                await asyncio.gather(*leaked, return_exceptions=True)
            await asyncio.sleep(0)
            gc.collect()
            await asyncio.sleep(0.01)  # let close callbacks run

        asyncio.run(_run_with_leak_check())
        return True
    return None

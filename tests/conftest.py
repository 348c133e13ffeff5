"""Test bootstrap.

Force JAX onto a virtual 8-device CPU platform BEFORE jax is imported so
multi-chip sharding paths (dp/tp/sp/ep meshes) compile and execute in CI
without TPU hardware (SURVEY.md §7: test sharding on a virtual 8-device
CPU mesh).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# JAX_PLATFORMS and XLA_FLAGS above take effect because nothing has
# imported jax yet; tests run on the CPU with Pallas kernels in interpret
# mode (utils/platform.on_tpu is False here).
import jax  # noqa: E402

# ---------------------------------------------------------------------------
# Minimal async test support (pytest-asyncio is not in the image): async test
# functions run on one shared background event loop, so module-scoped server
# fixtures can live on the same loop via the ``aloop`` fixture.
# ---------------------------------------------------------------------------
import asyncio  # noqa: E402
import inspect  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402


class AsyncLoopRunner:
    def __init__(self) -> None:
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self._run, name="test-aloop", daemon=True)
        self.thread.start()

    def _run(self) -> None:
        asyncio.set_event_loop(self.loop)
        self.loop.run_forever()

    def run(self, coro, timeout: float = 120.0):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(timeout)

    def stop(self) -> None:
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(timeout=5)


_RUNNER: AsyncLoopRunner | None = None


def _get_runner() -> AsyncLoopRunner:
    global _RUNNER
    if _RUNNER is None:
        _RUNNER = AsyncLoopRunner()
    return _RUNNER


@pytest.fixture(scope="session")
def aloop() -> AsyncLoopRunner:
    return _get_runner()


def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {name: pyfuncitem.funcargs[name] for name in pyfuncitem._fixtureinfo.argnames}
        _get_runner().run(fn(**kwargs), timeout=180.0)
        return True
    return None


def pytest_sessionfinish(session, exitstatus):
    global _RUNNER
    if _RUNNER is not None:
        _RUNNER.stop()
        _RUNNER = None



# ---------------------------------------------------------------------------
# Resource trajectory logging (enable with IG_TPU_RESLOG=/path): appends
# one line per test with RSS, open fds, threads, and mmap-region count —
# the instrument that located the full-suite XLA segfault (a process
# approaching vm.max_map_count crashes inside backend_compile_and_load).
# ---------------------------------------------------------------------------
if os.environ.get("IG_TPU_RESLOG"):
    @pytest.hookimpl(hookwrapper=True)
    def pytest_runtest_teardown(item):
        yield
        try:
            with open("/proc/self/maps") as f:
                n_maps = sum(1 for _ in f)
            with open("/proc/self/status") as f:
                rss = next((l.split()[1] for l in f if l.startswith("VmRSS")), "?")
            n_fds = len(os.listdir("/proc/self/fd"))
            with open(os.environ["IG_TPU_RESLOG"], "a") as out:
                out.write(f"{item.nodeid}\tmaps={n_maps}\trss_kb={rss}\t"
                          f"fds={n_fds}\tthreads={threading.active_count()}\n")
        except OSError:
            pass


# ---------------------------------------------------------------------------
# JIT-executable release between modules. Every compiled XLA:CPU
# executable holds ~3 anonymous mmap regions (code/rodata/data); the
# full suite compiles tens of thousands of programs, and with jax's
# global jit caches pinning all of them the process crosses Linux's
# vm.max_map_count (65,530) at ~92% of the run — the next compile's
# mmap fails inside backend_compile_and_load and segfaults the
# interpreter (the round-3/4 "full-suite segfault"). Dropping the caches
# after each module caps live executables at one module's worth;
# modules recompile what they reuse (their fixtures are module-scoped
# anyway).
# ---------------------------------------------------------------------------
import gc


@pytest.fixture(autouse=True, scope="module")
def _release_jit_executables():
    yield
    jax.clear_caches()
    gc.collect()

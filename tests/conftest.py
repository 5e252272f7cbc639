"""Test harness: simulate an 8-device TPU-like mesh on CPU.

The reference could only test distributed behavior on a real cluster
(SURVEY.md §4).  JAX lets us do better:
``--xla_force_host_platform_device_count=8`` gives 8 virtual CPU
devices, so collectives, shardings and all four rules' merge arithmetic
get real unit tests without hardware.

Both variables must be set before jax creates its backend.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"
# the suite neither reads nor writes the persistent compile cache: the
# launcher and serve paths place it (utils/helper_funcs.
# enable_compilation_cache), and an in-process tmlocal would otherwise
# turn it on for every later test — serializing each >=1 s CPU compile
# into artifacts/jax_cache and making a run depend on what the last
# one left there.  Children the tests spawn inherit this.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
# lock-order detection (analysis/lockgraph.py): tier-1 always runs the
# threaded host plane (_ExchangePipe, DynamicBatcher, WorkerSupervisor,
# InferenceServer) on TrackedLock, so an AB/BA inversion introduced by
# any PR raises LockOrderError in the test that exercises it instead of
# deadlocking until the CI timeout (docs/ANALYSIS.md)
os.environ.setdefault("THEANOMPI_TPU_LOCKCHECK", "1")

import threading  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run tests marked slow (full e2e rule sessions, multi-host "
             "subprocess tests; several extra minutes)")


def pytest_collection_modifyitems(config, items):
    """Default `pytest tests/` stays under ~5 min on this 1-core box:
    slow e2e tests need --runslow (or RUNSLOW=1).  The fast set keeps a
    short representative of each contract path (BSP rule e2e, one async
    rule e2e incl. resume, merge arithmetic, service wire protocol);
    the slow set runs every rule at full length plus the multi-host and
    separate-process sessions (VERDICT r1, next-round #7)."""
    if config.getoption("--runslow") or os.environ.get("RUNSLOW"):
        return
    skip = pytest.mark.skip(reason="slow: needs --runslow (or RUNSLOW=1)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


#: repo thread families that hold closures over models/clients — a
#: test that leaks one pins device buffers and sockets for the rest of
#: the session, so these fail the leak guard even though they are
#: daemonic (daemon= only means the INTERPRETER may exit; the suite
#: keeps running)
_REPO_THREAD_NAMES = ("-exchange-", "serving-batcher-",
                      "serving-reload-watcher", "monitor-heartbeat-",
                      "monitor-export", "collector-watcher",
                      "ingest-", "decode-", "rpc-", "frontdoor-")
#: library pools that are non-daemon BY DESIGN and process-lived
#: (concurrent.futures executors inside jax/orbax) — not leaks
_POOL_THREAD_PREFIXES = ("ThreadPoolExecutor", "asyncio_", "grpc",
                         "orbax")


def leaked_threads(before: set, grace_s: float = 2.0) -> list:
    """Threads started since ``before`` that are still alive after the
    grace window and are either non-daemon (excluding known library
    pools) or members of a repo thread family.  Exposed as a plain
    function so tests/test_analysis.py can pin the detection itself."""
    deadline = time.monotonic() + grace_s
    while True:
        fresh = [t for t in threading.enumerate()
                 if t not in before and t.is_alive()]
        leaked = [
            t for t in fresh
            if (not t.daemon
                and not t.name.startswith(_POOL_THREAD_PREFIXES))
            or any(p in t.name for p in _REPO_THREAD_NAMES)
        ]
        if not leaked or time.monotonic() > deadline:
            return leaked
        time.sleep(0.05)


@pytest.fixture(autouse=True)
def thread_leak_guard():
    """Tier-1 leak fence: every test must stop what it starts — a
    leaked `_ExchangePipe`/batcher/watcher/heartbeat thread fails the
    leaking test by name, not some later test by mystery."""
    before = set(threading.enumerate())
    yield
    leaked = leaked_threads(before)
    if leaked:
        names = ", ".join(f"{t.name}(daemon={t.daemon})"
                          for t in leaked)
        pytest.fail(f"test leaked {len(leaked)} thread(s): {names} — "
                    "close/stop the owning object (pipe.close(), "
                    "batcher.stop(), server.stop(), monitor session "
                    "exit) before returning")


@pytest.fixture(autouse=True)
def shm_segment_leak_guard():
    """Shared-memory twin of the thread fence: every test must decref
    what it leases — a leaked ``tmshm_*`` segment pins /dev/shm pages
    for the rest of the session.  Segments owned by shard/worker
    subprocesses a test spawned are swept by the dead-pid orphan probe
    before we judge."""
    from theanompi_tpu.parallel import shm

    before = set(shm.segment_names())
    yield
    shm.release_all()
    shm.sweep_orphans()
    deadline = time.monotonic() + 2.0
    while True:
        leaked = [n for n in shm.segment_names() if n not in before]
        if not leaked or time.monotonic() > deadline:
            break
        time.sleep(0.05)
        shm.sweep_orphans()
    if leaked:
        for n in leaked:  # unpin the suite before failing the test
            try:
                os.unlink(os.path.join("/dev/shm", n))
            except OSError:
                pass
        pytest.fail(
            f"test leaked {len(leaked)} shm segment(s): "
            f"{', '.join(sorted(leaked))} — close the owning channel "
            "(client.close(), server stop) or decref the lease before "
            "returning")


@pytest.fixture(params=["threaded", "selector"])
def rpc_loop(request, monkeypatch):
    """Both RPC substrates (parallel/rpc.py, ISSUE 11): tests naming
    this fixture run once per loop, so every byte-identity / fence /
    failover pin that opts in covers the legacy thread-per-connection
    loop AND the selector event plane during the migration window."""
    monkeypatch.setenv("THEANOMPI_TPU_RPC_LOOP", request.param)
    return request.param


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture(scope="session")
def mesh8(devices8):
    from theanompi_tpu.parallel import data_mesh

    return data_mesh(8, devices8)

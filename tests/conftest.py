"""Test fixtures.

- Forces JAX onto a virtual 8-device CPU mesh (multi-chip sharding tests run
  without TPU hardware, mirroring the reference's mocked-accelerator strategy,
  SURVEY §4 / tests/accelerators/*).
- ``ray_start`` fixtures mirror the reference's ``ray_start_regular`` /
  ``ray_start_cluster`` (``python/ray/tests/conftest.py:588/678``).
"""

import os

# Must be set before jax import (workers inherit via env). Force CPU even if
# the outer env points at a TPU — unit tests run on the virtual 8-device mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

# the env var only reaches a JAX that is not imported yet; the config does
# either way
jax.config.update("jax_platforms", "cpu")

import pytest

# ---------------------------------------------------------------- CI guards
#
# Per-test timeout watchdog (conftest-level; pytest-timeout is not in the
# image): a hung drain/health test must fail fast instead of eating the
# whole tier-1 wall-clock budget. SIGALRM-based — pytest runs tests on the
# main thread, and the exception subclasses BaseException so the blanket
# `except Exception` recovery paths under test cannot swallow the watchdog.
# Override per test with @pytest.mark.timeout(seconds), globally with
# RAY_TPU_TEST_TIMEOUT_S (0 disables).

_FAST_TEST_TIMEOUT_S = 300.0
_SLOW_TEST_TIMEOUT_S = 900.0


class _TestTimeout(BaseException):
    pass


def _test_timeout_s(item) -> float:
    env = os.environ.get("RAY_TPU_TEST_TIMEOUT_S")
    if env is not None:
        return float(env)
    marker = item.get_closest_marker("timeout")
    if marker and marker.args:
        return float(marker.args[0])
    if item.get_closest_marker("slow"):
        return _SLOW_TEST_TIMEOUT_S
    return _FAST_TEST_TIMEOUT_S


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    # wraps the WHOLE protocol (fixture setup + call + teardown), not just
    # the call phase — cluster bring-up/teardown is where drain/serve code
    # is likeliest to deadlock, and a hang there must fail fast too
    import signal
    import threading

    timeout = _test_timeout_s(item)
    if (
        timeout <= 0
        or not hasattr(signal, "SIGALRM")
        or threading.current_thread() is not threading.main_thread()
    ):
        yield
        return

    def _on_alarm(signum, frame):
        # Triage dump BEFORE unwinding: every thread's stack, the
        # registered-lock owner table, AND the live-resource table (shm
        # segments, plasma-client mapping counts, outstanding ObjectRef
        # counts — ray_tpu._private.locktrace), so a deadlock OR a leaked
        # segment is diagnosed from this log instead of a 300 s bisect
        # (the PR 3 seal-through-own-pump hang took exactly that; the PR 4
        # spilled-reply RSS leak was found by hand).
        import sys

        try:
            from ray_tpu._private import locktrace

            sys.stderr.write(
                f"\n===== watchdog: {item.nodeid} exceeded {timeout:.0f}s =====\n"
            )
            locktrace.dump_all(file=sys.stderr)
        except Exception:  # noqa: BLE001 — the dump must never mask the timeout
            import traceback

            traceback.print_exc(file=sys.stderr)
        raise _TestTimeout(
            f"test exceeded its {timeout:.0f}s watchdog "
            f"(per-test timeout guard; thread stacks + lock owner table + "
            f"live shm/ref resource table dumped to stderr; see "
            f"tests/conftest.py)"
        )

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.setitimer(signal.ITIMER_REAL, timeout)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


# ``--dist loadfile`` hands files out from a queue, and a long file handed out
# last is the run's tail. xdist orders that queue by a file's number of tests,
# most first, which puts a file of two long cases at the very end (PR 47:
# ``test_ssm_rows.py`` began 800 s into a run and ended it at 1,094 s). So the
# queue keeps collection order, and the files that cannot be cut under 150 s
# of cases (one parametrised test each, or one fixture's cases) are collected
# first, one to a worker; a name that is gone does nothing.
_LONGEST_FIRST = (
    "test_ssm_rows.py", "test_carried_decode.py", "test_patterned_prefill.py",
    "test_llm_layouts.py", "test_chunk_rows.py", "test_llm_pools.py",
)


def pytest_configure(config):
    if hasattr(config.option, "loadscopereorder"):  # xdist's, where it is loaded
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    items.sort(key=lambda item: item.path.name not in _LONGEST_FIRST)  # stable: files stay whole


# Test-run wall-time artifact: every run records its wall time into
# TEST_RUN.json at the repo root under "last_run"; a run of the FULL fast
# tier (`-m "not slow"`, no -k narrowing) additionally refreshes the sticky
# "fast_tier" section — the fast-tier budget is now measured, not guessed
# (VERDICT r5 weak #5), and a one-test invocation can't clobber the record.


def pytest_sessionstart(session):
    session._rtpu_t0 = __import__("time").monotonic()


@pytest.hookimpl(trylast=True)  # after the terminal reporter collected stats
def pytest_sessionfinish(session, exitstatus):
    import json
    import time

    t0 = getattr(session, "_rtpu_t0", None)
    cfg = session.config
    # under xdist the process that started the run writes, once: each worker
    # runs this hook too, with its own share of the outcomes, and the tracked
    # file was whichever worker's ended last
    if t0 is None or hasattr(cfg, "workerinput"):
        return
    # the terminal reporter's stats fill incrementally as tests finish, so
    # they are complete here even though its summary prints later
    tr = cfg.pluginmanager.get_plugin("terminalreporter")
    stats = (
        {k: len(v) for k, v in tr.stats.items() if k and k != "deselected"}
        if tr is not None
        else {}
    )
    record = {
        "wall_s": round(time.monotonic() - t0, 2),
        "exitstatus": int(exitstatus),
        "markexpr": cfg.option.markexpr or "",
        "keyword": cfg.option.keyword or "",
        "collected": session.testscollected,
        "failed": session.testsfailed,
        "outcomes": stats,
        "finished_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }
    path = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "TEST_RUN.json")
    )
    artifact = {}
    try:
        with open(path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        pass
    if not isinstance(artifact, dict) or "last_run" not in artifact:
        artifact = {}
    artifact["last_run"] = record
    is_full_fast_tier = (
        record["markexpr"].replace("'", "").replace('"', "") == "not slow"
        and not record["keyword"]
        and record["collected"] > 100  # full suite, not a -k/path slice
    )
    if is_full_fast_tier:
        artifact["fast_tier"] = record
    try:
        with open(path, "w") as f:
            json.dump(artifact, f, indent=1, sort_keys=True)
            f.write("\n")
    except OSError:
        pass


@pytest.fixture
def ray_start_thread():
    """Thread-mode runtime: fast, in-process (local_mode analog)."""
    import ray_tpu

    ray_tpu.init(num_cpus=8, mode="thread")
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_process():
    """Process-mode runtime: real worker processes + shared-memory objects."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, mode="process")
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-(fake-)node cluster fixture."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 4, "mode": "thread"})
    yield cluster
    cluster.shutdown()

"""Client-driver attach tests (ray:// analog).

Coverage modeled on the reference's ``python/ray/util/client`` tests: a
second process attaches to a running cluster and uses the full task/actor/
object API.
"""

import subprocess
import sys
import textwrap

import pytest

import ray_tpu

pytestmark = pytest.mark.timeout(300) if hasattr(pytest.mark, "timeout") else []


def test_client_driver_attach(tmp_path):
    ray_tpu.init(num_cpus=4, mode="process")
    try:
        addr = ray_tpu.cluster_address()
        assert addr and "?authkey=" in addr

        # head-side named actor the client will call
        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def bump(self, k):
                self.n += k
                return self.n

        counter = Counter.options(name="shared-counter").remote()
        assert ray_tpu.get(counter.bump.remote(1), timeout=60) == 1

        client_code = textwrap.dedent(
            f"""
            import os
            os.environ["JAX_PLATFORMS"] = "cpu"
            import numpy as np
            import ray_tpu

            ray_tpu.init(address={addr!r})

            @ray_tpu.remote
            def square(x):
                return x * x

            assert ray_tpu.get(square.remote(7), timeout=120) == 49

            # large object through the shared-memory plane
            big = np.arange(500_000, dtype=np.float64)
            ref = ray_tpu.put(big)

            @ray_tpu.remote
            def total(x):
                return float(x.sum())

            assert ray_tpu.get(total.remote(ref), timeout=120) == float(big.sum())

            # named actor created by the HEAD driver, called from the client
            c = ray_tpu.get_actor("shared-counter")
            assert ray_tpu.get(c.bump.remote(10), timeout=60) == 11

            # cluster state visible from the client
            assert ray_tpu.cluster_resources().get("CPU", 0) == 4
            ray_tpu.shutdown()
            print("CLIENT-OK")
            """
        )
        r = subprocess.run(
            [sys.executable, "-c", client_code],
            capture_output=True,
            text=True,
            timeout=240,
            env={
                "PATH": "/usr/bin:/bin:/usr/local/bin",
                "PYTHONPATH": "/root/repo",
                "JAX_PLATFORMS": "cpu",
                "HOME": "/root",
            },
        )
        assert r.returncode == 0, r.stderr[-2000:]
        assert "CLIENT-OK" in r.stdout

        # the head still sees the client's state changes
        assert ray_tpu.get(counter.bump.remote(0), timeout=60) == 11
    finally:
        ray_tpu.shutdown()


def test_client_auto_address(tmp_path):
    ray_tpu.init(num_cpus=2, mode="process")
    try:
        code = (
            "import os\nos.environ['JAX_PLATFORMS']='cpu'\n"
            "import ray_tpu\nray_tpu.init(address='auto')\n"
            "@ray_tpu.remote\ndef f(): return 5\n"
            "assert ray_tpu.get(f.remote(), timeout=120) == 5\n"
            "ray_tpu.shutdown()\nprint('AUTO-OK')\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=240,
            env={
                "PATH": "/usr/bin:/bin:/usr/local/bin",
                "PYTHONPATH": "/root/repo",
                "JAX_PLATFORMS": "cpu",
                "HOME": "/root",
            },
        )
        assert r.returncode == 0, r.stderr[-2000:]
        assert "AUTO-OK" in r.stdout
    finally:
        ray_tpu.shutdown()


def test_client_windowed_push_under_chunk_chaos(tmp_path):
    """An arena-less client pushes a large put through the chunked push
    protocol with the in-flight window open and 20% injected chunk
    failure: per-chunk retry completes the object intact (out-of-order
    windowed chunks + idempotent retried writes)."""
    ray_tpu.init(
        num_cpus=2,
        mode="process",
        config={"testing_rpc_failure": "push_object_chunk=0.2"},
    )
    try:
        addr = ray_tpu.cluster_address()
        code = textwrap.dedent(
            """
            import os
            os.environ["JAX_PLATFORMS"] = "cpu"
            import numpy as np
            import ray_tpu

            ray_tpu.init(address={addr!r})
            # drop the probed arena: force the chunked push protocol the
            # way a cross-host client would use it
            os.environ.pop("RAY_TPU_ARENA", None)
            big = np.arange(100_000, dtype=np.float64)  # ~13 chunks
            ref = ray_tpu.put(big)

            @ray_tpu.remote
            def total(x):
                return float(x.sum())

            assert ray_tpu.get(total.remote(ref), timeout=120) == float(big.sum())
            ray_tpu.shutdown()
            print("PUSH-OK")
            """.replace("{addr!r}", repr(addr))
        )
        r = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=240,
            env={
                "PATH": "/usr/bin:/bin:/usr/local/bin",
                "PYTHONPATH": "/root/repo",
                "JAX_PLATFORMS": "cpu",
                "HOME": "/root",
                "RAY_TPU_OBJECT_TRANSFER_CHUNK_BYTES": "65536",
                "RAY_TPU_OBJECT_TRANSFER_WINDOW": "4",
            },
        )
        assert r.returncode == 0, r.stderr[-2000:]
        assert "PUSH-OK" in r.stdout
    finally:
        ray_tpu.shutdown()


def test_client_same_host_arena_probe(tmp_path):
    """A same-host client (launched WITHOUT the inherited arena env) probes
    and attaches the head's native arena, so its large puts ride shared
    memory instead of the chunked push protocol. The head is named by its
    address: ``'auto'`` reads one session file a user, which a head started by
    another process of the same test run may have rewritten meanwhile, and
    that head goes away under the client (``test_client_auto_address`` holds
    ``'auto'``)."""
    ray_tpu.init(num_cpus=2, mode="process")
    try:
        code = (
            "import os\nos.environ['JAX_PLATFORMS']='cpu'\n"
            "import numpy as np\nimport ray_tpu\n"
            f"ray_tpu.init(address={ray_tpu.cluster_address()!r})\n"
            "print('ARENA:', os.environ.get('RAY_TPU_ARENA', ''))\n"
            "big = np.arange(400_000, dtype=np.float64)\n"
            "ref = ray_tpu.put(big)\n"
            "@ray_tpu.remote\ndef total(x): return float(x.sum())\n"
            "assert ray_tpu.get(total.remote(ref), timeout=120) == float(big.sum())\n"
            "print('PROBE-OK')\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=240,
            env={
                "PATH": "/usr/bin:/bin:/usr/local/bin",
                "PYTHONPATH": "/root/repo",
                "JAX_PLATFORMS": "cpu",
                "HOME": "/root",
            },
        )
        assert r.returncode == 0, r.stderr[-2000:]
        assert "PROBE-OK" in r.stdout
        # the head runs the native arena in this environment, so the probe
        # must have attached it
        import ray_tpu._private.worker as w

        if hasattr(w.global_worker().controller.plasma, "arena_name"):
            assert "ARENA: /rtpu-" in r.stdout
    finally:
        ray_tpu.shutdown()


def test_client_pushed_put_holds_its_ref_before_the_seal(tmp_path):
    """A put's own ``add_ref`` rides the submit coalescer, the chunked push
    does not: with a window far longer than the push, the ref still reaches
    the head first, so the head does not free the object where it seals it
    (it did, one run in fifty under load, and the task that took the ref
    waited for its argument for ever)."""
    ray_tpu.init(num_cpus=2, mode="process")
    try:
        code = textwrap.dedent(
            """
            import os
            os.environ["JAX_PLATFORMS"] = "cpu"
            import numpy as np
            import ray_tpu

            ray_tpu.init(address={addr!r})
            os.environ.pop("RAY_TPU_ARENA", None)  # the chunked push, as across hosts
            big = np.arange(100_000, dtype=np.float64)
            ref = ray_tpu.put(big)

            @ray_tpu.remote
            def total(x):
                return float(x.sum())

            assert ray_tpu.get(total.remote(ref), timeout=60) == float(big.sum())
            ray_tpu.shutdown()
            print("PUT-HELD")
            """.replace("{addr!r}", repr(ray_tpu.cluster_address()))
        )
        r = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=240,
            env={
                "PATH": "/usr/bin:/bin:/usr/local/bin",
                "PYTHONPATH": "/root/repo",
                "JAX_PLATFORMS": "cpu",
                "HOME": "/root",
                "RAY_TPU_OBJECT_TRANSFER_CHUNK_BYTES": "65536",
                "RAY_TPU_SUBMIT_BATCH_WINDOW_MS": "500",
            },
        )
        assert r.returncode == 0, r.stderr[-2000:]
        assert "PUT-HELD" in r.stdout
    finally:
        ray_tpu.shutdown()

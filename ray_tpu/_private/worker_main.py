"""Dedicated worker process entry point.

Analog of the reference's ``python/ray/_private/workers/default_worker.py``:
worker processes are exec'd fresh (never forked/spawned from driver state, so
the driver's ``__main__`` is never re-imported) and connect back to the
controller over the node's unix socket.

Usage: ``python -m ray_tpu._private.worker_main <socket> <worker_id_hex>
[<tpu_chips>]`` with ``RAY_TPU_AUTHKEY`` in the environment. ``<tpu_chips>``
is given to a worker spawned for a ``TPU`` grant: before it takes work it
checks that JAX sees exactly those chips, and every task or actor creation
sent to a worker that does not fails with that message.
"""

from __future__ import annotations

import os
import sys


def main():
    address = sys.argv[1]
    worker_id_hex = sys.argv[2]
    tpu_chips = int(sys.argv[3]) if len(sys.argv) > 3 else 0
    authkey = bytes.fromhex(os.environ.pop("RAY_TPU_AUTHKEY"))

    from multiprocessing.connection import Client

    from ray_tpu._private.ids import WorkerID
    from ray_tpu._private.worker_runtime import WorkerRuntime

    conn = Client(address, family="AF_UNIX", authkey=authkey)
    runtime = WorkerRuntime(
        WorkerID(bytes.fromhex(worker_id_hex)),
        conn,
        in_process=False,
        authkey=authkey,
    )
    if tpu_chips:
        from ray_tpu._private import jax_cache
        from ray_tpu.tpu.accelerator import verify_chip_grant

        try:
            jax_cache.configure()
            verify_chip_grant(tpu_chips)
        except Exception as e:  # noqa: BLE001 — surfaces as the task's error
            print(f"ray_tpu worker refuses work: {e}", file=sys.stderr, flush=True)
            runtime.startup_error = e
    runtime.run()


if __name__ == "__main__":
    main()

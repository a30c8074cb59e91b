#!/usr/bin/env python3
"""One run of one cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` (or the manifest given with
``--manifest``: ``benchmark/rehearsal.json`` holds tiny cells for the CPU).
Its configuration is ``benchmark/configs/<config>.json``, its traffic
``benchmark/traffic/<traffic>.json``; the traffic file's ``kind`` names the
module under ``benchmark/kinds/`` that drives it, and in a traced run every
per-layer metric the manifest lists for the cell is read by
``benchmark/metrics/<metric>.py``. So a later PR adds a configuration, a mix or
a metric as a file and an entry, and edits nothing here.

This process is the driver and never starts a JAX backend: a chip belongs to
one process at a time, and that is the worker or replica the program places.
The last line of a run that measured is the result object; a run that could
not measure (no chip, too few chips, a phase that failed) says why on stderr,
prints no result and exits nonzero. ``BENCH_RUN`` is not read."""

from __future__ import annotations

import time

T_START_WALL = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

SHUTDOWN_TIMEOUT_S = 60.0


def fail(why: str, code: int = 1) -> "NoReturn":  # noqa: F821
    print(f"benchmark: {why}", file=sys.stderr, flush=True)
    sys.stdout.flush()
    os._exit(code)  # a failed phase may have left a thread behind


def main() -> int:
    parser = argparse.ArgumentParser(description="one run of one benchmark cell")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    parser.add_argument("--control", choices=("int8",), default=None,
                        help="cut the program's weights to a lower precision: "
                             "the run must then come out as not correct")
    parser.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                        help="override a number of the traffic file (a sweep)")
    args = parser.parse_args()

    try:
        import ray_tpu
        from ray_tpu.tpu.accelerator import TPUAcceleratorManager
    except ImportError as e:
        fail(f"the system under test is not beside the benchmark: {e}", 2)

    from benchmark import common, trace
    from benchmark.common import BenchFailure, log

    try:
        manifest = common.load_manifest(args.manifest)
        cell = common.find_cell(manifest, args.workload)
        config = common.load_config(manifest, cell["config"])
        traffic = common.load_traffic(cell["traffic"])
    except (BenchFailure, OSError, ValueError) as e:
        fail(str(e), 2)
    for item in args.set:
        key, value = item.split("=", 1)
        traffic[key] = type(traffic[key])(value)
    rehearsal = bool(manifest.get("rehearsal"))
    chips = cell["chips"]

    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
        num_tpus = 0
    else:
        num_tpus = TPUAcceleratorManager.get_current_node_num_accelerators()
        if num_tpus < chips:
            fail(f"no accelerator for this cell: the host exposes {num_tpus} TPU "
                 f"device node(s), {cell['name']} needs {chips}")
    # every program, however small, goes to the persistent cache, so that a
    # second run in this checkout compiles nothing (workers inherit these)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

    # what the program and libtpu leave behind goes under TMPDIR (the driver
    # gives each side its own), never to a fixed path
    tmp = tempfile.gettempdir()
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tmp, "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

    out_dir = os.path.join(ROOT, ".bench_out", cell["name"])
    trace.fresh_dir(out_dir)
    ctx = dict(
        args=args, manifest=manifest, cell=cell, config=config, traffic=traffic,
        rehearsal=rehearsal, out_dir=out_dir, t_start_wall=T_START_WALL,
    )
    log(run=cell["name"], seed=args.seed, seconds=args.seconds, trace=args.trace,
        rehearsal=rehearsal, control=args.control)

    runner = importlib.import_module(f"benchmark.kinds.{traffic['kind']}")
    ray_tpu.init(mode="process", num_cpus=max(4, os.cpu_count() or 1), num_tpus=num_tpus,
                 config={"spill_directory": tmp})
    result, error = None, None
    try:
        result = runner.run(ctx)
    except Exception as e:  # noqa: BLE001 - reported below, after the shutdown
        error = e
    finally:
        stopper = threading.Thread(target=ray_tpu.shutdown, daemon=True)
        stopper.start()
        stopper.join(SHUTDOWN_TIMEOUT_S)
    if error is not None:
        fail(f"{type(error).__name__}: {error}")
    if stopper.is_alive():
        fail("shutdown left processes behind")
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge._backends:
            fail(f"the driver started JAX backends {sorted(xla_bridge._backends)}")

    device = result["device"]
    if args.trace:
        summary = result.get("trace")
        if not summary or summary["busy_s"] <= 0:
            fail("a traced run in which no operation ran on the device")
        device = dict(device, busy_s=summary["busy_s"], window_s=summary["window_s"])
        reader_ctx = dict(
            cell=cell, config=config, traffic=traffic, chips=chips,
            device_kind=device["kind"], e2e=result["e2e"], spans=result.get("spans", {}),
            samples=result.get("samples", []), trace=summary, extra=result.get("extra", {}),
        )
        metrics = {}
        for m in common.metrics_for(manifest, "per_layer", cell["name"]):
            value = common.load_reader(m["name"])(reader_ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        log(idle_share=1.0 - summary["busy_s"] / summary["window_s"],
            modules=summary["modules"], host_spans=summary["host_spans"])
    else:
        metrics = {
            m["name"]: {"value": result["e2e"][m["name"]], "unit": m["unit"]}
            for m in common.metrics_for(manifest, "end_to_end", cell["name"])
        }
    common.emit_result(
        result["correct"], result["attempted"], result["failed"], metrics, device,
        breakdown=trace.breakdown(result["trace"]) if args.trace and result.get("trace") else None,
        rehearsal=rehearsal,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Bytes a decode step must read (``benchmark/peaks.py decode_step_bytes``:
every weight once, and the keys and values of the tokens live in the active
slots, as sampled inside the replica; not the dead stripe) over the chip's
peak HBM bandwidth, over the device time of a decode step. Memory-bound: the
roofline of a decode step at these batch sizes is the bandwidth one."""

from benchmark import peaks, trace


def read(ctx):
    step_s = trace.module_mean_s(ctx["trace"], "jit_decode_fn")
    busy = [s for s in ctx["samples"] if s["active_slots"]]
    if step_s is None or not busy:
        return None
    live = sum(s["live_tokens"] for s in busy) / len(busy)
    needed = peaks.decode_step_bytes(ctx["config"], live)
    return 100.0 * needed / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"] / step_s

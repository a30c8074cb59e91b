"""From the profiler's ``.xplane.pb`` to numbers: device busy and idle time,
time per XLA module and per operation, collectives and their exposed part,
and idle gaps attributed to what the host was doing. Also the counter of
compilations inside a window. Only the process that holds the chip can trace
it, so that process calls ``reduce_dir`` and ships the summary (plain Python
values) to the driver.

How a TPU trace is laid out (looked at by hand on a v5e, PR 24): one plane per
chip named ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per
execution of a compiled program, named ``<jit name>(<fingerprint>)``) and
``XLA Ops`` (one event per operation, named by its whole HLO line,
``%fusion.1 = bf16[...] fusion(...)``, with the body of a ``while`` nested
inside the ``while`` event); host threads are lines of the plane
``/host:CPU``, and ``jax.profiler.TraceAnnotation`` spans are events there,
under the name given. Times are nanoseconds; the device's events read about a
millisecond earlier than the host's for the same instant, which a window of
seconds does not notice."""

from __future__ import annotations

import glob
import os
import re
import shutil

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)", re.I
)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE, OPS_LINE, ASYNC_LINE = "XLA Modules", "XLA Ops", "Async XLA Ops"
TOP = 10


def fresh_dir(path: str) -> None:
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)


def start(trace_dir: str) -> None:
    """Start the profiler with the Python tracer off: host spans come from
    TraceAnnotation alone, and the trace stays small."""
    import jax

    fresh_dir(trace_dir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


class CompileCounter:
    """Counts the programs JAX compiled (or fetched from its cache) while the
    context was open: inside a measured window there should be none."""

    def __init__(self):
        self.count = 0
        self.names = []

    def _on_event(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name")))

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        try:
            jax.monitoring.unregister_event_duration_listener(self._on_event)
        except Exception:  # noqa: BLE001 - older JAX: the listener stays, harmless
            pass


# ---------------------------------------------------------------- intervals


def union(intervals):
    """Merge [start, end) pairs; returns the merged list, sorted."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals, lo, hi):
    return [[max(a, lo), min(b, hi)] for a, b in intervals if b > lo and a < hi]


def subtract(intervals, holes):
    """Parts of merged ``intervals`` not covered by merged ``holes``."""
    out, j = [], 0
    for a, b in intervals:
        cur = a
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cur:
                out.append([cur, holes[k][0]])
            cur = max(cur, holes[k][1])
            k += 1
        if cur < b:
            out.append([cur, b])
    return out


def overlap(a, b) -> float:
    """Total overlap of two merged interval lists."""
    i = j = 0
    s = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            s += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return s


# ------------------------------------------------------------------ reading


CONTROL_FLOW = re.compile(r"^(while|conditional|call)(\.|$)")


def op_name(event_name: str) -> str:
    """``%fusion.1 = bf16[512,512]{...} fusion(...)`` -> ``fusion.1``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """``jit_step_fn(1234567)`` -> ``jit_step_fn``."""
    return event_name.split("(", 1)[0]


def read_planes(path: str) -> dict:
    """{"devices": {n: {"modules": [(name, start, end)], "ops": [...],
    "async": [...]}}, "host": [(name, start, end)]} with times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {"modules": [], "ops": [], "async": []})
            for line in plane.lines:
                key = {MODULE_LINE: "modules", OPS_LINE: "ops", ASYNC_LINE: "async"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    name = ev.name if key == "modules" else op_name(ev.name)
                    dev[key].append(
                        (name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append(
                            (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                        )
    return {"devices": devices, "host": host}


def reduce(planes: dict) -> dict:
    """The summary the per-layer readers and the result line's ``device`` and
    ``breakdown`` are made from. Device figures are averaged over the chips."""
    devices, host = planes["devices"], planes["host"]
    if not devices:
        raise ValueError("the trace holds no /device:TPU plane: nothing ran on a chip")
    windows = [(a, b) for name, a, b in host if name == WINDOW_SPAN]
    if windows:
        lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    else:
        every = [t for d in devices.values() for _, a, b in d["ops"] for t in (a, b)]
        lo, hi = min(every), max(every)
    n_dev = len(devices)
    busy_s = coll_s = exposed_s = async_s = 0.0
    modules, ops, first_idle = {}, {}, None
    for index in sorted(devices):
        dev = devices[index]
        in_window = [(n, max(a, lo), min(b, hi)) for n, a, b in dev["ops"] if b > lo and a < hi]
        busy = union([[a, b] for _, a, b in in_window])
        busy_s += total(busy)
        # a collective on the operations' line holds the core for as long as
        # the event lasts (an asynchronous one is a short start and a done
        # that waits); what else may run beside it is not the ``while`` that
        # merely contains it
        coll = union([[a, b] for n, a, b in in_window if COLLECTIVE.match(n)])
        other = union([[a, b] for n, a, b in in_window
                       if not COLLECTIVE.match(n) and not CONTROL_FLOW.match(n)])
        coll_s += total(coll)
        exposed_s += total(coll) - overlap(coll, other)
        # start to done of the asynchronous ones, hidden or not
        async_s += total(clip(union(
            [[a, b] for n, a, b in dev.get("async", []) if COLLECTIVE.match(n)]), lo, hi))
        for n, a, b in in_window:
            if CONTROL_FLOW.match(n):
                continue  # its body's operations are events of their own
            entry = ops.setdefault(n, [0.0, 0])
            entry[0] += (b - a) / n_dev
            entry[1] += 1
        for n, a, b in dev["modules"]:
            # a module counts where it lies wholly inside the window
            if a >= lo and b <= hi:
                entry = modules.setdefault(module_name(n), {"count": 0, "total_s": 0.0})
                entry["count"] += 1
                entry["total_s"] += b - a
        if first_idle is None:
            first_idle = subtract([[lo, hi]], busy)
    for entry in modules.values():
        entry["count"] /= n_dev
        entry["total_s"] /= n_dev

    # idle gaps of the first chip, by what the host was doing in them
    spans = {}
    for name, a, b in host:
        if name != WINDOW_SPAN:
            spans.setdefault(name, []).append([a, b])
    gaps, attributed = {}, 0.0
    for name, ivs in spans.items():
        s = overlap(first_idle, union(ivs))
        if s > 0:
            gaps[name[len(SPAN_PREFIX):]] = s
    # spans may nest, so the unattributed part is what no span covers at all
    covered = union([iv for ivs in spans.values() for iv in ivs])
    gaps["host_unattributed"] = total(first_idle) - overlap(first_idle, covered)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][0])
    return {
        "window_s": hi - lo,
        "busy_s": busy_s / n_dev,
        "devices": n_dev,
        "modules": modules,
        "ops": [[n, s, c] for n, (s, c) in top_ops[:50]],
        "collective_s": coll_s / n_dev,
        "collective_exposed_s": exposed_s / n_dev,
        "collective_async_s": async_s / n_dev,
        "idle_gaps": sorted(([n, s] for n, s in gaps.items() if s > 0), key=lambda g: -g[1]),
        "host_spans": {
            n[len(SPAN_PREFIX):]: {"count": len(ivs), "total_s": total(union(ivs))}
            for n, ivs in spans.items()
        },
    }


def module_mean_s(summary: dict, *names: str):
    """Mean device seconds of one execution of the first of ``names`` that
    ran inside the window, or None."""
    for name in names:
        m = summary["modules"].get(name)
        if m and m["count"]:
            return m["total_s"] / m["count"]
    return None


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str) -> dict:
    return reduce(read_planes(find_xplane(trace_dir)))


def breakdown(summary: dict) -> dict:
    return {
        "device_ops": [[n, s] for n, s, _ in summary["ops"][:TOP]],
        "idle_gaps": [[n, s] for n, s in summary["idle_gaps"][:TOP]],
    }

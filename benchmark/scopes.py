"""What the readers of scopes, engine spans and engine counters share.

The program names its work with ``jax.named_scope`` (``models/llama.py``:
embed, norm, attn_qkv, attn_core, attn_out, ffn, moe_ffn, lm_head, loss,
optimizer, grad_norm, and in the engine's programs kv_write, sampling,
prefix_seed), and its engine loop with ``util.tracing.annotate``
(``engine.*`` spans). Both land in the profiler's ``.xplane.pb``:

- a scope is one ``/``-separated part of an operation's ``op_name``
  (``jit(step_fn)/jvp()/while/body/closed_call/ffn/bte,ef->btf/dot_general``),
  or sits inside the transform that precedes it (``transpose(jvp(loss))``).
  On a v5e trace that string is not a stat of the ``XLA Ops`` event but the
  stat ``tf_op`` of the event's *metadata* (``XEventMetadata.stats``; looked
  at by hand, PR 26), which ``jax.profiler.ProfileData`` does not show. So
  the file is parsed here with ``google.protobuf`` against the few fields of
  ``tsl/profiler/protobuf/xplane.proto`` that are needed, declared below;
- a fused operation carries one ``op_name``, that of the fusion's root, so a
  fusion that spans two scopes is booked whole to the scope of its root;
- the backward pass has no scopes of its own: JAX writes ``transpose(jvp(..))``
  around the forward scope, and ``checkpoint`` / ``rematted_computation``
  where remat computes the forward pass again;
- ``engine.*`` spans are events of the engine thread's line of ``/host:CPU``.

Against a program that has no scopes, spans or counters (the parent of the PR
that brought them), every function here finds nothing and the readers return
None: the result line then leaves the metric out."""

from __future__ import annotations

import bisect
import functools
import os
import re

from benchmark import common, trace

SCOPES = (
    "embed", "norm", "attn_qkv", "attn_core", "attn_out", "ffn", "moe_ffn", "lm_head", "loss",
    "optimizer", "grad_norm", "kv_write", "sampling", "prefix_seed",
)
OPTIMIZER = ("optimizer", "grad_norm")
DECODE_MATMULS = ("attn_qkv", "attn_out", "ffn", "moe_ffn", "lm_head")
BACKWARD = re.compile(r"transpose\(|(^|/)(checkpoint|rematted_computation)(/|$)")
FORWARD = re.compile(r"(^|/)jvp\(")
_WORDS = re.compile(r"[A-Za-z_0-9]+")
ENGINE_SPAN = "engine."
OP_NAME_STAT = "tf_op"
SPAN_SEAM_S = 20e-6


# ------------------------------------------------------------ the file format


@functools.lru_cache(maxsize=None)
def _xspace_class():
    """``XSpace`` of xplane.proto, cut to the fields read here."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    file = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def message(name, *fields):
        m = file.message_type.add(name=name)
        for fname, number, ftype, label, type_name in fields:
            f = m.field.add(name=fname, number=number, type=ftype, label=label)
            if type_name:
                f.type_name = ".bench_xplane." + type_name
        return m

    def map_field(m, fname, number, value_type):
        entry = m.nested_type.add(name=fname.title().replace("_", "") + "Entry")
        entry.options.map_entry = True
        entry.field.add(name="key", number=1, type=F.TYPE_INT64, label=F.LABEL_OPTIONAL)
        entry.field.add(name="value", number=2, type=F.TYPE_MESSAGE, label=F.LABEL_OPTIONAL,
                        type_name=".bench_xplane." + value_type)
        m.field.add(name=fname, number=number, type=F.TYPE_MESSAGE, label=F.LABEL_REPEATED,
                    type_name=f".bench_xplane.{m.name}.{entry.name}")

    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    message("XStat", ("metadata_id", 1, F.TYPE_INT64, one, None),
            ("str_value", 5, F.TYPE_STRING, one, None), ("ref_value", 7, F.TYPE_UINT64, one, None))
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64, one, None),
            ("offset_ps", 2, F.TYPE_INT64, one, None), ("duration_ps", 3, F.TYPE_INT64, one, None))
    message("XLine", ("name", 2, F.TYPE_STRING, one, None),
            ("timestamp_ns", 3, F.TYPE_INT64, one, None),
            ("events", 4, F.TYPE_MESSAGE, many, "XEvent"))
    message("XEventMetadata", ("id", 1, F.TYPE_INT64, one, None),
            ("name", 2, F.TYPE_STRING, one, None), ("stats", 5, F.TYPE_MESSAGE, many, "XStat"))
    message("XStatMetadata", ("id", 1, F.TYPE_INT64, one, None),
            ("name", 2, F.TYPE_STRING, one, None))
    plane = message("XPlane", ("name", 2, F.TYPE_STRING, one, None),
                    ("lines", 3, F.TYPE_MESSAGE, many, "XLine"))
    map_field(plane, "event_metadata", 4, "XEventMetadata")
    map_field(plane, "stat_metadata", 5, "XStatMetadata")
    message("XSpace", ("planes", 1, F.TYPE_MESSAGE, many, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xplane.XSpace"))


@functools.lru_cache(maxsize=2)
def read_xplane(path: str) -> dict:
    """{"ops": [...], "modules": [...], "spans": [...], "window": (lo, hi)}
    of the first chip and the host, times in seconds on the trace's clock.

    ``ops``: (start, end, name, op_name) of every operation that is not
    control flow (a ``while`` merely contains its body's operations), sorted;
    ``modules``: (start, end, name) of every execution of a compiled program;
    ``spans``: (start, end, name) of the ``engine.*`` host spans; ``window``:
    the benchmark's window span, or the extent of the operations."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops, modules, spans, windows = [], [], [], []
    device = None
    for plane in space.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m and (device is None or int(m.group(1)) < device):
            device = int(m.group(1))
    for plane in space.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        is_host = plane.name.startswith("/host:")
        if not is_host and not (m and int(m.group(1)) == device):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = plane.event_metadata
        for line in plane.lines:
            if not is_host and line.name not in (trace.OPS_LINE, trace.MODULE_LINE):
                continue
            base = line.timestamp_ns * 1e-9
            cache = {}
            for ev in line.events:
                got = cache.get(ev.metadata_id)
                if got is None:
                    md = meta[ev.metadata_id]
                    op_name = ""
                    for st in md.stats:
                        if stat_names.get(st.metadata_id) == OP_NAME_STAT:
                            op_name = st.str_value or stat_names.get(st.ref_value, "")
                    got = cache[ev.metadata_id] = (md.name, op_name)
                start = base + ev.offset_ps * 1e-12
                end = start + ev.duration_ps * 1e-12
                if is_host:
                    if got[0].startswith(ENGINE_SPAN):
                        spans.append((start, end, got[0]))
                    elif got[0] == trace.WINDOW_SPAN:
                        windows.append((start, end))
                elif line.name == trace.MODULE_LINE:
                    modules.append((start, end, trace.module_name(got[0])))
                else:
                    name = trace.op_name(got[0])
                    if not trace.CONTROL_FLOW.match(name):
                        ops.append((start, end, name, got[1]))
    ops.sort()
    modules.sort()
    spans.sort()
    if windows:
        window = (min(a for a, _ in windows), max(b for _, b in windows))
    elif ops:
        window = (ops[0][0], max(b for _, b, _, _ in ops))
    else:
        window = (0.0, 0.0)
    return {"ops": ops, "modules": modules, "spans": spans, "window": window}


def trace_of(ctx: dict):
    """The parsed trace of this run's cell, or None where there is none."""
    trace_dir = os.path.join(common.ROOT, ".bench_out", ctx["cell"]["name"], "trace")
    try:
        return read_xplane(trace.find_xplane(trace_dir))
    except (FileNotFoundError, OSError):
        return None


# ------------------------------------------------------------------- scopes


def scope_of(op_name: str):
    """The outermost of the program's scopes on an operation's path, or None."""
    for part in op_name.split("/"):
        if part.startswith(("jit(", "pjit(")):
            continue
        # a transform wraps the scope that follows it: transpose(jvp(loss))
        inner = _WORDS.findall(part)
        if inner and inner[-1] in SCOPES:
            return inner[-1]
    return None


def direction_of(op_name: str) -> str:
    """``backward`` (transposed, or recomputed by remat), ``forward`` (under
    ``jvp`` only), or ``other`` (outside the differentiated function)."""
    if BACKWARD.search(op_name):
        return "backward"
    return "forward" if FORWARD.search(op_name) else "other"


def module_ops(parsed: dict, module: str):
    """(executions, operations) of ``module`` inside the window: the
    executions that lie wholly inside it, as ``trace.reduce`` counts them,
    and the operations that ran inside those."""
    lo, hi = parsed["window"]
    runs = [(a, b) for a, b, name in parsed["modules"] if name == module and a >= lo and b <= hi]
    starts = [a for a, _ in runs]
    inside = []
    for op in parsed["ops"]:
        i = bisect.bisect_right(starts, op[0]) - 1
        if i >= 0 and op[1] <= runs[i][1] + 1e-9:
            inside.append(op)
    return len(runs), inside


def scope_seconds(ops, directions=None) -> dict:
    """Device seconds by scope (None: no scope) over ``ops``."""
    out = {}
    for start, end, _, op_name in ops:
        if directions is not None and direction_of(op_name) not in directions:
            continue
        scope = scope_of(op_name)
        out[scope] = out.get(scope, 0.0) + (end - start)
    return out


def scoped_module_ops(ctx: dict, module: str):
    """``module_ops`` of this run's trace, or None where the trace has no
    execution of ``module`` or no operation of it carries any scope at all
    (a program without scopes)."""
    parsed = trace_of(ctx)
    if parsed is None:
        return None
    n, ops = module_ops(parsed, module)
    if not n or not any(scope_of(op[3]) for op in ops):
        return None
    return n, ops


def per_step_ms(ctx: dict, module: str, scopes) -> "float | None":
    """Mean device milliseconds under ``scopes`` in one execution of
    ``module``."""
    found = scoped_module_ops(ctx, module)
    if found is None:
        return None
    n, ops = found
    by_scope = scope_seconds(ops)
    return 1e3 * sum(by_scope.get(s, 0.0) for s in scopes) / n


def coverage(ctx: dict) -> "float | None":
    """Device time under any scope over the device's busy time, percent."""
    parsed = trace_of(ctx)
    if parsed is None or not ctx["trace"]["busy_s"]:
        return None
    lo, hi = parsed["window"]
    scoped = sum(
        min(b, hi) - max(a, lo) for a, b, _, op_name in parsed["ops"]
        if b > lo and a < hi and scope_of(op_name) is not None
    )
    return 100.0 * scoped / ctx["trace"]["busy_s"] if scoped else None


def direction_ms(ctx: dict, module: str, direction: str) -> "float | None":
    """Mean device milliseconds of one execution of ``module`` by direction:
    ``forward``, ``backward``, or ``optimizer`` (the scopes ``optimizer`` and
    ``grad_norm``, which lie outside the differentiated function)."""
    found = scoped_module_ops(ctx, module)
    if found is None:
        return None
    n, ops = found
    total = sum(
        end - start for start, end, _, op_name in ops
        if direction == ("optimizer" if scope_of(op_name) in OPTIMIZER else direction_of(op_name))
    )
    return 1e3 * total / n


# the engine loop's spans round a launch of device work or a fetch of its results
DEVICE_CALL_SPANS = ("engine.fetch", "engine.prefill_chunk", "engine.decode_launch",
                     "engine.prefix_seed")


def span_share(ctx: dict, names) -> "float | None":
    """Time under any of the host spans ``names`` over the traced window, percent."""
    parsed = trace_of(ctx)
    if parsed is None:
        return None
    lo, hi = parsed["window"]
    ivs = trace.clip(trace.union([[a, b] for a, b, n in parsed["spans"] if n in names]), lo, hi)
    return 100.0 * trace.total(ivs) / (hi - lo) if ivs and hi > lo else None


def uncovered_idle(parsed: dict, longer_than_s: float = 1e-3) -> list:
    """The device's idle gaps inside the window longer than ``longer_than_s``
    that ``engine.*`` spans do not cover wholly: [(start, end)]. Empty too
    where the trace has no such span at all (a program without them)."""
    if not parsed["spans"]:
        return []
    # a span is written when it ends, so the one open when the profiler stops
    # is not in the file: judge up to the end of the last that is
    lo = max(parsed["window"][0], min(a for a, _, _ in parsed["spans"]))
    hi = min(parsed["window"][1], max(b for _, b, _ in parsed["spans"]))
    busy = trace.union([[a, b] for a, b, _, _ in parsed["ops"] if b > lo and a < hi])
    idle = [g for g in trace.subtract([[lo, hi]], trace.clip(busy, lo, hi))
            if g[1] - g[0] > longer_than_s]
    # consecutive stages of the loop leave microseconds between their spans
    covered = trace.union([[a - SPAN_SEAM_S, b + SPAN_SEAM_S] for a, b, _ in parsed["spans"]])
    return [g for g in idle if trace.total(trace.subtract([g], covered)) > 0]


# ----------------------------------------------------------------- counters


def engine_stats(ctx: dict) -> dict:
    """The engine's ``get_stats()`` at the window's close. Its counters and
    histograms are cumulative since the engine started: the probe, the
    repeated greedy request, the warm-up (eight prompts, each sent unary and
    streamed) and the ramp are in them beside the window."""
    return ctx["extra"].get("stats_at_end") or {}


def counter(ctx: dict, name: str):
    return (engine_stats(ctx).get("counters") or {}).get(name)


def latency_quantile_ms(ctx: dict, name: str, q: float) -> "float | None":
    """Quantile ``q`` of one of the engine's latency histograms, in ms, read
    from its bucket counts (geometric interpolation inside the bucket: the
    bounds grow by a quarter each)."""
    latency = engine_stats(ctx).get("latency") or {}
    hist, bounds = latency.get(name), latency.get("boundaries")
    if not hist or not bounds or not sum(hist["counts"]):
        return None
    counts = hist["counts"]
    target, seen = q * sum(counts), 0
    for i, c in enumerate(counts):
        if c and seen + c >= target:
            hi = bounds[min(i, len(bounds) - 1)]
            lo = bounds[i - 1] if i > 0 else hi / 1.25
            return 1e3 * lo * (hi / lo) ** ((target - seen) / c)
        seen += c
    return None

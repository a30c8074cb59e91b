#!/usr/bin/env python3
"""Record a small trace on the chip: a jitted matmul chain run a few times
under ``TraceAnnotation`` spans, with pauses between, so that the reduction in
``benchmark/trace.py`` has a real ``.xplane.pb`` to be checked on
(``benchmark/tests/data/``). Also prints the planes, lines and first events, to
be looked at by hand. Run it in the one process that holds the chip:

    python3 benchmark/tools/record_trace.py <out_dir>
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark import trace

    @jax.jit
    def small_step(x):
        for _ in range(3):
            x = jnp.tanh(x @ x) * 0.01
        return x

    x = jnp.ones((512, 512), jnp.bfloat16)
    small_step(x).block_until_ready()
    trace.start(out_dir)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                y = small_step(x)
            with jax.profiler.TraceAnnotation("bench.wait_loss"):
                y.block_until_ready()
            with jax.profiler.TraceAnnotation("bench.pause"):
                time.sleep(0.002)
    jax.profiler.stop_trace()

    from jax.profiler import ProfileData

    path = trace.find_xplane(out_dir)
    print("xplane", path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), len(lines), "lines")
        for line in lines[:40]:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events), "events")
            for ev in events[:4]:
                stats = dict(list(ev.stats)[:6]) if hasattr(ev, "stats") else {}
                print("    EV", repr(ev.name), ev.start_ns, ev.duration_ns, stats)
    print("REDUCED", trace.reduce_dir(out_dir))


if __name__ == "__main__":
    main(sys.argv[1])

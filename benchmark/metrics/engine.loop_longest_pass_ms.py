"""Program span: the longest pass of the engine's loop that started inside the
measured window, milliseconds: the largest of the loop's own records (the
longest pass of each wall second of its last 120,
``get_stats()["loop"]["longest_pass_by_second"]`` on the ``stats_at_end`` line
of every run, traced or not) whose wall time lies in ``extra["window"]``. A
quiet window reads a pass that held a fetch of one step and a chunk launch; a
window in which the loop stood still reads that halt, and the record beside
it names the stage and the call.

It is a maximum, so it swings from run to run, and a quiet window already reads
68-177 ms on a v5e (a launch or a fetch that waits in a busy device's queue):
the number names only a halt well above 0.2 s. For anything shorter (the
0.1 s gap under ``engine.drain`` of the Nemotron cell) read the records
themselves: ``call`` and ``call_s`` say which fetch or launch a pass waited
for, ``stage_s`` in which stage."""

from benchmark import scopes


def read(ctx):
    loop = scopes.engine_stats(ctx).get("loop") or {}
    window = ctx["extra"].get("window")
    if not window:
        return None
    inside = [r["s"] for r in loop.get("longest_pass_by_second") or ()
              if window[0] <= r["t"] <= window[1]]
    return 1e3 * max(inside) if inside else None

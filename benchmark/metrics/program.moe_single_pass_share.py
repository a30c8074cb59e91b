"""Program counter: of the expert-layer runs of a model that holds a share of
the router's experts, those that one block of sorted rows served, percent. A
layer run works on the assignments that fell on its held experts a block at a
time (``models/patterned.py held_block``: twice the rows expected), and takes
a second block only where more than a block's rows fell here, so
``moe_passes`` is ``moe_layer_steps`` plus the overflows:
100 x (2 x ``moe_layer_steps`` - ``moe_passes``) / ``moe_layer_steps`` over the
decode and the two prompt-chunk programs, floored at 0 (a run that took three
blocks or more counts as more than one overflow). 100 when no run overflowed;
under it, the second blocks are time the chunk and decode programs spend
twice. Cumulative since the engine started. None on an engine that does not
count passes (one that works on every assignment in one pass)."""

from benchmark import moe_window, scopes


def read(ctx):
    passes, runs = scopes.counter(ctx, "moe_passes"), scopes.counter(ctx, "moe_layer_steps")
    if not isinstance(passes, dict) or not isinstance(runs, dict):
        return None
    n_passes, n_runs = (sum(by.get(p, 0) for p in moe_window.PROGRAMS) for by in (passes, runs))
    if not n_runs or not n_passes:
        return None
    return max(0.0, 100.0 * (2 * n_runs - n_passes) / n_runs)

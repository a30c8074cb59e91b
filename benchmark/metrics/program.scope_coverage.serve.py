"""Device trace: device time of operations whose ``op_name`` carries one of
the program's ``jax.named_scope`` names (``benchmark/scopes.py SCOPES``) over
the device's busy time in the traced window, percent. Guards the per-scope
metrics: what it leaves out they cannot see."""

from benchmark import scopes


def read(ctx):
    return scopes.coverage(ctx)

"""Host clock: ``serve.run`` called to one healthy replica behind the proxy
(placement, worker spawn, the engine's parameters, caches and programs)."""


def read(ctx):
    return ctx["spans"].get("replica_start_s")

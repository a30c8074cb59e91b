"""Program span: the replica's ``JaxEngine`` constructor entered to its loop
thread's first pass (parameters, caches, program wrappers), seconds, as
``LLMServer.stats()`` reports it."""

from benchmark import scopes


def read(ctx):
    return scopes.engine_stats(ctx).get("engine_init_s")

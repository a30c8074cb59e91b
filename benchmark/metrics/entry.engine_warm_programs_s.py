"""Program span: the seconds of the ``JaxEngine`` constructor spent running
every program the loop can launch once (``_warm_programs``: compiling them, or
fetching them from the compile cache, and one run of each), as
``get_stats()["init"]["warm_programs_s"]`` reports it. A part of
``entry.engine_init_s``; ``init["warm_programs_by_program_s"]`` on the
``stats_at_end`` line splits it by program."""

from benchmark import scopes


def read(ctx):
    return (scopes.engine_stats(ctx).get("init") or {}).get("warm_programs_s")

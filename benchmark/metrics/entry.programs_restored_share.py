"""Program counter: of the program forms a ``JaxEngine`` readied before its
loop took requests (``_warm_programs``: the decode step, the prompt chunks at
each row count and width, the stripes, the prefix store's cuts and seeds),
those it ran as an executable restored from ``_private/program_store.py``,
percent: ``get_stats()["init"]["programs"]``, ``restored`` over ``restored`` +
``compiled`` + ``fallback``. 100 on a warm start (no form was traced or
lowered: ``init["warm_programs_phases_s"]`` splits the warm-up's seconds by
program and phase), 0 on a checkout's first, which compiles every form and
writes it; a ``fallback`` is a kept file that did not load or an executable
that refused its arguments. None on an engine without the counter, and on one
that restores nothing by design (no compile cache configured, or a mesh)."""

from benchmark import scopes


def read(ctx):
    programs = (scopes.engine_stats(ctx).get("init") or {}).get("programs")
    if not isinstance(programs, dict):
        return None
    forms = sum(programs.get(k, 0) for k in ("restored", "compiled", "fallback"))
    return 100.0 * programs.get("restored", 0) / forms if forms else None

"""Program span: of the traced window's decode steps, the share that a prompt
chunk's launch carried: 100 x ``decode_steps_in_chunk`` over ``decode_steps``,
both summed over the window's own ``engine.counts`` events
(``benchmark/window_counts.py``). A carried step's rows rode through
``jit_chunk_mid`` or ``jit_chunk_final`` beside the chunk's tokens and no
``jit_decode_fn`` ran for it (``llm/engine.py _advance_admissions``), so this
is how much of the window's decoding the chunk programs' time holds, and what
is left of it is what ``program.decode_step_ms`` and the decode shares still
describe. 0 where a pool's launches carry nothing (a latent pool); None on a
trace whose engine has no such counter (the parent of the PR that brought it)
or no ``engine.counts`` event."""

from benchmark import window_counts


def read(ctx):
    own = window_counts.window_counts(ctx)
    if own is None or "decode_steps_in_chunk" not in own or not own.get("decode_steps"):
        return None
    return 100.0 * own["decode_steps_in_chunk"] / own["decode_steps"]

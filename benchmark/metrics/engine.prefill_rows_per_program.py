"""Program counter: prompt chunks the engine prefilled (``prefill_chunks``,
middle and final: one a prompt chunk, that is a row of a chunk program) over
the launches of its chunk programs (``prefill_programs``): the mean number of
rows a ``jit_chunk_mid`` or ``jit_chunk_final`` run carried. 1.0 says every
chunk ran alone; above it, admissions that were due in the same pass shared a
read of the weights. Cumulative since the engine started, as ``stats_at_end``
has the two: the probe, the warm-up's single rows and the ramp are in it
beside the window. Nothing where the program counts no launches."""

from benchmark import scopes


def read(ctx):
    chunks, programs = (scopes.counter(ctx, k) for k in ("prefill_chunks", "prefill_programs"))
    if not isinstance(chunks, dict) or not isinstance(programs, dict) or not sum(programs.values()):
        return None
    return sum(chunks.values()) / sum(programs.values())

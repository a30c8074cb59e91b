"""Program span: of the cached positions the decode steps of the traced window
read, the share they had to: the window's ``decode_kv_tokens_global`` over its
``decode_kv_positions_read`` (the live tokens of the active slots over the
positions the decode kernel's blocks cover between a slot's bounds in a full
layer, ``ops/decode_attention.py positions_read``; the slot's whole stripe
where the steps keep the einsum), percent; the ``_latent`` pair for a
latent-attention model, whose global pair stays 0. Both are summed over the
window's own ``engine.counts`` events (``benchmark/window_counts.py``).
Sliding-window layers have a pair of their own (``_window``) and are not in
it."""

from benchmark import window_counts


def read(ctx):
    counts = window_counts.window_counts(ctx)
    if counts is None:
        return None
    for tokens, positions in (("decode_kv_tokens_latent", "decode_kv_positions_read_latent"),
                              ("decode_kv_tokens_global", "decode_kv_positions_read")):
        if counts.get(tokens) and counts.get(positions):
            return 100.0 * counts[tokens] / counts[positions]
    return None

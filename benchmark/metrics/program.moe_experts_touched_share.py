"""Program counter: experts that got at least one token over experts there
are, over every expert-layer run of the decode and prompt-chunk programs
(``moe_experts_touched`` over ``moe_layer_steps`` times ``num_experts``),
percent. Cumulative since the engine started."""

from benchmark import moe_window


def read(ctx):
    r = moe_window.routing(ctx, *moe_window.PROGRAMS)
    if r is None:
        return None
    return 100.0 * r["moe_experts_touched"] / (r["moe_layer_steps"] * ctx["config"]["num_experts"])

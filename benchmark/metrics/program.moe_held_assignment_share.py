"""Program counter: of the (token, expert) assignments the router made over
all its experts, those that fell on the experts this chip holds
(``moe_assignments_held`` over ``moe_assignments``, over every expert-block run
of the decode and prompt-chunk programs), percent. With 128 of 512 held and
seeded weights it reads near 25: read, not assumed. Cumulative since the
engine started."""

from benchmark import ssm_latent_moe


def read(ctx):
    return ssm_latent_moe.held_share(ctx)

"""Program counter: bytes a decode slot holds whatever its length (the
float32 state and the convolution tail of each state-space block), as the
engine reckons them from its cache's arrays (``get_stats()["pools"]``)."""

from benchmark import ssm_latent_moe


def read(ctx):
    return ssm_latent_moe.state_bytes_per_slot(ctx)

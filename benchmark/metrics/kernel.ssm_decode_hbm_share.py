"""Bytes the state-space blocks of one decode step must move (each block's
weights once; the state and the convolution tail of every slot that holds a
request, read and written: ``decode_slot_steps`` over ``decode_steps``) over
the chip's peak HBM bandwidth, over the step's device time under
``ssm_mixer`` (input projection, convolution, ``ssm_step``, gate, norm and
output projection), percent."""

from benchmark import peaks, ssm_latent_moe
from benchmark.families import ssm_latent_moe as family


def read(ctx):
    ms = ssm_latent_moe.under_ms(ctx, "jit_decode_fn", "ssm_mixer")
    slots = ssm_latent_moe.active_slots_per_step(ctx)
    if not ms or slots is None:
        return None
    needed = family.ssm_decode_bytes(ctx["config"], slots)
    return 100.0 * needed / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"] / (1e-3 * ms)

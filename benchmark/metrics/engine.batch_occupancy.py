"""Program counter: active decode slots over ``max_num_seqs``, from the
engine's stats sampled inside the replica every ``sample_every_s`` of the
window; the mean, in percent."""


def read(ctx):
    samples = ctx["samples"]
    if not samples:
        return None
    return 100.0 * sum(s["active_slots"] / s["max_num_seqs"] for s in samples) / len(samples)

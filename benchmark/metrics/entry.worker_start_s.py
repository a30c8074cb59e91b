"""Host clock: ``JaxTrainer.fit`` called to the first line of the loop in the
worker that holds the chips (placement, worker spawn, JAX import)."""


def read(ctx):
    return ctx["spans"].get("worker_start_s")

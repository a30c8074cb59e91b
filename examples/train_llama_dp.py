"""Data-parallel Llama training with JaxTrainer.

Run (CPU virtual mesh): JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/train_llama_dp.py
As written the worker runs on the CPU: a worker only gets chips if the trainer
asks, e.g. ScalingConfig(num_workers=1, use_tpu=True,
resources_per_worker={"TPU": 1}) (use_tpu alone asks for a four-chip host);
multi-host pods get one trainer worker per host (num_workers=<hosts>).
"""

import numpy as np

import ray_tpu
from ray_tpu.train import Checkpoint, JaxTrainer, RunConfig, ScalingConfig


def train_loop(config):
    import jax
    import jax.numpy as jnp

    import ray_tpu.train as train
    from ray_tpu.models import LlamaConfig
    from ray_tpu.models.training import make_train_step
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg = LlamaConfig.tiny(max_seq_len=config["seq_len"])
    mesh = build_mesh(MeshSpec(dp=-1))  # all local devices on the dp axis
    init_fn, step_fn = make_train_step(cfg, mesh)
    state = init_fn(jax.random.PRNGKey(0))

    rng = np.random.default_rng(train.get_context().get_world_rank())
    for step in range(config["steps"]):
        batch = {
            "tokens": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (config["batch"], config["seq_len"] + 1)),
                jnp.int32,
            )
        }
        state, metrics = step_fn(state, batch)
        if step % 5 == 0 or step == config["steps"] - 1:
            train.report(
                {"loss": float(metrics["loss"]), "step": step},
                checkpoint=Checkpoint.from_pytree(state.params),
            )


if __name__ == "__main__":
    ray_tpu.init(mode="process")
    result = JaxTrainer(
        train_loop,
        train_loop_config={"steps": 20, "batch": 8, "seq_len": 64},
        scaling_config=ScalingConfig(num_workers=1),  # CPU; see the docstring
        run_config=RunConfig(name="llama-dp-example"),
    ).fit()
    print("final:", result.metrics, "checkpoint:", result.checkpoint)
    ray_tpu.shutdown()

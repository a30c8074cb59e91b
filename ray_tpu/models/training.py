"""Sharded train-step builder.

Where the reference wraps ``torch.nn.parallel.DistributedDataParallel``
(``python/ray/train/torch/train_loop_utils.py``), here the train step is one
jit-compiled SPMD program: gradients are averaged by XLA-inserted collectives
over the mesh's data axes, parameters/optimizer state shard per the logical
rules (fsdp axis = ZeRO-3 analog), and remat is per-layer ``jax.checkpoint``
inside the model's scan.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn
from ray_tpu.parallel.mesh import logical_sharding


def default_optimizer(
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    warmup_steps: int = 100,
    total_steps: int = 10000,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
):
    sched = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1)
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b1=b1, b2=b2, weight_decay=weight_decay),
    )


def batch_sharding(mesh: Mesh):
    """Input batch sharding: batch over dp/fsdp, seq over sp."""
    return logical_sharding(mesh, "batch", "seq")


class TrainState:
    """Lightweight pytree-of-(params, opt_state, step)."""

    def __init__(self, params, opt_state, step):
        self.params = params
        self.opt_state = opt_state
        self.step = step

    def tree_flatten(self):
        return (self.params, self.opt_state, self.step), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten
)


def make_train_step(
    cfg: LlamaConfig,
    mesh: Mesh,
    optimizer=None,
    loss: Optional[Callable] = None,
    donate: bool = True,
):
    """Returns (init_fn(key) -> TrainState, step_fn(state, batch) -> (state, metrics)).

    Both are jitted with explicit in/out shardings so XLA lays out params on
    the mesh from the first step (no host round-trip).
    """
    optimizer = optimizer or default_optimizer()
    loss = loss or loss_fn

    def init_fn(key):
        params = init_params(key, cfg, mesh=mesh)
        # Each moment buffer takes its parameter's sharding, said outright:
        # the zeros do not depend on the parameters, so left to itself the
        # compiler puts every one of them whole on the first device (4.8 GB
        # of moments on chip 0 of 4 at 1.2 B parameters, until the first
        # step spread them).
        replicated = NamedSharding(mesh, PartitionSpec())
        shardings = optax.tree_utils.tree_map_params(
            optimizer,
            lambda _, p: p.sharding,
            jax.eval_shape(optimizer.init, params),
            params,
            transform_non_params=lambda _: replicated,
        )
        opt_state = jax.jit(optimizer.init, out_shardings=shardings)(params)
        return TrainState(params, opt_state, jnp.zeros((), jnp.int32))

    def step_fn(state: TrainState, batch):
        def lf(p):
            return loss(p, batch, cfg, mesh)

        lval, grads = jax.value_and_grad(lf)(state.params)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
        with jax.named_scope("grad_norm"):
            gnorm = optax.global_norm(grads)
        metrics = {"loss": lval, "grad_norm": gnorm, "step": state.step + 1}
        return TrainState(params, opt_state, state.step + 1), metrics

    step_jit = jax.jit(
        step_fn,
        donate_argnums=(0,) if donate else (),
    )
    return init_fn, step_jit


def tokens_per_step(cfg: LlamaConfig, batch_size: int, seq_len: int) -> int:
    return batch_size * seq_len


def flops_per_token(cfg: LlamaConfig) -> float:
    """Approximate train FLOPs/token (fwd+bwd ≈ 6×params + attention)."""
    attn = 12 * cfg.n_layers * cfg.d_model * cfg.max_seq_len  # per token, rough
    return 6.0 * cfg.num_params() + attn


def mfu(cfg: LlamaConfig, tokens_per_sec: float, n_chips: int, device_kind: str):
    """Model FLOPs utilization against the published bf16 peak of
    ``device_kind`` (``ray_tpu.tpu.topology.CHIP_PEAKS``)."""
    from ray_tpu.tpu.topology import chip_peaks

    peak_flops = chip_peaks(device_kind)["bf16_flops_per_s"]
    return tokens_per_sec * flops_per_token(cfg) / (n_chips * peak_flops)

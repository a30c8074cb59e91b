"""The benchmark's own weights: made on the device from ``--seed`` in one
jitted call, in the type they are served in. The program is handed these
(``TrainState`` for the trainer, ``engine.params`` for the replica), and the
reference reads the same arrays, so nothing the program has made reaches the
reference.

The tree is the one ``models/llama.py`` takes: stacked per-layer leaves with a
leading layer axis. The recipe is the usual one for a pre-norm decoder: normal
with standard deviation ``fan_in ** -0.5`` (fan-in is the size contracted
away), norm scales at one."""

from __future__ import annotations


def param_shapes(config: dict) -> dict:
    """name -> (shape, fan_in or None for a norm scale)."""
    e, f, v = config["hidden_size"], config["intermediate_size"], config["vocab_size"]
    h, kv, hd = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    n = config["num_hidden_layers"]
    shapes = {
        "embed": ((v, e), e),
        "final_norm": ((e,), None),
        "wq": ((n, e, h, hd), e),
        "wk": ((n, e, kv, hd), e),
        "wv": ((n, e, kv, hd), e),
        "wo": ((n, h, hd, e), h * hd),
        "attn_norm": ((n, e), None),
        "mlp_norm": ((n, e), None),
        "w_gate": ((n, e, f), e),
        "w_up": ((n, e, f), e),
        "w_down": ((n, f, e), f),
    }
    if not config["tie_word_embeddings"]:
        shapes["unembed"] = ((e, v), e)
    return shapes


def make_params(seed: int, config: dict, dtype, shardings=None):
    """All leaves in one jitted call. Stacked leaves are drawn a layer at a
    time (``lax.map``), so the float32 draw of a whole 16-layer leaf (3.7 GB)
    never exists beside the weights."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(config)
    names = sorted(shapes)

    def make(key):
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            shape, fan_in = shapes[name]
            if fan_in is None:
                out[name] = jnp.ones(shape, dtype)
                continue
            std = fan_in ** -0.5

            def draw(k, shape=shape[1:], std=std):
                return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

            rows = shape[0]
            out[name] = jax.lax.map(draw, jax.random.split(k, rows))
        return out

    if shardings is not None:
        shardings = {name: shardings[name] for name in names}
    return jax.jit(make, out_shardings=shardings)(jax.random.PRNGKey(seed))


def int8_roundtrip(params):
    """Every weight matrix through symmetric int8 and back, one scale per
    index of the last axis (and per layer for stacked leaves): the lower
    precision a later PR would be tempted by. Norm scales are left alone.
    Used only by the control of ``correct``, never by a benchmark run. A leaf
    at a time, a layer at a time, in place: the float32 copy of a whole
    16-layer leaf (3.7 GB) never exists beside a resident engine."""
    import jax
    import jax.numpy as jnp

    def matrix(w):
        w32 = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w32), axis=tuple(range(w.ndim - 1)), keepdims=True) / 127.0
        q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
        return (q.astype(jnp.float32) * scale).astype(w.dtype)

    def leaf(w):
        return jax.lax.map(matrix, w) if w.ndim >= 3 else matrix(w)

    return {
        name: w if "norm" in name
        else jax.jit(leaf, out_shardings=w.sharding, donate_argnums=(0,))(w)
        for name, w in params.items()
    }

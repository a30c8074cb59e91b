"""A state-space model's prompt in chunks and in rows of one launch against
the prompt whole (``llm/engine.py programs`` over ``models/patterned.py``).
One parametrised test: the longest of what was ``tests/test_ssm.py``, a file
of its own so that a worker has it alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import programs
from ray_tpu.models.llama import init_kv_cache, prefill
from tests.ssm_models import CFG, STATE, model


@pytest.mark.parametrize("rows", [1, 2])
def test_a_prompt_in_chunks_of_a_multi_row_launch_equals_the_prompt_whole(model, rows):
    """The engine's own ``chunk_mid`` and ``chunk_final`` bodies: prompts of
    29 and 23 tokens go in as 8-token middle chunks, ``rows`` stripes a
    launch (stacked, run and handed back a row each: state and convolution
    tail with the keys and values), the shorter's last middle chunk beside
    the longer's (3 and 2 of them), then a final chunk of width 8 each into a
    pool of 3 slots; the slots' leaves and first tokens against each prompt
    whole through ``prefill``."""
    params, tokens, _, _ = model
    fns = programs(CFG)
    lens = (29, 23)
    ones = [fns["new_stripe"](64) for _ in lens]
    done = [0, 0]
    while any(n - d > 8 for n, d in zip(lens, done)):
        due = [b for b, n in enumerate(lens) if n - done[b] > 8]
        for group in ([due] if rows == 2 else [[b] for b in due]):
            out = fns["chunk_mid"](
                params, tuple(ones[b] for b in group),
                jnp.asarray(np.stack([tokens[b, done[b]:done[b] + 8] for b in group])),
                jnp.full((len(group),), 8, jnp.int32),
                jnp.asarray([done[b] for b in group], jnp.int32))
            for b, one in zip(group, out):
                ones[b], done[b] = one, done[b] + 8
    cache = init_kv_cache(CFG, 3, 64)
    # a tenant's leftovers in every slot: the final chunk must overwrite them
    cache = {k: (v + 1 if k in STATE else v) for k, v in cache.items()}
    first = []
    for b, n in enumerate(lens):
        tail = np.zeros((1, 8), np.int32)
        tail[0, :n - done[b]] = tokens[b, done[b]:n]
        tok, _, cache, _, stats = fns["chunk_final"](
            params, cache, ones[b], jnp.asarray(tail), jnp.asarray([n - done[b]], jnp.int32),
            jnp.asarray([done[b]], jnp.int32), jnp.int32(2 - b), jnp.float32(0.0), jnp.int32(1),
            jax.random.PRNGKey(0))
        first.append(int(tok))
        assert stats.shape == (2, 6)  # chunk_mid's and chunk_final's counts, the held ones and the blocks too
    for b, n in enumerate(lens):
        slot = 2 - b
        logits, whole = prefill(params, init_kv_cache(CFG, 1, 64), jnp.asarray(tokens[b:b + 1, :n]), CFG)
        assert first[b] == int(jnp.argmax(logits[0]))
        assert int(cache["length"][slot]) == n
        for name in STATE:
            np.testing.assert_allclose(cache[name][:, slot], whole[name][:, 0], atol=1e-5)
        for name in ("k", "v"):
            np.testing.assert_allclose(cache[name][:, slot, :, :n], whole[name][:, 0, :, :n], atol=1e-5)
